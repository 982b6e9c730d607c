"""BENCHMARK.json against the contract's letter, and against the files it
names: every name, unit and length; every configuration, cell and per-layer
metric has its file, and the file says the same."""
import json
import os
import re

from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    B = json.load(_f)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_units_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert all(PATH.match(p) for p in B["paths"]) and len(B["paths"]) <= 16
    assert len(B["command"]) <= 32 and all(_line(w) for w in B["command"])
    cells = len(B["workloads"])
    assert 1 <= cells <= 24 and 1 <= len(B["configs"]) <= 24
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, cells // 4)
    # a full check with 24 cells fits 43,200 s
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in B[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and _line(e["why"])
    for e in B["workloads"]:
        assert NAME.match(e["config"]) and NAME.match(e["traffic"])
        assert e["chips"] in (1, 4)
    pairs = [(e["config"], e["traffic"]) for e in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in ("end_to_end", "per_layer"):
        for m in B[group]:
            allowed = {"name", "unit", "better", "source", "workloads"} | (
                {"bound"} if group == "end_to_end" else {"layer", "moves"})
            assert set(m) <= allowed and set(m) >= allowed - {"workloads"}, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            assert m["name"] not in seen
            seen.add(m["name"])
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])


def test_every_named_file_is_there_and_says_the_same():
    configs = {c["name"]: c for c in B["configs"]}
    used = set()
    for w in B["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           cell["driver"] + ".py"))
        used.add(w["config"])
    assert used == set(configs)
    for c in B["configs"]:
        assert c["file"].startswith("benchmarks/") and PATH.match(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
    # ... and every per-layer entry says what its reader file says
    import importlib.util
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for m in B["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert (mod.NAME, mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["name"], m["layer"], m["unit"], m["moves"], m["source"])
        reported_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reported_in)) <= reported_in
