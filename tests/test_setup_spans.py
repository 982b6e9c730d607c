"""The set-up split's arithmetic (``benchmarks/lib/setup_spans.py``) on
hand-built rings, and the six ``setup.*`` readers over it.  No device, no
compile: spans are made by hand on a clock that starts at 100.

The same cases run among the benchmark's own tests
(``benchmarks/tests/test_setup_spans.py`` imports them).
"""
import importlib.util
import json
import os
import time

import pytest

from benchmarks.lib import setup_spans
from byol_tpu.observability import spans as spans_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = {"setup.build_s": ("build_s", "s", "program_span"),
           "setup.init_s": ("init_s", "s", "program_span"),
           "setup.step_compile_s": ("step_compile_s", "s", "program_span"),
           "setup.other_compile_s": ("other_compile_s", "s", "program_span"),
           "setup.cache_misses": ("cache_misses", "count",
                                  "program_counter"),
           "setup.unattributed_s": ("unattributed_s", "s", "program_span")}


ON_CHIP = {"peaks": {"bf16_flops_per_s": 197e12}}   # run.py's, on a TPU


class Ring:
    """Spans by hand, in closing order; ``seq`` in the order given."""

    def __init__(self):
        self.records = []

    def span(self, name, t0, t1, parent=None, **attrs):
        depth = 0 if parent is None else parent.depth + 1
        s = spans_lib.Span(name, t0, t1, 1, depth, len(self.records),
                           -1 if parent is None else parent.seq,
                           attrs or None)
        self.records.append(s)
        return s


def a_set_up(step_backends=1):
    """Process starts at 100.  2 s of imports, then config (1 s), a 20 s
    build whose init (12 s) holds a small program (trace 1 + lower 1 +
    backend 2 s, a miss) with a callee's trace (0.5 s) inside its trace;
    then 3 s of nothing (the host pool), the seeded weights' program (2 s,
    a hit), the step's trace (4 s, a callee's 1 s in it), lowering (2 s)
    and backend compile (30 s, a hit) — and after it a first step's small
    program, which is not set-up."""
    r = Ring()
    r.span("startup/config", 102.0, 103.0)
    build = r.span("startup/build", 103.0, 123.0)
    init = r.span("startup/build/init", 104.0, 116.0, build,
                  leaves=10, parameters=1000)
    trace = r.span("compile/trace", 105.0, 106.0, init, fun="normal")
    r.span("compile/trace", 105.2, 105.7, trace, fun="_where")
    r.span("compile/lower", 106.0, 107.0, init, fun="jit(normal)")
    r.span("compile/backend", 107.0, 109.0, init, fun="jit(normal)",
           cache="miss")
    r.span("startup/build/place", 116.0, 120.0, build)
    r.span("compile/backend", 126.0, 128.0, fun="jit(make_weights)",
           cache="hit", retrieval_s=1.5)
    step_trace = r.span("compile/trace", 128.0, 132.0, fun="train_step")
    r.span("compile/trace", 129.0, 130.0, step_trace, fun="_where")
    r.span("compile/lower", 132.0, 134.0, fun="jit(train_step)")
    r.span("compile/backend", 134.0, 164.0, fun="jit(train_step)",
           cache="hit", retrieval_s=29.0)
    r.span("compile/backend", 165.0, 166.0, fun="jit(convert_element_type)",
           cache="miss")
    if step_backends == 2:      # a recompile mid-run: trace, lower, backend
        r.span("compile/trace", 170.0, 171.0, fun="train_step")
        r.span("compile/lower", 171.0, 172.0, fun="jit(train_step)")
        r.span("compile/backend", 172.0, 272.0, fun="jit(train_step)",
               cache="miss")
    return r.records


@pytest.mark.parametrize("step_backends", [1, 2])
def test_the_six_numbers_of_a_hand_built_set_up(step_backends):
    """... and a step program compiled twice changes none of them: the
    FIRST backend compile ends the program's set-up."""
    got = setup_spans.split(a_set_up(step_backends), start=100.0)
    assert got["build_s"] == pytest.approx(20.0)
    assert got["init_s"] == pytest.approx(12.0)
    # trace 4 (its callee's second inside it counted once) + lower 2 + 30
    assert got["step_compile_s"] == pytest.approx(36.0)
    # normal: trace 1 (self 0.5 + the callee's 0.5) + lower 1 + backend 2;
    # make_weights 2; nothing of what nests under the step's trace, and
    # nothing that ended after the step's backend compile
    assert got["other_compile_s"] == pytest.approx(6.0)
    assert got["cache_misses"] == 1.0
    # 64 s from the start to the end of set-up, less config 1, build 20,
    # make_weights 2 and the step's 36
    assert got["unattributed_s"] == pytest.approx(64.0 - 59.0)
    assert got["init_s"] <= got["build_s"]
    assert (got["build_s"] + got["step_compile_s"] + got["unattributed_s"]
            <= 164.0 - 100.0)


def test_no_step_program_means_no_number_at_all():
    ring = [r for r in a_set_up()
            if setup_spans.function_of(r) != "train_step"]
    assert setup_spans.split(ring, start=100.0) is None
    assert setup_spans.split([], start=100.0) is None


def test_what_cannot_be_had_is_left_out():
    compiles_only = [r for r in a_set_up() if r.name.startswith("compile/")]
    got = setup_spans.split(compiles_only, start=None)
    assert set(got) == {"step_compile_s", "other_compile_s", "cache_misses"}
    # a start the clock puts AFTER the end of set-up is no start
    assert "unattributed_s" not in setup_spans.split(a_set_up(), start=1e9)


def test_the_step_function_is_found_under_both_of_jax_s_names():
    S = spans_lib.Span
    for fun in ("train_step", "jit(train_step)"):
        s = S("compile/backend", 0.0, 1.0, 1, 0, 0, -1, {"fun": fun})
        assert setup_spans.function_of(s) == "train_step"
    for fun in ("eval_step", "jit(train_step_2)", None):
        s = S("compile/backend", 0.0, 1.0, 1, 0, 0, -1, {"fun": fun})
        assert setup_spans.function_of(s) != "train_step"
    assert setup_spans.function_of(S("x", 0.0, 1.0, 1, 0, 0, -1, None)) == ""


def test_process_start_is_the_operating_system_s():
    start = setup_spans.process_start()
    assert start is not None
    # before this module's anchor (taken at import), and by less than the
    # time this interpreter can have been alive
    assert start < spans_lib._ANCHOR[1] <= time.perf_counter()
    assert spans_lib._ANCHOR[1] - start < 24 * 3600.0


def test_of_this_process_reads_the_program_s_recorder(monkeypatch):
    rec = spans_lib.SpanRecorder()
    for r in a_set_up():
        rec._append(r)
    monkeypatch.setattr(spans_lib, "PROCESS", rec)
    monkeypatch.setattr(setup_spans, "process_start", lambda: 100.0)
    setup_spans.of_this_process.cache_clear()
    try:
        assert setup_spans.read("step_compile_s", ON_CHIP) == \
            pytest.approx(36.0)
        assert setup_spans.read("unattributed_s", ON_CHIP) == \
            pytest.approx(5.0)
        # off the chip (a CPU rehearsal) the readers say nothing
        assert setup_spans.read("step_compile_s", {"peaks": None}) is None
        # a ring that has dropped spans cannot say where set-up went
        small = spans_lib.SpanRecorder(capacity=4)
        for r in a_set_up():
            small._append(r)
        monkeypatch.setattr(spans_lib, "PROCESS", small)
        setup_spans.of_this_process.cache_clear()
        assert setup_spans.read("step_compile_s", ON_CHIP) is None
        # a program without the recorder (a parent commit): nothing, quietly
        monkeypatch.delattr(spans_lib, "PROCESS")
        setup_spans.of_this_process.cache_clear()
        assert all(setup_spans.read(key, ON_CHIP) is None
                   for key, _, _ in READERS.values())
    finally:
        setup_spans.of_this_process.cache_clear()


def _reader(name):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_says_what_benchmark_json_says_and_reads_its_number(
        name, monkeypatch):
    key, unit, source = READERS[name]
    mod = _reader(name)
    assert (mod.NAME, mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        name, "entry / set-up", unit, "setup_s", source)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "entry / set-up",
                     "moves": "setup_s",
                     "workloads": [w["name"] for w in bench["workloads"]]}
    numbers = setup_spans.split(a_set_up(), start=100.0)
    monkeypatch.setattr(setup_spans, "of_this_process", lambda: numbers)
    assert mod.read(ON_CHIP) == numbers[key]
    assert mod.read({"peaks": None}) is None
    monkeypatch.setattr(setup_spans, "of_this_process", lambda: None)
    assert mod.read(ON_CHIP) is None
