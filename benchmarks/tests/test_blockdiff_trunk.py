"""The block-diffusion trunk's cell end to end on the CPU at tiny size: a
tiny configuration and a tiny cell ADDED as files to a copy of
``benchmarks/`` (as test_latent_trunk.py adds its own), driven through
``run.py --rehearse-cpu`` by ``drivers/train_blockdiff_tokens.py``; the
contract of the new files; the flops file's counts against a brute-force
count of visible pairs; the new readers on a canned trace; and the broken
twin that ``correct`` refuses."""
import importlib.util
import json
import os
import shutil
import sys

import numpy as np
import pytest

from conftest import BENCH, REPO, TIGHT_F32, run_cell
from test_end_to_end import _last

CONFIG = "byol_sdar_30b_a3b_ep8"
CELL = "sdar_train_b2_s4096"
NEW = ["train_step.blockdiff_ms", "train_step.blockdiff_core_ms",
       "blockdiff.core_roofline", "train_step.blockdiff_moe_ms",
       "train_step.blockdiff_update_share", "train_step.blockdiff_seq_mfu"]
TINY = dict(
    name="tiny_blockdiff_f32", arch="blockdiff_trunk_tiny", seq_len=16,
    layer_share="1/4,vocab=2,heads=1", trunk_depth="0+2", hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=2, num_experts_per_tok=3,
    num_hidden_layers=2, vocab_size=64, head_latent_size=64,
    projection_size=32, num_classes=10, per_chip_batch=4,
    precision="float32",
    published={"num_experts": 8, "vocab_size": 128, "num_hidden_layers": 2})


def _conf():
    return json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def blockdiff_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_blockdiff")
    dst = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = _conf()
    conf.update(TINY)
    flags = conf["flags"]
    for flag, key in (("--arch", "arch"), ("--seq-len", "seq_len"),
                      ("--layer-share", "layer_share"),
                      ("--trunk-depth", "trunk_depth"),
                      ("--head-latent-size", "head_latent_size"),
                      ("--projection-size", "projection_size")):
        flags[flags.index(flag) + 1] = str(conf[key])
    flags.append("--no-half")
    cell = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    cell.update(name="tiny_blockdiff_train", config="tiny_blockdiff_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = dict(TIGHT_F32, early_hidden_gap=1e-4)
    for rel, obj in (("configs/tiny_blockdiff_f32.json", conf),
                     ("workloads/tiny_blockdiff_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_blockdiff_cell_runs_and_agrees_in_float32(blockdiff_copy):
    rc, out, err = run_cell(blockdiff_copy, "tiny_blockdiff_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["traced_end_to_end"]) == {
        "setup_s", "train_images_per_s_per_chip"}
    # on the CPU: the counters' readers report, the device's stay silent
    assert set(line["metrics"]) == {
        "train_step.step_ms", "input.host_feed_ms", "moe.load_max_over_mean"}
    # five numbers held to a limit, the momentum through the heads read
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 5
    assert sum("through_heads" in ln and "not compared" in ln
               for ln in out) == 1
    # the direction with every expert a leaf: printed, held to no limit
    assert sum("grad_dir_gap_by_expert" in ln and "not compared" in ln
               for ln in out) == 1
    assert any("rows dropped 0" in ln for ln in out)


@pytest.mark.parametrize("script,over", [
    # the leak that makes the published objective trivial: seen IN FRONT OF
    # THE HEADS, by the representations and the trunk's gradient
    ("broken_noised_sees_own_clean.py",
     ("early_hidden_gap", "grad_dir_gap", "grad_norm_gap")),
    # a state left as it was: the LARS-scaled leaves' change reads 1
    ("broken_step.py", ("update_norm_gap",)),
    # half the samples never reach the program: the loss
    ("broken_half_batch.py", ("loss_rel_gap",))])
def test_a_broken_twin_is_not_correct(blockdiff_copy, script, over):
    rc, out, err = run_cell(blockdiff_copy, "tiny_blockdiff_train", trace=0,
                            script=os.path.join("tests", script))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    for name in over:
        assert any(f"] check: {name} =" in ln and "OVER" in ln
                   for ln in out), name


def test_a_host_batch_is_two_noisings_of_one_clean_sequence():
    """``[noised | clean]``: the clean half the same in both views and never
    the mask id, the noised half the clean ids or the mask id, masked at a
    rate that differs block by block (one ``t`` a block)."""
    sys.path.insert(0, REPO)
    from benchmarks.drivers.train_blockdiff_tokens import host_batches
    vocab, length, b = 64, 4096, 4
    first, second = host_batches(7, 2, 3, length, vocab, 10, block_length=b)
    again = host_batches(7, 1, 3, length, vocab, 10, block_length=b)[0]
    for name in ("view1", "view2", "label"):
        np.testing.assert_array_equal(first[name], again[name])
    v1, v2 = first["view1"], first["view2"]
    assert v1.shape == v2.shape == (3, 2 * length) and v1.dtype == np.int32
    clean = v1[:, length:]
    np.testing.assert_array_equal(clean, v2[:, length:])
    assert clean.max() == vocab - 2 and clean.min() == 0
    assert not np.array_equal(clean, second["view1"][:, length:])
    for view in (v1, v2):
        noised = view[:, :length]
        masked = noised == vocab - 1
        assert np.array_equal(noised[~masked], clean[~masked])
        rate = masked.reshape(3, length // b, b).mean(-1)
        # t ~ U(0, 1) a block: a block of 4 is 0, 1, .. 4 masked, all often
        assert 0.45 < masked.mean() < 0.55
        assert all((rate == k / b).mean() > 0.1 for k in range(b + 1))
    assert not np.array_equal(v1[:, :length], v2[:, :length])


# ---- the contract of the new files ------------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "SDAR-30B-A3B-Chat":
            return row
    pytest.skip("the catalog has no such row")


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    conf, row = _conf(), _catalog_row()
    assert conf["source"] == row["source_url"] and len(conf["source"]) <= 200
    differs = sorted(k for k, v in row["config"].items()
                     if conf.get(k, "absent") != v)
    assert differs == sorted(conf["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size"])
    for key in conf["reduced"]:
        assert conf["published"][key] == row["config"][key]
        assert key in conf["reduced_detail"]
    # every width, the heads, the router's top-k as published
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["moe_intermediate_size"], conf["num_experts_per_tok"]) == (
                2048, 32, 4, 128, 768, 8)
    # the floors of the guide's section 4
    assert conf["num_hidden_layers"] >= 4 and conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]
    # what the row does not give is assumed, in the file
    assert conf["block_length"] == 4
    assert any("block_length 4" in a for a in conf["assumed"])
    assert any("noise schedule" in a for a in conf["assumed"])
    assert "8 that share every layer" in conf["deployment"]


def test_the_benchmark_names_the_configuration_the_cell_and_the_metrics():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    # by NAME: a later PR appends its own entries after these
    conf, = (c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == _conf()["reduced"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell, = (w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert all(len(x["why"]) <= 200 for x in (conf, cell))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == set(NEW) | {
        "train_step.step_ms", "input.host_feed_ms", "setup.build_s",
        "setup.init_s", "setup.step_compile_s", "setup.other_compile_s",
        "setup.cache_misses", "setup.unattributed_s",
        "moe.load_max_over_mean"}
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == NEW
    for m in new:
        reader = _reader(m["name"])
        assert (reader.NAME, reader.LAYER, reader.UNIT, reader.MOVES,
                reader.SOURCE) == (m["name"], m["layer"], m["unit"],
                                   m["moves"], m["source"])
        assert m["workloads"] == [CELL]
    file_cell = json.load(open(os.path.join(BENCH, "workloads",
                                            CELL + ".json")))
    assert file_cell["traffic"]["name"] == cell["traffic"]
    assert file_cell["driver"] == "train_blockdiff_tokens"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("length,b", [(16, 4), (24, 2), (12, 1), (64, 4)])
def test_the_visible_pairs_are_a_brute_force_count_of_the_references_mask(
        length, b):
    """``L^2 + L b`` is what the reference's own ``[2L, 2L]`` rule shows,
    counted pair by pair; and its parts."""
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_blockdiff_trunk as flops
    from benchmarks.lib.reference_blockdiff_trunk import visible
    seen = np.asarray(visible(np.arange(2 * length)[:, None],
                              np.arange(2 * length)[None, :], length, b))
    beta = np.arange(length) // b
    want = np.zeros((2 * length, 2 * length), bool)
    for p in range(length):
        for r in range(length):
            want[length + p, length + r] = beta[r] <= beta[p]
            want[p, length + r] = beta[r] < beta[p]
            want[p, r] = beta[r] == beta[p]
    np.testing.assert_array_equal(seen, want)
    assert int(seen.sum()) == flops.visible_pairs(length, b)
    assert not seen[length:, :length].any()            # clean on noised
    assert seen.any(axis=1).all()                      # every row sees a key


def test_the_counts_are_the_configurations():
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_blockdiff_trunk as flops
    conf = _conf()
    assert flops.applies(conf)
    assert flops.visible_pairs(4096, 4) == 16_793_600
    assert flops.core_macs_per_pair(conf) == 32 * 256
    # 4 rows a pass x 5 layers x (3 forwards + a backward of 2.5)
    assert flops.core_flops(conf) == 2 * 32 * 256 * 16_793_600 * 4 * 5 * 5.5
    assert flops.core_flops(conf) == pytest.approx(30.27e12, rel=1e-3)
    # q and o 32 x 128, k and v 4 x 128, bf16, 32,768 positions, 5 passes
    assert flops.core_bytes(conf) == (2 * 32 + 2 * 4) * 128 * 2 * 32768 \
        * 5 * 5
    macs = flops.forward_macs_per_position(conf, 4096)
    assert macs["projections"] == 5 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    assert macs["core"] == 5 * 32 * 256 * 16_793_600 / 8192
    assert macs["routed_experts"] == 5 * 3 * 2048 * 768     # top-8 x 16/128
    assert sum(macs.values()) == pytest.approx(203.3e6, rel=2e-3)
    assert flops.train_flops_per_sample(conf, 4096) == pytest.approx(
        26.65e12, rel=2e-3)


def test_the_new_readers_read_a_canned_trace_and_no_other_configuration(
        monkeypatch):
    """``blockdiff`` ops of a canned step trace; the roofline share is the
    count's least time over the scope's.  Another trunk's configuration, a
    missing trace and a program that names no such scope (the parent) read
    nothing, and nothing raises."""
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_blockdiff_trunk, trace_decoder_trunk
    from benchmarks.lib.trace_blockdiff_trunk import RATE_COUNTER
    conf = _conf()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [("jit(train_step)/online_forward/layer1/blockdiff/core/"
            "causal_attention_fwd", 0.2),
           ("jit(train_step)/transpose(jvp(layer1))/blockdiff/core/"
            "causal_attention_bwd", 0.4),
           ("jit(train_step)/online_forward/layer1/blockdiff/q/dot", 0.5),
           ("jit(train_step)/online_forward/layer1/moe/route/sort", 0.3),
           ("jit(train_step)/online_forward/layer1/gqa/core/x", 0.7)]
    canned = {"steps": 2, "ops": ops,
              "reduced": {"op_s": 2.0, "phase_s": {"update": 0.05}}}
    monkeypatch.setattr(
        trace_decoder_trunk, "step_trace",
        lambda sources: canned if sources["trace"] and
        trace_decoder_trunk.RATE_COUNTER in sources["counters"] else None)
    src = lambda config, trace=True: {
        "trace": trace, "config": config, "peaks": peaks, "meter": None,
        "counters": {RATE_COUNTER: 2.0}, "cell": {"name": CELL}}
    read = lambda name, *a, **kw: _reader(name).read(src(*a, **kw))
    assert read("train_step.blockdiff_core_ms", conf) == pytest.approx(300.0)
    assert read("train_step.blockdiff_ms", conf) == pytest.approx(550.0)
    assert read("train_step.blockdiff_moe_ms", conf) == pytest.approx(150.0)
    assert read("train_step.blockdiff_update_share", conf) == pytest.approx(
        2.5)
    least_ms = flops_blockdiff_trunk.core_flops(conf) / 197e12 * 1e3
    assert least_ms == pytest.approx(153.7, rel=1e-3)      # operation-bound
    assert read("blockdiff.core_roofline", conf) == pytest.approx(
        100 * least_ms / 300.0)
    # 2 samples/s x 26.65 TFLOP a sample / 197 TFLOP/s
    assert read("train_step.blockdiff_seq_mfu", conf) == pytest.approx(
        100 * 2.0 * 26.65e12 / 197e12, rel=2e-3)
    assert all(read(n, conf) < 100.0 for n in NEW if n.endswith(
        ("roofline", "mfu", "share")))
    canned["ops"] = ops[3:]                # the parent: no such scope
    for name in NEW[:3]:
        assert read(name, conf) is None, name
    canned["ops"] = ops
    for name in NEW:
        assert read(name, conf, trace=None) is None or name.endswith("mfu")
        for other in ("byol_xing4_29b_a4b_ep8", "byol_lfm2_24b_a2b_ep8",
                      "byol_keye_vl2_30b_a3b_ep8",
                      "byol_qwen3next_80b_a3b_ep16",
                      "byol_joyai_llm_flash_ep16"):
            theirs = json.load(open(os.path.join(BENCH, "configs",
                                                 other + ".json")))
            assert read(name, theirs) is None, (name, other)
    # the other trunks' readers find nothing in this cell's run
    for name in ("train_step.seq_mfu", "train_step.moe_ms",
                 "train_step.sparse_seq_mfu", "train_step.hybrid_seq_mfu",
                 "train_step.shortconv_seq_mfu", "dsa.core_roofline",
                 "gqa.core_roofline", "mla.core_roofline", "train_step.mfu",
                 "train_step.dsa_ms", "train_step.mla_core_ms"):
        assert read(name, conf) is None, name
