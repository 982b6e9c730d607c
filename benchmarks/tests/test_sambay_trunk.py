"""The decoder-hybrid-decoder trunk's cell end to end on the CPU at tiny
size: a tiny configuration and a tiny cell ADDED as files to a copy of
``benchmarks/`` (as test_blockdiff_trunk.py adds its own), driven through
``run.py --rehearse-cpu`` by ``drivers/train_sambay_tokens.py``; the contract
of the new files; the flops file's counts against brute-force counts; the
new readers on a canned trace; and the three broken twins that ``correct``
refuses."""
import importlib.util
import json
import os
import shutil
import sys

import numpy as np
import pytest

from conftest import BENCH, REPO, TIGHT_F32, run_cell
from test_end_to_end import _last

CONFIG = "byol_phi4_mini_flash_vp8"
CELL = "phi4flash_train_b2_s8192"
NEW = ["train_step.ssm_ms", "train_step.ssm_scan_ms", "ssm.scan_roofline",
       "train_step.diff_ms", "train_step.diff_core_ms", "diff.core_roofline",
       "diff.band_tile_share", "train_step.gmu_ms",
       "train_step.sambay_ffn_ms", "train_step.sambay_update_share",
       "train_step.sambay_seq_mfu", "ssm.dt_max"]
COUNTED = ["diff.band_tile_share", "ssm.dt_max"]      # no device needed
TINY = dict(
    name="tiny_sambay_f32", arch="sambay_tiny", seq_len=32,
    layer_share="0/2,heads=1", trunk_depth="5-9", kept_layers=[5, 9],
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, sliding_window=8, d_state=4,
    dt_rank=4, num_hidden_layers=5, vocab_size=64, head_latent_size=64,
    projection_size=32, num_classes=10, per_chip_batch=4,
    precision="float32",
    published={"vocab_size": 128, "num_hidden_layers": 12})


def _conf():
    return json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def sambay_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_sambay")
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
    cell.update(name="tiny_sambay_train", config="tiny_sambay_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = dict(TIGHT_F32, early_hidden_gap=1e-4)
    for rel, obj in (("configs/tiny_sambay_f32.json", conf),
                     ("workloads/tiny_sambay_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_sambay_cell_runs_and_agrees_in_float32(sambay_copy):
    rc, out, err = run_cell(sambay_copy, "tiny_sambay_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["traced_end_to_end"]) == {
        "setup_s", "train_images_per_s_per_chip"}
    # on the CPU: the counters' readers report, the device's stay silent
    assert set(line["metrics"]) == {
        "train_step.step_ms", "input.host_feed_ms", *COUNTED}
    # 32 positions in tiles of 8 under a window of 8: 7 of 10 tiles
    assert line["metrics"]["diff.band_tile_share"]["value"] == 70.0
    assert 0 < line["metrics"]["ssm.dt_max"]["value"] < 10
    # five numbers held to a limit, the momentum through the heads read
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 5
    assert sum("through_heads" in ln and "not compared" in ln
               for ln in out) == 1


@pytest.mark.parametrize("script,over", [
    # a band layer that sees every causal key: positions past the window
    ("broken_band_sees_every_key.py", ("early_hidden_gap", "grad_dir_gap")),
    # lambda = 0, one softmax a pair
    ("broken_one_softmax.py", ("early_hidden_gap", "grad_dir_gap")),
    # the cross-decoder reads its own layer's input, not what was handed on
    ("broken_reads_its_own_input.py", ("early_hidden_gap", "grad_dir_gap")),
    # a state left as it was: the LARS-scaled leaves' change reads 1
    ("broken_step.py", ("update_norm_gap",)),
    # half the samples never reach the program: the loss
    ("broken_half_batch.py", ("loss_rel_gap",))])
def test_a_broken_twin_is_not_correct(sambay_copy, script, over):
    rc, out, err = run_cell(sambay_copy, "tiny_sambay_train", trace=0,
                            script=os.path.join("tests", script))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    for name in over:
        assert any(f"] check: {name} =" in ln and "OVER" in ln
                   for ln in out), name


# ---- the contract of the new files ------------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "Phi-4-mini-flash-reasoning":
            return row
    pytest.skip("the catalog has no such row")


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    conf, row = _conf(), _catalog_row()
    assert conf["source"] == row["source_url"] and len(conf["source"]) <= 200
    differs = sorted(k for k, v in row["config"].items()
                     if conf.get(k, "absent") != v)
    assert differs == sorted(conf["reduced"]) == sorted(
        ["num_hidden_layers", "vocab_size"])
    for key in conf["reduced"]:
        assert conf["published"][key] == row["config"][key]
        assert key in conf["reduced_detail"]
    # every width as published, and the source's own constants as assumed
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["intermediate_size"], conf["sliding_window"]) == (
                2560, 40, 20, 64, 10240, 512)
    assert (conf["d_state"], conf["d_conv"], conf["expand"],
            conf["dt_rank"]) == (16, 4, 2, 160)
    # the floors of the guide's section 4; the cut names published layers
    assert conf["num_hidden_layers"] >= 4
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]
    assert conf["kept_layers"] == [15, 19] and conf["trunk_depth"] == "15-19"
    assert "RATIO" in conf["reduced_detail"]["num_hidden_layers"]
    for said in ("d_state 16", "roles by published index", "lambda_0",
                 "no positional encoding", "A_log"):
        assert any(said in a for a in conf["assumed"]), said
    assert "8 that hold the tied embedding" in conf["deployment"]


def test_the_drivers_check_of_flags_against_plain_keys():
    sys.path.insert(0, REPO)
    from benchmarks.drivers.train_sambay_tokens import program_config
    conf = _conf()
    cfg = program_config(conf, seed=3, chips=1)
    assert (cfg.model.arch, cfg.model.trunk_depth, cfg.task.seq_len) == (
        "phi4_mini_flash", "15-19", 8192)
    with pytest.raises(ValueError, match="kept layers"):
        program_config(dict(conf, kept_layers=[14, 19]), seed=3, chips=1)
    with pytest.raises(ValueError, match="seq_len"):
        program_config(dict(conf, seq_len=4096), seed=3, chips=1)


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
        "setup.cache_misses", "setup.unattributed_s"}
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
    assert file_cell["driver"] == "train_sambay_tokens"
    assert set(file_cell["check"]["limits"]) == {
        "loss_rel_gap", "early_hidden_gap", "grad_norm_gap", "grad_dir_gap",
        "update_norm_gap"}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("length,window,tile", [
    (32, 8, 8), (32, 5, 8), (40, 12, 8), (24, 100, 8), (16, 0, 4)])
def test_the_pairs_and_tiles_are_brute_force_counts_of_the_references_rule(
        length, window, tile):
    """Visible pairs are what the reference's own ``[S, S]`` rule shows,
    counted pair by pair; formed tiles are the tiles that hold one."""
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_sambay_trunk as flops
    from benchmarks.lib.reference_sambay_trunk import visible
    seen = np.asarray(visible(np.arange(length)[:, None],
                              np.arange(length)[None, :], window))
    want = np.zeros((length, length), bool)
    for t in range(length):
        for r in range(length):
            want[t, r] = r <= t and (not window or t - r < window)
    np.testing.assert_array_equal(seen, want)
    assert int(seen.sum()) == flops.visible_pairs(length, window)
    blocks = length // tile
    held = seen.reshape(blocks, tile, blocks, tile).any(axis=(1, 3))
    assert int(held.sum()) == flops.formed_tiles(length, window, tile)
    assert seen.any(axis=1).all()                      # every row sees a key


def test_the_roles_are_the_published_ones():
    sys.path.insert(0, REPO)
    from benchmarks.lib.reference_sambay_trunk import role
    kinds = [role(i, 32, 2) for i in range(32)]
    assert [kinds.count(k) for k in ("ssm", "band", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[15:20] == ["band", "ssm", "full", "gmu", "cross"]
    assert kinds[:5] == ["ssm", "band", "ssm", "band", "ssm"]


def test_the_counts_are_the_configurations():
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_sambay_trunk as flops
    conf = _conf()
    assert flops.applies(conf)
    assert flops.roles(conf) == ["band", "ssm", "full", "gmu", "cross"]
    assert flops.formed_tiles(8192, 512) == 31
    assert flops.formed_tiles(8192) == 136
    assert flops.band_tile_share(conf) == pytest.approx(100 * 31 / 136)
    # 20 pairs x 2 softmaxes x (64 + 128) = 40 heads x 192
    assert flops.core_macs_per_pair(conf) == 7680
    assert flops.core_macs_per_pair(conf, backward=True) == 40 * 448
    macs = flops.forward_macs_per_position(conf, 8192)
    assert macs["ffn"] == 5 * 3 * 2560 * 10240
    assert macs["ffn"] == pytest.approx(393.2e6, rel=1e-3)
    assert macs["projections"] == pytest.approx(119.9e6, rel=2e-3)
    # the triangle twice and the band: 4,096.5 and 496 visible keys a query
    assert macs["cores"] == pytest.approx(7680 * (2 * 4096.5 + 496.03),
                                          rel=1e-4)
    assert sum(macs.values()) == pytest.approx(580e6, rel=5e-3)
    assert flops.train_flops_per_sample(conf, 8192) == pytest.approx(
        76.0e12, rel=5e-3)
    # 303 tiles of 512 x 512 a row, 4 rows, 3 forwards and a backward
    assert flops.core_flops(conf) == 2 * 303 * 512 * 512 * 4 * (
        3 * 7680 + 40 * 448)
    assert flops.core_flops(conf) == pytest.approx(26.0e12, rel=5e-3)
    # the scan: 32,768 positions x 5,120 channels x 16 states a pass
    assert flops.scan_elements(conf) == 32768 * 5120 * 16
    wide, narrow = 4 * 32768 * 5120, 4 * 32768 * 16
    borders = wide * 16 / 128
    assert flops.scan_bytes(conf) == 3 * (3 * wide + 2 * narrow) \
        + 2 * borders + 5 * wide + 4 * narrow + borders
    assert flops.scan_bytes(conf) == pytest.approx(9.66e9, rel=5e-3)


def test_the_new_readers_read_a_canned_trace_and_no_other_configuration(
        monkeypatch):
    """This trunk's ops of a canned step trace; a roofline share is the
    count's least time over the scope's.  Another trunk's configuration, a
    missing trace and a program that names no such scope (the parent) read
    nothing, and nothing raises."""
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_sambay_trunk, trace_decoder_trunk
    from benchmarks.lib.trace_sambay_trunk import RATE_COUNTER
    conf = _conf()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [("jit(train_step)/online_forward/layer1/ssm/scan/"
            "selective_scan_fwd", 0.02),
           ("jit(train_step)/transpose(jvp(layer1))/ssm/scan/"
            "selective_scan_bwd", 0.06),
           ("jit(train_step)/online_forward/layer1/ssm/proj/dot", 0.1),
           ("jit(train_step)/online_forward/layer0/diff/core/"
            "causal_attention_fwd", 0.2),
           ("jit(train_step)/transpose(jvp(layer2))/diff/core/"
            "causal_attention_bwd", 0.4),
           ("jit(train_step)/online_forward/layer2/diff/qkv/dot", 0.1),
           ("jit(train_step)/online_forward/layer3/gmu/in_proj/dot", 0.05),
           ("jit(train_step)/online_forward/layer3/ffn/gate/dot", 1.5),
           ("jit(train_step)/online_forward/layer1/gqa/core/x", 0.7)]
    canned = {"steps": 2, "ops": ops,
              "reduced": {"op_s": 4.0, "phase_s": {"update": 0.1}}}
    monkeypatch.setattr(
        trace_decoder_trunk, "step_trace",
        lambda sources: canned if sources["trace"] and
        trace_decoder_trunk.RATE_COUNTER in sources["counters"] else None)
    src = lambda config, trace=True: {
        "trace": trace, "config": config, "peaks": peaks, "meter": None,
        "counters": {RATE_COUNTER: 1.0, "ssm_dt_max": [0.3, 0.5, 0.4],
                     "diff_band_tiles": 31, "diff_causal_tiles": 136},
        "cell": {"name": CELL}}
    read = lambda name, *a, **kw: _reader(name).read(src(*a, **kw))
    assert read("train_step.ssm_ms", conf) == pytest.approx(90.0)
    assert read("train_step.ssm_scan_ms", conf) == pytest.approx(40.0)
    assert read("train_step.diff_ms", conf) == pytest.approx(350.0)
    assert read("train_step.diff_core_ms", conf) == pytest.approx(300.0)
    assert read("train_step.gmu_ms", conf) == pytest.approx(25.0)
    assert read("train_step.sambay_ffn_ms", conf) == pytest.approx(750.0)
    assert read("train_step.sambay_update_share", conf) == pytest.approx(2.5)
    assert read("diff.band_tile_share", conf) == pytest.approx(
        100 * 31 / 136)
    assert read("ssm.dt_max", conf) == 0.4
    least_ms = flops_sambay_trunk.scan_bytes(conf) / 819e9 * 1e3
    assert read("ssm.scan_roofline", conf) == pytest.approx(
        100 * least_ms / 40.0)
    least_ms = flops_sambay_trunk.core_flops(conf) / 197e12 * 1e3
    assert read("diff.core_roofline", conf) == pytest.approx(
        100 * least_ms / 300.0)
    # 1 sample/s x 76 TFLOP a sample / 197 TFLOP/s
    assert read("train_step.sambay_seq_mfu", conf) == pytest.approx(
        100 * 76.0e12 / 197e12, rel=5e-3)
    assert all(read(n, conf) < 100.0 for n in NEW if n.endswith(
        ("roofline", "mfu", "share")))
    canned["ops"] = ops[-1:]               # the parent: no such scope
    for name in NEW:
        if name not in COUNTED and not name.endswith(("mfu", "share")):
            assert read(name, conf) is None, name
    canned["ops"] = ops
    for name in NEW:
        assert read(name, conf, trace=None) is None or name in COUNTED \
            or name.endswith("mfu")
        for other in ("byol_xing4_29b_a4b_ep8", "byol_lfm2_24b_a2b_ep8",
                      "byol_keye_vl2_30b_a3b_ep8",
                      "byol_qwen3next_80b_a3b_ep16",
                      "byol_joyai_llm_flash_ep16", "byol_sdar_30b_a3b_ep8"):
            theirs = json.load(open(os.path.join(BENCH, "configs",
                                                 other + ".json")))
            assert read(name, theirs) is None, (name, other)
    # the other trunks' readers find nothing in this cell's run
    for name in ("train_step.seq_mfu", "train_step.moe_ms",
                 "train_step.sparse_seq_mfu", "train_step.hybrid_seq_mfu",
                 "train_step.shortconv_seq_mfu", "dsa.core_roofline",
                 "gqa.core_roofline", "mla.core_roofline", "train_step.mfu",
                 "train_step.blockdiff_seq_mfu", "blockdiff.core_roofline",
                 "train_step.blockdiff_ms", "moe.load_max_over_mean",
                 "train_step.shortconv_ffn_ms", "train_step.gdn_ms"):
        assert read(name, conf) is None, name
