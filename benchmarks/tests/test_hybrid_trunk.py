"""The patterned trunk's cell end to end on the CPU at tiny size: a tiny
configuration and a tiny cell ADDED as files to a copy of ``benchmarks/``
(as test_decoder_trunk.py adds its own), driven through ``run.py
--rehearse-cpu`` by ``drivers/train_hybrid_tokens.py``, the new readers
beside the old ones."""
import json
import os
import shutil

import pytest

from conftest import BENCH, TIGHT_F32, run_cell
from test_end_to_end import _last

TINY = dict(
    name="tiny_hybrid_f32", arch="hybrid_trunk_tiny", seq_len=20,
    layer_share="1/4,vocab=2,heads=1", trunk_depth="0+4", hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, full_attention_interval=2,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.5, num_hidden_layers=4,
    num_experts=2, num_experts_per_tok=3, vocab_size=64,
    head_latent_size=64, projection_size=32, num_classes=10,
    per_chip_batch=4, precision="float32",
    published={"num_experts": 8, "vocab_size": 128, "num_hidden_layers": 4})


@pytest.fixture(scope="module")
def hybrid_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_hybrid")
    dst = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.load(open(os.path.join(
        BENCH, "configs", "byol_qwen3next_80b_a3b_ep16.json")))
    conf.update(TINY)
    flags = conf["flags"]
    for flag, key in (("--arch", "arch"), ("--seq-len", "seq_len"),
                      ("--layer-share", "layer_share"),
                      ("--trunk-depth", "trunk_depth"),
                      ("--head-latent-size", "head_latent_size"),
                      ("--projection-size", "projection_size")):
        flags[flags.index(flag) + 1] = str(conf[key])
    flags.append("--no-half")
    cell = json.load(open(os.path.join(
        BENCH, "workloads", "qwen3next_train_b4_s4096.json")))
    cell.update(name="tiny_hybrid_train", config="tiny_hybrid_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = TIGHT_F32
    for rel, obj in (("configs/tiny_hybrid_f32.json", conf),
                     ("workloads/tiny_hybrid_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_hybrid_cell_runs_and_agrees_in_float32(hybrid_copy):
    rc, out, err = run_cell(hybrid_copy, "tiny_hybrid_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["traced_end_to_end"]) == {
        "setup_s", "train_images_per_s_per_chip"}
    # on the CPU: the counters' readers report, the device's stay silent,
    # and no reader of the latent-attention trunk's or an image cell's
    # counter finds anything
    assert set(line["metrics"]) == {"train_step.step_ms",
                                    "input.host_feed_ms",
                                    "moe.load_max_over_mean"}
    assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 4
    assert any("rows dropped 0" in ln for ln in out)


def test_a_bias_in_front_of_a_batch_norm_is_not_compared():
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    import numpy as np
    from benchmarks.drivers import train_hybrid_tokens as driver
    head = lambda: {"dense1": {"kernel": np.ones((3, 2)), "bias": np.ones(2)},
                    "bn": {"scale": np.ones(2), "bias": np.zeros(2)},
                    "dense2": {"kernel": np.ones((2, 2)), "bias": np.ones(2)}}
    tree = lambda: {"projector": head(), "predictor": head(),
                    "backbone": {"final_norm": {"scale": np.ones(4)}}}
    kept = driver.followed({"losses": [1.0], "first_trace": tree(),
                            "params": tree()}, tree())
    for name in ("first_trace", "change"):
        for part in ("projector", "predictor"):
            assert set(kept[name][part]["dense1"]) == {"kernel"}
            assert set(kept[name][part]["dense2"]) == {"kernel", "bias"}
            assert set(kept[name][part]["bn"]) == {"scale", "bias"}


def test_a_step_without_the_decay_gate_is_not_correct(hybrid_copy):
    rc, out, err = run_cell(
        hybrid_copy, "tiny_hybrid_train", trace=0,
        script=os.path.join("tests", "broken_decay_gate.py"))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("] check: " in ln and "OVER" in ln for ln in out)


def test_the_new_readers_read_their_counter_and_no_other(hybrid_copy):
    """With a peak and this driver's counter the MFU twin reports and the
    trunk's own stays silent; with the trunk's counter, the other way
    round; the trace readers return None without a trace."""
    import importlib.util
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    conf = json.load(open(os.path.join(
        BENCH, "configs", "byol_qwen3next_80b_a3b_ep16.json")))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    src = lambda counter: {"trace": None, "counters": {counter: 4.0},
                           "config": conf, "peaks": peaks, "meter": None,
                           "cell": {"name": "qwen3next_train_b4_s4096"}}
    mine = src("train_hybrid_sequences_per_s_per_chip")
    theirs = src("train_sequences_per_s_per_chip")
    mfu = reader("train_step.hybrid_seq_mfu").read(mine)
    # 4 sequences/s x 11.7 TFLOP a sequence / 197 TFLOP/s
    assert 22.0 < mfu < 26.0
    assert reader("train_step.hybrid_seq_mfu").read(theirs) is None
    assert reader("train_step.seq_mfu").read(mine) is None
    for name in ("train_step.gdn_ms", "train_step.gqa_ms",
                 "train_step.hybrid_moe_ms", "train_step.hybrid_update_share",
                 "gdn.delta_rule_roofline", "gqa.core_roofline",
                 "train_step.moe_ms", "train_step.update_share",
                 "moe.expert_matmul_roofline"):
        assert reader(name).read(mine) is None, name
