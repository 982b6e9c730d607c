"""The short-convolution trunk's cell end to end on the CPU at tiny size: a
tiny configuration and a tiny cell ADDED as files to a copy of
``benchmarks/`` (as test_sparse_trunk.py adds its own), driven through
``run.py --rehearse-cpu`` by ``drivers/train_shortconv_tokens.py``, the new
readers beside the old ones, and two broken twins that ``correct`` refuses."""
import importlib.util
import json
import os
import shutil
import sys

import pytest

from conftest import BENCH, TIGHT_F32, run_cell
from test_end_to_end import _last

CONFIG = "byol_lfm2_24b_a2b_ep8"
CELL = "lfm2_train_b4_s4096"
TINY = dict(
    name="tiny_shortconv_f32", arch="shortconv_trunk_tiny", seq_len=20,
    layer_share="1/4,vocab=2,heads=1", trunk_depth="1+4", hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16, num_attention_heads=4,
    num_key_value_heads=2, num_experts=2, num_experts_per_tok=2,
    vocab_size=64, head_latent_size=64, projection_size=32, num_classes=10,
    per_chip_batch=4, precision="float32",
    published={"num_experts": 8, "vocab_size": 128, "num_hidden_layers": 7})


@pytest.fixture(scope="module")
def shortconv_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_shortconv")
    dst = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
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
    cell.update(name="tiny_shortconv_train", config="tiny_shortconv_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = TIGHT_F32
    for rel, obj in (("configs/tiny_shortconv_f32.json", conf),
                     ("workloads/tiny_shortconv_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_shortconv_cell_runs_and_agrees_in_float32(shortconv_copy):
    rc, out, err = run_cell(shortconv_copy, "tiny_shortconv_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["traced_end_to_end"]) == {
        "setup_s", "train_images_per_s_per_chip"}
    # on the CPU: the counters' readers report, the device's stay silent,
    # and no reader of another trunk's or an image cell's counter finds
    # anything
    assert set(line["metrics"]) == {
        "train_step.step_ms", "input.host_feed_ms", "moe.load_max_over_mean"}
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 4
    assert any("rows dropped 0" in ln for ln in out)


@pytest.mark.parametrize("twin", ["broken_acausal_conv.py",
                                  "broken_bias_in_weights.py"])
def test_a_step_with_a_broken_mixer_or_router_is_not_correct(
        shortconv_copy, twin):
    rc, out, err = run_cell(shortconv_copy, "tiny_shortconv_train", trace=0,
                            script=os.path.join("tests", twin))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("] check: " in ln and "OVER" in ln for ln in out)


def test_the_seeded_bias_is_small_and_the_taps_have_their_fan_in():
    import jax
    import numpy as np
    sys.path.insert(0, os.path.dirname(BENCH))
    from benchmarks.lib.weights_shortconv_trunk import BIAS_STD, make_weights
    like = {"backbone": {
        "embed": {"embedding": jax.ShapeDtypeStruct((512, 256), np.float32)},
        "layer0": {"shortconv": {"conv": jax.ShapeDtypeStruct(
            (3, 4096), np.float32)},
            "moe": {"e_score_correction_bias": jax.ShapeDtypeStruct(
                (4096,), np.float32)}}}}
    backbone = make_weights(like, {}, 7)[0]["backbone"]
    assert np.std(backbone["embed"]["embedding"]) == pytest.approx(1.0, 0.02)
    assert np.std(backbone["layer0"]["shortconv"]["conv"]) == pytest.approx(
        3 ** -0.5, 0.03)
    bias = backbone["layer0"]["moe"]["e_score_correction_bias"]
    assert np.std(bias) == pytest.approx(BIAS_STD, 0.05) and BIAS_STD > 0
    # the sparse-attention trunk's rule is its own again
    from benchmarks.lib import weights_sparse_trunk
    with pytest.raises(KeyError):
        weights_sparse_trunk._leaf(["backbone", "x", "conv"], (3, 4), None)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NEW = ("train_step.shortconv_ms", "shortconv.core_roofline",
       "train_step.shortconv_gqa_ms", "shortconv_gqa.core_roofline",
       "train_step.shortconv_ffn_ms", "train_step.shortconv_moe_ms",
       "train_step.shortconv_update_share", "train_step.shortconv_seq_mfu")


def test_the_new_readers_read_their_architecture_and_no_other():
    """With a peak, this driver's counter and this architecture the MFU
    reader reports; for another architecture (or the trunk's own counter)
    every new reader stays silent, as the other trunks' readers do here; the
    trace readers return None without a trace."""
    sys.path.insert(0, os.path.dirname(BENCH))
    conf = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    src = lambda counter, config: {
        "trace": None, "config": config, "peaks": peaks, "meter": None,
        "counters": {counter: 3.5}, "cell": {"name": CELL}}
    mine = src("train_shortconv_sequences_per_s_per_chip", conf)
    mfu = _reader("train_step.shortconv_seq_mfu").read(mine)
    # 3.5 sequences/s x 11.65 TFLOP a sequence / 197 TFLOP/s
    assert mfu == pytest.approx(3.5 * 11.65 / 197 * 100, rel=2e-3)
    assert [n for n in NEW if _reader(n).read(mine) is not None] == [
        "train_step.shortconv_seq_mfu"]
    others = [json.load(open(os.path.join(BENCH, "configs", name)))
              for name in ("byol_qwen3next_80b_a3b_ep16.json",
                           "byol_keye_vl2_30b_a3b_ep8.json",
                           "byol_xing4_29b_a4b_ep8.json")]
    for theirs in [src("train_shortconv_sequences_per_s_per_chip", other)
                   for other in others] + [
                       src("train_sequences_per_s_per_chip", conf),
                       src("train_hybrid_sequences_per_s_per_chip", conf)]:
        for name in NEW:
            assert _reader(name).read(theirs) is None, name
    # no accepted trunk reader answers this cell by accident
    for name in ("train_step.seq_mfu", "train_step.hybrid_seq_mfu",
                 "train_step.sparse_seq_mfu", "train_step.gqa_ms",
                 "gqa.core_roofline", "train_step.gdn_ms",
                 "gdn.delta_rule_roofline", "train_step.hybrid_moe_ms",
                 "train_step.sparse_moe_ms", "train_step.moe_ms",
                 "train_step.update_share", "train_step.dsa_ms",
                 "dsa.core_roofline", "dsa.selected_share",
                 "moe.expert_matmul_roofline", "train_step.mfu"):
        assert _reader(name).read(mine) is None, name
    for key in ("full_attention_interval", "sa_config", "kv_lora_rank",
                "q_lora_rank", "head_dim"):
        assert key not in conf, key


def test_the_counts_are_the_configurations():
    sys.path.insert(0, os.path.dirname(BENCH))
    from benchmarks.lib import flops_shortconv_trunk as flops
    conf = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    assert flops.layer_counts(conf) == (4, 1, 1, 4)
    macs = flops.forward_macs_per_token(conf, 4096)
    assert macs["shortconv_projections"] == 4 * 4 * 2048 ** 2      # 67.1 M
    assert macs["gqa_projections"] == 2 * 2048 ** 2 + 2 * 2048 * 512
    assert macs["gqa_core"] == 32 * 2 * 64 * 4097 / 2               # 8.4 M
    assert macs["dense_ffn"] == 3 * 2048 * 11776                    # 72.4 M
    assert macs["routed_experts"] == 4 * 0.5 * 3 * 2048 * 1536      # 18.9 M
    assert sum(macs.values()) == pytest.approx(177.8e6, rel=1e-3)
    assert flops.train_flops_per_sequence(conf, 4096) == pytest.approx(
        11.65e12, rel=1e-3)
    tokens = 32768
    # 3 forwards of 4D elements and a backward of 7D, bf16, four layers
    assert flops.conv_core_bytes(conf) == (3 * 4 + 7) * 2048 * 2 * tokens * 4
    # q, o of 32 heads and k, v of 8, 64 wide, bf16: 5 passes, one layer
    assert flops.core_bytes(conf) == 80 * 64 * 2 * tokens * 5
    assert flops.core_flops(conf) == 2 * 4096 * 4097 / 2 * tokens * 5.5
    # the configuration is the catalog's, but for what `reduced` lists
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "num_experts", "vocab_size", "layer_types"]
    assert conf["layer_types"] == [conf["published"]["layer_types"][i]
                                   for i in conf["published"]["kept_layers"]]
