"""The token cell's path end to end on the CPU at tiny size: a tiny
decoder-trunk configuration and a tiny cell ADDED as files to a copy of
``benchmarks/`` (as test_end_to_end.py adds its own), driven through
``run.py --rehearse-cpu`` by ``drivers/train_tokens.py``, the new readers
beside the old ones."""
import json
import os
import shutil

import pytest

from conftest import BENCH, TIGHT_F32, run_cell
from test_end_to_end import _last

TINY = dict(
    name="tiny_trunk_f32", arch="decoder_trunk_tiny", seq_len=16,
    layer_share="1/2", trunk_depth="1+2", hidden_size=64,
    intermediate_size=160, moe_intermediate_size=32, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_hidden_layers=3, first_k_dense_replace=1, n_routed_experts=4,
    num_attention_heads=2, num_key_value_heads=2, num_experts_per_tok=2,
    vocab_size=64, hc_mult=2, head_latent_size=64, projection_size=32,
    num_classes=10, per_chip_batch=4, precision="float32",
    published={"n_routed_experts": 8, "num_attention_heads": 4,
               "vocab_size": 128})


@pytest.fixture(scope="module")
def token_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_tokens")
    dst = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.load(open(os.path.join(
        BENCH, "configs", "byol_xing4_29b_a4b_ep8.json")))
    conf.update(TINY)
    conf["rope_scaling"] = dict(conf["rope_scaling"],
                                original_max_position_embeddings=16)
    flags = conf["flags"]
    for flag, key in (("--arch", "arch"), ("--seq-len", "seq_len"),
                      ("--layer-share", "layer_share"),
                      ("--trunk-depth", "trunk_depth"),
                      ("--head-latent-size", "head_latent_size"),
                      ("--projection-size", "projection_size")):
        flags[flags.index(flag) + 1] = str(conf[key])
    flags.append("--no-half")
    cell = json.load(open(os.path.join(
        BENCH, "workloads", "xing4_train_b8_s1024.json")))
    cell.update(name="tiny_trunk_train", config="tiny_trunk_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = TIGHT_F32
    for rel, obj in (("configs/tiny_trunk_f32.json", conf),
                     ("workloads/tiny_trunk_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_token_cell_runs_and_agrees_in_float32(token_copy):
    rc, out, err = run_cell(token_copy, "tiny_trunk_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["traced_end_to_end"]) == {
        "setup_s", "train_images_per_s_per_chip"}
    # on the CPU: the counters' readers report, the device's stay silent
    # (and the image readers find no image counter)
    assert set(line["metrics"]) == {"train_step.step_ms",
                                    "input.host_feed_ms",
                                    "moe.load_max_over_mean"}
    assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 4
    assert any("rows dropped 0" in ln for ln in out)


def test_a_step_that_skips_the_shared_expert_is_not_correct(token_copy):
    rc, out, err = run_cell(
        token_copy, "tiny_trunk_train", trace=0,
        script=os.path.join("tests", "broken_shared_expert.py"))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("] check: loss_rel_gap" in ln and "OVER" in ln for ln in out)
