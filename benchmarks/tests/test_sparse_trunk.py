"""The sparse-attention trunk's cell end to end on the CPU at tiny size: a
tiny configuration and a tiny cell ADDED as files to a copy of
``benchmarks/`` (as test_hybrid_trunk.py adds its own), driven through
``run.py --rehearse-cpu`` by ``drivers/train_sparse_tokens.py``, the new
readers beside the old ones, and two broken twins that ``correct`` refuses."""
import importlib.util
import json
import os
import shutil
import sys

import pytest

from conftest import BENCH, TIGHT_F32, run_cell
from test_end_to_end import _last

CONFIG = "byol_keye_vl2_30b_a3b_ep8"
CELL = "keye_train_b4_s4096"
TINY = dict(
    name="tiny_sparse_f32", arch="sparse_trunk_tiny", seq_len=20,
    layer_share="1/4,vocab=2,heads=1", trunk_depth="0+2", hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=2, num_experts=2,
    num_local_experts=8, num_experts_per_tok=3, vocab_size=64,
    sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                   indexer_num_kv_heads=1, kv_chunk_size=8, q_chunk_size=8,
                   topk=6),
    head_latent_size=64, projection_size=32, num_classes=10,
    per_chip_batch=4, precision="float32",
    published={"num_experts": 8, "vocab_size": 128, "num_hidden_layers": 2})


@pytest.fixture(scope="module")
def sparse_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_sparse")
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
    cell.update(name="tiny_sparse_train", config="tiny_sparse_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = TIGHT_F32
    for rel, obj in (("configs/tiny_sparse_f32.json", conf),
                     ("workloads/tiny_sparse_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_sparse_cell_runs_and_agrees_in_float32(sparse_copy):
    rc, out, err = run_cell(sparse_copy, "tiny_sparse_train", trace=1)
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
        "train_step.step_ms", "input.host_feed_ms", "moe.load_max_over_mean",
        "dsa.selected_share"}
    # 20 tokens, 6 keys a query: (21 + 14 x 6) / 210
    assert line["metrics"]["dsa.selected_share"]["value"] == \
        pytest.approx(105 / 210)
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 4
    assert any("rows dropped 0" in ln for ln in out)
    assert any("the reference's index losses" in ln for ln in out)


@pytest.mark.parametrize("twin", ["broken_attends_every_key.py",
                                  "broken_keeps_recent_keys.py"])
def test_a_step_that_selects_other_keys_is_not_correct(sparse_copy, twin):
    rc, out, err = run_cell(sparse_copy, "tiny_sparse_train", trace=0,
                            script=os.path.join("tests", twin))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("] check: " in ln and "OVER" in ln for ln in out)


def test_the_seeded_embedding_outweighs_what_a_layer_adds():
    """The routers must see the token, not the layers before them: the
    seeded embedding's rows are as large as a unit-variance stream, the
    kernels that write to the stream LeCun-normal."""
    import jax
    import numpy as np
    sys.path.insert(0, os.path.dirname(BENCH))
    from benchmarks.lib.weights_sparse_trunk import make_weights
    like = {"backbone": {
        "embed": {"embedding": jax.ShapeDtypeStruct((512, 256), np.float32)},
        "layer0": {"dsa": {"o": {"kernel": jax.ShapeDtypeStruct(
            (1024, 256), np.float32)}}}}}
    backbone = make_weights(like, {}, 7)[0]["backbone"]
    assert np.std(backbone["embed"]["embedding"]) == pytest.approx(1.0, 0.02)
    assert np.std(backbone["layer0"]["dsa"]["o"]["kernel"]) == pytest.approx(
        1024 ** -0.5, 0.02)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_new_readers_read_their_architecture_and_no_other():
    """With a peak, this driver's counter and this architecture the MFU
    reader reports; for another architecture (or the trunk's own counter)
    it stays silent, as the other trunks' readers do here; the trace
    readers return None without a trace."""
    sys.path.insert(0, os.path.dirname(BENCH))
    conf = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    other = json.load(open(os.path.join(
        BENCH, "configs", "byol_qwen3next_80b_a3b_ep16.json")))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    pairs = 8 * 4096 * 4097 // 2
    src = lambda counter, config: {
        "trace": None, "config": config, "peaks": peaks, "meter": None,
        "counters": {counter: 2.0, "sel_causal_pairs": [4 * pairs] * 3,
                     "sel_selected_pairs": [4 * 8 * 6292480] * 3},
        "cell": {"name": CELL}}
    mine = src("train_sparse_sequences_per_s_per_chip", conf)
    mfu = _reader("train_step.sparse_seq_mfu").read(mine)
    # 2 sequences/s x about 10 TFLOP a sequence / 197 TFLOP/s
    assert 5.0 < mfu < 20.0
    assert _reader("dsa.selected_share").read(mine) == pytest.approx(
        0.74994, abs=1e-5)
    for theirs in (src("train_sparse_sequences_per_s_per_chip", other),
                   src("train_sequences_per_s_per_chip", conf)):
        assert _reader("train_step.sparse_seq_mfu").read(theirs) is None
        assert _reader("dsa.selected_share").read(theirs) is None
    for name in ("train_step.seq_mfu", "train_step.hybrid_seq_mfu",
                 "train_step.dsa_ms", "train_step.dsa_index_ms",
                 "train_step.dsa_select_ms", "train_step.sparse_moe_ms",
                 "train_step.sparse_update_share", "dsa.core_roofline",
                 "dsa.index_roofline", "train_step.gqa_ms",
                 "gqa.core_roofline", "train_step.moe_ms",
                 "moe.expert_matmul_roofline"):
        assert _reader(name).read(mine) is None, name


def test_the_counts_are_the_selections():
    sys.path.insert(0, os.path.dirname(BENCH))
    from benchmarks.lib import flops_sparse_trunk as flops
    conf = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    assert flops.causal_pairs(4096) == 8390656
    assert flops.selected_pairs(4096, 2048) == 6292480
    assert flops.selected_pairs(20, 6) == 105 and flops.selected_pairs(
        5, 9) == 15
    macs = flops.forward_macs_per_token(conf, 4096)
    # the core over SELECTED pairs: 3/4 of the causal half's operations
    assert macs["core"] == 4 * 32 * 2 * 128 * 6292480 / 4096
    assert macs["index_scores"] == 4 * 16 * 64 * 8390656 / 4096
    assert macs["routed_experts"] == 4 * 8 * 16 / 128 * 3 * 2048 * 768
    assert "shared_expert" not in macs
    # one step's core from one pass's counted pairs: 5.5 passes under remat
    assert flops.core_flops(100.0, conf) == 2 * 8192 * 100.0 * 4 * 5.5
    assert flops.index_flops(100.0, conf) == 2 * 1024 * 100.0 * 4 * 6
