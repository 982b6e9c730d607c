"""The one-stream latent-attention trunk's cell end to end on the CPU at tiny
size: a tiny configuration and a tiny cell ADDED as files to a copy of
``benchmarks/`` (as test_shortconv_trunk.py adds its own), driven through
``run.py --rehearse-cpu`` by ``drivers/train_latent_tokens.py``; the contract
of the new files; the two new readers on a canned trace; and the broken twin
that ``correct`` refuses."""
import importlib.util
import json
import os
import shutil
import sys

import pytest

from conftest import BENCH, REPO, TIGHT_F32, run_cell
from test_end_to_end import _last

CONFIG = "byol_joyai_llm_flash_ep16"
CELL = "joyai_train_b4_s4096"
TINY = dict(
    name="tiny_latent_f32", arch="latent_trunk_tiny", seq_len=20,
    layer_share="1/4,vocab=2,heads=1", trunk_depth="1+2", hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=8,
    n_routed_experts=4, num_experts_per_tok=4, num_hidden_layers=3,
    vocab_size=64, head_latent_size=64, projection_size=32, num_classes=10,
    per_chip_batch=4, precision="float32",
    published={"n_routed_experts": 16, "vocab_size": 128,
               "num_hidden_layers": 3})


def _conf():
    return json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def latent_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_latent")
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
    cell.update(name="tiny_latent_train", config="tiny_latent_f32")
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = TIGHT_F32
    for rel, obj in (("configs/tiny_latent_f32.json", conf),
                     ("workloads/tiny_latent_train.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    return str(root)


def test_latent_cell_runs_and_agrees_in_float32(latent_copy):
    rc, out, err = run_cell(latent_copy, "tiny_latent_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["traced_end_to_end"]) == {
        "setup_s", "train_images_per_s_per_chip"}
    # on the CPU: the counters' readers report, the device's stay silent
    assert set(line["metrics"]) == {
        "train_step.step_ms", "input.host_feed_ms", "moe.load_max_over_mean"}
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 4
    assert any("rows dropped 0" in ln for ln in out)


def test_scores_without_the_shared_rotary_key_are_not_correct(latent_copy):
    rc, out, err = run_cell(
        latent_copy, "tiny_latent_train", trace=0,
        script=os.path.join("tests", "broken_no_shared_rope_key.py"))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("] check: " in ln and "OVER" in ln for ln in out)


# ---- the contract of the new files ------------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "JoyAI-LLM-Flash":
            return row
    pytest.skip("the catalog has no such row")


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    conf, row = _conf(), _catalog_row()
    assert conf["source"] == row["source_url"] and len(conf["source"]) <= 200
    differs = sorted(k for k, v in row["config"].items()
                     if conf.get(k, "absent") != v)
    assert differs == sorted(conf["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "num_nextn_predict_layers"])
    for key in conf["reduced"]:
        assert conf["published"][key] == row["config"][key]
        assert key in conf["reduced_detail"]
    # every width, the heads, the router's width and top-k as published
    assert (conf["num_attention_heads"], conf["num_experts_per_tok"],
            conf["routed_scaling_factor"], conf["first_k_dense_replace"]) == (
                32, 8, 2.5, 1)
    # the floors of the guide's section 4
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]
    assert conf["hc_mult"] == 1 and conf["rope_scaling"] is None


def test_the_benchmark_names_the_configuration_the_cell_and_the_metrics():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    conf = bench["configs"][-1]
    assert conf["name"] == CONFIG and conf["reduced"] == _conf()["reduced"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "hostfeed_pool4_b4_s4096", 1)
    assert all(len(x["why"]) <= 200 for x in (conf, cell))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "train_step.step_ms", "input.host_feed_ms", "setup.build_s",
        "setup.init_s", "setup.step_compile_s", "setup.other_compile_s",
        "setup.cache_misses", "setup.unattributed_s",
        "moe.load_max_over_mean", "train_step.moe_ms", "train_step.mla_ms",
        "train_step.update_share", "moe.expert_matmul_roofline",
        "train_step.seq_mfu", "train_step.mla_core_ms", "mla.core_roofline"}
    new = bench["per_layer"][-2:]
    assert [m["name"] for m in new] == ["train_step.mla_core_ms",
                                        "mla.core_roofline"]
    for m in new:
        reader = _reader(m["name"])
        assert (reader.NAME, reader.LAYER, reader.UNIT, reader.MOVES,
                reader.SOURCE) == (m["name"], m["layer"], m["unit"],
                                   m["moves"], m["source"])
        assert m["workloads"] == [CELL]
    file_cell = json.load(open(os.path.join(BENCH, "workloads",
                                            CELL + ".json")))
    assert file_cell["traffic"]["name"] == cell["traffic"]
    assert file_cell["driver"] == "train_latent_tokens"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_core_counts_are_the_configurations():
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_decoder_trunk, flops_latent_core as flops
    conf = _conf()
    assert flops.applies(conf)
    assert flops.core_macs_per_pair(conf) == 32 * (192 + 128)
    tokens = 32768
    # 5 layers x (3 forwards + a backward of 2.5): 37.8 TFLOP a step
    assert flops.core_flops(conf) == 2 * 32 * 320 * 4097 / 2 * tokens * 5 * 5.5
    assert flops.core_flops(conf) == pytest.approx(37.8e12, rel=2e-3)
    # q 32 x 192, k 32 x 128 + ONE 64, v and o 32 x 128, bf16, 5 passes
    assert flops.core_bytes(conf) == (
        32 * 192 + 32 * 128 + 64 + 2 * 32 * 128) * 2 * tokens * 5 * 5
    # the accepted count the issue quotes: 311 M MAC a token, MLA 76%
    macs = flops_decoder_trunk.forward_macs_per_token(conf, 4096)
    assert sum(macs.values()) == pytest.approx(311e6, rel=5e-3)
    assert (macs["mla_projections"] + macs["attention_core"]) / sum(
        macs.values()) == pytest.approx(0.76, abs=0.01)
    assert macs["attention_core"] == pytest.approx(104.9e6, rel=1e-3)
    # ... which still counts the maps a one-stream trunk does not run
    assert macs["stream_maps"] == 2 * 5 * 2048 * 3 > 0


def test_the_new_readers_read_a_canned_trace_and_no_other_configuration(
        monkeypatch):
    """``mla/core`` ops of a canned step trace: 300 ms a step under the
    scope; the roofline share is the count's least time over it.  Another
    trunk's configuration, a missing trace and a program that names no such
    scope (the parent) read nothing."""
    sys.path.insert(0, REPO)
    from benchmarks.lib import flops_latent_core, trace_decoder_trunk
    conf = _conf()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [("jit(train_step)/online_forward/layer1/mla/attn/core/"
            "causal_attention_fwd", 0.2),
           ("jit(train_step)/transpose(jvp(layer1))/mla/attn/core/"
            "causal_attention_bwd", 0.4),
           ("jit(train_step)/online_forward/layer1/mla/attn/q_b/dot", 0.5),
           ("jit(train_step)/online_forward/layer1/gqa/core/x", 0.7)]
    canned = {"steps": 2, "ops": ops, "reduced": {}}
    monkeypatch.setattr(trace_decoder_trunk, "step_trace",
                        lambda sources: canned if sources["trace"] else None)
    src = lambda config, trace=True: {
        "trace": trace, "config": config, "peaks": peaks, "meter": None,
        "counters": {"train_sequences_per_s_per_chip": 6.0},
        "cell": {"name": CELL}}
    core_ms, roofline = (_reader(n) for n in ("train_step.mla_core_ms",
                                              "mla.core_roofline"))
    assert core_ms.read(src(conf)) == pytest.approx(300.0)
    assert _reader("train_step.mla_ms").read(src(conf)) == pytest.approx(
        550.0)
    least_ms = flops_latent_core.core_flops(conf) / 197e12 * 1e3
    assert least_ms == pytest.approx(191.9, rel=1e-3)      # operation-bound
    assert roofline.read(src(conf)) == pytest.approx(100 * least_ms / 300.0)
    assert roofline.read(src(conf)) < 100.0
    canned["ops"] = ops[2:]                # the parent: no such scope
    for reader in (core_ms, roofline):
        assert reader.read(src(conf)) is None
        assert reader.read(src(conf, trace=None)) is None
        for other in ("byol_xing4_29b_a4b_ep8", "byol_lfm2_24b_a2b_ep8",
                      "byol_keye_vl2_30b_a3b_ep8",
                      "byol_qwen3next_80b_a3b_ep16"):
            theirs = json.load(open(os.path.join(BENCH, "configs",
                                                 other + ".json")))
            canned["ops"] = ops
            assert reader.read(src(theirs)) is None, other
            canned["ops"] = ops[2:]


def test_the_accepted_trunk_readers_answer_this_configuration():
    """The driver keeps ``train_tokens``'s rate counter, so the
    latent-attention trunk's own readers count this configuration's
    operations; the other trunks' twins find nothing."""
    sys.path.insert(0, REPO)
    conf = _conf()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    mine = {"trace": None, "config": conf, "peaks": peaks, "meter": None,
            "counters": {"train_sequences_per_s_per_chip": 6.0,
                         "moe_load_max": [1300.0], "moe_load_mean": [1000.0]},
            "cell": {"name": CELL}}
    # 6 sequences/s x 8 forwards x 2 x 311 M MAC x 4,096 tokens / 197 TFLOP/s
    assert _reader("train_step.seq_mfu").read(mine) == pytest.approx(
        100 * 6.0 * 8 * 2 * 311.1e6 * 4096 / 197e12, rel=5e-3)
    assert _reader("moe.load_max_over_mean").read(mine) == pytest.approx(1.3)
    for name in ("train_step.hybrid_seq_mfu", "train_step.sparse_seq_mfu",
                 "train_step.shortconv_seq_mfu", "gqa.core_roofline",
                 "shortconv_gqa.core_roofline", "dsa.core_roofline",
                 "dsa.selected_share", "train_step.mfu",
                 "train_step.shortconv_ms", "train_step.gdn_ms",
                 "train_step.mhc_ms", "train_step.mla_core_ms"):
        assert _reader(name).read(mine) is None, name
