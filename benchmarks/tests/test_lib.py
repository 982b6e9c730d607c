"""The yardstick's arithmetic: peaks, operation counts, the reduction of a
trace, and the comparison."""
import numpy as np
import pytest

from benchmarks.lib import check, flops, peaks, trace_reduce


def test_peaks_and_operation_counts():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    # torchvision: ResNet-50 4.09 GMACs (with its 2 MMAC classifier),
    # ResNet-18 1.81; ViT-B/16 at 224: 17.56 (12 blocks + patch embedding)
    assert flops.resnet_forward_macs("resnet50", 224) == pytest.approx(
        4.087e9, rel=2e-3)
    assert flops.resnet_forward_macs("resnet18", 224) == pytest.approx(
        1.814e9, rel=2e-3)
    assert flops.vit_forward_macs("vit_b16", 224) == pytest.approx(
        17.56e9, rel=2e-3)
    per_image = flops.train_flops_per_image("resnet50", 224)
    assert per_image == pytest.approx(8 * 2 * 4.087e9, rel=2e-3)
    with pytest.raises(KeyError):
        flops.forward_flops_per_image("alexnet", 224)


def _trace():
    us = 1000.0
    ops0 = [("fusion.1", 0 * us, 40 * us), ("all-reduce.7", 30 * us, 20 * us),
            ("fusion.2", 70 * us, 10 * us), ("fusion.1", 90 * us, 10 * us)]
    ops1 = [("fusion.1", 0 * us, 100 * us)]
    host = [("bench/window", 0.0, 100 * us),
            ("bench/feed_and_dispatch", 48 * us, 24 * us),
            ("bench/wait_device", 79 * us, 12 * us), ("other", 0.0, 5 * us)]
    return {"/device:TPU:0": {"XLA Ops": ops0, "Steps": [("1", 0, 100 * us)]},
            "/device:TPU:1": {"XLA Ops": ops1},
            "/host:CPU": {"python": host}}


def test_trace_reduce_on_a_hand_built_trace():
    r = trace_reduce.reduce(_trace(), devices=2)
    # device 0 busy 0-50, 70-80, 90-100 = 70 us; device 1 busy 100 us
    assert r["busy_s"] == pytest.approx(85e-6)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["collective_s"] == pytest.approx(20e-6)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(50e-6)]
    assert r["idle_gaps"][0] == ["bench/feed_and_dispatch",
                                 pytest.approx(20e-6)]
    assert r["idle_gaps"][1] == ["bench/wait_device", pytest.approx(10e-6)]
    one = trace_reduce.reduce(
        {k: v for k, v in _trace().items() if k != "/device:TPU:1"},
        devices=1)
    assert 1.0 - one["busy_s"] / one["window_s"] == pytest.approx(0.30)
    with pytest.raises(ValueError):
        trace_reduce.reduce(_trace(), devices=4)
    # a device-only trace (what the harness records): gaps are named by the
    # programs around them
    us = 1000.0
    dev = {"/device:TPU:0": {
        "XLA Ops": [("%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop",
                     0.0, 40 * us), ("%fusion.1 = f32[8]{0} fusion(f32[8] "
                                     "%p), kind=kLoop", 60 * us, 30 * us),
                    ("%copy.2 = f32[8]{0} copy(f32[8] %q)", 95 * us, 5 * us)],
        "XLA Modules": [("jit_train_step(123)", 0.0, 40 * us),
                        ("jit_train_step(123)", 60 * us, 40 * us)]}}
    r = trace_reduce.reduce(dev, devices=1)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(75e-6)
    assert r["idle_gaps"][0] == ["between jit_train_step and jit_train_step",
                                 pytest.approx(20e-6)]
    assert r["idle_gaps"][1] == ["inside jit_train_step",
                                 pytest.approx(5e-6)]
    assert r["device_ops"][0] == ["fusion.1 kLoop f32[8]",
                                  pytest.approx(70e-6)]


def test_check_numbers_and_verdict():
    rng = np.random.default_rng(0)
    p0 = {"a": {"kernel": rng.normal(size=(4, 3)), "bias": np.zeros(3)}}
    step = {"a": {"kernel": 0.1 * rng.normal(size=(4, 3)),
                  "bias": 0.1 * rng.normal(size=3)}}
    after = {"a": {k: p0["a"][k] + step["a"][k] for k in step["a"]}}
    ref = {"losses": [2.0, 1.9], "first_trace": step, "params": after}
    same = check.training_numbers(ref, ref, p0)
    assert all(v == pytest.approx(0.0, abs=1e-12) for v in same.values())
    lines = []
    assert check.verdict(same, {k: 1e-6 for k in same}, lines.append)
    assert all("limit" in ln for ln in lines)
    frozen = dict(ref, params=p0)             # a step that updated nothing
    numbers = check.training_numbers(frozen, ref, p0)
    assert numbers["update_norm_gap"] == pytest.approx(1.0)
    assert not check.verdict(numbers, {"update_norm_gap": 0.5}, lines.append)
    wrong = dict(ref, first_trace={"a": {"kernel": -step["a"]["kernel"],
                                         "bias": 2 * step["a"]["bias"]}})
    numbers = check.training_numbers(wrong, ref, p0)
    assert numbers["grad_dir_gap"] == pytest.approx(2.0)
    assert numbers["grad_norm_gap"] == pytest.approx(1.0)
    nan = dict(ref, losses=[float("nan"), 1.9])
    assert check.training_numbers(nan, ref, p0)["loss_rel_gap"] == float("inf")
    e = rng.normal(size=(5, 8))
    assert check.serving_numbers(e, e)["embed_rel_gap"] == 0.0
    assert check.serving_numbers(1.1 * e, e)["embed_rel_gap"] == \
        pytest.approx(0.1)
    # a limit without its number is a failure, not a pass
    assert not check.verdict({}, {"embed_rel_gap": 0.1}, lines.append)
