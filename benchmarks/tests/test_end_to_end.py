"""``run.py`` end to end on the CPU at tiny size, from a copy to which the
tiny configuration, cells and one per-layer metric were ADDED as files."""
import json
import os

from conftest import run_cell

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last(lines):
    line = json.loads(lines[-1])
    assert CONTRACT_KEYS <= set(line)
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    return line


def test_training_cell_added_as_files_runs_and_agrees_in_float32(bench_copy):
    rc, out, err = run_cell(bench_copy, "tiny_train", trace=0)
    assert rc == 0, err[-2000:]
    line = _last(out)
    # float32 program against the float32 reference, limits of 1e-4..5e-3
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "train_images_per_s_per_chip"}
    assert line["device"]["platform"] == "cpu"
    assert sum("] check: " in ln and "limit" in ln for ln in out) == 4
    # told nothing (no --rehearse-cpu) and finding no TPU, it exits non-zero
    # and prints no result line
    rc, out, err = run_cell(bench_copy, "tiny_train", rehearse=False)
    assert rc != 0
    assert not any(ln.startswith("{") for ln in out)


def test_traced_run_reports_layer_metrics_and_no_device_metric_on_cpu(
        bench_copy):
    rc, out, err = run_cell(bench_copy, "tiny_vit_train", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True
    # the added reader is found; readers with nothing to read are left out;
    # on the CPU no device metric (mfu, idle share, busy_s) is written
    assert set(line["metrics"]) == {"train_step.step_ms",
                                    "input.host_feed_ms",
                                    "train_step.last_loss"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_bfloat16_run_fails_limits_stated_for_float32(bench_copy):
    rc, out, err = run_cell(bench_copy, "tiny_train_bf16", trace=0)
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("OVER" in ln for ln in out)


def test_broken_step_comes_out_not_correct(bench_copy):
    rc, out, err = run_cell(bench_copy, "tiny_train", trace=0,
                            script=os.path.join("tests", "broken_step.py"))
    assert rc == 0, err[-2000:]
    assert _last(out)["correct"] is False
    assert any("] check: update_norm_gap" in ln and "OVER" in ln
               for ln in out)


def test_serving_cell_runs_and_agrees_in_float32(bench_copy):
    rc, out, err = run_cell(bench_copy, "tiny_serve", trace=1)
    assert rc == 0, err[-2000:]
    line = _last(out)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serving.latency_p50_ms",
                                    "serving.queue_wait_ms",
                                    "serving.batch_fill"}
    assert 0 < line["metrics"]["serving.batch_fill"]["value"] <= 100
