"""Flight recorder + goodput accounting + offline report (ISSUE 9).

Four layers, bottom-up:

1. **SpanRecorder semantics**: monotonic begin/end, per-thread nesting
   depth, bounded ring with drop accounting, and the spans-off contract —
   the NULL recorder records NOTHING and returns one shared no-op context
   manager (the hot loop's ``--spans off`` path).
2. **Goodput folding**: spans partition wall time into productive +
   named badput buckets that sum EXACTLY to the window (the 1% identity
   events.py validates on every ``goodput`` line), only depth-0 spans
   attribute, and contiguous windows cover the whole run.
3. **Chrome trace export**: the written file is valid Chrome-trace JSON
   (``traceEvents`` with name/ts/dur/pid/tid complete events).
4. **Offline report**: ``byol_tpu.observability.report`` renders the
   waterfall / step-time trend / serving breakdown / anomaly timeline
   from a log ALONE and fails (rc=1) on a violated partition.
"""
import json
import threading
import time

import pytest

from byol_tpu.observability import goodput as goodput_lib
from byol_tpu.observability import spans as spans_lib
from byol_tpu.observability.events import RunLog, read_events, validate_event


# ---------------------------------------------------------------------------
# 1. recorder semantics
# ---------------------------------------------------------------------------

class TestSpanRecorder:
    def test_span_records_name_duration_and_order(self):
        rec = spans_lib.SpanRecorder()
        with rec.span("train/dispatch", step=3):
            time.sleep(0.01)
        with rec.span("input/wait"):
            pass
        records = rec.records()
        assert [r.name for r in records] == ["train/dispatch", "input/wait"]
        assert records[0].seconds >= 0.009
        assert records[0].t1 <= records[1].t0   # sequential, monotonic
        assert records[0].attrs == {"step": 3}
        assert records[1].attrs is None
        assert records[0].seq < records[1].seq

    def test_nesting_tracks_depth_and_inner_closes_first(self):
        rec = spans_lib.SpanRecorder()
        with rec.span("outer"):
            with rec.span("inner"):
                pass
        inner, outer = rec.records()   # closed-order append: inner first
        assert (inner.name, inner.depth) == ("inner", 1)
        assert (outer.name, outer.depth) == ("outer", 0)
        # depth resets for the next top-level span
        with rec.span("again"):
            pass
        assert rec.records()[-1].depth == 0

    def test_depth_is_per_thread(self):
        rec = spans_lib.SpanRecorder()
        seen = {}

        def worker():
            with rec.span("thread/top"):
                pass
            seen["rec"] = [r for r in rec.records()
                           if r.name == "thread/top"][0]

        with rec.span("main/outer"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        # the other thread's span is depth 0 even while main is nested
        assert seen["rec"].depth == 0
        assert seen["rec"].tid != threading.get_ident()

    def test_exception_still_closes_and_records(self):
        rec = spans_lib.SpanRecorder()
        with pytest.raises(RuntimeError, match="boom"):
            with rec.span("train/dispatch"):
                raise RuntimeError("boom")
        assert [r.name for r in rec.records()] == ["train/dispatch"]
        # depth unwound: a following span is top-level again
        with rec.span("next"):
            pass
        assert rec.records()[-1].depth == 0

    def test_ring_bound_evicts_oldest_and_counts_dropped(self):
        rec = spans_lib.SpanRecorder(capacity=4)
        for i in range(7):
            with rec.span(f"s{i}"):
                pass
        assert [r.name for r in rec.records()] == ["s3", "s4", "s5", "s6"]
        assert rec.dropped == 3

    def test_records_since_seq(self):
        rec = spans_lib.SpanRecorder()
        with rec.span("a"):
            pass
        mark = rec.last_seq()
        with rec.span("b"):
            pass
        assert [r.name for r in rec.records(since_seq=mark)] == ["b"]

    def test_null_recorder_records_nothing(self):
        """The --spans off contract: one shared no-op context manager, no
        clock read, no ring append — the hot loop is untouched."""
        null = spans_lib.NULL
        ctx1 = null.span("train/dispatch", step=1)
        ctx2 = null.span("anything/else")
        assert ctx1 is ctx2          # ONE shared object: zero allocation
        with ctx1:
            pass
        assert null.records() == []
        assert null.dropped == 0
        assert not null.enabled

    def test_module_default_recorder(self):
        rec = spans_lib.SpanRecorder()
        old = spans_lib.get_default()
        try:
            spans_lib.set_default(rec)
            with spans_lib.span("via/default"):
                pass
            assert [r.name for r in rec.records()] == ["via/default"]
        finally:
            spans_lib.set_default(old)
        # default-default is NULL: module-level span() is opt-in
        assert old is spans_lib.NULL


# ---------------------------------------------------------------------------
# 2. goodput folding
# ---------------------------------------------------------------------------

def _spin(rec, name, seconds, **attrs):
    with rec.span(name, **attrs):
        time.sleep(seconds)


class TestGoodputFold:
    def test_partition_sums_to_wall_exactly(self):
        rec = spans_lib.SpanRecorder()
        meter = goodput_lib.GoodputMeter(rec)
        _spin(rec, "train/dispatch", 0.02)
        _spin(rec, "input/wait", 0.01)
        _spin(rec, "eval/run", 0.01)
        p = meter.fold(scope="epoch", epoch=0)
        total = p["productive_seconds"] + sum(p["badput"].values())
        assert total == pytest.approx(p["wall_seconds"], rel=1e-9)
        assert p["productive_seconds"] >= 0.019
        assert p["badput"]["input_wait"] >= 0.009
        assert p["badput"]["eval"] >= 0.009
        assert p["badput"]["host_other"] >= 0.0
        assert 0.0 < p["goodput_fraction"] < 1.0
        # the emitted event passes the schema's 1% identity check
        validate_event({"v": 1, "kind": "goodput", "t": 0.0, **p})

    def test_only_top_level_spans_attribute(self):
        """A nested span's seconds live inside its parent — counting both
        would exceed wall time."""
        rec = spans_lib.SpanRecorder()
        meter = goodput_lib.GoodputMeter(rec)
        with rec.span("train/epoch_readback"):
            _spin(rec, "telemetry/drain", 0.02)   # nested: NOT badput
        p = meter.fold()
        assert p["badput"]["telemetry_readback"] == 0.0
        assert p["productive_seconds"] >= 0.019

    def test_windows_are_contiguous_and_final_totals(self):
        rec = spans_lib.SpanRecorder()
        meter = goodput_lib.GoodputMeter(rec)
        _spin(rec, "train/dispatch", 0.01)
        p0 = meter.fold(scope="epoch", epoch=0)
        _spin(rec, "checkpoint/save", 0.01)
        p1 = meter.fold(scope="epoch", epoch=1)
        time.sleep(0.005)                          # tail after last fold
        run = meter.final()
        assert run["scope"] == "run"
        # run wall covers construction -> final with nothing counted twice
        assert run["wall_seconds"] == pytest.approx(
            p0["wall_seconds"] + p1["wall_seconds"] + 0.005, abs=0.05)
        assert run["wall_seconds"] >= (p0["wall_seconds"]
                                       + p1["wall_seconds"])
        assert run["productive_seconds"] == pytest.approx(
            p0["productive_seconds"] + p1["productive_seconds"], rel=1e-9)
        assert run["badput"]["checkpoint"] == pytest.approx(
            p1["badput"]["checkpoint"], rel=1e-9)
        total = run["productive_seconds"] + sum(run["badput"].values())
        assert total == pytest.approx(run["wall_seconds"], rel=1e-9)

    def test_fold_emits_goodput_and_span_stats_events(self, tmp_path):
        rec = spans_lib.SpanRecorder()
        meter = goodput_lib.GoodputMeter(rec)
        for _ in range(3):
            _spin(rec, "train/dispatch", 0.002)
        _spin(rec, "input/wait", 0.002)
        path = str(tmp_path / "run.jsonl")
        with RunLog(path) as log:
            meter.fold(scope="epoch", epoch=5, events=log,
                       images_per_sec_per_chip=100.0)
            meter.final(events=log)
        got = list(read_events(path))
        kinds = [e["kind"] for e in got]
        assert kinds == ["goodput", "span_stats", "goodput"]
        ep, stats, run = got
        assert ep["scope"] == "epoch" and ep["epoch"] == 5
        assert ep["images_per_sec_per_chip"] == 100.0
        assert run["scope"] == "run" and run["windows"] == 2
        s = stats["spans"]["train/dispatch"]
        assert s["count"] == 3 and s["seconds"] >= 0.005
        assert s["p50_ms"] <= s["p99_ms"] <= s["max_ms"]

    def test_goodput_event_schema_rejects_leaky_partition(self):
        bad = {"v": 1, "kind": "goodput", "t": 0.0, "scope": "epoch",
               "wall_seconds": 10.0, "productive_seconds": 5.0,
               "badput": {"input_wait": 1.0}}    # 4s unaccounted
        with pytest.raises(ValueError, match="sum"):
            validate_event(bad)

    def test_bucket_vocabulary(self):
        assert goodput_lib.bucket_of("input/wait") == "input_wait"
        assert goodput_lib.bucket_of("input/fill") == "input_wait"
        assert goodput_lib.bucket_of("startup/compile") == "startup_compile"
        assert goodput_lib.bucket_of("telemetry/readback") \
            == "telemetry_readback"
        assert goodput_lib.bucket_of("eval/run") == "eval"
        assert goodput_lib.bucket_of("checkpoint/save") == "checkpoint"
        assert goodput_lib.bucket_of("train/dispatch") is None
        assert goodput_lib.bucket_of("unknown/thing") is None
        assert goodput_lib.OTHER_BUCKET in goodput_lib.BADPUT_BUCKETS


# ---------------------------------------------------------------------------
# 3. chrome trace export
# ---------------------------------------------------------------------------

class TestChromeTraceExport:
    def test_exported_file_is_valid_chrome_trace(self, tmp_path):
        rec = spans_lib.SpanRecorder()
        with rec.span("train/dispatch", step=1):
            with rec.span("serve/stage", trace_ids=[1, 2]):
                pass
        path = str(tmp_path / "trace.json")
        n = spans_lib.export_chrome_trace(rec.records(), path)
        assert n == 2
        with open(path) as f:
            trace = json.load(f)
        events = trace["traceEvents"]
        xs = [e for e in events if e.get("ph") == "X"]
        assert len(xs) == 2
        for e in xs:
            assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
            assert e["dur"] >= 0.0
        # sorted by start time: the nested span starts after its parent
        assert xs[0]["name"] == "train/dispatch"
        # attrs beside the span's seq and its parent's (the span that
        # caused it)
        assert xs[1]["args"] == {"seq": 1, "parent": 0,
                                 "trace_ids": [1, 2]}
        # process metadata present (multi-file Perfetto sessions)
        assert any(e.get("ph") == "M" for e in events)

    def test_exported_timestamps_are_epoch_microseconds(self, tmp_path):
        rec = spans_lib.SpanRecorder()
        before = time.time_ns()
        with rec.span("train/dispatch"):
            time.sleep(0.002)
        after = time.time_ns()
        path = str(tmp_path / "trace.json")
        spans_lib.export_chrome_trace(rec.records(), path)
        with open(path) as f:
            (ev,) = [e for e in json.load(f)["traceEvents"]
                     if e["ph"] == "X"]
        # the two host clocks agree to well under a millisecond here
        assert before / 1e3 - 500 <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= after / 1e3 + 500
        r = rec.records()[0]
        assert spans_lib.epoch_ns(r.t1) - spans_lib.epoch_ns(r.t0) == \
            pytest.approx((r.t1 - r.t0) * 1e9, abs=2)

    def test_span_meets_the_profiler_trace_by_clock(self, tmp_path):
        """A span round a jitted call, inside ``profiling.trace``, contains
        that call's op event once the event is shifted by the trace's
        ``profile_start_time`` — no host tracer needed on the chip (on the
        CPU backend the ops ARE host events, and ``trace`` keeps them)."""
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from byol_tpu.observability import profiling
        f = jax.jit(lambda x: jnp.sin(x) @ x)
        x = jnp.ones((256, 256))
        f(x).block_until_ready()
        rec = spans_lib.SpanRecorder()
        with profiling.trace(str(tmp_path)):
            time.sleep(0.005)
            with rec.span("train/dispatch"):
                f(x).block_until_ready()
            time.sleep(0.005)
        (pb,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        start, ops = None, []
        for plane in ProfileData.from_file(pb).planes:
            if plane.name == "Task Environment":
                start = dict(plane.stats)["profile_start_time"]
            for line in plane.lines:
                ops += [e for e in line.events
                        if "hlo_op" in dict(e.stats)]
        assert start and ops
        (r,) = rec.records()
        t0, t1 = spans_lib.epoch_ns(r.t0), spans_lib.epoch_ns(r.t1)
        slack = 200_000                       # ns between the two clocks
        for e in ops:
            assert t0 - slack <= start + e.start_ns
            assert start + e.start_ns + e.duration_ns <= t1 + slack

    def test_export_creates_parent_dirs_and_handles_empty(self, tmp_path):
        path = str(tmp_path / "deep" / "dir" / "trace.json")
        n = spans_lib.export_chrome_trace([], path)
        assert n == 0
        with open(path) as f:
            assert json.load(f)["traceEvents"][0]["ph"] == "M"


# ---------------------------------------------------------------------------
# 4. offline report
# ---------------------------------------------------------------------------

def _write_log(tmp_path, events):
    path = str(tmp_path / "run.jsonl")
    with RunLog(path) as log:
        for kind, payload in events:
            log.emit(kind, **payload)
    return path


class TestReport:
    def _sample_events(self):
        return [
            ("run_header", {"config": {}, "jax_version": "0",
                            "backend": "cpu", "run_name": "r"}),
            ("epoch", {"epoch": 0, "split": "train", "metrics": {},
                       "step_time_p50_s": 0.1, "step_time_p99_s": 0.3}),
            ("goodput", {"scope": "epoch", "epoch": 0, "wall_seconds": 10.0,
                         "productive_seconds": 8.0,
                         "badput": {"input_wait": 1.5, "host_other": 0.5}}),
            ("goodput", {"scope": "run", "wall_seconds": 10.0,
                         "productive_seconds": 8.0,
                         "badput": {"input_wait": 1.5, "host_other": 0.5}}),
            ("serve_stats", {"requests": 4, "batches": 2, "p50_ms": 3.0,
                             "p99_ms": 9.0,
                             "phase_ms": {"coalesce": 1.0, "stage": 0.5,
                                          "dispatch": 1.0, "readback": 0.4,
                                          "deliver": 0.1}}),
            ("anomaly", {"step": 17, "rule": "collapse",
                         "detail": "feature_std low"}),
            ("run_end", {}),
        ]

    def test_report_renders_all_sections_rc0(self, tmp_path, capsys):
        from byol_tpu.observability import report
        path = _write_log(tmp_path, self._sample_events())
        rc = report.main([path])
        out = capsys.readouterr().out
        assert rc == 0
        assert "Goodput waterfall" in out and "80.0%" in out
        assert "input_wait" in out
        assert "Step-time trend" in out and "100.00ms" in out
        assert "Serving latency breakdown" in out and "coalesce" in out
        assert "Anomaly timeline" in out and "collapse" in out

    def test_report_fails_without_goodput_events(self, tmp_path, capsys):
        from byol_tpu.observability import report
        path = _write_log(tmp_path, [
            ("run_header", {"config": {}, "jax_version": "0",
                            "backend": "cpu"}),
            ("run_end", {}),
        ])
        rc = report.main([path])
        assert rc == 1
        assert "no goodput events" in capsys.readouterr().out

    def test_violated_partition_is_rc1_with_diagnostic(self, tmp_path,
                                                       capsys):
        """A goodput line whose buckets do NOT sum to wall must reach the
        renderer (rc 1 + the '!! partition off' diagnostic) — the strict
        reader raising on it would misreport the exact failure this
        command exists to show as an unreadable file (rc 2)."""
        import json as _json
        from byol_tpu.observability import report
        p = tmp_path / "broken.jsonl"
        bad = {"v": 1, "kind": "goodput", "t": 0.0, "scope": "run",
               "wall_seconds": 100.0, "productive_seconds": 10.0,
               "badput": {"input_wait": 1.0}}       # 89s unaccounted
        p.write_text(_json.dumps(bad) + "\n")
        rc = report.main([str(p)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "partition off by" in out
        # an EPOCH-scope violation must mark its own table row too, not
        # just flip the exit code while every printed line looks healthy
        p_ep = tmp_path / "broken_epoch.jsonl"
        ok_run = {"v": 1, "kind": "goodput", "t": 0.0, "scope": "run",
                  "wall_seconds": 10.0, "productive_seconds": 9.0,
                  "badput": {"host_other": 1.0}}
        bad_ep = {**bad, "scope": "epoch", "epoch": 3}
        p_ep.write_text(_json.dumps(ok_run) + "\n"
                        + _json.dumps(bad_ep) + "\n")
        rc = report.main([str(p_ep)])
        out = capsys.readouterr().out
        assert rc == 1
        epoch_row = next(l for l in out.splitlines()
                         if l.strip().startswith("3 "))
        assert "partition off by" in epoch_row
        # but a goodput line that is schema-broken in any OTHER way is
        # still an unreadable log (rc 2), not a renderable one
        p2 = tmp_path / "drifted.jsonl"
        p2.write_text(_json.dumps({"v": 1, "kind": "goodput", "t": 0.0,
                                   "scope": "run"}) + "\n")
        assert report.main([str(p2)]) == 2

    def test_report_rejects_corrupt_log(self, tmp_path, capsys):
        from byol_tpu.observability import report
        p = tmp_path / "bad.jsonl"
        p.write_text("{not json\n")
        assert report.main([str(p)]) == 2

    def test_report_usage(self):
        from byol_tpu.observability import report
        assert report.main([]) == 2

    def test_report_cli_subcommand_dispatch(self, tmp_path):
        """``python -m byol_tpu report`` reaches report.main — the no-live-
        process analysis entry point."""
        import subprocess
        import sys as _sys
        path = _write_log(tmp_path, self._sample_events())
        proc = subprocess.run(
            [_sys.executable, "-m", "byol_tpu", "report", path],
            capture_output=True, text=True, timeout=180)
        assert proc.returncode == 0, proc.stderr
        assert "Goodput waterfall" in proc.stdout


# ---------------------------------------------------------------------------
# scripts/validate_events.py --require (the CI goodput gate)
# ---------------------------------------------------------------------------

class TestValidateEventsRequire:
    def _run(self, *args):
        import pathlib
        import subprocess
        import sys as _sys
        repo = pathlib.Path(__file__).resolve().parent.parent
        return subprocess.run(
            [_sys.executable, str(repo / "scripts" / "validate_events.py"),
             *args], capture_output=True, text=True, timeout=120)

    def test_require_present_passes_absent_fails(self, tmp_path):
        rec = spans_lib.SpanRecorder()
        meter = goodput_lib.GoodputMeter(rec)
        _spin(rec, "train/dispatch", 0.001)
        with_goodput = str(tmp_path / "with.jsonl")
        with RunLog(with_goodput) as log:
            meter.fold(events=log)
        without = str(tmp_path / "without.jsonl")
        with RunLog(without) as log:
            log.emit("run_end")
        ok = self._run("--require", "goodput,span_stats", with_goodput)
        assert ok.returncode == 0, ok.stderr
        bad = self._run("--require", "goodput,span_stats", without)
        assert bad.returncode == 1
        assert "goodput" in bad.stderr
        # without --require the same file validates fine
        assert self._run(without).returncode == 0


# ---------------------------------------------------------------------------
# StepTimer step-time quantiles (meters.py satellite)
# ---------------------------------------------------------------------------

class TestStepTimeQuantiles:
    def test_quantiles_from_ticks(self):
        from byol_tpu.observability import StepTimer
        t = StepTimer(global_batch=8, n_chips=1)
        assert t.epoch_step_quantiles() is None          # no ticks
        stamps = [0.0, 0.1, 0.2, 0.3, 0.8]   # intervals .1,.1,.1,.5
        for s in stamps:
            t._ticks.append(s)
        q = t.epoch_step_quantiles()
        assert q["step_time_p50_s"] == pytest.approx(0.1)
        assert q["step_time_p99_s"] > q["step_time_p50_s"]
        assert q["step_time_max_s"] == pytest.approx(0.5)

    def test_too_few_ticks_is_none_and_reset_clears(self):
        from byol_tpu.observability import StepTimer
        t = StepTimer(global_batch=8, n_chips=1)
        for s in (0.0, 0.1, 0.2):            # 2 intervals: below the floor
            t._ticks.append(s)
        assert t.epoch_step_quantiles() is None
        for s in (0.3, 0.4):
            t._ticks.append(s)
        assert t.epoch_step_quantiles() is not None
        t.reset_ticks()
        assert t.epoch_step_quantiles() is None

    def test_tick_appends_perf_counter(self):
        from byol_tpu.observability import StepTimer
        t = StepTimer(global_batch=8, n_chips=1)
        t.tick()
        t.tick()
        assert len(t._ticks) == 2
        assert t._ticks[0] <= t._ticks[1]
