"""byol_tpu/serving/ — the embedding service (ISSUE 8 tentpole).

Four layers, cheapest first:

1. **Buckets**: the pad-to-power-of-two vocabulary is total and unique —
   every request row count maps to exactly ONE bucket (the property that
   makes the compile count an invariant rather than a load artifact).
2. **Batcher**: pure host-side policy — coalescing, the max-wait flush
   deadline, overflow carry, bounded-queue backpressure, drain-on-close.
3. **Engine/service correctness**: served embeddings BITWISE-match the
   linear-eval extractor for the same checkpoint and inputs (the serving
   path may add batching, padding, sharding, and AOT compilation, but it
   must never add numerics), under the guard_steps transfer guard; the
   checkpoint restores onto FEWER devices than it trained on.
4. **Compile discipline**: compile count == number of distinct buckets
   touched, and warmed steady-state serving issues ZERO recompiles (the
   GL102 hazard pinned at runtime).
"""
import threading
import time
import types

import numpy as np
import pytest

import jax

from byol_tpu.serving.batcher import (Backpressure, DynamicBatcher,
                                      ServiceClosed)
from byol_tpu.serving.buckets import BucketSpec
from byol_tpu.serving.meter import ServingMeter, serve_log_line
from byol_tpu.serving.service import EmbeddingService
from tests.conftest import guard_steps


# ---------------------------------------------------------------------------
# 1. buckets
# ---------------------------------------------------------------------------

class TestBuckets:
    def test_every_row_count_maps_to_exactly_one_bucket(self):
        spec = BucketSpec(min_bucket=8, max_bucket=64)
        assert spec.sizes == (8, 16, 32, 64)
        for n in range(1, 65):
            b = spec.bucket_for(n)
            # coverage: the bucket holds the rows
            assert b in spec.sizes and b >= n
            # uniqueness/minimality: no SMALLER bucket could hold them,
            # so no other bucket can be "the" bucket for n
            smaller = [s for s in spec.sizes if s < b]
            assert all(s < n for s in smaller)
            # determinism
            assert spec.bucket_for(n) == b

    def test_single_bucket_spec(self):
        spec = BucketSpec(min_bucket=16, max_bucket=16)
        assert spec.sizes == (16,)
        assert spec.bucket_for(1) == 16 and spec.bucket_for(16) == 16

    def test_validation(self):
        with pytest.raises(ValueError):
            BucketSpec(min_bucket=6, max_bucket=64)      # not a pow2
        with pytest.raises(ValueError):
            BucketSpec(min_bucket=32, max_bucket=8)      # inverted
        spec = BucketSpec(min_bucket=8, max_bucket=32)
        with pytest.raises(ValueError):
            spec.bucket_for(33)                          # over the ceiling
        with pytest.raises(ValueError):
            spec.bucket_for(0)


# ---------------------------------------------------------------------------
# 2. batcher (no jax anywhere)
# ---------------------------------------------------------------------------

def _img(rows=1, size=4):
    return np.zeros((rows, size, size, 3), np.float32)


class TestBatcher:
    def test_coalesces_up_to_max_batch(self):
        b = DynamicBatcher(max_batch=8, max_wait_s=0.2)
        for _ in range(4):
            b.submit(_img(2), timeout=0.1)
        batch = b.next_batch()
        assert len(batch) == 4
        assert sum(r.rows for r in batch) == 8

    def test_overflow_request_is_carried_never_split(self):
        b = DynamicBatcher(max_batch=8, max_wait_s=0.2)
        b.submit(_img(6), timeout=0.1)
        b.submit(_img(5), timeout=0.1)     # 6+5 > 8: must not join
        first = b.next_batch()
        assert [r.rows for r in first] == [6]
        second = b.next_batch()
        assert [r.rows for r in second] == [5]

    def test_max_wait_deadline_flushes_partial_batch(self):
        b = DynamicBatcher(max_batch=64, max_wait_s=0.05)
        b.submit(_img(2), timeout=0.1)
        t0 = time.perf_counter()
        batch = b.next_batch()
        waited = time.perf_counter() - t0
        assert sum(r.rows for r in batch) == 2       # flushed well short
        assert waited < 5.0                          # of max_batch
        # and the deadline actually gated the flush (>= max_wait, minus
        # scheduler slop)
        assert waited >= 0.04

    def test_backpressure_when_queue_full(self):
        b = DynamicBatcher(max_batch=4, max_queue=2, max_wait_s=0.01)
        b.submit(_img(), timeout=0.1)
        b.submit(_img(), timeout=0.1)
        with pytest.raises(Backpressure):
            b.submit(_img(), timeout=0.05)
        # draining one frees a slot
        assert b.next_batch() is not None
        b.submit(_img(), timeout=0.5)

    def test_oversized_and_empty_requests_rejected(self):
        b = DynamicBatcher(max_batch=4)
        with pytest.raises(ValueError):
            b.submit(_img(5), timeout=0.1)
        with pytest.raises(ValueError):
            b.submit(_img(0), timeout=0.1)
        with pytest.raises(ValueError):
            b.submit(np.zeros((4, 4), np.float32), timeout=0.1)

    def test_single_image_lifted_to_one_row(self):
        b = DynamicBatcher(max_batch=4, max_wait_s=0.01)
        req = b.submit(np.zeros((4, 4, 3), np.float32), timeout=0.1)
        assert req.rows == 1
        assert b.next_batch()[0] is req

    def test_close_drains_then_ends(self):
        b = DynamicBatcher(max_batch=2, max_wait_s=0.01)
        b.submit(_img(), timeout=0.1)
        b.close()
        with pytest.raises(ServiceClosed):
            b.submit(_img(), timeout=0.1)
        assert b.next_batch() is not None    # queued work still served
        assert b.next_batch(poll_s=0.01) is None

    def test_fail_pending_resolves_raced_requests(self):
        """A submit that raced close() into an already-drained queue (the
        TOCTOU between the closed-check and the put) must still get its
        future RESOLVED — fail_pending covers the queue AND the carry
        slot, so no client can block forever on stop()."""
        b = DynamicBatcher(max_batch=8, max_wait_s=0.01)
        raced = b.submit(_img(), timeout=0.1)
        b.submit(_img(6), timeout=0.1)
        b.submit(_img(5), timeout=0.1)       # 1+6+5 > 8: carried
        b.next_batch()                        # drains 1+6, carries the 5
        assert b.fail_pending(ServiceClosed("stopped")) == 1   # the carry
        b._q.put(raced)                       # simulate the raced put
        assert b.fail_pending(ServiceClosed("stopped")) == 1
        with pytest.raises(ServiceClosed):
            raced.result(timeout=0.1)


# ---------------------------------------------------------------------------
# 3. meter + events
# ---------------------------------------------------------------------------

class TestServingMeter:
    def test_window_stats_and_reset(self):
        m = ServingMeter()
        t0 = 100.0
        m.record_batch(rows=6, bucket=8, t_now=t0)
        for lat in (0.010, 0.020, 0.030):
            m.record_latency(lat)
        m.record_enqueue(2)
        snap = m.snapshot(t0 + 1.0, reset=True)
        assert snap["requests"] == 3 and snap["batches"] == 1
        assert snap["fill_ratio"] == pytest.approx(6 / 8)
        assert snap["p50_ms"] == pytest.approx(20.0)
        assert snap["queue_depth"] == 2.0
        assert snap["rows_per_sec"] == pytest.approx(6.0)
        # window reset: empty stats, lifetime totals kept
        empty = m.snapshot(t0 + 2.0, reset=False)
        assert empty["requests"] == 0 and np.isnan(empty["p50_ms"])
        assert m.total_requests == 3 and m.total_batches == 1
        # the log line renders NaN windows without crashing
        assert "serve[" in serve_log_line(empty)

    def test_quantiles_at_small_sample_counts(self):
        """p50 <= p99 must hold from the FIRST sample on — tail math over
        one or two latencies (a cold service's first stats window) must
        interpolate, never crash or invert (ISSUE 9 satellite)."""
        m = ServingMeter()
        m.record_latency(0.010)
        one = m.snapshot(1.0, reset=False)
        assert one["p50_ms"] == pytest.approx(10.0)
        assert one["p99_ms"] == pytest.approx(10.0)      # 1 sample: p50==p99
        m.record_latency(0.030)
        two = m.snapshot(2.0, reset=True)
        assert two["requests"] == 2
        assert two["p50_ms"] <= two["p99_ms"] <= 30.0 + 1e-9
        m.record_latency(0.005)
        m.record_latency(0.007)
        m.record_latency(0.009)
        three = m.snapshot(3.0, reset=True)
        assert three["p50_ms"] == pytest.approx(7.0)
        assert three["p50_ms"] <= three["p99_ms"]

    def test_snapshot_under_load_never_drops_or_inverts(self):
        """Concurrent record_latency vs snapshot(reset=True): every sample
        lands in exactly ONE window (nothing lost to a reset race) and
        every window's percentiles stay ordered (ISSUE 9 satellite)."""
        m = ServingMeter()
        n_threads, per_thread = 4, 500
        stop = threading.Event()
        windows = []

        def producer(idx):
            rng = np.random.RandomState(idx)
            for _ in range(per_thread):
                m.record_latency(float(rng.uniform(0.001, 0.050)))

        def reader():
            while not stop.is_set():
                windows.append(m.snapshot(time.perf_counter(), reset=True))

        threads = [threading.Thread(target=producer, args=(i,))
                   for i in range(n_threads)]
        snap_thread = threading.Thread(target=reader)
        snap_thread.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        snap_thread.join()
        windows.append(m.snapshot(time.perf_counter(), reset=True))
        counted = sum(int(w["requests"]) for w in windows)
        assert counted == n_threads * per_thread     # reset drops nothing
        assert m.total_requests == n_threads * per_thread
        for w in windows:
            if w["requests"]:
                assert w["p50_ms"] <= w["p99_ms"] + 1e-9

    def test_lifecycle_phase_breakdown(self):
        """record_lifecycle folds per-request phase deltas into window
        means; snapshot exposes them as the additive ``phase_ms`` field
        and reset clears them."""
        m = ServingMeter()
        m.record_latency(0.010)
        m.record_lifecycle({"coalesce": 0.004, "stage": 0.001,
                            "dispatch": 0.003, "readback": 0.001,
                            "deliver": 0.001})
        m.record_lifecycle({"coalesce": 0.002, "stage": 0.001,
                            "dispatch": 0.001, "readback": 0.001,
                            "deliver": 0.001})
        snap = m.snapshot(1.0, reset=True)
        assert snap["phase_ms"]["coalesce"] == pytest.approx(3.0)
        assert snap["phase_ms"]["dispatch"] == pytest.approx(2.0)
        empty = m.snapshot(2.0, reset=False)
        assert "phase_ms" not in empty               # window reset cleared

    def test_serve_stats_event_roundtrip(self, tmp_path):
        from byol_tpu.observability.events import RunLog, read_events
        m = ServingMeter()
        m.record_batch(rows=4, bucket=8, t_now=1.0)
        m.record_latency(0.005)
        path = str(tmp_path / "serve.jsonl")
        with RunLog(path) as log:
            m.emit(log, 2.0, compile_count=3, streams=8)
            # an EMPTY window must also produce a valid line (NaN
            # percentiles -> "NaN" strings, still schema-valid)
            m.emit(log, 3.0)
        events = list(read_events(path))
        assert [e["kind"] for e in events] == ["serve_stats", "serve_stats"]
        assert events[0]["requests"] == 1 and events[0]["compile_count"] == 3
        assert events[1]["p50_ms"] == "NaN"


# ---------------------------------------------------------------------------
# 4. engine + service on the mesh (one shared model/checkpoint setup)
# ---------------------------------------------------------------------------

_NUM_CLASSES = 10


def _serve_cfg():
    from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                      TaskConfig)
    return Config(
        task=TaskConfig(task="fake", batch_size=16, epochs=2,
                        image_size_override=16),
        model=ModelConfig(arch="resnet18", head_latent_size=32,
                          projection_size=16),
        device=DeviceConfig(num_replicas=8, half=False, seed=0),
    )


@pytest.fixture(scope="module")
def served(mesh8, tmp_path_factory):
    """Train-state on the 8-device mesh -> checkpoint -> serving restore
    onto a 4-device mesh (FEWER devices than it trained on) -> a built
    (unstarted) service plus the pieces the tests compare against."""
    from byol_tpu.checkpoint import CheckpointStore
    from byol_tpu.core.config import resolve
    from byol_tpu.parallel.compile_plan import build_plan
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from byol_tpu.serving.engine import ServingEngine
    from byol_tpu.serving.service import (ServeConfig, build_service,
                                          restore_params_for_serving)
    from byol_tpu.training.build import build_net, build_tx, init_variables
    from byol_tpu.training.state import create_train_state

    cfg = _serve_cfg()
    rcfg = resolve(cfg, num_train_samples=64, num_test_samples=16,
                   output_size=_NUM_CLASSES, input_shape=(16, 16, 3))
    net = build_net(rcfg)
    plan8 = build_plan(mesh8)
    with mesh8:
        variables = init_variables(net, rcfg, jax.random.PRNGKey(0))
        tx, _ = build_tx(rcfg)
        state = create_train_state(variables, tx)
    state, _ = plan8.prepare_state(state, tx)

    ckpt_dir = str(tmp_path_factory.mktemp("serve") / "ckpt")
    store = CheckpointStore(ckpt_dir)
    store.save(0, plan8.to_canonical(state))   # identity for a replicated
    store._ckptr.wait_until_finished()         # plan; mesh-size portable
    store.close()

    mesh4 = build_mesh(MeshSpec(data=4), jax.devices()[:4])
    net_s, params, batch_stats, epoch = restore_params_for_serving(
        cfg, ckpt_dir, mesh4, num_classes=_NUM_CLASSES)
    assert epoch == 0
    service = build_service(
        cfg, ServeConfig(min_bucket=8, max_bucket=16, max_wait_ms=2.0,
                         num_classes=_NUM_CLASSES),
        checkpoint_dir=ckpt_dir, mesh=mesh4)
    yield types.SimpleNamespace(
        cfg=cfg, net=net_s, params=params, batch_stats=batch_stats,
        service=service, mesh4=mesh4, ckpt_dir=ckpt_dir)
    service.batcher.close()


def _extractor_features(served, images, chunk=None):
    """The linear-eval ground truth: extract_features over the SAME
    restored checkpoint params (the offline-protocol path serving must
    reproduce), fed ``chunk`` rows per compiled batch (default: all rows
    in one batch)."""
    from byol_tpu.training.linear_eval import (encoder_apply_fn,
                                               extract_features)
    state = types.SimpleNamespace(params=served.params,
                                  batch_stats=served.batch_stats)
    apply_fn = encoder_apply_fn(served.net, state, half=False,
                                normalize=False)
    chunk = chunk or len(images)
    feats, labels = extract_features(
        apply_fn,
        iter([{"view1": images[i:i + chunk],
               "label": np.arange(len(images[i:i + chunk]), dtype=np.int32)}
              for i in range(0, len(images), chunk)]))
    return feats


def _assert_served_matches_offline(served, got, images, bucket):
    """Batching, bucket padding, data-sharding, donation and AOT
    compilation may change WHERE the flops run, not what the user gets.

    Why two comparisons and not one ``assert_array_equal`` (PR 22): the
    installed XLA CPU backend picks its convolution/matmul kernels by the
    COMPILED batch shape, and the two paths compile different ones — the
    service pads to a bucket and splits it over the mesh (``bucket / 4``
    rows per device here), ``extract_features`` compiles whatever batch it
    is handed.  Measured on this stack: per-device batches of 3-4 rows
    round differently from 1, 2, 8, 11 or 16 rows, by 1 ulp of the largest
    embedding magnitude on ~54% of elements.  So:

    - BITWISE where the compiled per-device batch shape is equal — the
      offline path fed ``bucket / n_devices`` rows per batch;
    - <= 4 ulp of the largest embedding magnitude across shapes — the
      offline path fed everything in one batch.  (ulp of the largest
      magnitude, not per element: near-zero elements carry the same
      absolute rounding.)  No relative tolerance anywhere.
    """
    per_device = bucket // len(served.mesh4.devices.flat)
    np.testing.assert_array_equal(
        got, _extractor_features(served, images, chunk=per_device))
    expected = _extractor_features(served, images)
    four_ulp = 4 * np.spacing(np.float32(np.max(np.abs(expected))))
    assert float(np.max(np.abs(got - expected))) <= four_ulp


class TestServingCorrectness:
    def test_served_embeddings_match_linear_eval(self, served):
        """The acceptance pin (see _assert_served_matches_offline for what
        'match' means and why) — and the hot path runs clean under the
        guard_steps transfer guard (explicit device_put/device_get only)."""
        rng = np.random.RandomState(7)
        images = rng.rand(16, 16, 16, 3).astype(np.float32)
        engine = served.service.engine
        # exact-fill bucket (16 rows -> bucket 16)
        _assert_served_matches_offline(
            served, guard_steps(engine.embed)(images), images, 16)
        # padded bucket (11 rows -> bucket 16, 5 pad rows sliced off):
        # pad rows must never bleed into real rows
        _assert_served_matches_offline(
            served, guard_steps(engine.embed)(images[:11]), images[:11], 16)
        # and below the floor (3 rows -> bucket 8)
        _assert_served_matches_offline(
            served, guard_steps(engine.embed)(images[:3]), images[:3], 8)

    def test_full_service_roundtrip_matches_too(self, served):
        """Same pin through the THREADED path: queue -> coalesce ->
        worker -> futures (the engine test above bypasses the batcher) —
        and every request that came back carries its COMPLETE lifecycle
        (enqueue -> coalesce -> stage -> dispatch -> readback -> deliver,
        monotonic, with a unique trace id): the ISSUE 9 acceptance pin
        that serving spans cover the full request path under the same
        scenario as the parity check.  Which requests the worker coalesces
        is timing-dependent, so only the across-shapes bound applies."""
        from byol_tpu.serving.batcher import LIFECYCLE_PHASES
        rng = np.random.RandomState(8)
        images = rng.rand(6, 16, 16, 3).astype(np.float32)
        expected = _extractor_features(served, images)
        svc = served.service
        if svc._thread is None:
            svc.start(warmup=True)
        reqs = [svc.submit(images[i]) for i in range(6)]
        got = np.stack([r.result(timeout=120.0)[0] for r in reqs])
        four_ulp = 4 * np.spacing(np.float32(np.max(np.abs(expected))))
        assert float(np.max(np.abs(got - expected))) <= four_ulp
        assert len({r.trace_id for r in reqs}) == len(reqs)
        for r in reqs:
            stamps = [r.marks[p] for p in LIFECYCLE_PHASES]
            assert len(stamps) == len(LIFECYCLE_PHASES)   # all phases hit
            assert stamps == sorted(stamps)               # causal order
            # the phase deltas reconstruct the meter's latency sample
            assert sum(r.lifecycle().values()) == pytest.approx(
                r.marks["deliver"] - r.marks["enqueue"])

    def test_restored_onto_fewer_devices(self, served):
        """The checkpoint trained on 8 devices; the serving mesh has 4 —
        the canonical codec makes that a non-event."""
        assert len(served.mesh4.devices.flat) == 4
        assert served.service.engine._plan.num_shards == 4


class TestBuildServiceValidation:
    def test_bad_bucket_config_fails_before_model_build(self, mesh8):
        """A bucket vocabulary incompatible with the serving mesh must be
        an immediate, actionable ValueError — not a traceback after the
        encoder build / checkpoint restore has already been paid."""
        import time as _time

        from byol_tpu.serving.service import ServeConfig, build_service
        t0 = _time.perf_counter()
        with pytest.raises(ValueError, match="multiple of the serving"):
            build_service(_serve_cfg(),
                          ServeConfig(min_bucket=4, max_bucket=16),
                          mesh=mesh8)          # 4 % 8 != 0
        assert _time.perf_counter() - t0 < 5.0   # pre-build fail-fast


class TestCompileDiscipline:
    def test_compile_count_equals_distinct_buckets_touched(self, served):
        """Lazy path (no warmup): the engine compiles exactly once per
        DISTINCT bucket, never per distinct request size."""
        from byol_tpu.parallel.compile_plan import build_plan
        from byol_tpu.serving.engine import ServingEngine
        from byol_tpu.training.linear_eval import frozen_representation_fn

        represent = frozen_representation_fn(
            served.net, served.params, served.batch_stats,
            half=False, normalize=False)
        engine = ServingEngine(
            represent, build_plan(served.mesh4), input_shape=(16, 16, 3),
            buckets=BucketSpec(min_bucket=8, max_bucket=16))
        rng = np.random.RandomState(0)
        assert engine.compile_count == 0
        touched = set()
        for rows in (3, 5, 1, 8, 7):          # all -> bucket 8
            engine.embed(rng.rand(rows, 16, 16, 3).astype(np.float32))
            touched.add(engine.buckets.bucket_for(rows))
        assert engine.compile_count == len(touched) == 1
        for rows in (9, 16, 12):              # all -> bucket 16
            engine.embed(rng.rand(rows, 16, 16, 3).astype(np.float32))
            touched.add(engine.buckets.bucket_for(rows))
        assert engine.compile_count == len(touched) == 2

    def test_zero_recompiles_after_warmup_steady_state(self, served):
        """The acceptance pin: a warmed service answers an arbitrary mix
        of request sizes with the compile counter FROZEN."""
        svc = served.service
        if svc._thread is None:
            svc.start(warmup=True)
        else:
            svc.engine.warmup()
        warm = svc.engine.compile_count
        assert warm == len(svc.engine.buckets.sizes)
        rng = np.random.RandomState(1)
        reqs = [svc.submit(
                    rng.rand(int(rng.randint(1, 17)), 16, 16, 3)
                    .astype(np.float32), timeout=10.0)
                for _ in range(24)]
        for r in reqs:
            r.result(timeout=120.0)
        assert svc.engine.compile_count == warm
        # and the meter saw it all
        assert svc.meter.total_requests >= 24


class _StubEngine:
    """Engine double for service-policy tests: instant, jax-free.

    Implements the worker's REAL surface (dispatch/readback, the
    pipelined split) — dispatch "computes" eagerly and readback hands the
    result over, so the stub exercises the worker's in-flight plumbing
    without an accelerator."""

    input_shape = (4, 4, 3)              # matches _img()'s default rows

    def __init__(self, fail_rows=(), dispatch_delay_s=0.0):
        self.buckets = BucketSpec(min_bucket=8, max_bucket=16)
        self.compile_count = len(self.buckets.sizes)
        self.fail_rows = set(fail_rows)
        self.dispatch_delay_s = dispatch_delay_s
        self.max_concurrent_inflight = 0
        self._inflight = 0

    def dispatch(self, rows, timeline=None):
        if rows.shape[0] in self.fail_rows:
            raise RuntimeError(f"boom at {rows.shape[0]} rows")
        if self.dispatch_delay_s:
            time.sleep(self.dispatch_delay_s)
        if timeline is not None:
            t = time.perf_counter()
            timeline.update(stage=t, dispatch=t)
        self._inflight += 1
        self.max_concurrent_inflight = max(self.max_concurrent_inflight,
                                           self._inflight)
        out = rows.reshape(rows.shape[0], -1)[:, :4].astype(np.float32)
        return types.SimpleNamespace(
            out=out, rows=int(rows.shape[0]),
            bucket=self.buckets.bucket_for(rows.shape[0]))

    def readback(self, inflight, timeline=None):
        self._inflight -= 1
        if timeline is not None:
            timeline["readback"] = time.perf_counter()
        return inflight.out

    def embed(self, rows, timeline=None):
        return self.readback(self.dispatch(rows, timeline), timeline)


class TestServicePolicy:
    def test_engine_failure_hits_only_that_batch(self):
        """An embed failure is relayed to the requests in THAT batch;
        the worker keeps serving the queue behind them."""
        svc = EmbeddingService(
            _StubEngine(fail_rows=(2,)),
            DynamicBatcher(max_batch=16, max_wait_s=0.01))
        svc.start(warmup=False)
        bad = [svc.submit(_img()) for _ in range(2)]      # coalesce to 2
        for r in bad:
            with pytest.raises(RuntimeError, match="boom"):
                r.result(timeout=10.0)
        time.sleep(0.05)                   # let the failed flush clear
        ok = svc.submit(_img(3))
        assert ok.result(timeout=10.0).shape == (3, 4)
        svc.stop()

    def test_stop_drains_accepted_requests(self):
        svc = EmbeddingService(
            _StubEngine(), DynamicBatcher(max_batch=16, max_wait_s=0.01))
        svc.start(warmup=False)
        reqs = [svc.submit(_img()) for _ in range(5)]
        svc.stop()
        for r in reqs:
            assert r.result(timeout=1.0).shape == (1, 4)
        with pytest.raises(ServiceClosed):
            svc.submit(_img())

    def test_result_return_is_a_meter_barrier(self):
        """By the time result() returns, the request's latency sample is
        already in the meter — a caller that joins its clients and
        immediately snapshots (the bench rungs, the CLI smoke) must not
        race the worker's bookkeeping."""
        svc = EmbeddingService(
            _StubEngine(), DynamicBatcher(max_batch=16, max_wait_s=0.001))
        svc.start(warmup=False)
        for i in range(5):
            svc.embed(_img(), timeout=10.0)
            assert svc.meter.total_requests == i + 1
        svc.stop()

    def test_mismatched_shape_rejected_in_client_thread(self):
        """A wrong-sized image is THAT client's ValueError at submit —
        it must never coalesce with valid requests and kill the worker
        (which would strand every future behind it)."""
        svc = EmbeddingService(
            _StubEngine(), DynamicBatcher(max_batch=16, max_wait_s=0.01))
        svc.start(warmup=False)
        with pytest.raises(ValueError, match="do not match"):
            svc.submit(np.zeros((8, 8, 3), np.float32))
        # the worker is alive and serving
        assert svc.embed(_img(), timeout=10.0).shape == (1, 4)
        svc.stop()

    def test_stop_racing_submits_strands_no_future(self):
        """Hammer close() against concurrent submitters: every Request a
        submit RETURNED must resolve (result or ServiceClosed) — the
        close-lock + fail_pending contract under real contention."""
        svc = EmbeddingService(
            _StubEngine(), DynamicBatcher(max_batch=16, max_wait_s=0.001))
        svc.start(warmup=False)
        accepted, lock = [], threading.Lock()

        def spam():
            while True:
                try:
                    req = svc.submit(_img(), timeout=0.05)
                except ServiceClosed:
                    return
                except Exception:
                    continue        # Backpressure: retry
                with lock:
                    accepted.append(req)

        threads = [threading.Thread(target=spam) for _ in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        svc.stop()
        for t in threads:
            t.join(timeout=5.0)
        assert accepted
        for req in accepted:
            try:
                out = req.result(timeout=1.0)   # must NOT TimeoutError
                assert out.shape == (1, 4)
            except ServiceClosed:
                pass                            # refused is resolved too

    def test_padded_result_owns_its_rows(self, served):
        """engine.embed's padded-bucket result is a COPY, not a view of
        the full (bucket, D) buffer — a held single-row result must not
        pin bucket-times its own memory."""
        rng = np.random.RandomState(9)
        out = served.service.engine.embed(
            rng.rand(3, 16, 16, 3).astype(np.float32))   # bucket 8, n=3
        assert out.base is None

    def test_lifecycle_spans_and_trace_ids_through_worker(self):
        """The per-request flight path through the REAL worker loop (stub
        engine): coalesced requests share batch-level stage/dispatch/
        readback stamps, each keeps its own enqueue, the worker's
        serve/batch span carries the members' trace ids, and phase means
        reach the serve_stats snapshot."""
        from byol_tpu.observability import spans as spans_lib
        from byol_tpu.serving.batcher import LIFECYCLE_PHASES
        rec = spans_lib.SpanRecorder()
        svc = EmbeddingService(
            _StubEngine(), DynamicBatcher(max_batch=16, max_wait_s=0.01),
            recorder=rec)
        svc.start(warmup=False)
        reqs = [svc.submit(_img()) for _ in range(3)]
        for r in reqs:
            r.result(timeout=10.0)
        svc.stop()
        for r in reqs:
            assert set(LIFECYCLE_PHASES) <= set(r.marks)
            stamps = [r.marks[p] for p in LIFECYCLE_PHASES]
            assert stamps == sorted(stamps)
        batch_spans = [s for s in rec.records() if s.name == "serve/batch"]
        assert batch_spans
        spanned_ids = {tid for s in batch_spans
                       for tid in s.attrs["trace_ids"]}
        assert {r.trace_id for r in reqs} <= spanned_ids
        # lifetime totals prove the breakdown was fed once per request
        assert svc.meter.total_requests == 3

    def test_failed_request_keeps_partial_lifecycle(self):
        """An engine failure resolves the future with the error; the
        request still carries the phases it reached (enqueue/coalesce) —
        the post-mortem breadcrumb — and never a deliver stamp."""
        svc = EmbeddingService(
            _StubEngine(fail_rows=(2,)),
            DynamicBatcher(max_batch=16, max_wait_s=0.01))
        svc.start(warmup=False)
        bad = [svc.submit(_img()) for _ in range(2)]
        for r in bad:
            with pytest.raises(RuntimeError, match="boom"):
                r.result(timeout=10.0)
        for r in bad:
            assert "enqueue" in r.marks and "coalesce" in r.marks
            assert "deliver" not in r.marks
        svc.stop()

    def test_concurrent_streams_all_answered(self):
        svc = EmbeddingService(
            _StubEngine(), DynamicBatcher(max_batch=16, max_wait_s=0.002))
        svc.start(warmup=False)
        done = []
        lock = threading.Lock()

        def stream(n):
            for _ in range(n):
                out = svc.embed(_img(), timeout=30.0)
                with lock:
                    done.append(out.shape)

        threads = [threading.Thread(target=stream, args=(10,))
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        svc.stop()
        assert len(done) == 80 and set(done) == {(1, 4)}
        assert svc.meter.total_requests == 80


# ---------------------------------------------------------------------------
# 5. async dispatch pipelining (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

class TestBatcherNonblockingProbe:
    def test_empty_vs_closed_vs_batch(self):
        """next_batch(block=False) distinguishes the three worker states:
        a batch when traffic is queued, EMPTY when open-but-idle (read
        back in-flight work now), None when closed AND drained (exit)."""
        from byol_tpu.serving.batcher import EMPTY
        b = DynamicBatcher(max_batch=8, max_wait_s=0.001)
        assert b.next_batch(block=False) is EMPTY
        b.submit(_img(), timeout=0.1)
        batch = b.next_batch(block=False)
        assert batch is not EMPTY and len(batch) == 1
        b.submit(_img(6), timeout=0.1)
        b.submit(_img(5), timeout=0.1)        # 6+5 > 8: carried
        b.next_batch(block=False)
        assert [r.rows for r in b.next_batch(block=False)] == [5]  # carry
        b.close()                             # counts as available
        assert b.next_batch(block=False) is None

    def test_trace_id_override(self):
        """A caller-supplied trace id (the wire's X-Request-Id) rides the
        request verbatim; absent, the counter assigns one."""
        b = DynamicBatcher(max_batch=8)
        req = b.submit(_img(), timeout=0.1, trace_id="wire-77")
        assert req.trace_id == "wire-77"
        auto = b.submit(_img(), timeout=0.1)
        assert isinstance(auto.trace_id, int)


class TestDispatchPipelining:
    def test_results_map_to_their_requests_and_match_unpipelined(self):
        """Same distinct-valued burst through pipeline off and on: every
        request gets ITS OWN rows back (no reordering, no cross-batch
        mixup) and the two modes' results are identical."""
        outs = {}
        for pipeline in ("off", "on"):
            svc = EmbeddingService(
                _StubEngine(),
                DynamicBatcher(max_batch=16, max_wait_s=0.005),
                pipeline=pipeline)
            reqs = []
            for i in range(40):   # > 2 batches: the pipeline must turn over
                img = np.full((1, 4, 4, 3), float(i), np.float32)
                reqs.append(svc.batcher.submit(img, timeout=1.0))
            svc.start(warmup=False)
            got = np.stack([r.result(timeout=30.0)[0] for r in reqs])
            svc.stop()
            np.testing.assert_array_equal(got,
                                          np.repeat(np.arange(40.0,
                                                    dtype=np.float32)[:, None],
                                                    4, axis=1))
            outs[pipeline] = got
        np.testing.assert_array_equal(outs["off"], outs["on"])

    def test_pipelined_worker_overlaps_two_batches(self):
        """The mechanism pin: with pipelining on, the worker dispatches
        batch i+1 BEFORE reading back batch i (stub engine observes two
        concurrent in-flight batches); with it off, never."""
        for pipeline, expected_max in (("off", 1), ("on", 2)):
            engine = _StubEngine()
            svc = EmbeddingService(
                engine, DynamicBatcher(max_batch=16, max_wait_s=0.005),
                pipeline=pipeline)
            # enqueue a burst BEFORE starting the worker: > max_batch rows
            # guarantees at least two coalesced batches back-to-back
            reqs = [svc.batcher.submit(_img(), timeout=1.0)
                    for _ in range(24)]
            svc.start(warmup=False)
            for r in reqs:
                r.result(timeout=30.0)
            svc.stop()
            assert engine.max_concurrent_inflight == expected_max, pipeline

    def test_pipelined_stop_drains_dispatched_batches(self):
        """stop() during a pipelined burst still resolves EVERY accepted
        request — dispatched-but-unread batches are read back on the
        drain path, not dropped."""
        svc = EmbeddingService(
            _StubEngine(dispatch_delay_s=0.002),
            DynamicBatcher(max_batch=8, max_wait_s=0.001),
            pipeline="on")
        svc.start(warmup=False)
        reqs = [svc.submit(_img()) for _ in range(30)]
        svc.stop()
        for r in reqs:
            assert r.result(timeout=1.0).shape == (1, 4)

    def test_pipeline_bitwise_parity_on_real_engine(self, served):
        """Off vs on around the SAME warmed engine (identical
        executables): bitwise-equal embeddings, zero extra compiles —
        pipelining changes host/device overlap, nothing else."""
        engine = served.service.engine
        rng = np.random.RandomState(21)
        images = rng.rand(12, 16, 16, 3).astype(np.float32)
        outs = {}
        for pipeline in ("off", "on"):
            svc = EmbeddingService(
                engine, DynamicBatcher(max_batch=16, max_wait_s=0.005),
                pipeline=pipeline)
            svc.start(warmup=True)
            compiles_before = engine.compile_count
            reqs = [svc.submit(images[i]) for i in range(12)]
            outs[pipeline] = np.stack(
                [r.result(timeout=120.0)[0] for r in reqs])
            svc.stop()
            assert engine.compile_count == compiles_before
        np.testing.assert_array_equal(outs["off"], outs["on"])

    def test_invalid_pipeline_mode_rejected(self):
        with pytest.raises(ValueError, match="pipeline"):
            EmbeddingService(_StubEngine(),
                             DynamicBatcher(max_batch=8),
                             pipeline="double")
