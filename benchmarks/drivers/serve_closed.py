"""Time-budgeted closed-loop clients against an in-process
EmbeddingService: ``clients`` threads, each sends its next request when the
last is answered.  A time-budgeted, multi-row copy of the program's
``serving/net/loadgen.run_closed_loop`` (which is budgeted by count and
sends one row); the original is listed in PERF.md for a later PR to merge.

The service is the program's own — ``DynamicBatcher`` -> worker ->
``ServingEngine`` with the documented ``ServeConfig`` defaults — assembled
as ``build_service`` assembles it, except that the encoder's weights are
the benchmark's seeded ones (lib/weights.py), so the reference takes
nothing the program has made.

Traffic (all from the cell's file): every client walks one fixed,
interleaved list of request sizes (``rows_cycle``) from its own seeded
starting point, so every seed sends the same mix in every stretch of a few
requests, in another phase; rows come from a seeded pool of distinct
images.  Requests are timed on the client's clock from submit to
result.  A request that fails, is refused or times out counts as failed and
as over any latency limit.  After the window a seeded sample of the served
requests (with the largest size in it) is compared with the float32
reference's served forward.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np


def build_service(ctx):
    """Mirror of ``serving.service.build_service`` with seeded weights."""
    import jax
    from byol_tpu.parallel.compile_plan import build_plan
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from byol_tpu.serving.batcher import DynamicBatcher
    from byol_tpu.serving.buckets import BucketSpec
    from byol_tpu.serving.engine import ServingEngine
    from byol_tpu.serving.service import (EmbeddingService, ServeConfig,
                                          _serving_rcfg)
    from byol_tpu.training.build import build_net, init_variables
    from byol_tpu.training.linear_eval import frozen_representation_fn
    from benchmarks.drivers.train_loop import program_config
    from benchmarks.lib.weights import make_weights

    conf = ctx.config
    cfg = program_config(conf, seed=ctx.seed, chips=ctx.chips)
    serve_cfg = ServeConfig(num_classes=conf["num_classes"],
                            **ctx.cell["traffic"].get("serve_config", {}))
    mesh = build_mesh(MeshSpec(data=ctx.chips), ctx.devices)
    rcfg = _serving_rcfg(cfg, serve_cfg.num_classes)
    net = build_net(rcfg)
    like = jax.eval_shape(lambda k: init_variables(net, rcfg, k),
                          jax.random.PRNGKey(0))
    ctx.scratch["like"] = (like["params"], like.get("batch_stats", {}))
    params, stats = make_weights(
        *ctx.scratch["like"], ctx.seed,
        zero_init_residual=ctx.cell["traffic"].get(
            "zero_init_residual", False))
    represent = frozen_representation_fn(
        net, params, stats, half=cfg.device.half,
        normalize=cfg.parity.normalize_inputs)
    engine = ServingEngine(
        represent, build_plan(mesh), input_shape=rcfg.input_shape,
        buckets=BucketSpec(min_bucket=serve_cfg.min_bucket,
                           max_bucket=serve_cfg.max_bucket))
    batcher = DynamicBatcher(max_batch=serve_cfg.max_bucket,
                             max_queue=serve_cfg.max_queue,
                             max_wait_s=serve_cfg.max_wait_ms / 1e3)
    return EmbeddingService(engine, batcher,
                            stats_interval_s=1e9,
                            pipeline=serve_cfg.pipeline)


def image_pool(seed: int, rows: int, image: int) -> np.ndarray:
    return np.random.default_rng(seed).random(
        (rows, image, image, 3), dtype=np.float32)


class Client(threading.Thread):
    """One closed-loop caller."""

    def __init__(self, idx, service, pool, sizes, offsets, start, deadline,
                 timeout_s, keep):
        super().__init__(name=f"bench-client-{idx}", daemon=True)
        self.idx, self.service, self.pool = idx, service, pool
        self.sizes, self.offsets = sizes, offsets
        self.start_gate, self.deadline = start, deadline
        self.timeout_s, self.keep = timeout_s, keep
        self.records = []       # (rows, t_submit, t_done, ok)
        self.kept = []          # (pool offset, rows, embeddings)
        self.errors = []

    def run(self):
        self.start_gate.wait()
        n = len(self.sizes)
        i = 0
        while time.perf_counter() < self.deadline[0]:
            rows, off = self.sizes[i % n], self.offsets[i % n]
            images = self.pool[off:off + rows]
            t0 = time.perf_counter()
            try:
                out = self.service.submit(
                    images, timeout=self.timeout_s).result(self.timeout_s)
                ok = out.shape[0] == rows and bool(np.isfinite(out).all())
            except Exception as e:  # noqa: BLE001 — counted, not fatal
                ok, out = False, None
                if len(self.errors) < 4:
                    self.errors.append(repr(e)[:200])
            t1 = time.perf_counter()
            self.records.append((rows, t0, t1, ok))
            if ok and i in self.keep:          # first pass of the cycle only
                self.kept.append((off, rows, np.array(out)))
            i += 1


def control(ctx, precision: str) -> dict:
    """The control: the reference's served forward in ``precision`` on the
    sampled requests of the run just made, against float32."""
    from benchmarks.lib import check, reference
    images, ref, params, stats = ctx.scratch["compared"]
    ctl = reference.embed(params, stats, images,
                          image_size=ctx.config["image_size"],
                          vit_heads=ctx.config.get("num_heads", 0),
                          precision=precision)
    return check.serving_numbers(ctl, ref)


def run(ctx) -> dict:
    import jax
    from benchmarks.lib import check, reference
    from benchmarks.lib.weights import make_weights
    traffic, conf = ctx.cell["traffic"], ctx.config
    image = conf["image_size"]
    service = build_service(ctx)
    # The bucket programs hold the encoder's weights as constants (PR 22):
    # about 100 MB each, different for every seed, so the persistent cache
    # could only ever hit on a repeated seed, and writing them would push
    # every other cell's programs out of a size-capped cache.  They are
    # compiled in every run and never written.
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        service.start(warmup=True)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    engine = service.engine
    ctx.say(f"serve_closed: warm — {engine.compile_count} bucket programs, "
            f"compile seconds {engine.describe()['compile_seconds']}")
    temp = max(int(getattr(e.memory_analysis(), "temp_size_in_bytes", 0) or 0)
               for e in engine._executables.values())

    rng = np.random.default_rng(ctx.seed)
    cycle = list(traffic["rows_cycle"])
    pool_rows = int(traffic["pool_rows"])
    pool = image_pool(ctx.seed, pool_rows, image)
    biggest = max(cycle)
    for rows in sorted(set(cycle)):            # every size once, untimed
        service.submit(pool[:rows], timeout=60.0).result(120.0)
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    start, deadline = threading.Event(), [0.0]
    clients = []
    sample = int(ctx.cell["check"]["sample_requests_per_client"])
    for c in range(int(traffic["clients"])):
        phase = int(rng.integers(0, len(cycle)))
        sizes = cycle[phase:] + cycle[:phase]
        offsets = [int(rng.integers(0, pool_rows - r + 1)) for r in sizes]
        # the sample: among each client's first requests, so that a slow
        # system has finished them too
        keep = set(int(j) for j in rng.choice(
            min(len(sizes), int(ctx.cell["check"]["sample_from_first"])),
            sample, replace=False))
        if c == 0:                              # the longest is in the sample
            keep.add(sizes.index(biggest))
        clients.append(Client(c, service, pool, sizes, offsets, start,
                              deadline, float(traffic["timeout_s"]), keep))
    for c in clients:
        c.start()
    service.meter.snapshot(time.perf_counter(), reset=True)
    compiles_before = ctx.compile_count()
    ctx.start_trace()
    setup_s = time.perf_counter() - ctx.t0
    with ctx.annotate("bench/window"):
        t_start = time.perf_counter()
        deadline[0] = t_start + seconds
        start.set()
        for c in clients:
            c.join()
        t_end = time.perf_counter()
    ctx.stop_trace()
    meter = service.meter.snapshot(time.perf_counter(), reset=False)
    compiles = (ctx.compile_count() - compiles_before
                + engine.compile_count - len(engine.buckets.sizes))
    service.stop()
    memory = ctx.memory_peak(extra_temp_bytes=temp)

    records = [r for c in clients for r in c.records]
    errors = [e for c in clients for e in c.errors]
    ok = [r for r in records if r[3]]
    rows_done = sum(r[0] for r in ok)
    window_s = t_end - t_start
    lat_ms = sorted((r[2] - r[1]) * 1e3 for r in ok)
    # a failed or refused request is over any limit: it sits at +inf
    lat_all = lat_ms + [float("inf")] * (len(records) - len(ok))
    p95 = lat_all[min(len(lat_all) - 1, int(0.95 * len(lat_all)))] \
        if lat_all else float("inf")
    rate = rows_done / window_s
    ctx.say(f"serve_closed: {len(ok)}/{len(records)} requests ok, "
            f"{rows_done} rows in {window_s:.3f}s = {rate:.1f} images/s, "
            f"p95 {p95:.1f} ms; errors {errors[:4]}")

    kept = [k for c in clients for k in c.kept]
    del service, engine, clients
    gc.collect()
    params, stats = make_weights(
        *ctx.scratch["like"], ctx.seed,
        zero_init_residual=traffic.get("zero_init_residual", False))
    numbers = {"embed_rel_gap": float("inf")}
    if kept:
        images = np.concatenate([pool[o:o + r] for o, r, _ in kept])
        served = np.concatenate([e for _, _, e in kept])
        t_ref = time.perf_counter()
        ref = reference.embed(params, stats, images, image_size=image,
                              vit_heads=conf.get("num_heads", 0))
        ctx.say(f"serve_closed: reference embedded {len(images)} rows of "
                f"{len(kept)} sampled requests in "
                f"{time.perf_counter() - t_ref:.1f}s")
        numbers = check.serving_numbers(served, ref)
        ctx.scratch["compared"] = (images, ref, params, stats)
    counters = {"serve_images_per_s": rate, "requests": len(records),
                "rows": rows_done, "window_s": window_s, "chips": ctx.chips,
                "latency_ms": lat_ms, "compiles_in_window": compiles}
    return {
        "attempted": len(records),
        "failed": len(records) - len(ok),
        "setup_s": setup_s,
        "end_to_end": {"serve_images_per_s": (rate, "images/s"),
                       "serve_latency_p95_ms": (p95, "ms")},
        "numbers": numbers,
        "counters": counters,
        "meter": meter,
        "memory_peak_bytes": memory,
    }
