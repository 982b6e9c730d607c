"""``python -m byol_tpu serve`` — stand up the embedding service.

Reuses the TRAINING parser (byol_tpu/cli.py) plus a serving argument
group, so the net-defining flags (--arch, --half, --normalize-inputs,
--image-size-override, ...) are spelled exactly as they were at training
time — the checkpoint only restores into the architecture those flags
describe.  Serving-only knobs:

    --checkpoint DIR      CheckpointStore root — the trainer saves to
                          <model_dir>/<run_name> (default .models/...);
                          empty serves a RANDOM-init encoder (smoke/bench
                          only — compute is identical, embeddings are
                          meaningless)
    --restore-best        restore the best-metric epoch instead of last
    --min-bucket/--max-batch   the power-of-two bucket vocabulary
    --max-queue           bounded-queue depth (backpressure past it)
    --max-wait-ms         coalescing flush deadline
    --pipeline off|on     worker dispatch pipelining (double-buffered
                          stage+dispatch overlapping the device; on)
    --http HOST:PORT      the wire front end (serving/net/): POST
                          /v1/embed + healthz/readyz/statsz, X-Deadline-Ms
                          admission budgets, 429/503 backpressure, SIGTERM
                          graceful drain.  Empty = in-process only.
    --http-deadline-ms    default per-request budget when the client
                          sends no X-Deadline-Ms
    --drain-grace-s       seconds /readyz answers 503 BEFORE in-flight
                          waiting begins — the window a load balancer's
                          readiness prober needs to evict this replica
    --serve-events PATH   serve_stats JSONL log (observability/events.py
                          schema; default <log_dir>/serve.jsonl)
    --smoke N             drive N synthetic requests through the full
                          stack from --smoke-streams client threads,
                          print the stats line, and exit — over the WIRE
                          (with request/readiness assertions) when --http
                          is given, in-process otherwise.  Exits NONZERO
                          when any stream's request fails or times out —
                          a smoke where half the requests died must not
                          pass CI on the strength of the other half.

Without --smoke the process serves until SIGTERM/SIGINT, then drains
gracefully: /readyz flips to 503 immediately, --drain-grace-s elapses,
accepted requests complete, the listener closes, and the service stops —
every accepted request resolves before exit.
"""
from __future__ import annotations

import sys
import time
from typing import List, Optional


def build_serve_parser():
    from byol_tpu.cli import build_parser
    p = build_parser()
    p.prog = "python -m byol_tpu serve"
    s = p.add_argument_group("serving")
    s.add_argument("--checkpoint", type=str, default="",
                   help="CheckpointStore directory to restore — the "
                        "trainer writes <model_dir>/<run_name> (the dir "
                        "holding ckpt-N/ + meta.json); empty = "
                        "random-init encoder (smoke/bench only)")
    s.add_argument("--restore-best", action="store_true",
                   help="restore the best-metric checkpoint, not the last")
    s.add_argument("--num-classes", type=int, default=10,
                   help="probe-head width the checkpoint trained with "
                        "(tree structure must match to restore)")
    s.add_argument("--min-bucket", type=int, default=8,
                   help="smallest pad-to bucket (power of two, multiple "
                        "of the data-axis size)")
    s.add_argument("--max-batch", type=int, default=64,
                   help="largest bucket = the coalescing ceiling "
                        "(power of two)")
    s.add_argument("--max-queue", type=int, default=256,
                   help="bounded request queue depth; submits past it "
                        "get backpressure")
    s.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="coalescing flush deadline per batch")
    s.add_argument("--pipeline", choices=("off", "on"), default="on",
                   help="worker dispatch pipelining: 'on' double-buffers "
                        "stage+dispatch so the host prepares batch i+1 "
                        "while the device computes batch i (bitwise-"
                        "identical results; serve-ladder A/B in "
                        "RESULTS.md)")
    s.add_argument("--http", type=str, default="",
                   help="bind the wire front end at HOST:PORT "
                        "(serving/net/server.py: POST /v1/embed, GET "
                        "/healthz|/readyz|/statsz); empty = in-process "
                        "submit() only")
    s.add_argument("--http-deadline-ms", type=float, default=30_000.0,
                   help="default admission budget for requests without "
                        "an X-Deadline-Ms header")
    s.add_argument("--drain-grace-s", type=float, default=0.5,
                   help="seconds /readyz serves 503 before the drain "
                        "waits out in-flight requests (load-balancer "
                        "eviction window)")
    s.add_argument("--stats-interval", type=float, default=10.0,
                   help="seconds between serve_stats event emits")
    s.add_argument("--serve-events", type=str, default="",
                   help="serve_stats JSONL path (default "
                        "<log_dir>/serve.jsonl)")
    s.add_argument("--serve-trace", type=str, default="",
                   help="Chrome-trace JSON written at shutdown from the "
                        "serving flight recorder (per-batch spans with "
                        "request trace ids + engine stage/dispatch/"
                        "readback + wire http/read|parse|wait|write; "
                        "observability/spans.py) on one timeline with "
                        "set-up (startup/*) and JAX's compiles "
                        "(compile/*, under each bucket's startup/compile); "
                        "default <log_dir>/serve_trace.json, 'off' keeps "
                        "the serving path free of spans and writes no file")
    s.add_argument("--smoke", type=int, default=0,
                   help="drive N synthetic requests through the service "
                        "(over the wire when --http is given), print "
                        "stats, exit nonzero on ANY failed/timed-out "
                        "request (CI smoke)")
    s.add_argument("--smoke-streams", type=int, default=4,
                   help="concurrent client threads for --smoke")
    s.add_argument("--cpu-devices", type=int, default=0,
                   help="size a virtual CPU mesh (forces the cpu "
                        "platform; bench.py's flag, same semantics)")
    return p


def _smoke_rc(result, requested: int) -> int:
    """The smoke gate, factored for the exit-code pin in tests/test_net:
    ANY failed or missing request is a nonzero exit — the loadgen
    accounts, this judges."""
    return 0 if (result.failed == 0
                 and result.completed == requested) else 1


def _run_smoke_inproc(service, n_requests: int, n_streams: int, *,
                      seed: int = 0, timeout_s: float = 600.0):
    """Closed-loop smoke through the in-process submit() path."""
    from byol_tpu.serving.net.loadgen import run_closed_loop

    return run_closed_loop(
        lambda idx, img: service.embed(img, timeout=timeout_s),
        service.engine.input_shape, n_requests, n_streams, seed=seed)


def _run_smoke_wire(server, n_requests: int, n_streams: int, *,
                    seed: int = 0, deadline_ms: float = 30_000.0):
    """Closed-loop smoke OVER THE WIRE: one connection-reusing client per
    stream, every request carrying an explicit deadline."""
    from byol_tpu.serving.net.client import EmbedClient
    from byol_tpu.serving.net.loadgen import run_closed_loop

    host, port = server.address
    clients = {}

    def setup(idx: int) -> None:
        clients[idx] = EmbedClient(host, port,
                                   timeout_s=deadline_ms / 1e3 + 5.0,
                                   seed=seed + idx)

    def embed(idx: int, img) -> None:
        clients[idx].embed(img, deadline_ms=deadline_ms,
                           request_id=f"smoke-{idx}")

    try:
        return run_closed_loop(
            embed, server.input_shape, n_requests, n_streams,
            seed=seed, stream_setup=setup)
    finally:
        for c in clients.values():
            c.close()


def _assert_drain_transition(server) -> List[str]:
    """The lifecycle contract, checked over the REAL wire: ready before
    drain, 503 readyz + 200 healthz DURING drain.  Returns the list of
    violations (empty = clean); begin_drain is left set — the caller
    finishes with server.drain()."""
    from byol_tpu.serving.net.client import EmbedClient

    host, port = server.address
    problems: List[str] = []
    with EmbedClient(host, port, timeout_s=10.0) as probe:
        status, _ = probe.get("/healthz")
        if status != 200:
            problems.append(f"healthz {status} != 200 before drain")
        status, _ = probe.get("/readyz")
        if status != 200:
            problems.append(f"readyz {status} != 200 before drain")
        server.begin_drain()
        status, _ = probe.get("/readyz")
        if status != 503:
            problems.append(f"readyz {status} != 503 during drain")
        status, _ = probe.get("/healthz")
        if status != 200:
            problems.append(f"healthz {status} != 200 during drain "
                            "(liveness must outlive readiness)")
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    args = build_serve_parser().parse_args(argv)
    from byol_tpu.models.registry import get_spec
    if get_spec(args.arch).input_kind != "image":
        # before any backend starts: requests, buckets and staging are
        # images through and through
        print(f"serve: --arch {args.arch} takes token ids; the embedding "
              "server takes images only", file=sys.stderr)
        return 2
    import os
    import signal
    import threading

    import jax

    from byol_tpu.core import preflight
    if args.no_cuda:
        jax.config.update("jax_platforms", "cpu")
    if args.cpu_devices:
        preflight.force_cpu_devices(args.cpu_devices)
    preflight.place_compile_cache()
    # this process owns the chip; CPU only when asked for
    preflight.require_tpu("byol_tpu serve")

    from byol_tpu.cli import config_from_args
    from byol_tpu.observability import spans as spans_lib
    from byol_tpu.observability.events import RunLog
    from byol_tpu.serving.meter import serve_log_line
    from byol_tpu.serving.service import ServeConfig, build_service

    cfg = config_from_args(args)
    serve_cfg = ServeConfig(
        min_bucket=args.min_bucket, max_bucket=args.max_batch,
        max_queue=args.max_queue, max_wait_ms=args.max_wait_ms,
        num_classes=args.num_classes,
        stats_interval_s=args.stats_interval,
        pipeline=args.pipeline)
    http_addr = None
    if args.http:
        from byol_tpu.serving.net.client import parse_address
        try:
            http_addr = parse_address(args.http)
        except ValueError as e:
            print(f"serve: {e}", file=sys.stderr)
            return 2
    events_path = args.serve_events or os.path.join(cfg.task.log_dir,
                                                    "serve.jsonl")
    trace_path = args.serve_trace or os.path.join(cfg.task.log_dir,
                                                  "serve_trace.json")
    recorder = (spans_lib.NULL if args.serve_trace == "off"
                else spans_lib.PROCESS)

    def _export_trace() -> None:
        if not recorder.enabled:
            return
        try:
            n = spans_lib.export_chrome_trace(recorder.records(),
                                              trace_path,
                                              process_name="byol_serve")
            print(f"serve: wrote {n} span(s) to {trace_path}",
                  file=sys.stderr)
        except OSError as e:   # evidence, never a reason to fail shutdown
            print(f"serve: trace export failed ({e!r})", file=sys.stderr)

    with RunLog(events_path, best_effort=True) as events:
        events.emit("run_header",
                    config={**cfg.to_dict(),
                            "serving": {
                                "checkpoint": args.checkpoint,
                                "min_bucket": args.min_bucket,
                                "max_batch": args.max_batch,
                                "max_queue": args.max_queue,
                                "max_wait_ms": args.max_wait_ms,
                                "pipeline": args.pipeline,
                                "http": args.http}},
                    jax_version=jax.__version__,
                    backend=jax.default_backend(),
                    device=preflight.describe_device())
        service = build_service(cfg, serve_cfg,
                                checkpoint_dir=args.checkpoint,
                                best=args.restore_best, events=events,
                                recorder=recorder)
        if not args.checkpoint:
            print("serve: no --checkpoint given — serving a RANDOM-init "
                  "encoder (embeddings are meaningless; smoke/bench "
                  "only)", file=sys.stderr)
        t0 = time.perf_counter()
        service.start()          # warmup: full bucket vocabulary compiles
        print(f"serve: warm — {service.engine.compile_count} bucket "
              f"program(s) {list(service.engine.buckets.sizes)} compiled "
              f"in {time.perf_counter() - t0:.1f}s; "
              f"accepting requests ({service.engine.describe()})")
        server = None
        if http_addr is not None:
            from byol_tpu.serving.net.server import WireServer
            server = WireServer(
                service, http_addr[0], http_addr[1],
                default_deadline_ms=args.http_deadline_ms).start()
            print(f"serve: wire front end at "
                  f"http://{server.address[0]}:{server.address[1]} "
                  "(POST /v1/embed, GET /healthz /readyz /statsz)",
                  file=sys.stderr)

        if args.smoke:
            problems: List[str] = []
            if server is not None:
                res = _run_smoke_wire(
                    server, args.smoke, args.smoke_streams,
                    seed=cfg.device.seed,
                    deadline_ms=args.http_deadline_ms)
                # read the window BEFORE the drain: the final stats emit
                # in stop() resets it
                snap = service.meter.snapshot(time.perf_counter(),
                                              reset=False)
                # the lifecycle assertions ride the smoke: readiness
                # flips to 503 the moment the drain begins, liveness
                # stays 200, and the drain completes cleanly
                problems = _assert_drain_transition(server)
                if not server.drain(grace_s=0.0, timeout_s=60.0):
                    problems.append("drain timed out with requests "
                                    "still in flight")
            else:
                res = _run_smoke_inproc(service, args.smoke,
                                        args.smoke_streams,
                                        seed=cfg.device.seed)
                # read the window BEFORE stop(), same reason
                snap = service.meter.snapshot(time.perf_counter(),
                                              reset=False)
                service.stop()
            _export_trace()
            print(serve_log_line(snap))
            print(res.summary(), file=sys.stderr)
            for p in problems:
                print(f"serve: smoke lifecycle violation: {p}",
                      file=sys.stderr)
            events.emit("run_end", smoke_requests=res.completed,
                        smoke_failed=res.failed,
                        compile_count=service.engine.compile_count,
                        engine=service.engine.describe())
            return 1 if problems else _smoke_rc(res, args.smoke)

        # long-running mode: the worker serves; this thread naps and
        # flushes stats windows until SIGTERM/SIGINT starts the drain
        stop_signal = threading.Event()
        sig_name = {}

        def _on_signal(signum, frame):  # noqa: ARG001 — handler contract
            sig_name["got"] = signal.Signals(signum).name
            stop_signal.set()

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
        try:
            while not stop_signal.wait(serve_cfg.stats_interval_s):
                service._emit_stats(force=True)
        finally:
            print(f"serve: {sig_name.get('got', 'shutdown')} — draining "
                  f"(readyz 503 for {args.drain_grace_s}s, then "
                  "completing in-flight requests)", file=sys.stderr)
            if server is not None:
                server.drain(grace_s=args.drain_grace_s)
            else:
                service.stop()
            _export_trace()
            events.emit("run_end",
                        compile_count=service.engine.compile_count,
                        engine=service.engine.describe())
            print("serve: drained — every accepted request resolved",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
