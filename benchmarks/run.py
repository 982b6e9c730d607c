#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chips the
cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``) and
``device`` (as JAX reports it, ``memory_peak_bytes``, and with ``--trace 1``
``busy_s`` / ``window_s``), plus ``breakdown`` in a traced run.

Everything that belongs to one cell, configuration or per-layer metric is a
file found by name: ``workloads/<cell>.json`` (which names its driver in
``drivers/`` and its configuration in ``configs/``) and every
``layer_metrics/*.py``.  This file holds none of it.

Told nothing it requires a TPU and at least the cell's chips, and exits
non-zero without a result line otherwise.  ``--rehearse-cpu`` (never given
by the driver) walks the same path on the CPU backend with as many virtual
devices as the cell has chips: every device metric is then absent, and no
CPU time is written under a device metric's name.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # process start, as near as Python allows

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")     # git-ignored scratch, in-checkout


def say(msg: str) -> None:
    """One line of the run's log, stamped with the seconds since start."""
    print(f"[{time.perf_counter() - T0:8.2f}s] {msg}", flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str):
    """``drivers/<name>.py``, imported under its package name so that a
    test can reach into it."""
    return importlib.import_module(f"benchmarks.drivers.{name}")


def layer_metric_readers() -> list:
    folder = os.path.join(HERE, "layer_metrics")
    return [load_module(os.path.join(folder, f))
            for f in sorted(os.listdir(folder))
            if f.endswith(".py") and not f.startswith("_")]


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="walk the path on the CPU backend (no device "
                        "metric is reported)")
    return p.parse_args(argv)


def start_backend(chips: int, rehearse: bool):
    """The program's own start-up sequence (core/preflight.py), then the
    benchmark's stricter rule: a TPU with enough chips, or nothing."""
    sys.path.insert(0, ROOT)
    import jax
    from byol_tpu.core import preflight
    if rehearse:
        preflight.force_cpu_devices(max(chips, 1))
    cache_dir = preflight.place_compile_cache()
    # the reference's many small programs are worth caching too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmarks/run.py: JAX found platform "
            f"{devices[0].platform!r}, not 'tpu' (pass --rehearse-cpu to "
            "walk the path on the CPU; it reports no device metric)")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmarks/run.py: the cell needs {chips} chip(s), JAX found "
            f"{len(devices)}")
    return devices[:chips], cache_dir


class Context(types.SimpleNamespace):
    """What a driver gets: the cell, its configuration, the seed, the
    window, the devices, and the harness's tracing and counting hooks."""

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_trace(self) -> None:
        if not self.trace:
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # Device planes only.  With the host tracer on (even at level 1) the
        # runtime traces every chunk of the host-side transposes that lay a
        # batch out for the device, millions of events a second, and a
        # ResNet-50 step took 1,061-1,196 ms instead of 329; with it off
        # the traced window runs at the untraced rate (my chip runs, PR 24).
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True

    def stop_trace(self) -> None:
        if getattr(self, "_tracing", False):
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    def compile_count(self) -> int:
        return self._compiles[0]

    def memory_peak(self, extra_temp_bytes: int = 0) -> int:
        """Peak on the fullest chip.  The allocator's ``peak_bytes_in_use``
        counts live arrays only; a running program's temporaries are a
        RESERVATION it reports apart (``peak_bytes_reserved``: PR 24 read
        1.17 GB in use beside 14.87 GB reserved, which is PR 22's
        1.79-against-13.90 GiB riddle).  The peak is their sum; where the
        allocator reports no reservation, what is live plus the largest
        program's temporaries by the compiler."""
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            if self.verbose_memory:
                say(f"memory_stats {d.id}: " + json.dumps(
                    {k: int(v) for k, v in stats.items()
                     if isinstance(v, (int, float))}))
            if "peak_bytes_reserved" in stats:
                here = (int(stats.get("peak_bytes_in_use", 0))
                        + int(stats["peak_bytes_reserved"]))
            elif stats:
                here = max(int(stats.get("peak_bytes_in_use", 0)),
                           int(stats.get("bytes_in_use", 0))
                           + extra_temp_bytes)
            else:
                here = 0
            peak = max(peak, here)
        return peak


def count_compiles() -> list:
    """A counter of backend compilations, for 'nothing compiles inside the
    window'."""
    import jax
    counter = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counter[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return counter


def make_context(args, cell, config, devices, compiles=None) -> Context:
    os.makedirs(OUT, exist_ok=True)
    return Context(
        cell=cell, config=config, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, chips=len(devices),
        on_tpu=devices[0].platform == "tpu", t0=T0, root=ROOT, say=say,
        scratch={},
        trace_dir=os.path.join(OUT, f"profile_{args.workload}"),
        verbose_memory=True,
        _compiles=compiles if compiles is not None else count_compiles())


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_json("workloads", f"{args.workload}.json")
    config = load_json("configs", f"{cell['config']}.json")
    chips = int(cell["chips"])
    devices, cache_dir = start_backend(chips, args.rehearse_cpu)
    on_tpu = devices[0].platform == "tpu"
    ctx = make_context(args, cell, config, devices)
    say(f"run: cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; device {devices[0].platform} "
        f"{devices[0].device_kind} x{chips}; compile cache {cache_dir}")

    driver = load_driver(cell["driver"])
    result = driver.run(ctx)
    ctx.stop_trace()

    from benchmarks.lib import check, peaks, trace_reduce
    correct = check.verdict(result["numbers"], cell["check"]["limits"], say)
    correct = correct and result["counters"].get("compiles_in_window", 0) == 0
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": int(result["memory_peak_bytes"])}
    metrics = {"setup_s": {"value": result["setup_s"], "unit": "s"}}
    metrics.update({k: {"value": v, "unit": u}
                    for k, (v, u) in result["end_to_end"].items()})
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if args.trace:
        reduced = None
        if on_tpu:
            planes = trace_reduce.load(
                trace_reduce.find_xplane(ctx.trace_dir))
            try:
                reduced = trace_reduce.reduce(planes, devices=chips)
            except Exception:
                say("trace did not reduce; it holds:\n"
                    + trace_reduce.describe(planes))
                raise
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"][:10]}
        sources = {
            "trace": reduced, "counters": result["counters"],
            "meter": result.get("meter"), "config": config, "cell": cell,
            "peaks": peaks.peaks_for(devices[0].device_kind)
            if on_tpu else None}
        layer = {}
        for reader in layer_metric_readers():
            value = reader.read(sources)
            if value is not None:
                layer[reader.NAME] = {"value": float(value),
                                      "unit": reader.UNIT}
        line["traced_end_to_end"] = metrics
        line["metrics"] = layer
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
