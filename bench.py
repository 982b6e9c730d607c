"""Benchmark: BYOL training-step throughput, images/sec/chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu"}.

The reference publishes no throughput numbers (BASELINE.md), so the baseline
here is measured in-process: a reference-faithful configuration (fp32, four
separate encoder forwards with per-view BN batches — the semantics of
/root/reference/main.py:244-247 — and pre-update EMA, main.py:255) versus the
TPU-first default (bf16 compute, fused two-view forward).  ``vs_baseline`` is
the speedup of the TPU-first path over that faithful translation on the same
chip, i.e. what the TPU-native redesign buys.

Needs a TPU: with no chip and no request for the CPU (``--cpu-devices N`` /
``JAX_PLATFORMS=cpu``) it exits non-zero and prints no number.  One process
owns the chip; the only children (the ladder compile gates) run and exit
before this process initialises its backend.  The modes, partial files and
OOM pinning below are the next benchmark PR's to replace (ROADMAP S1).

Robustness contract:
- ANY failure while building/measuring one batch-ladder candidate is treated
  as "that batch did not fit" (logged to stderr with the real traceback) and
  the ladder steps down.  Earlier rounds' records show compile-time OOM
  spelled ``JaxRuntimeError: INTERNAL: ... tpu_compile_helper subprocess
  exit code 1`` — not RESOURCE_EXHAUSTED — so string-matching specific OOM
  spellings is a losing game.
- Every measured result is flushed to ``bench_partial.json`` IMMEDIATELY, so
  a later failure (e.g. the fp32 baseline config) can never zero out an
  already-measured number.
- If the baseline config fails at every ladder rung, the primary result is
  still printed with ``vs_baseline: null`` rather than crashing.

MFU: analytic model FLOPs / measured step time / chip peak.  FLOPs count
multiply-add as 2 (the same convention as the quoted chip peaks).  Per
sample: 2 online forwards + 2 target forwards + backward (~2x the online
forwards) = 8 encoder-forward-equivalents; head MLP/probe FLOPs are <1% of
the RN50 trunk at 224px and are ignored.

Every measured row now carries ``compile_seconds`` and
``hbm_high_water_bytes`` (from ``jit(...).lower(...).compile()
.memory_analysis()``), so spill/OOM regimes are visible in BENCH_*.json
without reading OOM dumps.

Usage:
  python bench.py                  # the two headline configs -> one JSON line
  python bench.py --mvc            # minimum-viable capture: one rung per
                                   #   family + the rematted bs512 sweep row,
                                   #   sized for a few chip-minutes
  python bench.py --sweep          # batch x remat x fuse grid -> bench_sweep.json
  python bench.py --profile DIR    # jax.profiler trace of the headline config
  python bench.py --stem-ab        # conv vs space_to_depth stem A/B
  python bench.py --data           # host data pipeline: tf vs native C++
  python bench.py --accum-ladder   # microbatch-accumulation ladder: effective
                                   #   512/1024/4096 at the per-chip-optimal
                                   #   microbatch (256), each rung's compile
                                   #   gated behind a killable subprocess
                                   #   timeout; records compile_seconds +
                                   #   HBM high-water + img/s/chip
  python bench.py --dry-compile    # AOT-compile ONE accumulation config
                                   #   (default: effective 4096 @ microbatch
                                   #   256, --remat-policy dots) and report
                                   #   memory_analysis() without executing;
                                   #   --augment-placement loader|step picks
                                   #   the input contract (float32 views vs
                                   #   raw uint8 + in-step augmentation)
  python bench.py --input-ladder   # augment-placement A/B: loader-aug
                                   #   float32 vs step-aug uint8 at effective
                                   #   512/1024/4096 @ microbatch 256; every
                                   #   row records h2d_bytes_per_step + HBM
                                   #   high-water (same compile gating as
                                   #   --accum-ladder)
  python bench.py --telemetry-ab   # telemetry-overhead A/B: --telemetry off
                                   #   vs step @ --telemetry-interval 50,
                                   #   full observation cost (in-graph
                                   #   health vector + lagged sink
                                   #   readback); budget < 2%
  python bench.py --spans-ab       # flight-recorder overhead A/B: ONE
                                   #   compiled executable timed with the
                                   #   spans-off no-op recorder vs a live
                                   #   SpanRecorder wrapping every dispatch
                                   #   + the readback, INTERLEAVED reps +
                                   #   median (spans are host-side only,
                                   #   so the arms share the identical
                                   #   program and box drift cancels);
                                   #   budget < 2%.  The spans arm also
                                   #   emits goodput/span_stats events into
                                   #   bench_events.jsonl and exports
                                   #   bench_trace.json (Chrome trace)
  python bench.py --zero1-ab       # ZeRO-1 weight-update-sharding A/B
                                   #   (--dry-compile flavored: AOT compile
                                   #   only, no execution): replicated vs
                                   #   --zero1 on at the accumulation
                                   #   target config; every row records
                                   #   hbm_high_water_bytes + the per-chip
                                   #   optimizer_state_bytes column (which
                                   #   must scale ~1/N with mesh size).
                                   #   --cpu-devices N sizes the virtual
                                   #   CPU mesh for off-hardware captures
  python bench.py --augment-ab     # fused-augmentation A/B: the step-
                                   #   placement config with the XLA op
                                   #   chain (--fused-augment off) vs the
                                   #   fused Pallas kernel (on), both arms
                                   #   AOT-compiled and timed under a live
                                   #   SpanRecorder (wall + train/dispatch
                                   #   span p50 -> bench_events.jsonl), plus
                                   #   an in-process microbench row: bare
                                   #   two_view XLA chain vs fused call
  python bench.py --serve-ladder   # embedding-service latency/throughput
                                   #   at 1/8/64 closed-loop streams;
                                   #   --serve-pipeline off|on|ab A/Bs the
                                   #   worker dispatch pipelining on the
                                   #   same warmed engine
  python bench.py --wire-ladder    # the WIRE TAX: in-process vs
                                   #   over-HTTP (serving/net/) per rung —
                                   #   client-observed p50/p99 for both
                                   #   arms and the per-rung delta

Every run also appends structured events (run header + one ``bench_row``
per measured config) to ``bench_events.jsonl`` — the same schema-versioned
JSONL format trainer.fit writes as ``run.jsonl``
(byol_tpu/observability/events.py), so one reader parses runs and benches.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import jax
import numpy as np

# fwd GMACs per image (multiply-accumulates; FLOPs = 2x). torchvision-style
# counts for the conv trunk; heads ignored (sub-1% at these shapes).
_GMACS = {
    ("resnet50", 224): 4.089,
    ("resnet50", 96): 0.76,
    ("resnet18", 224): 1.814,
    ("resnet18", 32): 0.557,   # CIFAR stem (3x3 s1, no maxpool)
    # ViT-B/16 @224 (BASELINE.json config 5): 197 tokens; per block
    # 4*S*D^2 qkvo + 2*S^2*D attn + 8*S*D^2 MLP = 1.454 GMACs, x12 blocks
    # + 0.116 patch embed = 17.56 GMACs/forward-image.
    ("vit_b16", 224): 17.56,
}

# Chip peak table lives with the framework's MFU accounting (the trainer
# reports live MFU from the same source, observability/flops.py).
from byol_tpu.observability.flops import chip_peak_tflops as _chip_peak_tflops

# Strict-JSON output contract (GL110): every JSON line/file this script
# emits goes through the event sink's sanitize + allow_nan=False path,
# so an anomalous run (NaN loss, inf step time) still prints parseable
# JSON instead of bare NaN/Infinity tokens.
from byol_tpu.observability.events import sanitize as _sanitize_json


def _json_line(obj) -> str:
    return json.dumps(_sanitize_json(obj), allow_nan=False)



def _flops_per_sample(arch: str, image_size: int) -> float | None:
    gmacs = _GMACS.get((arch, image_size))
    if gmacs is None:
        return None
    # 2 online + 2 target fwds + bwd (2x online's 2 fwds) = 8 fwd-images.
    return 8.0 * gmacs * 2.0 * 1e9


class _Rate(float):
    """img/s/chip that also carries per-rung compile/memory side-channel
    stats (``compile_seconds``, ``hbm_high_water_bytes``, ...) for the JSON
    rows — arithmetic call sites keep treating it as a plain float."""

    stats: dict = {}

    def __new__(cls, value, stats=None):
        r = super().__new__(cls, value)
        r.stats = dict(stats or {})
        return r


def _row_stats(val) -> dict:
    return dict(getattr(val, "stats", {}) or {})


def _memory_stats(compiled) -> dict:
    """Extract the HBM picture from ``compiled.memory_analysis()``.

    ``hbm_high_water_bytes`` is the executable's device-memory high-water
    mark: arguments + outputs + XLA temp buffers, minus donated aliases
    (donation makes the output share the argument buffer).  Best-effort:
    a backend without the analysis yields {} rather than failing the rung.
    """
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out = {}
    for key in ("temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes"):
        v = getattr(mem, key, None)
        if v is not None:
            out[key] = int(v)
    peak = getattr(mem, "peak_memory_in_bytes", None)
    if peak is not None and int(peak) > 0:
        out["hbm_high_water_bytes"] = int(peak)
    elif "temp_size_in_bytes" in out:
        out["hbm_high_water_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
            + out["temp_size_in_bytes"])
    return out


def _build(batch_size: int, image_size: int, arch: str, *, half: bool,
           fuse_views: bool, ema_update_mode: str, remat: bool = False,
           stem: str = "conv", attn_impl: str = "dense",
           accum_steps: int = 1, accum_bn_mode: str = "average",
           remat_policy: str = "none", augment_placement: str = "loader",
           telemetry: str = "off", zero1: str = "off",
           fused_augment: str = "off", materialize_batch: bool = True):
    from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                      OptimConfig, ParityConfig, TaskConfig,
                                      resolve)
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh
    from byol_tpu.training.build import setup_training

    n_dev = len(jax.devices())
    mesh = build_mesh(MeshSpec(data=n_dev))
    cfg = Config(
        task=TaskConfig(task="fake", batch_size=batch_size * n_dev, epochs=100,
                        image_size_override=image_size,
                        augment_placement=augment_placement,
                        fused_augment=fused_augment),
        model=ModelConfig(arch=arch, fuse_views=fuse_views, remat=remat,
                          remat_policy=remat_policy,
                          stem=stem, attn_impl=attn_impl),
        optim=OptimConfig(accum_steps=accum_steps,
                          accum_bn_mode=accum_bn_mode),
        device=DeviceConfig(num_replicas=n_dev, half=half, seed=0,
                            telemetry=telemetry, zero1=zero1),
        parity=ParityConfig(ema_update_mode=ema_update_mode),
    )
    rcfg = resolve(cfg, num_train_samples=1_281_167, num_test_samples=50_000,
                   output_size=1000,
                   input_shape=(image_size, image_size, 3))
    net, state, train_step, _, _ = setup_training(
        rcfg, mesh, jax.random.PRNGKey(0))

    b = cfg.task.batch_size
    if not materialize_batch:
        # Compile-only paths lower against shapes + shardings; no pixels.
        return (state, train_step,
                _abstract_batch(b, image_size, mesh,
                                augment_placement=augment_placement), mesh)
    # fp32-native generation: RandomState.rand materializes a float64
    # intermediate, which at the effective-4096 rung is a ~40 GB host
    # transient PER VIEW — enough to OOM the 1-core TPU host before the
    # measurement starts.
    rng = np.random.default_rng(0)
    if augment_placement == "step":
        # raw-uint8 contract (loader._raw_pipeline): the step augments
        batch = {
            "images": rng.integers(0, 256, (b, image_size, image_size, 3),
                                   dtype=np.uint8),
            "label": rng.integers(0, 1000, size=(b,)).astype(np.int32),
        }
    else:
        batch = {
            "view1": rng.random((b, image_size, image_size, 3),
                                dtype=np.float32),
            "view2": rng.random((b, image_size, image_size, 3),
                                dtype=np.float32),
            "label": rng.integers(0, 1000, size=(b,)).astype(np.int32),
        }
    batch = shard_batch_to_mesh(batch, mesh)
    return state, train_step, batch, mesh


def _batch_h2d_bytes(batch) -> int:
    """Host bytes one step's input batch ships over PCIe/H2D — works for
    concrete arrays and for the compile-only ShapeDtypeStruct batches.
    ONE implementation shared with the trainer's input meter
    (data/prefetch.py host_nbytes), so the bench column and the epoch log
    can never disagree."""
    from byol_tpu.data.prefetch import host_nbytes
    return host_nbytes(batch)


def _optimizer_state_bytes(state) -> int | None:
    """PER-CHIP bytes of the weight-update state (optimizer state + EMA
    target): the HBM the ZeRO-1 A/B exists to measure.  Computed from each
    leaf's SHARDING (``shard_shape``), not its global shape — a flat
    leaf-partitioned tree reports ~1/N of its replicated size, which is
    exactly the per-chip truth ``memory_analysis()``'s aggregate argument
    bytes cannot break out.  Best-effort: states without shardings (or
    non-TrainState pytrees) yield None rather than failing the rung."""
    import math
    try:
        leaves = jax.tree_util.tree_leaves(
            (state.opt_state, state.target_params))
        total = 0
        for leaf in leaves:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                shape = tuple(sharding.shard_shape(shape))
            itemsize = np.dtype(leaf.dtype).itemsize
            total += int(math.prod(shape)) * itemsize
        return total
    except Exception:
        return None


def _aot_compile(train_step, state, batch, mesh):
    """AOT lower+compile the step ONCE; returns (compiled, stats).

    The explicit lower/compile (instead of compile-on-first-call) is what
    makes ``compile_seconds`` and ``memory_analysis()`` observable per rung;
    the returned executable is then used for the measurement itself, so the
    rung still compiles exactly once.
    """
    fn = getattr(train_step, "__wrapped__", train_step)
    t0 = time.perf_counter()
    with mesh:
        compiled = fn.lower(state, batch).compile()
    stats = {"compile_seconds": round(time.perf_counter() - t0, 2),
             "h2d_bytes_per_step": _batch_h2d_bytes(batch)}
    opt_bytes = _optimizer_state_bytes(state)
    if opt_bytes is not None:
        stats["optimizer_state_bytes"] = opt_bytes
    stats.update(_memory_stats(compiled))
    return compiled, stats


def _throughput(batch_size: int, image_size: int, arch: str, *, half: bool,
                fuse_views: bool, ema_update_mode: str, remat: bool = False,
                stem: str = "conv", attn_impl: str = "dense",
                accum_steps: int = 1, accum_bn_mode: str = "average",
                remat_policy: str = "none",
                augment_placement: str = "loader", steps: int = 20) -> _Rate:
    """Images/sec/chip for one configuration (global images / sec / n_dev);
    the returned float carries compile/HBM stats (``_Rate.stats``)."""
    state, train_step, batch, mesh = _build(
        batch_size, image_size, arch, half=half, fuse_views=fuse_views,
        ema_update_mode=ema_update_mode, remat=remat, stem=stem,
        attn_impl=attn_impl, accum_steps=accum_steps,
        accum_bn_mode=accum_bn_mode, remat_policy=remat_policy,
        augment_placement=augment_placement)
    compiled, stats = _aot_compile(train_step, state, batch, mesh)
    # warmup: 3 steady steps.  The timed region ends in a scalar READBACK
    # of a value that depends on the whole step chain — the sync the
    # trainer's epoch loop itself performs.  On the v5e under jax 0.9.0
    # block_until_ready waits for completion just the same: chip_smoke.py
    # read 337.9 ms/step ending in it against 340.9 ms/step ending in the
    # readback (PERF.md, PR 22), so either ending is sound there; only the
    # bare dispatch loop (77 ms/step) returns early.
    for _ in range(3):
        state, metrics = compiled(state, batch)
    float(metrics["loss_mean"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = compiled(state, batch)
    float(metrics["loss_mean"])
    dt = time.perf_counter() - t0
    n_dev = len(jax.devices())
    global_batch = batch["label"].shape[0]
    return _Rate(global_batch * steps / dt / n_dev, stats)


_PARTIAL_PATH = "bench_partial.json"
_partial: dict = {"results": []}

def _config_failed(context: str, exc: BaseException) -> None:
    """Shared per-config failure path: ANY failure while building or
    measuring one config counts as did-not-fit (module docstring) — log it
    with the real traceback; the caller records ``fit=False`` and steps
    the ladder down."""
    print(f"bench: {context} failed (treating as did-not-fit):",
          file=sys.stderr)
    traceback.print_exc()


def _oom_signature(exc_text: str) -> bool:
    """Does a recorded failure look like a deterministic memory/compile
    failure (safe to pin across runs), as opposed to a transient error
    that deserves a re-attempt?  The spellings are the ones the earlier
    rounds' records show (``RESOURCE_EXHAUSTED``, "Ran out of memory",
    ``tpu_compile_helper``); the next benchmark PR (ROADMAP S1) re-grounds
    them on what the installed compiler prints."""
    low = exc_text.lower()
    return ("resource_exhausted" in low or "out of memory" in low
            or "ran out of memory" in low or "tpu_compile_helper" in low)


def _known_oom(bs: int, arch: str, image_size: int,
               remat: bool = False) -> bool:
    """Is this rung the documented deterministic compile-OOM?  An earlier
    round's record shows the un-rematted resnet50@224 bs1024 step failing
    to compile for a 16 GB chip after a 25+ minute attempt.  The sweep
    grid rule is "never re-attempted without remat"; this predicate
    extends the same rule to the headline and profile ladders.  Not
    re-observed under the installed compiler (ROADMAP S4)."""
    return (not remat and bs >= 1024 and arch == "resnet50"
            and image_size == 224)


_flushed_paths: set = set()


def _flush_partial():
    try:
        # A fresh run must never DESTROY prior evidence: the first write of
        # this process moves any existing file to <path>.prev instead of
        # truncating it.  (Learned the hard way: an import-time classifier
        # check once overwrote the committed TPU artifact with a single
        # backend_died stub.)
        if _PARTIAL_PATH not in _flushed_paths:
            if os.path.exists(_PARTIAL_PATH):
                os.replace(_PARTIAL_PATH, _PARTIAL_PATH + ".prev")
            # only after the backup succeeded: a failed replace must retry
            # next flush, never fall through to truncating the evidence
            _flushed_paths.add(_PARTIAL_PATH)
        with open(_PARTIAL_PATH, "w") as f:
            json.dump(_sanitize_json(_partial), f, indent=2,
                      allow_nan=False)
            f.write("\n")
    except OSError as e:  # read-only fs must not kill the measurement
        print(f"bench: could not write {_PARTIAL_PATH}: {e}", file=sys.stderr)


_events = None          # observability.events.RunLog, opened by main()


def _open_events(path: str = "bench_events.jsonl") -> None:
    """Open the structured bench event log (same JSONL schema as
    trainer.fit's run.jsonl, observability/events.py) and stamp the run
    header.  Deliberately backend-client-free: the header reads only the
    static jax config, so it is safe to call BEFORE the accum-ladder gate
    children claim the single-client TPU.  RunLog(best_effort=True)
    swallows construction and write failures alike — a read-only fs must
    not kill the measurement (same contract as _flush_partial)."""
    global _events
    from byol_tpu.observability.events import RunLog
    _events = RunLog(path, best_effort=True)
    _events.emit("run_header",
                 config={"argv": sys.argv[1:], "tool": "bench.py"},
                 jax_version=jax.__version__,
                 backend=str(jax.config.jax_platforms or "auto"))


def _record(name: str, **fields):
    global _events
    _partial["results"].append({"config": name, **fields})
    _flush_partial()
    if _events is not None:
        # every bench row doubles as a structured event — one reader
        # (observability/events.py) parses runs and benches alike.
        # Best-effort like _flush_partial: a disk that fills mid-sweep
        # must not kill hours of measurement.
        try:
            _events.emit("bench_row", config=name, **fields)
        except (OSError, TypeError, ValueError) as e:
            print(f"bench: event log write failed ({e!r}); disabling "
                  "bench_events.jsonl for the rest of the run",
                  file=sys.stderr)
            _events = None


def main():
    if "--data" in sys.argv[1:]:
        _data_pipeline_bench()     # host-only: no accelerator preflight
        return
    # --cpu-devices N: size a virtual CPU mesh for off-hardware captures
    # (the --zero1-ab 1/N scaling rows need several mesh sizes).  Must run
    # before any backend touch.
    from byol_tpu.core import preflight
    n_cpu = _int_flag("--cpu-devices", 0)
    if n_cpu:
        preflight.force_cpu_devices(n_cpu)
    # Optional arch override (e.g. --arch vit_b16, the BASELINE.json
    # config-5 encoder swap).  Non-default archs measure into their OWN
    # partial file so they can never rotate away the resnet50 one.
    arch_override = None
    if "--arch" in sys.argv[1:]:
        i = sys.argv.index("--arch") + 1
        if i >= len(sys.argv):
            raise SystemExit("usage: bench.py --arch <registry name>")
        arch_override = sys.argv[i]
        # Fail fast on typos: otherwise every ladder rung "fails to fit"
        # and the exit misdiagnoses a misspelling as a memory ceiling.
        from byol_tpu.models.registry import get_spec
        try:
            get_spec(arch_override)
        except ValueError as e:
            raise SystemExit(f"bench: {e}")
    # Attention backend for ViT archs (--attn dense|ring).
    attn_impl = "dense"
    if "--attn" in sys.argv[1:]:
        i = sys.argv.index("--attn") + 1
        if i >= len(sys.argv) or sys.argv[i] not in ("dense", "ring"):
            # fail fast like --arch: a typo here would otherwise record
            # every ladder rung as "did not fit" (trace-time error)
            raise SystemExit("usage: bench.py --attn dense|ring")
        attn_impl = sys.argv[i]
    global _PARTIAL_PATH
    if arch_override and arch_override != "resnet50":
        _PARTIAL_PATH = f"bench_partial_{arch_override}.json"
    if attn_impl != "dense":
        _PARTIAL_PATH = _PARTIAL_PATH.replace(
            ".json", f"_{attn_impl}.json")
    if "--dry-compile" not in sys.argv[1:]:
        # --dry-compile is also the accum/input-ladder GATE CHILD body: a
        # header per child would interleave N+1 run_headers into the
        # parent sweep's event stream (and a standalone dry-compile emits
        # its one JSON line on stdout — nothing to log here either)
        _open_events()
    # Persistent compile cache (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache): a sweep re-run, and the parent after its gate
    # children, find every program already compiled.
    preflight.place_compile_cache()
    accum_gates = input_gates = None
    if "--accum-ladder" in sys.argv[1:] or "--input-ladder" in sys.argv[1:]:
        # Gate children run and EXIT before this process initialises its
        # backend (a chip belongs to one process at a time); nothing below
        # this block starts a child.  Whether they compile for the chip is
        # decided without touching the backend: CPU only when asked for.
        is_accel = not preflight.cpu_requested()
        if "--accum-ladder" in sys.argv[1:]:
            accum_gates = _accum_gate_phase(is_accel, arch_override,
                                            attn_impl)
        if "--input-ladder" in sys.argv[1:]:
            input_gates = _input_gate_phase(is_accel, arch_override,
                                            attn_impl)
    # From here on this process owns the chip.  It measures on the CPU
    # only when asked to (--cpu-devices N / JAX_PLATFORMS=cpu): with no
    # chip and no such request it exits non-zero, printing no number.
    preflight.require_tpu("bench")
    on_tpu = jax.default_backend() not in ("cpu",)
    if on_tpu:
        arch, image_size = arch_override or "resnet50", 224
        candidates = [1024, 512, 256, 128, 64, 32]
        if arch != "resnet50":
            # Non-default archs start below the 1024 rung: the un-rematted
            # rn50 bs1024 compile once took 25+ min to fail — no first
            # contact with a new arch should spend chip-minutes there.
            candidates = [512, 256, 128, 64, 32]
    else:  # the CPU was asked for (require_tpu above): a toy config that
        # walks the control flow — its rates are not device metrics
        arch, image_size = "resnet18", 32
        candidates = [64, 32]
        # CPU rehearsals write their own partial file
        _PARTIAL_PATH = "bench_partial_cpu.json"

    flops_per_sample = _flops_per_sample(arch, image_size)
    peak = _chip_peak_tflops()
    _partial.update(arch=arch, image_size=image_size,
                    device_kind=jax.devices()[0].device_kind,
                    n_devices=len(jax.devices()),
                    peak_bf16_tflops=peak)

    def mfu_of(img_per_sec_per_chip: float) -> float | None:
        if flops_per_sample is None or peak is None or not on_tpu:
            return None
        return img_per_sec_per_chip * flops_per_sample / (peak * 1e12)

    def best_throughput(name: str, **kw):
        """Best throughput over the candidate ladder — each config measured
        at ITS OWN best batch size, as a real user would run it.  ANY
        per-candidate failure counts as "didn't fit" (see module doc).
        The largest FITTING batch is not always the fastest (near-OOM
        batches can spill/fragment), so on TPU the next rung down is
        measured too and the max of the two is returned (CPU fallback keeps
        a single rung — it exists for liveness, not measurement)."""
        rungs = 2 if on_tpu else 1
        measured = 0
        best = None
        for bs in candidates:
            if _known_oom(bs, arch, image_size, kw.get("remat", False)):
                _record(name, batch_per_chip=bs, fit=False, reused=True,
                        error="skipped: documented un-rematted bs1024 "
                              "compile-OOM (25+ minute failing compile)")
                continue
            try:
                val = _throughput(bs, image_size, arch, **kw)
            except Exception as e:
                _config_failed(f"config={name} bs/chip={bs}", e)
                _record(name, batch_per_chip=bs, fit=False,
                        error=repr(e)[:300])
                continue
            _record(name, batch_per_chip=bs, fit=True,
                    images_per_sec_per_chip=round(val, 2), mfu=mfu_of(val),
                    **_row_stats(val),
                    **{k: v for k, v in kw.items() if k != "steps"})
            best = val if best is None else max(best, val)
            measured += 1
            if measured >= rungs:
                break
        return best

    if "--stem-ab" in sys.argv[1:]:
        # A/B the headline config's stem: plain 7x7/2 conv vs the
        # space-to-depth rearrangement (identical numerics; layout only).
        if not on_tpu:
            raise SystemExit(
                "bench: --stem-ab needs the TPU config — the CPU fallback "
                "(resnet18@32) uses the CIFAR stem, where the stem knob is "
                "inert and an A/B would compare identical models")
        for stem in ("conv", "space_to_depth"):
            val = best_throughput(f"stem_{stem}", half=True, fuse_views=True,
                                  ema_update_mode="post", stem=stem)
            print(_json_line({"metric": f"stem_ab_{stem}",
                              "value": round(val, 2) if val else None,
                              "unit": "images/sec/chip",
                              "vs_baseline": None,
                              "mfu": (round(mfu_of(val), 4)
                                      if val and mfu_of(val) else None)}))
        return
    if "--sweep" in sys.argv[1:]:
        _sweep(arch, image_size, candidates, mfu_of)
        return
    if "--profile" in sys.argv[1:]:
        i = sys.argv.index("--profile") + 1
        if i >= len(sys.argv):
            raise SystemExit("usage: bench.py --profile <logdir>")
        _profile(arch, image_size, candidates, sys.argv[i])
        return
    if "--mvc" in sys.argv[1:]:
        _mvc(arch, image_size, candidates, on_tpu, mfu_of, attn_impl)
        return
    if "--dry-compile" in sys.argv[1:]:
        _dry_compile(arch, image_size, on_tpu, attn_impl)
        return
    if "--accum-ladder" in sys.argv[1:]:
        _accum_ladder(arch, image_size, on_tpu, mfu_of, attn_impl,
                      accum_gates)
        return
    if "--input-ladder" in sys.argv[1:]:
        _input_ladder(arch, image_size, on_tpu, mfu_of, attn_impl,
                      input_gates)
        return
    if "--telemetry-ab" in sys.argv[1:]:
        _telemetry_ab(arch, image_size, on_tpu, attn_impl)
        return
    if "--spans-ab" in sys.argv[1:]:
        _spans_ab(arch, image_size, on_tpu, attn_impl)
        return
    if "--zero1-ab" in sys.argv[1:]:
        _zero1_ab(arch, image_size, on_tpu, attn_impl)
        return
    if "--augment-ab" in sys.argv[1:]:
        _augment_ab(arch, image_size, on_tpu, attn_impl)
        return
    if "--serve-ladder" in sys.argv[1:]:
        _serve_ladder(arch, image_size, on_tpu, attn_impl)
        return
    if "--wire-ladder" in sys.argv[1:]:
        _wire_ladder(arch, image_size, on_tpu, attn_impl)
        return

    value = best_throughput("tpu_first", half=True, fuse_views=True,
                            ema_update_mode="post", attn_impl=attn_impl)
    if value is None:
        # Checked BEFORE the baseline/bf16 ladders: their rungs are only
        # reported relative to a measured primary.
        raise RuntimeError(
            "no batch size fit in memory for the primary config; "
            f"per-candidate tracebacks above, partial log in {_PARTIAL_PATH}")
    baseline = best_throughput("reference_faithful", half=False,
                               fuse_views=False,
                               ema_update_mode="reference_pre", steps=10,
                               attn_impl=attn_impl)
    # Middle rung: reference SEMANTICS (four forwards, pre-update EMA) at
    # bf16.  Separates what dtype buys from what the redesign buys:
    #   vs_baseline      = tpu_first / fp32-reference   (total win)
    #   bf16_ref/baseline = dtype alone
    #   tpu_first/bf16_ref = redesign alone (fuse_views + post-EMA)
    bf16_ref = best_throughput("reference_semantics_bf16", half=True,
                               fuse_views=False,
                               ema_update_mode="reference_pre", steps=10,
                               attn_impl=attn_impl)
    _print_headline(arch, value, baseline, bf16_ref, mfu_of)


def _prior_best_rungs() -> dict:
    """Best-known FITTING batch size per config name from the committed
    partial artifact (live file or its ``.prev`` backup), same device
    class only.  Must be called BEFORE the run's first ``_record`` (which
    rotates the live file to ``.prev``)."""
    best: dict = {}
    kind = jax.devices()[0].device_kind
    for path in (_PARTIAL_PATH + ".prev", _PARTIAL_PATH):   # live file wins
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        if d.get("device_kind") != kind:
            continue
        for r in d.get("results", []):
            if r.get("fit") and "images_per_sec_per_chip" in r:
                name = str(r.get("config", ""))
                cur = best.get(name)
                if cur is None or r["images_per_sec_per_chip"] > cur[0]:
                    best[name] = (r["images_per_sec_per_chip"],
                                  r["batch_per_chip"])
    return {k: v[1] for k, v in best.items()}


def _mvc(arch, image_size, candidates, on_tpu, mfu_of, attn_impl):
    """Minimum-viable capture (``--mvc``): a fresh headline plus the
    rematted bs512 sweep row in a few chip-minutes.

    This mode measures ONE rung per headline family — the best
    known-fitting rung from the partial file when available, else the
    historically-fitting default — with a single step-down fallback and
    few timing steps.  It prints the same headline JSON line as the
    default mode, and records the rematted row under the
    ``sweep_bs*_remat1_fuse1`` naming contract so a later full
    ``--sweep`` reuses it instead of re-measuring
    (see ``_sweep_prior_rows``)."""
    prior = _prior_best_rungs() if on_tpu else {}
    top = max(candidates)

    def rungs_for(name, defaults):
        lst = ([prior[name]] if name in prior else [])
        lst += [d for d in defaults if d not in lst]
        lst = [b for b in lst if b <= top]
        return (lst or list(candidates))[:2]    # known-good + one fallback

    def fam(name, defaults, *, steps, **kw):
        for bs in rungs_for(name, defaults):
            try:
                val = _throughput(bs, image_size, arch, steps=steps,
                                  attn_impl=attn_impl, **kw)
            except Exception as e:
                if _config_failed(f"mvc {name} bs={bs}", e):
                    return None
                _record(name, batch_per_chip=bs, fit=False,
                        error=repr(e)[:300])
                continue
            _record(name, batch_per_chip=bs, fit=True,
                    images_per_sec_per_chip=round(val, 2), mfu=mfu_of(val),
                    **_row_stats(val), **kw)
            return val                   # MVC: first fitting rung only
        return None

    value = fam("tpu_first", [256, 128], steps=10, half=True,
                fuse_views=True, ema_update_mode="post")
    if value is None:
        raise RuntimeError(
            f"mvc: no rung fit for the primary config ({_PARTIAL_PATH})")
    baseline = fam("reference_faithful", [128, 64], steps=5, half=False,
                   fuse_views=False, ema_update_mode="reference_pre")
    bf16_ref = fam("reference_semantics_bf16", [256, 128], steps=5,
                   half=True, fuse_views=False,
                   ema_update_mode="reference_pre")
    # The one sweep row no round has landed: rematted bs512 — the stated
    # hypothesis for the un-rematted bs512 spill (RESULTS.md §1).
    remat_bs = 512 if top >= 512 else top
    name = f"sweep_bs{remat_bs}_remat1_fuse1"
    try:
        val = _throughput(remat_bs, image_size, arch, steps=10,
                          half=True, fuse_views=True, remat=True,
                          ema_update_mode="post", attn_impl=attn_impl)
        _record(name, fit=True, batch_per_chip=remat_bs, remat=True,
                fuse_views=True,
                images_per_sec_per_chip=round(val, 2), mfu=mfu_of(val),
                **_row_stats(val))
    except Exception as e:
        _config_failed(f"mvc {name}", e)
        _record(name, batch_per_chip=remat_bs, fit=False,
                error=repr(e)[:300])
    _print_headline(arch, value, baseline, bf16_ref, mfu_of,
                    note="minimum-viable capture (--mvc): one rung per "
                         "family")


def _print_headline(arch, value, baseline, bf16_ref, mfu_of, note=None):
    """The one headline JSON line — shared by the default mode and --mvc
    so the output contract can never diverge between them (downstream
    round tooling parses these lines)."""
    mfu = mfu_of(value)
    out = {
        "metric": f"{arch}_byol_train_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "images/sec/chip",
        "vs_baseline": (round(value / baseline, 3)
                        if baseline is not None else None),
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    if note:
        out["note"] = note
    if bf16_ref is not None:
        out["bf16_reference_semantics"] = round(bf16_ref, 2)
        if baseline is not None:
            out["dtype_gain"] = round(bf16_ref / baseline, 3)
        out["redesign_gain"] = round(value / bf16_ref, 3)
    print(_json_line(out))


def _profile(arch, image_size, candidates, logdir):
    """Capture a jax.profiler trace of a few steady-state headline-config
    steps (TensorBoard profile plugin / Perfetto readable) — the tuning
    input for the MFU push (RESULTS.md §1).

    Like ``best_throughput``, the FASTEST of the top two fitting rungs is
    the one traced — the largest fitting batch can be the slower, spilling
    one, and a trace of the degraded config would misdirect the tuning.
    Rungs are measured one at a time with nothing retained (holding rung
    A's buffers while building rung B would change B's memory picture);
    the winner is rebuilt for the trace (compile is cached)."""
    rates = []                                  # (rate, bs)
    for bs in candidates:
        if _known_oom(bs, arch, image_size):
            continue
        try:
            rates.append((_throughput(bs, image_size, arch, half=True,
                                      fuse_views=True,
                                      ema_update_mode="post", steps=5), bs))
        except Exception as e:
            _config_failed(f"profile bs={bs}", e)
            continue
        if len(rates) >= 2:
            break
    if not rates:
        raise RuntimeError("no batch size fit for profiling")
    bs = max(rates)[1]
    state, train_step, batch, _ = _build(bs, image_size, arch, half=True,
                                         fuse_views=True,
                                         ema_update_mode="post")
    for _ in range(3):                          # compile (cached) + warm
        state, metrics = train_step(state, batch)
    float(metrics["loss_mean"])
    from byol_tpu.observability import profiling
    with profiling.trace(logdir):               # device planes only
        for _ in range(5):
            state, metrics = train_step(state, batch)
        float(metrics["loss_mean"])             # readback inside the trace
    print(_json_line({"metric": "profile", "value": bs,
                      "unit": "batch/chip", "vs_baseline": None,
                      "logdir": logdir}))


def _data_pipeline_bench():
    """Host data-layer throughput: tf.data vs the native C++ backend.

    Quantifies the DALI-analog claim (SURVEY §2.4: NVIDIA DALI ->
    tf.data / custom C++ host pipeline): images/sec of fully-augmented
    two-view batches produced per host, measured through the real loader
    path (``get_loader`` -> per-epoch iterators).  Pure host work — runs
    identically with or without an accelerator attached.
    """
    # Host-only measurement, but the loader touches jax (process_index for
    # per-host sharding) — pin the cpu platform so a pure-host benchmark
    # never claims the chip.
    jax.config.update("jax_platforms", "cpu")

    from byol_tpu.core.config import Config, DeviceConfig, TaskConfig
    from byol_tpu.data import native_aug
    from byol_tpu.data.loader import get_loader

    size, bs, n = 96, 256, 2048
    backends = ["tf"] + (["native"] if native_aug.available() else [])
    rates = {}
    for backend in backends:
        cfg = Config(
            task=TaskConfig(task="synth", batch_size=bs, epochs=1,
                            image_size_override=size, data_backend=backend),
            device=DeviceConfig(num_replicas=1, seed=0))
        bundle = get_loader(cfg, num_synth_samples=n)
        for _ in bundle.train_loader:          # warm: thread pools, tf graph
            pass                               # (streaming: one batch live)
        epochs = 3
        t0 = time.perf_counter()
        batches = 0
        for e in range(epochs):
            bundle.set_all_epochs(e)
            for _ in bundle.train_loader:
                batches += 1
        dt = time.perf_counter() - t0
        rates[backend] = bs * batches / dt
        print(f"bench: data backend {backend}: {rates[backend]:.1f} img/s "
              f"(two-view {size}px batches, {batches} batches)",
              file=sys.stderr)
    if "native" not in rates:
        print("bench: native C++ backend unavailable (no toolchain/.so); "
              "reporting tf only", file=sys.stderr)

    # --data-threads 1,2,4,8: measure the native pipeline's thread-scaling
    # curve over the JPEG tree.  The RESULTS §1 feeding math (66.3
    # img/s/core x host cores >= chip demand) was a 1-core extrapolation;
    # this turns it into measurement on the first multi-core host (TPU
    # hosts have 24+ vCPU/chip).  nproc is recorded with the curve so an
    # oversubscribed 1-core run can't masquerade as real scaling.
    threads = None
    if "--data-threads" in sys.argv[1:]:
        i = sys.argv.index("--data-threads") + 1
        if i >= len(sys.argv):
            raise SystemExit("usage: bench.py --data --data-threads 1,2,4,8")
        try:
            threads = [int(t) for t in sys.argv[i].split(",")]
            if not threads or any(t < 1 for t in threads):
                raise ValueError
        except ValueError:
            raise SystemExit("usage: bench.py --data --data-threads 1,2,4,8")

    try:
        jpeg_rates = _jpeg_tree_bench(threads=threads)
    except Exception as e:     # degrade, never discard the measured rates
        print(f"bench: jpeg_224 stage failed ({e!r}); array rates stand",
              file=sys.stderr)
        jpeg_rates = None

    primary = rates.get("native", rates["tf"])
    print(_json_line({
        "metric": "host_data_pipeline_images_per_sec",
        "value": round(primary, 1),
        "unit": "images/sec/host",
        "vs_baseline": (round(rates["native"] / rates["tf"], 3)
                        if "native" in rates else None),
        "note": "two-view augmented batches; vs_baseline = native/tf",
        "jpeg_224": jpeg_rates,
    }))


def _jpeg_tree_bench(threads=None):
    """224px fused-JPEG-decode ladder over an on-disk ImageFolder tree —
    the configuration the DALI analog exists for (reference main.py:356-382
    serves ImageNet JPEG trees).  Synthetic ~500x375 JPEGs with smooth
    content so compression ratio and decode cost look like photographs,
    not noise.  Reports img/s per host for the tf fused-decode path and the
    native libjpeg fused decode+crop path, plus the per-core rate (this box
    has few cores; TPU pod hosts have 100+ — the per-core number is what
    scales).

    ``threads``: optional list of worker counts; the native path is then
    re-measured at each count and the curve reported under
    ``native_thread_curve`` (with ``cores`` = nproc alongside, so the
    reader can tell real scaling from oversubscription)."""
    import os
    import shutil
    import tempfile

    from byol_tpu.core.config import Config, DeviceConfig, TaskConfig
    from byol_tpu.data import native_aug
    from byol_tpu.data.loader import get_loader

    try:
        from PIL import Image
    except ImportError:
        print("bench: PIL unavailable; skipping jpeg_224 stage",
              file=sys.stderr)
        return None

    root = tempfile.mkdtemp(prefix="byol_jpeg_bench_")
    rng = np.random.RandomState(0)
    n_imgs, hw = 256, (375, 500)
    try:
        for split, n in (("train", n_imgs), ("test", 8)):
            for cls in ("a", "b"):
                d = os.path.join(root, split, cls)
                os.makedirs(d)
                for i in range(n // 2):
                    # low-frequency content: upsampled 12x16 noise ->
                    # photograph-like JPEG entropy (~100 KB at q87)
                    low = rng.randint(0, 255, (12, 16, 3), np.uint8)
                    img = Image.fromarray(low).resize(
                        (hw[1], hw[0]), Image.BILINEAR)
                    img.save(os.path.join(d, f"{i}.jpg"), quality=87)
        backends = ["tf"] + (["native"] if native_aug.available()
                             and native_aug.has_jpeg() else [])
        out = {}
        bs = 64

        def measure(backend, workers):
            cfg = Config(
                task=TaskConfig(task="image_folder", data_dir=root,
                                batch_size=bs, epochs=1,
                                image_size_override=224,
                                data_backend=backend),
                device=DeviceConfig(num_replicas=1, seed=0,
                                    workers_per_replica=workers))
            bundle = get_loader(cfg)
            for _ in bundle.train_loader:      # warm: tf graph/thread pools
                pass
            t0 = time.perf_counter()
            batches = 0
            for e in range(2):
                bundle.set_all_epochs(e)
                for _ in bundle.train_loader:
                    batches += 1
            dt = time.perf_counter() - t0
            return bs * batches / dt, batches

        default_workers = min(os.cpu_count() or 1, 16)
        for backend in backends:
            rate, batches = measure(backend, default_workers)
            out[backend] = round(rate, 1)
            print(f"bench: jpeg_224 backend {backend}: {rate:.1f} img/s "
                  f"({rate / (os.cpu_count() or 1):.1f} img/s/core, "
                  f"{batches} two-view batches)", file=sys.stderr)
        if threads and "native" in out:
            curve = {}
            for t in threads:
                rate, _ = measure("native", t)
                curve[str(t)] = round(rate, 1)
                print(f"bench: jpeg_224 native @{t} threads: "
                      f"{rate:.1f} img/s", file=sys.stderr)
            out["native_thread_curve"] = curve
        out["cores"] = os.cpu_count() or 1
        out["note"] = ("fused decode+crop, two 224px views/img; scale by "
                       "host cores vs the chip's img/s consumption")
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _int_flag(name: str, default: int) -> int:
    if name in sys.argv[1:]:
        i = sys.argv.index(name) + 1
        if i >= len(sys.argv):
            raise SystemExit(f"usage: bench.py ... {name} <value>")
        return int(sys.argv[i])
    return default


def _str_flag(name: str, default: str) -> str:
    if name in sys.argv[1:]:
        i = sys.argv.index(name) + 1
        if i >= len(sys.argv):
            raise SystemExit(f"usage: bench.py ... {name} <value>")
        return sys.argv[i]
    return default


_V5E_HBM_BYTES = 16 * 2 ** 30            # the budget the ladder reports against


def _abstract_batch(batch_size: int, image_size: int, mesh,
                    augment_placement: str = "loader"):
    """ShapeDtypeStruct batch for compile-only paths: lowering needs shapes
    and shardings, not 5 GB of host random pixels."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from byol_tpu.parallel.mesh import DATA_AXIS
    sh = NamedSharding(mesh, P(DATA_AXIS))
    b = batch_size
    if augment_placement == "step":
        return {
            "images": jax.ShapeDtypeStruct((b, image_size, image_size, 3),
                                           np.uint8, sharding=sh),
            "label": jax.ShapeDtypeStruct((b,), np.int32, sharding=sh),
        }
    return {
        "view1": jax.ShapeDtypeStruct((b, image_size, image_size, 3),
                                      np.float32, sharding=sh),
        "view2": jax.ShapeDtypeStruct((b, image_size, image_size, 3),
                                      np.float32, sharding=sh),
        "label": jax.ShapeDtypeStruct((b,), np.int32, sharding=sh),
    }


def _dry_compile(arch, image_size, on_tpu, attn_impl):
    """AOT-compile ONE accumulation config and report memory_analysis()
    without executing a step (``--dry-compile``).

    Defaults to the paper-scale target: effective 4096 per chip at the
    measured-optimal microbatch 256 (accum_steps 16) with the 'dots'
    selective policy.  Prints one JSON line with compile_seconds + the HBM
    high-water mark and whether it clears the v5e 16 GiB budget.  Also the
    killable subprocess body behind the accumulation ladder's compile-
    timeout gate (a compile that never ends dies with the subprocess).
    """
    eff = _int_flag("--effective-batch", 4096 if on_tpu else 64)
    mb = _int_flag("--microbatch", 256 if on_tpu else 16)
    policy = _str_flag("--remat-policy", "dots")
    bn_mode = _str_flag("--accum-bn-mode", "average")
    placement = _str_flag("--augment-placement", "loader")
    from byol_tpu.core.remat import validate_policy
    validate_policy(policy)                  # fail fast on typos
    if placement not in ("loader", "step"):
        raise SystemExit(
            "usage: bench.py ... --augment-placement loader|step")
    if eff % mb:
        raise SystemExit(
            f"bench: effective batch {eff} not divisible by microbatch {mb}")
    accum = eff // mb
    # Same wiring as every measured rung (_build), but against an ABSTRACT
    # batch (shapes + shardings): the compile-only path must not allocate
    # effective-4096 of host pixels — and sharing _build keeps the gate's
    # config from drifting away from the config the ladder then measures.
    state, train_step, batch, mesh = _build(
        eff, image_size, arch, half=True, fuse_views=True,
        ema_update_mode="post", attn_impl=attn_impl, accum_steps=accum,
        accum_bn_mode=bn_mode, remat_policy=policy,
        augment_placement=placement, materialize_batch=False)
    compiled, stats = _aot_compile(train_step, state, batch, mesh)
    del compiled
    hbm = stats.get("hbm_high_water_bytes")
    print(_json_line({
        "metric": "dry_compile_hbm_high_water_bytes",
        "value": hbm,
        "unit": "bytes",
        "vs_baseline": None,
        "arch": arch, "image_size": image_size,
        "effective_batch_per_chip": eff,
        "microbatch_per_chip": mb,
        "accum_steps": accum,
        "remat_policy": policy,
        "accum_bn_mode": bn_mode,
        "augment_placement": placement,
        "device_kind": jax.devices()[0].device_kind,
        "under_v5e_16gib": (None if hbm is None
                            else bool(hbm < _V5E_HBM_BYTES)),
        **stats,
    }))


def _accum_flags(on_tpu):
    """Shared knob parsing for the accumulation ladder and its gate phase
    (one source of truth: the gate children must compile exactly the rungs
    the ladder then measures)."""
    mb = _int_flag("--microbatch", 256 if on_tpu else 16)
    policy = _str_flag("--remat-policy", "dots")
    bn_mode = _str_flag("--accum-bn-mode", "average")
    timeout = _int_flag("--compile-timeout", 900)
    from byol_tpu.core.remat import validate_policy
    validate_policy(policy)
    # CPU fallback: ONE tiny rung — liveness, not measurement (a CPU "chip"
    # sustains ~1 img/s on this model; a second rung would run for minutes).
    effectives = [512, 1024, 4096] if on_tpu else [32]
    return mb, policy, bn_mode, timeout, effectives


def _run_compile_gates(rungs, timeout):
    """Run each rung's ``--dry-compile`` gate in a killable subprocess
    BEFORE the parent initializes its own backend client.

    This ORDER is what makes the children legal: a chip belongs to one
    process at a time, so a child that needs it can only run while no
    other process of this bench holds it.  The children run strictly
    before the parent initialises its backend, strictly one after another,
    each releasing the chip on exit and leaving its compile in the
    persistent cache (core/preflight.place_compile_cache), which makes
    the parent's measurement compile a cache read.  After they have
    exited, the parent takes the chip and starts no further child.

    ``rungs``: ``[(rung_name, extra_dry_compile_argv)]``.  Returns
    ``{rung_name: {"status": "ok"|"timeout"|"error", ...}}`` for the
    ladder to consume after the parent initializes.
    """
    import subprocess
    gates = {}
    for name, extra in rungs:
        gate_cmd = [sys.executable, os.path.abspath(__file__),
                    "--dry-compile"] + extra
        try:
            gate = subprocess.run(gate_cmd, timeout=timeout,
                                  capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            gates[name] = {"status": "timeout", "timeout": timeout}
            print(f"bench: {name}: compile gate timed out after {timeout}s",
                  file=sys.stderr)
            continue
        if gate.returncode != 0:
            gates[name] = {"status": "error",
                           "err": (gate.stderr or "").strip()[-300:]}
            continue
        try:
            row = json.loads(gate.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            row = {}
        gates[name] = {"status": "ok", "row": row}
    return gates


def _gate_args(eff, mb, policy, bn_mode, attn_impl, arch_override,
               placement="loader"):
    """argv for one --dry-compile gate child; the gate must compile the
    SAME model the ladder measures (an un-forwarded --arch would gate the
    default arch while the parent compiled the overridden one ungated)."""
    extra = ["--effective-batch", str(eff), "--microbatch", str(mb),
             "--remat-policy", policy, "--accum-bn-mode", bn_mode,
             "--attn", attn_impl, "--augment-placement", placement]
    if arch_override:
        extra += ["--arch", arch_override]
    return extra


def _accum_gate_phase(on_tpu, arch_override, attn_impl):
    """Compile gates for the accumulation ladder (see _run_compile_gates)."""
    mb, policy, bn_mode, timeout, effectives = _accum_flags(on_tpu)
    rungs = [(f"accum_eff{eff}_mb{mb}_{policy}",
              _gate_args(eff, mb, policy, bn_mode, attn_impl, arch_override))
             for eff in effectives]
    return _run_compile_gates(rungs, timeout)


def _input_gate_phase(on_tpu, arch_override, attn_impl):
    """Compile gates for the input-pipeline ladder: BOTH placements per
    effective-batch rung (loader-aug float32 views vs step-aug uint8)."""
    mb, policy, bn_mode, timeout, effectives = _accum_flags(on_tpu)
    rungs = [(f"input_eff{eff}_mb{mb}_{placement}",
              _gate_args(eff, mb, policy, bn_mode, attn_impl, arch_override,
                         placement))
             for eff in effectives
             for placement in ("loader", "step")]
    return _run_compile_gates(rungs, timeout)


def _accum_ladder(arch, image_size, on_tpu, mfu_of, attn_impl, gates):
    """Accumulation ladder (``--accum-ladder``): effective batch
    512/1024/4096 per chip, ALL at the per-chip-optimal microbatch 256
    (RESULTS.md §1: bs256 is the throughput peak; bs512 spills; bs1024
    OOMs un-rematted).

    Every rung's compile already ran in a killable subprocess
    (``--dry-compile`` body, :func:`_accum_gate_phase`, BEFORE this
    process claimed the backend) under ``--compile-timeout`` seconds —
    the compile-timeout gate: a compile that never ends is killed without
    taking this process down, and the rung records ``fit=False`` with a
    timeout signature.  On a clean gate pass this function measures
    throughput in-process; the persistent compile cache makes the second
    compile nearly free.  Rows record compile_seconds,
    hbm_high_water_bytes, and img/s/chip.
    """
    mb, policy, bn_mode, timeout, effectives = _accum_flags(on_tpu)
    timing_steps = 10 if on_tpu else 3
    rungs = []
    for eff in effectives:
        accum = eff // mb
        name = f"accum_eff{eff}_mb{mb}_{policy}"
        gate = gates.get(name) or {"status": "error",
                                   "err": "no gate result for this rung"}
        if gate["status"] == "timeout":
            _record(name, fit=False, effective_batch_per_chip=eff,
                    microbatch_per_chip=mb, accum_steps=accum,
                    remat_policy=policy,
                    error=f"compile-timeout gate: exceeded {timeout}s "
                          "(compile-timeout signature; subprocess killed)")
            continue
        if gate["status"] == "error":
            err = gate["err"]
            _config_failed(f"accum gate {name}", RuntimeError(err))
            _record(name, fit=False, effective_batch_per_chip=eff,
                    microbatch_per_chip=mb, accum_steps=accum,
                    remat_policy=policy, error=f"gate subprocess: {err}")
            continue
        gate_row = gate.get("row", {})
        try:
            val = _throughput(eff, image_size, arch, half=True,
                              fuse_views=True, ema_update_mode="post",
                              attn_impl=attn_impl, accum_steps=accum,
                              accum_bn_mode=bn_mode, remat_policy=policy,
                              steps=timing_steps)
        except Exception as e:
            _config_failed(f"accum ladder {name}", e)
            _record(name, fit=False, effective_batch_per_chip=eff,
                    microbatch_per_chip=mb, accum_steps=accum,
                    remat_policy=policy, error=repr(e)[:300],
                    gate_hbm_high_water_bytes=gate_row.get(
                        "hbm_high_water_bytes"))
            continue
        row = {"effective_batch_per_chip": eff, "microbatch_per_chip": mb,
               "accum_steps": accum, "remat_policy": policy,
               "accum_bn_mode": bn_mode,
               "images_per_sec_per_chip": round(val, 2),
               "mfu": mfu_of(val), **_row_stats(val)}
        if "hbm_high_water_bytes" not in row and gate_row:
            row["hbm_high_water_bytes"] = gate_row.get(
                "hbm_high_water_bytes")
        rungs.append(row)
        _record(name, fit=True, **row)
        print(f"bench: {name}: {float(val):.1f} img/s/chip "
              f"compile={row.get('compile_seconds')}s "
              f"hbm={row.get('hbm_high_water_bytes')}", file=sys.stderr)
    print(_json_line({"metric": "accum_ladder", "value": len(rungs),
                      "unit": "rungs", "vs_baseline": None,
                      "microbatch_per_chip": mb, "remat_policy": policy,
                      "rungs": rungs}))


def _input_ladder(arch, image_size, on_tpu, mfu_of, attn_impl, gates):
    """Input-pipeline ladder (``--input-ladder``): loader-placement
    (two float32 views shipped from the host) vs step-placement (raw uint8
    shipped, views materialized per microbatch inside the accumulation
    scan) at effective 512/1024/4096 per chip @ microbatch 256 — the
    augment-placement A/B ISSUE 3 exists for.

    Every row records ``h2d_bytes_per_step`` (the ~8x payload difference),
    ``hbm_high_water_bytes`` (step placement must be strictly lower: only
    one microbatch of views is ever live), ``compile_seconds`` and
    img/s/chip.  Same killable-subprocess compile gating as the
    accumulation ladder (:func:`_input_gate_phase` ran BEFORE this process
    claimed the backend).
    """
    mb, policy, bn_mode, timeout, effectives = _accum_flags(on_tpu)
    timing_steps = 10 if on_tpu else 3
    rungs = []
    grid = [(eff, placement) for eff in effectives
            for placement in ("loader", "step")]
    for eff, placement in grid:
        accum = eff // mb
        name = f"input_eff{eff}_mb{mb}_{placement}"
        tags = {"effective_batch_per_chip": eff, "microbatch_per_chip": mb,
                "accum_steps": accum, "remat_policy": policy,
                "augment_placement": placement}
        gate = gates.get(name) or {"status": "error",
                                   "err": "no gate result for this rung"}
        if gate["status"] == "timeout":
            _record(name, fit=False, **tags,
                    error=f"compile-timeout gate: exceeded {timeout}s "
                          "(compile-timeout signature; subprocess killed)")
            continue
        if gate["status"] == "error":
            err = gate["err"]
            _config_failed(f"input gate {name}", RuntimeError(err))
            _record(name, fit=False, **tags,
                    error=f"gate subprocess: {err}")
            continue
        gate_row = gate.get("row", {})
        try:
            val = _throughput(eff, image_size, arch, half=True,
                              fuse_views=True, ema_update_mode="post",
                              attn_impl=attn_impl, accum_steps=accum,
                              accum_bn_mode=bn_mode, remat_policy=policy,
                              augment_placement=placement,
                              steps=timing_steps)
        except Exception as e:
            _config_failed(f"input ladder {name}", e)
            _record(name, fit=False, **tags, error=repr(e)[:300],
                    gate_hbm_high_water_bytes=gate_row.get(
                        "hbm_high_water_bytes"))
            continue
        row = {**tags, "accum_bn_mode": bn_mode,
               "images_per_sec_per_chip": round(val, 2),
               "mfu": mfu_of(val), **_row_stats(val)}
        if "hbm_high_water_bytes" not in row and gate_row:
            row["hbm_high_water_bytes"] = gate_row.get(
                "hbm_high_water_bytes")
        rungs.append(row)
        _record(name, fit=True, **row)
        print(f"bench: {name}: {float(val):.1f} img/s/chip "
              f"h2d={row.get('h2d_bytes_per_step')} "
              f"hbm={row.get('hbm_high_water_bytes')}", file=sys.stderr)
    print(_json_line({"metric": "input_ladder", "value": len(rungs),
                      "unit": "rungs", "vs_baseline": None,
                      "microbatch_per_chip": mb, "remat_policy": policy,
                      "rungs": rungs}))


def _telemetry_ab(arch, image_size, on_tpu, attn_impl):
    """Telemetry-overhead A/B (``--telemetry-ab``): the SAME config measured
    with ``telemetry='off'`` (the exact pre-telemetry graph — pinned by the
    HLO-identity test) and ``telemetry='step'`` with the TelemetrySink
    polling at ``--telemetry-interval`` (default 50) in the timing loop —
    i.e. the FULL observation cost: the in-graph health reductions plus the
    sink's lagged explicit device_get.  Prints one JSON line with both
    rates and ``overhead_pct``; the acceptance budget is < 2%.
    """
    from byol_tpu.observability.telemetry import TelemetrySink
    interval = _int_flag("--telemetry-interval", 50)
    # CPU rung: smallest batch that still pays >= one interval-50 sink
    # readback in the timing loop — the 1-core box sustains ~0.5 step/s on
    # the fallback model, so 55 steps x 2 arms is minutes, not tens
    bs = 256 if on_tpu else 16
    steps = 120 if on_tpu else 55
    rates = {}
    for mode in ("off", "step"):
        state, train_step, batch, mesh = _build(
            bs, image_size, arch, half=on_tpu, fuse_views=True,
            ema_update_mode="post", attn_impl=attn_impl, telemetry=mode)
        compiled, stats = _aot_compile(train_step, state, batch, mesh)
        sink = (TelemetrySink(interval, nan_policy="warn", verbose=False)
                if mode == "step" else None)
        for _ in range(3):                       # warm; sync via readback
            state, metrics = compiled(state, batch)
        float(metrics["loss_mean"])
        t0 = time.perf_counter()
        for i in range(steps):
            state, metrics = compiled(state, batch)
            if sink is not None:
                sink.offer(i + 1, metrics["health"])
        if sink is not None:
            sink.drain()
        float(metrics["loss_mean"])
        dt = time.perf_counter() - t0
        n_dev = len(jax.devices())
        rates[mode] = batch["label"].shape[0] * steps / dt / n_dev
        _record(f"telemetry_{mode}", fit=True, batch_per_chip=bs,
                telemetry=mode,
                telemetry_interval=interval if mode == "step" else None,
                images_per_sec_per_chip=round(rates[mode], 2), **stats)
        print(f"bench: telemetry_{mode}: {rates[mode]:.1f} img/s/chip",
              file=sys.stderr)
    overhead = 1.0 - rates["step"] / rates["off"]
    print(_json_line({
        "metric": "telemetry_step_overhead_pct",
        "value": round(100.0 * overhead, 2),
        "unit": "%",
        "vs_baseline": None,
        "off_images_per_sec_per_chip": round(rates["off"], 2),
        "step_images_per_sec_per_chip": round(rates["step"], 2),
        "telemetry_interval": interval,
        "batch_per_chip": bs, "arch": arch, "image_size": image_size,
        "timing_steps": steps,
        "device_kind": jax.devices()[0].device_kind,
    }))


def _spans_ab(arch, image_size, on_tpu, attn_impl):
    """Flight-recorder overhead A/B (``--spans-ab``): ONE compiled
    executable, timed with the spans-off path (the shared no-op
    :data:`spans.NULL` returned by ``--spans off``, which records
    NOTHING) and with a live :class:`spans.SpanRecorder` wrapping every
    step dispatch plus the closing readback — exactly the trainer's
    hot-loop instrumentation.  Spans are host-side only, so both arms can
    (and must) run the IDENTICAL program: the arms are INTERLEAVED across
    reps and compared by median, because on a noisy shared box the
    build-to-build / minute-to-minute drift is several percent — an order
    of magnitude above the span cost under measurement.  Prints one JSON
    line with both median rates and ``overhead_pct``; the acceptance
    budget is < 2% (the telemetry bar).

    The spans arm additionally exercises the whole downstream pipeline on
    real measurements: a goodput fold into ``bench_events.jsonl``
    (``goodput`` + ``span_stats`` events) and a Chrome-trace export to
    ``bench_trace.json`` — so the capture CI validates the full
    span -> goodput -> trace path, not just the timer deltas.
    """
    from byol_tpu.observability import goodput as goodput_lib
    from byol_tpu.observability import spans as spans_lib
    bs = 256 if on_tpu else 16
    steps = 30 if on_tpu else 15       # per rep; 4 interleaved reps/arm
    reps = 4
    # ONE build, ONE executable for BOTH arms: spans are host-side only —
    # unlike telemetry they change nothing in the graph — so the honest
    # A/B times the IDENTICAL program and varies only the recorder.
    # Interleaved reps (off, on, off, on, ...) with a median across reps
    # cancel the box's slow drift (page cache, thermals, neighbors): a
    # sequential two-arm design on this class of box shows arm-to-arm
    # deltas of several percent from drift alone, an order of magnitude
    # above the span cost it is trying to measure.
    state, train_step, batch, mesh = _build(
        bs, image_size, arch, half=on_tpu, fuse_views=True,
        ema_update_mode="post", attn_impl=attn_impl)
    compiled, stats = _aot_compile(train_step, state, batch, mesh)
    recorder = spans_lib.SpanRecorder()
    recorders = {"off": spans_lib.NULL, "on": recorder}
    n_dev = len(jax.devices())
    for _ in range(3):                       # warm; sync via readback
        state, metrics = compiled(state, batch)
    float(metrics["loss_mean"])
    rates = {"off": [], "on": []}
    on_wall = 0.0                # ONLY the on-arm windows: the goodput
    for _ in range(reps):        # payload must not attribute warmup/off
        for mode in ("off", "on"):   # time it never observed
            rec = recorders[mode]
            t0 = time.perf_counter()
            for _ in range(steps):
                with rec.span("train/dispatch"):
                    state, metrics = compiled(state, batch)
            with rec.span("train/epoch_readback"):
                float(metrics["loss_mean"])
            dt = time.perf_counter() - t0
            if mode == "on":
                on_wall += dt
            rates[mode].append(batch["label"].shape[0] * steps / dt
                               / n_dev)
    # falsifiable spans-off pin: the off arm's span() must be the ONE
    # shared no-op object (zero allocation, nothing recorded by
    # construction — asserting NULL.records()==[] would be vacuous)
    assert (recorders["off"].span("train/dispatch")
            is recorders["off"].span("train/epoch_readback")), \
        "the spans-off path must hand back the shared no-op span"
    assert len(recorder.records()) == reps * (steps + 1), \
        "recorder must hold one span per dispatch + readback per rep"
    # goodput over the on-arm windows alone (attribute() keeps the
    # partition identity exact against their summed wall)
    wall, productive, badput = goodput_lib.attribute(recorder.records(),
                                                     on_wall)
    payload = {"scope": "epoch", "wall_seconds": wall,
               "productive_seconds": productive, "badput": badput,
               "goodput_fraction": (productive / wall if wall > 0
                                    else 0.0),
               "label": "spans_ab", "timing_steps": reps * steps}
    if _events is not None:
        _events.emit("goodput", **payload)
        _events.emit("span_stats", scope="epoch", label="spans_ab",
                     spans=goodput_lib.span_stats(recorder.records()))
    spans_lib.export_chrome_trace(recorder.records(), "bench_trace.json")
    print(f"bench: spans_on goodput {payload['goodput_fraction']:.3f} "
          f"(wall {payload['wall_seconds']:.2f}s over the on-arm "
          "windows); trace -> bench_trace.json", file=sys.stderr)
    med = {m: float(np.median(rs)) for m, rs in rates.items()}
    # The per-span PRIMITIVE cost, measured in-process on a fresh
    # recorder (so the ring/trace/goodput above stay clean): two
    # perf_counter reads + a TraceAnnotation enter/exit + a deque append.
    # This is the number a noisy box CAN resolve — wall-clock arm deltas
    # at the < 2% scale are swamped by the +/-20% rep-to-rep drift the
    # rep_rates columns document — and spans_per_step x span_cost /
    # step_time bounds the true overhead from the same run's
    # measurements.  (On stable-clock TPU silicon the wall-clock A/B is
    # the headline; there the rep spread collapses.)
    micro_rec = spans_lib.SpanRecorder()
    n_micro = 200_000
    t0 = time.perf_counter()
    for _ in range(n_micro):
        with micro_rec.span("micro/span"):
            pass
    span_cost_s = (time.perf_counter() - t0) / n_micro
    step_s = batch["label"].shape[0] / (med["off"] * n_dev)
    implied = span_cost_s / step_s       # 1 dispatch span per step
    for mode in ("off", "on"):
        _record(f"spans_{mode}", fit=True, batch_per_chip=bs, spans=mode,
                images_per_sec_per_chip=round(med[mode], 2),
                rep_rates=[round(r, 2) for r in rates[mode]],
                span_cost_us=round(span_cost_s * 1e6, 3), **stats)
        print(f"bench: spans_{mode}: {med[mode]:.2f} img/s/chip "
              f"(reps {[round(r, 2) for r in rates[mode]]})",
              file=sys.stderr)
    overhead = 1.0 - med["on"] / med["off"]
    print(_json_line({
        "metric": "spans_overhead_pct",
        "value": round(100.0 * overhead, 2),
        "unit": "%",
        "vs_baseline": None,
        "off_images_per_sec_per_chip": round(med["off"], 2),
        "on_images_per_sec_per_chip": round(med["on"], 2),
        "off_rep_rates": [round(r, 2) for r in rates["off"]],
        "on_rep_rates": [round(r, 2) for r in rates["on"]],
        "span_cost_us": round(span_cost_s * 1e6, 3),
        "step_seconds": round(step_s, 4),
        "implied_overhead_pct": round(100.0 * implied, 6),
        "batch_per_chip": bs, "arch": arch, "image_size": image_size,
        "timing_steps": steps, "reps": reps,
        "device_kind": jax.devices()[0].device_kind,
    }))


def _zero1_ab(arch, image_size, on_tpu, attn_impl):
    """ZeRO-1 A/B (``--zero1-ab``): the SAME accumulation config AOT-
    compiled twice — replicated (``--zero1 off``, the pre-plan graph) vs
    flat leaf-partitioned weight-update sharding (``--zero1 on``) — with
    no execution (the ``--dry-compile`` discipline: memory_analysis() is
    the deliverable, and the off-hardware CPU mesh can report it too).

    Per row: ``hbm_high_water_bytes`` (executable high-water) and
    ``optimizer_state_bytes`` — per-chip bytes of LARS momentum + the EMA
    target computed from the leaf SHARDINGS, the column that must scale
    ~1/N with mesh size when ZeRO-1 is doing its job.  The printed JSON
    line carries both rows plus the on/off ratio; expected ratio ~=
    (1/N + padding) with params replicated either way.
    """
    eff = _int_flag("--effective-batch", 4096 if on_tpu else 64)
    mb = _int_flag("--microbatch", 256 if on_tpu else 16)
    policy = _str_flag("--remat-policy", "dots")
    bn_mode = _str_flag("--accum-bn-mode", "average")
    from byol_tpu.core.remat import validate_policy
    validate_policy(policy)
    if eff % mb:
        raise SystemExit(
            f"bench: effective batch {eff} not divisible by microbatch {mb}")
    accum = eff // mb
    rows = {}
    for z in ("off", "on"):
        name = f"zero1_{z}"
        tags = {"zero1": z, "effective_batch_per_chip": eff,
                "microbatch_per_chip": mb, "accum_steps": accum,
                "remat_policy": policy, "accum_bn_mode": bn_mode,
                "n_devices": len(jax.devices())}
        try:
            # shares _build with every measured rung: the A/B's config
            # cannot drift from the config the ladders measure
            state, train_step, batch, mesh = _build(
                eff, image_size, arch, half=on_tpu, fuse_views=True,
                ema_update_mode="post", attn_impl=attn_impl,
                accum_steps=accum, accum_bn_mode=bn_mode,
                remat_policy=policy, zero1=z, materialize_batch=False)
            compiled, stats = _aot_compile(train_step, state, batch, mesh)
            del compiled, state, train_step
        except Exception as e:
            _config_failed(f"zero1-ab arm {name}", e)
            _record(name, fit=False, **tags, error=repr(e)[:300])
            continue
        rows[z] = {**tags, **stats}
        _record(name, fit=True, **rows[z])
        print(f"bench: {name}: opt_state={stats.get('optimizer_state_bytes')}"
              f" hbm={stats.get('hbm_high_water_bytes')} "
              f"compile={stats.get('compile_seconds')}s", file=sys.stderr)
    ratio = None
    if "off" in rows and "on" in rows:
        off_b = rows["off"].get("optimizer_state_bytes")
        on_b = rows["on"].get("optimizer_state_bytes")
        # _optimizer_state_bytes is best-effort (None on exotic states):
        # either arm missing the column degrades the ratio, not the run
        if off_b and on_b:
            ratio = round(on_b / off_b, 4)
    print(_json_line({
        "metric": "zero1_ab_optimizer_state_bytes",
        "value": rows.get("on", {}).get("optimizer_state_bytes"),
        "unit": "bytes/chip",
        "vs_baseline": ratio,       # on/off — ~1/N + padding
        "replicated_optimizer_state_bytes":
            rows.get("off", {}).get("optimizer_state_bytes"),
        "hbm_high_water_off": rows.get("off", {}).get(
            "hbm_high_water_bytes"),
        "hbm_high_water_on": rows.get("on", {}).get("hbm_high_water_bytes"),
        "n_devices": len(jax.devices()),
        "arch": arch, "image_size": image_size,
        "effective_batch_per_chip": eff, "microbatch_per_chip": mb,
        "accum_steps": accum, "remat_policy": policy,
        "device_kind": jax.devices()[0].device_kind,
    }))


def _augment_ab(arch, image_size, on_tpu, attn_impl):
    """Fused-augmentation A/B (``--augment-ab``): the step-placement
    config (raw uint8 batches, in-step two-view augmentation) AOT-compiled
    with the XLA op chain (``--fused-augment off`` — the exact unfused
    graph, pinned byte-identical by test) and with the fused Pallas
    augmentation kernel (``on``; ops/fused_augment.py), each arm timed
    under a live :class:`spans.SpanRecorder` wrapping every step dispatch
    plus the closing readback — wall rate + per-step dispatch-span stats
    into ``bench_events.jsonl`` as bench_row + span_stats, the same
    flight-recorder currency the trainer logs.

    Also records an IN-PROCESS input-path microbenchmark row: the bare
    two-view augmentation (``device_augment.two_view`` XLA chain vs
    ``fused_two_view``) on a synthetic uint8 batch, each on its own
    executable — the number that isolates the input path from the model
    around it.  NB on CPU the fused arm runs under the Pallas INTERPRETER
    (one XLA op dispatched per kernel instruction — correctness-grade,
    not speed-grade): the CPU capture documents mechanism and event
    plumbing; the TPU row (ROADMAP capture batch) is the perf claim.
    """
    import jax.numpy as jnp

    from byol_tpu.data import device_augment
    from byol_tpu.observability import goodput as goodput_lib
    from byol_tpu.observability import spans as spans_lib
    from byol_tpu.ops import fused_augment as fused_aug_lib
    bs = 256 if on_tpu else 16
    steps = 60 if on_tpu else 30
    rates, span_p50 = {}, {}
    for mode in ("off", "on"):
        state, train_step, batch, mesh = _build(
            bs, image_size, arch, half=on_tpu, fuse_views=True,
            ema_update_mode="post", attn_impl=attn_impl,
            augment_placement="step", fused_augment=mode)
        compiled, stats = _aot_compile(train_step, state, batch, mesh)
        recorder = spans_lib.SpanRecorder()
        for _ in range(3):                       # warm; sync via readback
            state, metrics = compiled(state, batch)
        float(metrics["loss_mean"])
        t0 = time.perf_counter()
        for _ in range(steps):
            with recorder.span("train/dispatch"):
                state, metrics = compiled(state, batch)
        with recorder.span("train/epoch_readback"):
            float(metrics["loss_mean"])
        dt = time.perf_counter() - t0
        n_dev = len(jax.devices())
        rates[mode] = batch["label"].shape[0] * steps / dt / n_dev
        sstats = goodput_lib.span_stats(recorder.records())
        span_p50[mode] = sstats.get("train/dispatch", {}).get("p50_ms")
        if _events is not None:
            _events.emit("span_stats", scope="epoch",
                         label=f"augment_{mode}", spans=sstats)
        _record(f"augment_{mode}", fit=True, batch_per_chip=bs,
                fused_augment=mode, augment_placement="step",
                images_per_sec_per_chip=round(rates[mode], 2),
                dispatch_span_p50_ms=span_p50[mode], **stats)
        print(f"bench: augment_{mode}: {rates[mode]:.2f} img/s/chip "
              f"(dispatch p50 {span_p50[mode]}ms)", file=sys.stderr)

    # ---- in-process input-path microbenchmark --------------------------
    # the bare two-view program on a raw uint8 microbatch: XLA op chain
    # vs one fused kernel call (+ its blur conv), both jitted standalone
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(
        0, 256, (bs, image_size, image_size, 3), dtype=np.uint8))
    key = jax.random.PRNGKey(0)

    @jax.jit
    def xla_chain(k, im):
        return device_augment.two_view(k, im, image_size)

    @jax.jit
    def fused(k, im):
        return fused_aug_lib.fused_two_view(k, im, image_size)

    def bench_fn(fn, args, reps=5, inner=3):
        out = fn(*args)                       # compile + warm
        jax.block_until_ready(out)
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(inner):
                jax.block_until_ready(fn(*args))
            times.append((time.perf_counter() - t0) / inner)
        return float(np.median(times))

    t_chain = bench_fn(xla_chain, (key, imgs))
    # graphlint: disable=GL103 -- A/B arms deliberately consume the same key: the fused kernel must see the XLA chain's exact random draws
    t_fused = bench_fn(fused, (key, imgs))
    row = {
        "batch": bs,
        "image_size": image_size,
        "xla_chain_us": round(t_chain * 1e6, 1),
        "fused_kernel_us": round(t_fused * 1e6, 1),
        "fused_speedup": round(t_chain / t_fused, 3),
        "interpret_mode": not on_tpu,
    }
    _record("augment_microbench", fit=True, **row)
    overhead = 1.0 - rates["on"] / rates["off"]
    print(_json_line({
        "metric": "fused_augment_ab",
        "value": round(rates["on"], 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(rates["on"] / rates["off"], 4),
        "off_images_per_sec_per_chip": round(rates["off"], 2),
        "on_images_per_sec_per_chip": round(rates["on"], 2),
        "step_overhead_pct": round(100.0 * overhead, 2),
        "dispatch_span_p50_ms": span_p50,
        "microbench": row,
        "batch_per_chip": bs, "arch": arch, "image_size": image_size,
        "timing_steps": steps,
        "device_kind": jax.devices()[0].device_kind,
    }))


def _serve_setup(arch, image_size, on_tpu):
    """Shared --serve-ladder/--wire-ladder startup: validate the bucket/
    mesh constraints, build the config + serve config, return everything
    a rung loop needs.  One helper so the two ladders cannot drift."""
    from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                      TaskConfig)
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from byol_tpu.serving.service import ServeConfig

    streams_list = [int(s) for s in
                    _str_flag("--serve-streams", "1,8,64").split(",")]
    budget = _int_flag("--serve-requests", 2048 if on_tpu else 256)
    max_batch = _int_flag("--serve-max-batch", 64)
    n_dev = len(jax.devices())
    if n_dev & (n_dev - 1):
        # fail fast with the actionable constraint, not a BucketSpec /
        # engine divisibility error after the model is already built:
        # buckets are powers of two and shard their rows over the mesh
        raise SystemExit(
            f"bench: serve ladders need a power-of-two device count "
            f"(got {n_dev}): bucket shapes are powers of two and must "
            "shard evenly over the data axis; pass --cpu-devices 2|4|8|...")
    min_bucket = _int_flag("--serve-min-bucket", max(8, n_dev))
    if min_bucket > max_batch:
        raise SystemExit(
            f"bench: serve min bucket {min_bucket} (default max(8, "
            f"n_devices)) exceeds --serve-max-batch {max_batch}; raise "
            "the max batch or lower --serve-min-bucket")
    wait_ms = float(_str_flag("--serve-wait-ms", "5.0"))
    half = bool(on_tpu)      # bf16 embed on real silicon, fp32 on CPU

    mesh = build_mesh(MeshSpec(data=n_dev))
    cfg = Config(
        task=TaskConfig(task="fake", batch_size=max(max_batch, n_dev),
                        epochs=1, image_size_override=image_size),
        model=ModelConfig(arch=arch),
        device=DeviceConfig(num_replicas=n_dev, half=half),
    )
    serve_cfg = ServeConfig(min_bucket=min_bucket, max_bucket=max_batch,
                            max_wait_ms=wait_ms,
                            stats_interval_s=1e9)   # rows emit explicitly
    return (streams_list, budget, max_batch, min_bucket, wait_ms, half,
            n_dev, mesh, cfg, serve_cfg)


def _serve_ladder(arch, image_size, on_tpu, attn_impl):
    """Serve ladder (``--serve-ladder``): latency vs throughput for the
    embedding service (byol_tpu/serving/) at 1/8/64 concurrent synthetic
    client streams.

    Each rung drives a closed-loop budget of single-image requests through
    the FULL serving stack — bounded queue, request coalescing, bucket
    padding, pinned-host staging, AOT embed, readback — and records the
    request-latency tail (p50/p99 ms), achieved rows/sec, batch fill
    ratio, and the engine compile counter.  The counter column is the
    zero-recompile contract made visible: after the warmup phase it must
    not move, or a rung's latency includes XLA compiles (the GL102 hazard
    on the latency path) and the row says so.

    CPU-runnable with ``--cpu-devices N`` (random-init encoder — latency
    is independent of parameter values); on TPU the same command measures
    the real serving config.  Knobs: ``--serve-streams 1,8,64``,
    ``--serve-requests <budget/rung>``, ``--serve-max-batch``,
    ``--serve-min-bucket``, ``--serve-wait-ms``, and ``--serve-pipeline
    off|on|ab`` — 'ab' re-runs the whole ladder with worker dispatch
    pipelining off then on (same engine, same executables: the delta is
    pure host/device overlap), the ISSUE 13 before/after row.
    """
    import dataclasses
    import time

    from byol_tpu.serving.batcher import DynamicBatcher
    from byol_tpu.serving.net.loadgen import run_closed_loop
    from byol_tpu.serving.service import EmbeddingService, build_service

    (streams_list, budget, max_batch, min_bucket, wait_ms, half,
     n_dev, mesh, cfg, serve_cfg) = _serve_setup(arch, image_size, on_tpu)
    pipe_flag = _str_flag("--serve-pipeline", "on")
    if pipe_flag not in ("off", "on", "ab"):
        raise SystemExit("usage: bench.py --serve-ladder "
                         "--serve-pipeline off|on|ab")
    arms = ("off", "on") if pipe_flag == "ab" else (pipe_flag,)

    engine = None
    warmup_s = 0.0
    ladder = []
    for pipeline in arms:
        if engine is None:
            service = build_service(
                cfg, dataclasses.replace(serve_cfg, pipeline=pipeline),
                mesh=mesh)
            engine = service.engine
            t0 = time.perf_counter()
            service.start()   # AOT-compiles the whole bucket vocabulary
            warmup_s = time.perf_counter() - t0
            print(f"bench: serve warmup: {engine.compile_count} bucket "
                  f"programs {list(engine.buckets.sizes)} in "
                  f"{warmup_s:.1f}s", file=sys.stderr)
        else:
            # second arm reuses the warmed ENGINE (identical executables
            # — the A/B delta is worker overlap, not compilation) under a
            # fresh batcher/worker
            service = EmbeddingService(
                engine,
                DynamicBatcher(max_batch=max_batch,
                               max_queue=serve_cfg.max_queue,
                               max_wait_s=wait_ms / 1e3),
                stats_interval_s=1e9, pipeline=pipeline)
            service.start(warmup=False)
        shape = engine.input_shape
        try:
            for n_streams in streams_list:
                # untimed warm pass: first execution of each bucket
                # program pays one-time backend setup that is not
                # steady-state latency
                run_closed_loop(
                    lambda i, img: service.embed(img, timeout=600.0),
                    shape, max(2 * n_streams, 8), n_streams, seed=17)
                service.meter.snapshot(time.perf_counter())  # reset window
                rung_base = engine.compile_count  # per-rung baseline: a
                res = run_closed_loop(            # compile counts in the
                    lambda i, img: service.embed(img, timeout=600.0),
                    shape, budget, n_streams,     # rung it ran in
                    seed=n_streams)
                done, elapsed = res.completed, res.elapsed_s
                recompiles = engine.compile_count - rung_base
                # one serve_stats event per rung next to the bench_row —
                # the serving schema exercised by the capture CI validates
                snap = service.meter.emit(
                    _events, time.perf_counter(), streams=n_streams,
                    compile_count=engine.compile_count)
                row = {
                    "streams": n_streams, "requests": done,
                    "failed": res.failed,
                    "pipeline": pipeline,
                    "p50_ms": round(snap["p50_ms"], 3),
                    "p99_ms": round(snap["p99_ms"], 3),
                    "mean_ms": round(snap["mean_ms"], 3),
                    "throughput_img_per_sec": round(done / elapsed, 2),
                    "throughput_img_per_sec_per_chip":
                        round(done / elapsed / n_dev, 2),
                    "fill_ratio": round(snap["fill_ratio"], 4),
                    "queue_depth": round(snap["queue_depth"], 2),
                    "batches": int(snap["batches"]),
                    "recompiles_after_warmup": recompiles,
                    "max_batch": max_batch, "min_bucket": min_bucket,
                    "max_wait_ms": wait_ms, "n_devices": n_dev,
                    "half": half,
                    "warmup_compile_seconds": round(warmup_s, 2),
                }
                ladder.append(row)
                _record(f"serve_s{n_streams}_pipe_{pipeline}", fit=True,
                        **row)
                print(f"bench: serve s{n_streams} pipe={pipeline}: "
                      f"p50 {row['p50_ms']}ms p99 {row['p99_ms']}ms "
                      f"{row['throughput_img_per_sec']} img/s "
                      f"fill {row['fill_ratio']} "
                      f"recompiles {recompiles}", file=sys.stderr)
        finally:
            service.stop()
    print(_json_line({
        "metric": "serve_ladder_p99_ms",
        "value": ladder[-1]["p99_ms"] if ladder else None,
        "unit": "ms @ most-concurrent rung",
        "vs_baseline": None,
        "arch": arch, "image_size": image_size,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "recompiles_after_warmup": sum(r["recompiles_after_warmup"]
                                       for r in ladder),
        "rows": ladder,
    }))


def _wire_ladder(arch, image_size, on_tpu, attn_impl):
    """Wire ladder (``--wire-ladder``): the WIRE TAX measured — the same
    closed-loop streams driven twice per rung, once through the
    in-process ``service.embed`` path and once over HTTP through the
    serving/net front end (protocol encode → POST /v1/embed → decode),
    against ONE warmed service.  Client-observed p50/p99 per arm; the
    per-rung delta is what the network front door costs on top of the
    batching/AOT machinery (localhost floor — real networks add RTT on
    top, but the protocol + HTTP + framing overhead is all here).

    Knobs: the --serve-* family (shared with --serve-ladder) plus
    ``--wire-deadline-ms`` (per-request X-Deadline-Ms; generous default —
    the ladder measures latency, not admission policy).
    """
    import time

    from byol_tpu.serving.net.client import EmbedClient
    from byol_tpu.serving.net.loadgen import run_closed_loop
    from byol_tpu.serving.net.server import WireServer
    from byol_tpu.serving.service import build_service

    (streams_list, budget, max_batch, min_bucket, wait_ms, half,
     n_dev, mesh, cfg, serve_cfg) = _serve_setup(arch, image_size, on_tpu)
    deadline_ms = float(_str_flag("--wire-deadline-ms", "600000"))

    service = build_service(cfg, serve_cfg, mesh=mesh)
    t0 = time.perf_counter()
    service.start()           # AOT-compiles the whole bucket vocabulary
    warmup_s = time.perf_counter() - t0
    engine = service.engine
    print(f"bench: wire warmup: {engine.compile_count} bucket programs "
          f"{list(engine.buckets.sizes)} in {warmup_s:.1f}s",
          file=sys.stderr)
    server = WireServer(service, "127.0.0.1", 0,
                        default_deadline_ms=deadline_ms).start()
    host, port = server.address
    print(f"bench: wire front end at http://{host}:{port}",
          file=sys.stderr)
    shape = engine.input_shape
    ladder = []

    def inproc_fn(idx, img):
        service.embed(img, timeout=deadline_ms / 1e3)

    clients = {}

    def wire_setup(idx):
        # create-if-absent: the warm pass dials each stream's connection
        # and the measured pass must REUSE it — re-dialing here would put
        # the TCP connect the warm pass exists to absorb back into the
        # first measured sample of every stream (at 64 streams / 256
        # requests that is a quarter of the published p99's samples)
        if idx not in clients:
            clients[idx] = EmbedClient(host, port,
                                       timeout_s=deadline_ms / 1e3 + 5.0,
                                       seed=idx)

    def wire_fn(idx, img):
        clients[idx].embed(img, deadline_ms=deadline_ms)

    try:
        for n_streams in streams_list:
            rows_by_arm = {}
            for arm, fn, setup in (("inproc", inproc_fn, None),
                                   ("wire", wire_fn, wire_setup)):
                # untimed warm pass (per arm: the wire arm's first
                # requests also pay connection dialing)
                run_closed_loop(fn, shape, max(2 * n_streams, 8),
                                n_streams, seed=17, stream_setup=setup)
                service.meter.snapshot(time.perf_counter())  # reset
                rung_base = engine.compile_count
                res = run_closed_loop(fn, shape, budget, n_streams,
                                      seed=n_streams, stream_setup=setup)
                snap = service.meter.emit(
                    _events, time.perf_counter(), streams=n_streams,
                    arm=arm, compile_count=engine.compile_count)
                row = {
                    "streams": n_streams, "arm": arm,
                    "requests": res.completed, "failed": res.failed,
                    # CLIENT-observed latency (loadgen's clock): the
                    # meter's enqueue->deliver window cannot see wire
                    # time by construction
                    "p50_ms": round(res.percentile_ms(50), 3),
                    "p99_ms": round(res.percentile_ms(99), 3),
                    "throughput_img_per_sec":
                        round(res.throughput(), 2),
                    "serve_p50_ms": round(snap["p50_ms"], 3),
                    "fill_ratio": round(snap["fill_ratio"], 4),
                    "recompiles_after_warmup":
                        engine.compile_count - rung_base,
                    "max_batch": max_batch, "min_bucket": min_bucket,
                    "max_wait_ms": wait_ms, "n_devices": n_dev,
                    "half": half,
                }
                rows_by_arm[arm] = row
                ladder.append(row)
                _record(f"wire_s{n_streams}_{arm}", fit=True, **row)
            tax_p50 = round(rows_by_arm["wire"]["p50_ms"]
                            - rows_by_arm["inproc"]["p50_ms"], 3)
            tax_p99 = round(rows_by_arm["wire"]["p99_ms"]
                            - rows_by_arm["inproc"]["p99_ms"], 3)
            print(f"bench: wire s{n_streams}: inproc p50 "
                  f"{rows_by_arm['inproc']['p50_ms']}ms, wire p50 "
                  f"{rows_by_arm['wire']['p50_ms']}ms -> tax "
                  f"{tax_p50}ms (p99 tax {tax_p99}ms)", file=sys.stderr)
    finally:
        for c in clients.values():
            c.close()
        server.drain(grace_s=0.0, timeout_s=60.0)   # stops the service
    print(_json_line({
        "metric": "wire_ladder_p50_tax_ms",
        "value": (round(ladder[-1]["p50_ms"] - ladder[-2]["p50_ms"], 3)
                  if len(ladder) >= 2 else None),
        "unit": "ms wire-minus-inproc @ most-concurrent rung",
        "vs_baseline": None,
        "arch": arch, "image_size": image_size,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "rows": ladder,
    }))


def _sweep_prior_rows() -> dict:
    """Sweep rows measured by a previous, interrupted attempt.

    A sweep can outlast one chip call, so a re-run must converge instead
    of starting over: any ``sweep_*`` row in
    the live partial file or its ``.prev`` backup — same device class only —
    is reused rather than re-measured.  Must be called BEFORE the first
    ``_record`` of the run (which rotates the live file to ``.prev``)."""
    prior: dict = {}
    kind = jax.devices()[0].device_kind
    for path in (_PARTIAL_PATH + ".prev", _PARTIAL_PATH):   # live file wins
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        if d.get("device_kind") != kind:   # a v5e row is not a v4/v6e row
            continue
        for r in d.get("results", []):
            name = str(r.get("config", ""))
            if name.startswith("sweep_") and "fit" in r:
                prior[name] = r
    return prior


def _sweep(arch, image_size, candidates, mfu_of):
    """Tuning grid: batch x remat x fuse_views, bf16. Results accumulate in
    bench_partial.json (incremental) and bench_sweep.json (final table).

    Hard-learned grid rules:
    - Rungs 512/384/256 only: smaller batches are strictly slower on this
      model class (the headline ladder's 128-class rungs trail by >30%),
      and un-rematted bs1024 is a recorded compile-OOM after a ~25-minute
      attempt — it is never re-attempted without remat.
    - The rematted bs1024 rows (the one config where 1024 might newly fit)
      go LAST, so a failure there cannot cost other rows.
    - Rows from a previous interrupted sweep are reused (_sweep_prior_rows)
      so a re-run finishes the grid instead of repeating it.
    """
    top = max(candidates)
    rungs = [bs for bs in (512, 384, 256) if bs <= top]
    if not rungs:        # CPU-fallback ladder (tiny model): keep liveness
        rungs = list(candidates)
    grid = [(remat, fuse, bs)
            for remat in (False, True) for fuse in (True, False)
            for bs in rungs]
    if top >= 1024:
        grid += [(True, True, 1024), (True, False, 1024)]
    prior = _sweep_prior_rows() if jax.default_backend() != "cpu" else {}
    rows = []
    for remat, fuse, bs in grid:
        name = f"sweep_bs{bs}_remat{int(remat)}_fuse{int(fuse)}"
        # Reuse rule: fit=True rows always; fit=False rows only at the
        # >=1024 rungs (the multi-minute compile-OOMs worth never
        # repeating) AND only when the recorded error carries a genuine
        # OOM signature — a transient error must not permanently mask a
        # config that fits.
        # Smaller rungs' fit=False rows always re-measure (cheap).
        if name in prior and (
                prior[name].get("fit")
                or (bs >= 1024
                    and _oom_signature(str(prior[name].get("error", ""))))):
            # strip 'reused' too: a thrice-interrupted sweep reloads rows
            # that were themselves recorded by a resume
            r = {k: v for k, v in prior[name].items()
                 if k not in ("config", "reused")}
            _record(name, reused=True, **r)
            print(f"bench: {name}: reusing prior measurement "
                  f"(fit={r.get('fit')}, "
                  f"{r.get('images_per_sec_per_chip')})", file=sys.stderr)
            if r.get("fit"):
                rows.append({k: r[k] for k in
                             ("batch_per_chip", "remat", "fuse_views",
                              "images_per_sec_per_chip", "mfu")
                             if k in r})
            continue
        try:
            val = _throughput(bs, image_size, arch, half=True,
                              fuse_views=fuse, remat=remat,
                              ema_update_mode="post", steps=10)
        except Exception as e:
            _config_failed(name, e)
            _record(name, batch_per_chip=bs, fit=False,
                    error=repr(e)[:300])
            continue
        row = {"batch_per_chip": bs, "remat": remat,
               "fuse_views": fuse,
               "images_per_sec_per_chip": round(val, 2),
               "mfu": mfu_of(val)}
        rows.append(row)
        _record(name, fit=True, **row, **_row_stats(val))
        print(f"bench: {name}: {val:.1f} img/s/chip "
              f"mfu={row['mfu']}", file=sys.stderr)
    # CPU-fallback tables must not shadow the committed TPU table, an early
    # backend death must not truncate it to [], and a non-default arch
    # writes its OWN table (same isolation contract as _PARTIAL_PATH — a
    # vit sweep must never rotate away the committed resnet50 table).
    if jax.default_backend() == "cpu":
        sweep_path = "bench_sweep_cpu.json"
    elif arch != "resnet50":
        sweep_path = f"bench_sweep_{arch}.json"
    else:
        sweep_path = "bench_sweep.json"
    if rows:
        try:
            if os.path.exists(sweep_path):
                # same evidence-preservation contract as _flush_partial: a
                # partial re-run must never destroy a complete prior table
                os.replace(sweep_path, sweep_path + ".prev")
            with open(sweep_path, "w") as f:
                json.dump(_sanitize_json(rows), f, indent=2,
                          allow_nan=False)
                f.write("\n")
        except OSError as e:  # same contract as _flush_partial
            print(f"bench: could not write {sweep_path}: {e}",
                  file=sys.stderr)
    else:
        print(f"bench: no rows measured; leaving {sweep_path} untouched",
              file=sys.stderr)
    print(_json_line({"metric": "sweep", "value": len(rows),
                      "unit": "configs", "vs_baseline": None}))


if __name__ == "__main__":
    main()
