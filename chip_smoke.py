#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that byol-tpu still starts on the chip.

    python chip_smoke.py                  # one TPU chip: the main path + kernels
    python chip_smoke.py --chips 4        # four chips: the data-parallel /
                                          #   ZeRO-1 comparison, nothing else
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU backend, to
                                          #   rehearse control flow (add
                                          #   --chips 4 for four virtual devices)

Told nothing, it requires ``jax.devices()[0].platform == "tpu"`` and exits
non-zero otherwise, printing no result line.  The last line of standard
output of a run that passed is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
with the device as JAX reported it to the process that ran on it.

What runs (one chip), through the entry points a user calls:

1. ``train.py``: ResNet-50 BYOL as ``byol_tpu/cli.py`` builds it by default
   (224 px, head 4096, projection 256, bf16, LARS, ``--fuse-views``), batch
   256, ``--task synth`` from ``--seed``: a few optimizer steps past step 0,
   an eval pass per epoch, a checkpoint, a ``run.jsonl`` that passes
   ``scripts/validate_events.py``.
2. ``python -m byol_tpu serve --checkpoint <that run> --smoke N``: in
   process, then again with ``--http`` — every request ok, no compile after
   warm-up.  The second serve is a second process on the same compile
   cache, so its warm-up time next to the first one's is cold vs warm.
3. ``--phase probe`` (this file, in a child): step time ending in
   ``block_until_ready`` and ending in a scalar readback, peak device
   memory, served-vs-offline embedding difference, and the kernel phase —
   ``--fused-augment on`` (raw uint8 256->224) next to its un-fused arm on
   the same seed and batch, asserting ``tpu_custom_call`` in the compiled
   program.  No ``interpret=``
   is passed anywhere: on a TPU backend the kernels compile for the chip.

PROCESS RULE.  A chip belongs to one process at a time.  The parent (this
``main``) never imports ``jax`` — nor ``byol_tpu``, which does at import —
and runs every phase as a child, strictly one after another, on a shared
compile cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``: core/preflight.place_compile_cache).  It learns
the device from what the children report (the ``device`` field of the
trainer's ``run_header``, the probe's result line).

Tolerances (stated here because the CPU parity tests run fp32 on an exact
backend, and the chip runs bf16 convolutions): fused-vs-unfused and
one-vs-four-device losses must agree per step within
``LOSS_RTOL``/``LOSS_ATOL`` below —
tests/test_fused_augment.py (2e-4), tests/test_zero1.py and
tests/test_train_step.py (1e-5 .. 1e-4) widened to bf16's 2^-8 relative
rounding; served-vs-offline embeddings within ``EMBED_RTOL`` of the largest
embedding magnitude (tests/test_attention.py uses 2e-2 in bf16).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

LOSS_RTOL = 1e-2
LOSS_ATOL = 1e-2
EMBED_RTOL = 2e-2

# The one size the repo's records show fitting a v5e's 16 GB, and the tiny
# stand-in the CPU rehearsal uses to walk the same control flow.
FULL = dict(arch="resnet50", image=224, raw=256, batch=256, head=4096,
            proj=256, samples=512, epochs=3, smoke=48, streams=4,
            min_bucket=8, max_batch=64, steps=3, timing_steps=5)
TINY = dict(arch="resnet18", image=32, raw=36, batch=16, head=64, proj=32,
            samples=64, epochs=2, smoke=8, streams=2, min_bucket=8,
            max_batch=16, steps=3, timing_steps=1)

RESULT_TAG = "CHIP_SMOKE_RESULT "        # child -> parent, one JSON object


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# parent: no jax in this half of the file
# ---------------------------------------------------------------------------

class PhaseFailed(RuntimeError):
    pass


def _cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")


def _cache_entries() -> int:
    try:
        return len(os.listdir(_cache_dir()))
    except OSError:
        return 0


def _child_env(rehearsal: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"      # asked for: the one way onto CPU
    else:
        # told nothing, nothing holds the children to the CPU: they take
        # JAX's default backend and refuse it unless it is the TPU
        env.pop("JAX_PLATFORMS", None)
    return env


def run_child(name: str, argv: list, *, rehearsal: bool,
              timeout: float) -> str:
    """Run one phase to its end; echo its stdout, keep its stderr in a log,
    return its stdout.  The child is the only process on the chip."""
    log = os.path.join(OUT, f"{name}.stderr.log")
    cache_before = _cache_entries()
    say(f"== {name}: {' '.join(argv[1:])}")
    t0 = time.monotonic()
    lines = []
    timed_out = threading.Event()
    with open(log, "w") as err:
        proc = subprocess.Popen(
            argv, cwd=REPO, env=_child_env(rehearsal),
            stdout=subprocess.PIPE, stderr=err, text=True,
            start_new_session=True)

        def _kill_group() -> None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass

        def _on_timeout() -> None:       # a hung chip must not hang the smoke
            timed_out.set()
            _kill_group()

        timer = threading.Timer(timeout, _on_timeout)
        timer.start()
        try:
            for line in proc.stdout:
                lines.append(line)
                sys.stdout.write(f"   {name}| {line}")
                sys.stdout.flush()
            rc = proc.wait()
        finally:
            timer.cancel()
            _kill_group()                # stop everything the phase started
            proc.wait()
    if timed_out.is_set():
        rc = None
    secs = time.monotonic() - t0
    say(f"== {name}: rc={rc} in {secs:.1f}s; compile cache entries "
        f"{cache_before} -> {_cache_entries()} ({_cache_dir()})")
    if rc != 0:
        try:
            with open(log) as f:
                tail = f.readlines()[-60:]
        except OSError:
            tail = []
        sys.stdout.write("".join(f"   {name}! {l}" for l in tail))
        raise PhaseFailed(
            f"{name} " + ("timed out" if rc is None else f"exited {rc}"))
    return "".join(lines)


def _child_result(stdout: str) -> dict:
    for line in reversed(stdout.splitlines()):
        if line.startswith(RESULT_TAG):
            return json.loads(line[len(RESULT_TAG):])
    raise PhaseFailed("child printed no result line")


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)
    say(f"   ok: {what}")


def _net_flags(s: dict) -> list:
    """The net-defining flags, spelled once for train and serve."""
    return ["--arch", s["arch"], "--image-size-override", str(s["image"]),
            "--batch-size", str(s["batch"]),
            "--head-latent-size", str(s["head"]),
            "--projection-size", str(s["proj"])]


def phase_train(s: dict, seed: int, rehearsal: bool) -> dict:
    argv = [sys.executable, "train.py", "--task", "synth",
            "--num-synth-samples", str(s["samples"]),
            "--epochs", str(s["epochs"]), "--warmup", "1", "--fuse-views",
            "--grapher", "jsonl", "--seed", str(seed),
            "--log-dir", os.path.join(OUT, "runs"),
            "--model-dir", os.path.join(OUT, "models")] + _net_flags(s)
    run_child("train", argv, rehearsal=rehearsal, timeout=900)
    runs = glob.glob(os.path.join(OUT, "runs", "*", "run.jsonl"))
    _check(len(runs) == 1, f"one run.jsonl written ({runs})")
    v = subprocess.run(
        [sys.executable, os.path.join("scripts", "validate_events.py"),
         "--require", "goodput,span_stats", runs[0]],
        cwd=REPO, capture_output=True, text=True)
    _check(v.returncode == 0,
           f"run.jsonl passes scripts/validate_events.py "
           f"({(v.stdout + v.stderr).strip()[-200:]})")
    events = [json.loads(l) for l in open(runs[0])]
    header = next(e for e in events if e["kind"] == "run_header")
    device = header["device"]
    if not rehearsal:
        _check(device["platform"] == "tpu",
               f"the trainer ran on a TPU ({device})")
    train = [e for e in events if e["kind"] == "epoch"
             and e["split"] == "train"]
    tests = [e for e in events if e["kind"] == "epoch"
             and e["split"] == "test"]
    steps = max(e["step"] for e in train)
    losses = [e["metrics"]["loss_mean"] for e in train + tests]
    _check(steps >= 4, f"{steps} optimizer steps (>= 3 past step 0, which "
                       "runs at lr 0)")
    _check(all(isinstance(x, float) and x == x and abs(x) != float("inf")
               for x in losses), f"finite train/test losses {losses}")
    _check(len(tests) >= 1, f"{len(tests)} eval pass(es)")
    ckpts = glob.glob(os.path.join(OUT, "models", "*", "ckpt-*"))
    _check(len(ckpts) >= 1, f"checkpoint written ({len(ckpts)} ckpt dirs)")
    for e in events:
        if e["kind"] == "goodput" and e.get("scope") == "epoch":
            say(f"   train epoch {e.get('epoch')}: wall "
                f"{e['wall_seconds']:.1f}s, startup_compile "
                f"{e['badput'].get('startup_compile', 0.0):.1f}s, eval "
                f"{e['badput'].get('eval', 0.0):.1f}s, productive "
                f"{e['productive_seconds']:.2f}s  [{device['kind']}]")
    return {"device": device,
            "checkpoint": os.path.dirname(sorted(ckpts)[0])}


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def phase_serve(s: dict, seed: int, rehearsal: bool, checkpoint: str,
                http: bool) -> float:
    name = "serve_http" if http else "serve_inproc"
    events_path = os.path.join(OUT, f"{name}.jsonl")
    argv = [sys.executable, "-m", "byol_tpu", "serve",
            "--checkpoint", checkpoint, "--num-classes", "10",
            "--smoke", str(s["smoke"]), "--smoke-streams", str(s["streams"]),
            "--min-bucket", str(s["min_bucket"]),
            "--max-batch", str(s["max_batch"]), "--seed", str(seed),
            "--log-dir", os.path.join(OUT, "runs"),
            "--serve-events", events_path] + _net_flags(s)
    if http:
        argv += ["--http", f"127.0.0.1:{_free_port()}"]
    run_child(name, argv, rehearsal=rehearsal, timeout=600)
    events = [json.loads(l) for l in open(events_path)]
    end = next(e for e in events if e["kind"] == "run_end")
    engine = end["engine"]
    n_buckets = len(engine["buckets"])
    _check(end["smoke_requests"] == s["smoke"] and end["smoke_failed"] == 0,
           f"{end['smoke_requests']}/{s['smoke']} requests ok, "
           f"{end['smoke_failed']} failed ({'HTTP' if http else 'in-process'})")
    _check(end["compile_count"] == n_buckets,
           f"recompiles {end['compile_count'] - n_buckets} "
           f"({n_buckets} bucket programs compiled at warm-up)")
    warm = sum(engine["compile_seconds"].values())
    say(f"   {name}: bucket programs {engine['compile_seconds']} "
        f"(sum {warm:.1f}s)")
    return warm


def run_one_chip(s: dict, seed: int, rehearsal: bool) -> dict:
    trained = phase_train(s, seed, rehearsal)
    cold = phase_serve(s, seed, rehearsal, trained["checkpoint"], http=False)
    warm = phase_serve(s, seed, rehearsal, trained["checkpoint"], http=True)
    say(f"   serve warm-up, cold process {cold:.1f}s vs second process on "
        f"the same cache {warm:.1f}s  [{trained['device']['kind']}]")
    out = run_child(
        "probe", [sys.executable, os.path.abspath(__file__), "--phase",
                  "probe", "--seed", str(seed), "--checkpoint",
                  trained["checkpoint"]]
        + (["--cpu-rehearsal"] if rehearsal else []),
        rehearsal=rehearsal, timeout=1000)
    res = _child_result(out)
    _check(res["device"] == trained["device"],
           f"probe and trainer saw the same device {res['device']}")
    return res["device"]


def run_four_chips(seed: int, rehearsal: bool) -> dict:
    out = run_child(
        "multichip", [sys.executable, os.path.abspath(__file__), "--phase",
                      "multichip", "--seed", str(seed)]
        + (["--cpu-rehearsal"] if rehearsal else []),
        rehearsal=rehearsal, timeout=1100)
    return _child_result(out)["device"]


# ---------------------------------------------------------------------------
# children: jax lives below this line
# ---------------------------------------------------------------------------

def _start_backend(rehearsal: bool, cpu_devices: int = 0):
    """The same start-up sequence as the CLIs (core/preflight.py)."""
    import jax
    from byol_tpu.core import preflight
    if rehearsal and cpu_devices:
        preflight.force_cpu_devices(cpu_devices)
    preflight.place_compile_cache()
    preflight.require_tpu("chip_smoke")
    device = preflight.describe_device()
    say(f"device: {device}  jax {jax.__version__}")
    return device


def _train_cfg(s: dict, seed: int, extra: list, *, batch: int = 0,
               num_replicas: int = 0):
    """CLI flags -> Config, exactly as ``train.py`` parses them."""
    from byol_tpu.cli import build_parser, config_from_args
    flags = (["--task", "synth", "--epochs", "2", "--warmup", "1",
              "--fuse-views", "--seed", str(seed)] + _net_flags(s) + extra)
    if batch:
        flags += ["--batch-size", str(batch)]
    if num_replicas:
        flags += ["--num-replicas", str(num_replicas)]
    return config_from_args(build_parser().parse_args(flags)), flags


def _host_batches(s: dict, seed: int, n: int, *, raw: bool):
    """``n`` host batches from ``seed`` — float32 views (loader placement)
    or raw uint8 images (step placement)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    b, size = s["batch"], s["image"]
    out = []
    for _ in range(n):
        label = rng.randint(0, 10, size=(b,)).astype(np.int32)
        if raw:
            out.append({"images": rng.randint(
                0, 256, (b, s["raw"], s["raw"], 3), dtype=np.uint8),
                "label": label})
        else:
            v = rng.rand(2, b, size, size, 3).astype(np.float32)
            out.append({"view1": v[0], "view2": v[1], "label": label})
    return out


class Arm:
    """One compiled train step on a mesh, built the way trainer.fit builds
    it (config -> mesh -> resolve -> compile plan -> setup_training), with
    the jitted step AOT-compiled so the program text can be inspected."""

    def __init__(self, s: dict, seed: int, extra: list, batch0: dict, *,
                 devices=None):
        import jax
        from byol_tpu.core.config import resolve
        from byol_tpu.core.rng import root_key
        from byol_tpu.parallel.compile_plan import build_plan
        from byol_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                            shard_batch_to_mesh)
        from byol_tpu.training.build import setup_training
        devices = list(devices if devices is not None else jax.devices())
        cfg, self.flags = _train_cfg(s, seed, extra,
                                     num_replicas=len(devices))
        self.mesh = build_mesh(MeshSpec(data=len(devices)), devices)
        rcfg = resolve(cfg, num_train_samples=4 * s["batch"],
                       num_test_samples=s["batch"], output_size=10,
                       input_shape=(s["image"], s["image"], 3))
        self.plan = build_plan(self.mesh, zero1=cfg.device.zero1 == "on")
        _, self.state, step, _, _ = setup_training(
            rcfg, self.mesh, root_key(seed), plan=self.plan)
        self._shard = lambda b: shard_batch_to_mesh(dict(b), self.mesh)
        t0 = time.perf_counter()
        with self.mesh:
            self.compiled = step.__wrapped__.lower(
                self.state, self._shard(batch0)).compile()
        self.compile_seconds = time.perf_counter() - t0
        self.text = self.compiled.as_text()

    def step(self, host_batch: dict):
        self.state, metrics = self.compiled(self.state,
                                            self._shard(host_batch))
        return metrics

    def losses(self, host_batches: list) -> list:
        return [float(self.step(b)["loss_mean"]) for b in host_batches]


def _close(a: list, b: list) -> bool:
    return all(abs(x - y) <= LOSS_ATOL + LOSS_RTOL * abs(y)
               for x, y in zip(a, b))


def _finite(xs: list) -> bool:
    import math
    return all(math.isfinite(x) for x in xs)


def _release(*objs) -> None:
    """Drop an arm's device buffers and executable before the next arm is
    built: one ResNet-50 program at batch 256 nearly fills the chip."""
    import gc
    for o in objs:
        o.__dict__.clear()
    gc.collect()


def _kernel_arm(s, seed, name, extra, ref_extra, *, raw):
    """``name``: flags ``extra`` next to the un-fused arm ``ref_extra`` on
    the same seed and batches.  A compiler refusal is reported as
    ``refused`` with its message — never passed, never swapped for the
    un-fused program."""
    batches = _host_batches(s, seed, s["steps"], raw=raw)
    arm = Arm(s, seed, ref_extra, batches[0])
    say(f"{name}: un-fused arm {' '.join(ref_extra) or '(defaults)'} "
        f"compiled in {arm.compile_seconds:.1f}s")
    ref = arm.losses(batches)
    _release(arm)
    try:
        arm = Arm(s, seed, extra, batches[0])
    except Exception as e:            # the chip's compiler said no
        say(f"{name}: refused — {type(e).__name__}: {str(e)[:600]}")
        return False
    has_kernel = "tpu_custom_call" in arm.text
    secs = arm.compile_seconds
    got = arm.losses(batches)
    _release(arm)
    ok = _finite(got) and _close(got, ref)
    say(f"{name}: {' '.join(extra)} compiled in {secs:.1f}s; "
        f"tpu_custom_call {'present' if has_kernel else 'ABSENT'}; losses "
        f"{got} vs un-fused {ref} "
        f"(max |diff| {max(abs(x - y) for x, y in zip(got, ref)):.3g}, "
        f"tolerance {LOSS_ATOL}+{LOSS_RTOL}*|loss|): "
        f"{'ok' if ok else 'MISMATCH'}")
    return ok and has_kernel


def child_probe(s: dict, seed: int, rehearsal: bool, checkpoint: str) -> int:
    import jax
    device = _start_backend(rehearsal)
    on_tpu = device["platform"] == "tpu"
    failures = []

    # ---- the main step: two ways to end a timed region -----------------
    batches = _host_batches(s, seed, s["steps"], raw=False)
    base = Arm(s, seed, [], batches[0])
    say(f"base step: {' '.join(base.flags)}")
    say(f"base step compiled in {base.compile_seconds:.1f}s; "
        f"memory_analysis {_mem(base.compiled)}")
    base_losses = base.losses(batches)
    say(f"base step losses {base_losses}")
    if not _finite(base_losses):
        failures.append("base step loss not finite")
    k = s["timing_steps"]
    t0 = time.perf_counter()
    for _ in range(k):
        m = base.step(batches[0])
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready((base.state, m))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(k):
        m = base.step(batches[0])
    float(m["loss_mean"])
    t_read = time.perf_counter() - t0
    say(f"step time over {k} steps [{device['kind']}]: dispatch returned "
        f"after {t_dispatch / k * 1e3:.1f} ms/step; ending in "
        f"block_until_ready {t_block / k * 1e3:.1f} ms/step; ending in a "
        f"scalar readback {t_read / k * 1e3:.1f} ms/step")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"memory_stats peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')} "
        f"(bytes_limit {stats.get('bytes_limit', 'not reported')})")
    _release(base)

    # ---- kernel phases, each next to its un-fused arm ------------------
    step_aug = ["--augment-placement", "step"]
    phases = (
        ("fused-augment", lambda: _kernel_arm(
            s, seed, "fused-augment", step_aug + ["--fused-augment", "on"],
            step_aug, raw=True)),
        # serving: served vs offline
        ("serve-parity", lambda: _serve_parity(s, seed, checkpoint)))
    for name, phase in phases:
        try:        # a phase that dies must not hide the ones after it
            ok = phase()
        except Exception as e:
            traceback.print_exc()
            say(f"{name}: raised {type(e).__name__}: {str(e)[:600]}")
            ok = False
        if not ok:
            failures.append(name)

    if not on_tpu:
        say("CPU rehearsal: kernels ran under the Pallas interpreter, so "
            "'tpu_custom_call ABSENT' is expected and not counted")
        failures = [f for f in failures
                    if f in ("base step loss not finite", "serve-parity")]
    for f in failures:
        say(f"FAILED: {f}")
    say(RESULT_TAG + json.dumps({"device": device, "failures": failures}))
    return 1 if failures else 0


def _mem(compiled) -> str:
    ma = compiled.memory_analysis()
    if ma is None:
        return "not reported"
    gib = 2.0 ** 30
    return (f"args {ma.argument_size_in_bytes / gib:.2f} GiB, out "
            f"{ma.output_size_in_bytes / gib:.2f}, alias "
            f"{ma.alias_size_in_bytes / gib:.2f}, temp "
            f"{ma.temp_size_in_bytes / gib:.2f}")


def _serve_args(s: dict, seed: int, extra: list):
    from byol_tpu.serving.cli import build_serve_parser
    return build_serve_parser().parse_args(
        ["--seed", str(seed), "--num-classes", "10"] + _net_flags(s) + extra)


def _service(args, checkpoint: str):
    """A built, warmed engine from serve flags — serving/cli.main's own
    construction, minus the worker thread."""
    from byol_tpu.cli import config_from_args
    from byol_tpu.serving.service import ServeConfig, build_service
    svc = build_service(
        config_from_args(args),
        ServeConfig(min_bucket=args.min_bucket, max_bucket=args.max_batch,
                    num_classes=args.num_classes),
        checkpoint_dir=checkpoint)
    svc.engine.warmup()
    return svc


def _serve_parity(s: dict, seed: int, checkpoint: str) -> bool:
    """Served embeddings vs the offline linear-eval extractor on the same
    restored checkpoint: bitwise at equal compiled batch shape, and the
    max abs difference across shapes (a padded bucket) printed."""
    import types

    import jax
    import numpy as np
    from byol_tpu.cli import config_from_args
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from byol_tpu.serving.service import restore_params_for_serving
    from byol_tpu.training.linear_eval import (encoder_apply_fn,
                                               extract_features)
    args = _serve_args(s, seed, ["--min-bucket", str(s["min_bucket"]),
                                 "--max-batch", str(s["max_batch"])])
    cfg = config_from_args(args)
    svc = _service(args, checkpoint)
    mesh = build_mesh(MeshSpec(data=len(jax.devices())))
    net, params, batch_stats, _ = restore_params_for_serving(
        cfg, checkpoint, mesh, num_classes=10)
    apply_fn = encoder_apply_fn(
        net, types.SimpleNamespace(params=params, batch_stats=batch_stats),
        half=cfg.device.half, normalize=cfg.parity.normalize_inputs)

    def offline(images):
        feats, _ = extract_features(apply_fn, iter(
            [{"view1": images,
              "label": np.arange(len(images), dtype=np.int32)}]))
        return feats

    rng = np.random.RandomState(seed)
    full = s["max_batch"]
    images = rng.rand(full, s["image"], s["image"], 3).astype(np.float32)
    same_shape = float(np.max(np.abs(svc.engine.embed(images)
                                     - offline(images))))
    n = full - 3                        # pads to the same bucket
    padded = float(np.max(np.abs(svc.engine.embed(images[:n])
                                 - offline(images[:n]))))
    served = svc.engine.embed(images)
    finite = bool(np.isfinite(served).all())
    tol = EMBED_RTOL * float(np.max(np.abs(served)))
    say(f"serve-parity: served vs offline max |diff| {same_shape:.3g} at "
        f"equal batch shape ({full}: "
        f"{'bitwise' if same_shape == 0.0 else 'NOT bitwise'}), "
        f"{padded:.3g} across shapes ({n} rows padded to {full} vs offline "
        f"at {n}; tolerance {tol:.3g}); embeddings finite: {finite}")
    svc.batcher.close()
    return finite and max(same_shape, padded) <= tol


def _collectives(text: str) -> dict:
    import re
    found = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all"):
        n = len(re.findall(rf"= [^=\n]*\b{op}(?:-start)?\(", text))
        if n:
            found[op] = n
    return found


def child_multichip(s: dict, seed: int, rehearsal: bool) -> int:
    """Three steps of the same step at one global batch on (i) a one-device
    mesh, (ii) data=4, (iii) data=4 with --zero1 on; one process drives all
    four devices."""
    import jax
    device = _start_backend(rehearsal, cpu_devices=4)
    devs = jax.devices()
    if len(devs) != 4:
        say(f"FAILED: --chips 4 needs four devices, JAX reports {len(devs)}")
        return 1
    batches = _host_batches(s, seed, s["steps"], raw=False)
    failures = []
    losses = {}
    for name, extra, devices in (
            ("one-device", [], devs[:1]),
            ("data=4", [], devs),
            ("data=4 zero1", ["--zero1", "on"], devs)):
        arm = Arm(s, seed, extra, batches[0], devices=devices)
        say(f"{name}: compiled in {arm.compile_seconds:.1f}s; per-device "
            f"memory_analysis {_mem(arm.compiled)}; collectives "
            f"{_collectives(arm.text) or 'none'}")
        if len(devices) == 4:
            placed = arm._shard(batches[0])["view1"]
            homes = {sh.device for sh in placed.addressable_shards}
            rows = {sh.data.shape[0] for sh in placed.addressable_shards}
            ok = len(homes) == 4 and rows == {s["batch"] // 4}
            say(f"{name}: batch shards of {sorted(rows)} rows on "
                f"{len(homes)} distinct devices: {'ok' if ok else 'WRONG'}")
            if not ok:
                failures.append(f"{name}: batch placement")
        if "zero1" in name:
            from byol_tpu.optim.factory import extract_sgdm_state
            trace, _ = extract_sgdm_state(arm.state.opt_state)
            for what, tree in (("momentum", trace),
                               ("target", arm.state.target_params)):
                leaves = jax.tree_util.tree_leaves(tree)
                spread = [
                    len({sh.device for sh in l.addressable_shards}) == 4
                    and all(sh.data.shape[0] * 4 == l.shape[0]
                            for sh in l.addressable_shards)
                    for l in leaves]
                ok = all(spread)
                say(f"{name}: {sum(spread)}/{len(leaves)} {what} leaves "
                    f"split 1/4 each over four distinct devices: "
                    f"{'ok' if ok else 'WRONG'}")
                if not ok:
                    failures.append(f"{name}: {what} placement")
        losses[name] = arm.losses(batches)
        _release(arm)
    ref = losses["one-device"]
    for name in ("data=4", "data=4 zero1"):
        ok = _finite(losses[name]) and _close(losses[name], ref)
        say(f"{name}: losses {losses[name]} vs one-device {ref} (max |diff| "
            f"{max(abs(x - y) for x, y in zip(losses[name], ref)):.3g}, "
            f"tolerance {LOSS_ATOL}+{LOSS_RTOL}*|loss|): "
            f"{'ok' if ok else 'MISMATCH'}")
        if not ok:
            failures.append(f"{name}: loss parity")
    for f in failures:
        say(f"FAILED: {f}")
    say(RESULT_TAG + json.dumps({"device": device, "failures": failures}))
    return 1 if failures else 0


# ---------------------------------------------------------------------------

def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the four-chip comparison")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny sizes on the CPU backend (control flow only; "
                        "never a measurement)")
    p.add_argument("--phase", choices=("probe", "multichip"),
                   help=argparse.SUPPRESS)         # set by the parent only
    p.add_argument("--checkpoint", default="", help=argparse.SUPPRESS)
    a = p.parse_args()
    s = TINY if a.cpu_rehearsal else FULL
    if a.phase == "probe":
        return child_probe(s, a.seed, a.cpu_rehearsal, a.checkpoint)
    if a.phase == "multichip":
        return child_multichip(s, a.seed, a.cpu_rehearsal)

    # the four-chip run keeps a directory of its own: tier-1 rehearses both
    # side by side (two xdist workers), and each clears its directory first
    global OUT
    if a.chips == 4:
        OUT += "_4"
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    t0 = time.monotonic()
    try:
        device = (run_four_chips(a.seed, a.cpu_rehearsal) if a.chips == 4
                  else run_one_chip(s, a.seed, a.cpu_rehearsal))
        _check(device["count"] == a.chips,
               f"JAX reported {device['count']} device(s), asked for "
               f"{a.chips}")
    except (PhaseFailed, KeyError, StopIteration, OSError, ValueError) as e:
        say(f"chip_smoke: FAILED after {time.monotonic() - t0:.0f}s — "
            f"{type(e).__name__}: {e}")
        return 1
    say(f"chip_smoke: all phases passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
