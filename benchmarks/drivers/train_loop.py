"""Time-budgeted BYOL train loop: one driver for every training cell, on
one chip or on a ``data=N`` mesh of several.

Set-up builds ONE object — the program's jitted train step, AOT-compiled,
with its state on the mesh — the way ``trainer.fit`` builds it (CLI flags ->
Config -> mesh -> resolve -> compile plan -> ``setup_training``), swaps the
benchmark's seeded weights in (lib/weights.py), and drives it through its
first ``check_steps`` optimizer steps on the first batches of the pool,
through the same feed and the same compiled call the window uses.  What
the reference needs of those steps (losses, momentum after the first,
parameters after the last) is read back there.  The same object then runs
the window.  After the window the program's buffers are dropped and the
float32 reference follows the same steps from the same weights
(lib/reference.py), so its memory never shows in the program's peak and
its time never in ``setup_s``.

Feed: a pool of ``pool`` seeded host batches (two float32 views and
labels), handed to ``shard_batch_to_mesh`` and then to the step in the
dispatch thread, at most ``max_in_flight`` steps ahead of the device.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np


def host_batches(seed: int, n: int, batch: int, image: int, classes: int):
    """``n`` host batches from ``seed``: rows all differ."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        out.append({
            "view1": rng.random((batch, image, image, 3), dtype=np.float32),
            "view2": rng.random((batch, image, image, 3), dtype=np.float32),
            "label": rng.integers(0, classes, size=(batch,)).astype(
                np.int32)})
    return out


def program_config(conf: dict, *, seed: int, chips: int):
    """The configuration file's flags, as ``train.py`` parses them."""
    from byol_tpu.cli import build_parser, config_from_args
    sched = conf["schedule"]
    flags = list(conf["flags"]) + [
        "--batch-size", str(conf["per_chip_batch"] * chips),
        "--num-replicas", str(chips), "--seed", str(seed % (2 ** 31 - 1)),
        "--epochs", str(sched["epochs"]),
        "--warmup", str(sched["warmup_epochs"])]
    cfg = config_from_args(build_parser().parse_args(flags))
    stated = {"arch": cfg.model.arch,
              "image_size": cfg.task.image_size_override,
              "head_latent_size": cfg.model.head_latent_size,
              "projection_size": cfg.model.projection_size,
              "lr": cfg.optim.lr, "weight_decay": cfg.regularizer.weight_decay,
              "base_decay": cfg.model.base_decay,
              "fuse_views": cfg.model.fuse_views,
              "precision": "bfloat16" if cfg.device.half else "float32"}
    for key, got in stated.items():
        if conf[key] != got:
            raise ValueError(
                f"configuration {conf['name']}: its flags give {key}={got!r} "
                f"but the file states {conf[key]!r}")
    return cfg


def hyperparameters(conf: dict, chips: int) -> dict:
    """What the reference's update needs, from the file's plain keys."""
    sched = conf["schedule"]
    return {"lr": conf["lr"], "weight_decay": conf["weight_decay"],
            "base_decay": conf["base_decay"],
            "global_batch": conf["per_chip_batch"] * chips,
            "warmup_steps": sched["warmup_epochs"] * sched["steps_per_epoch"],
            "total_steps": sched["epochs"] * sched["steps_per_epoch"]}


class Program:
    """The compiled step with its state: built once, checked, then timed."""

    def __init__(self, ctx):
        import jax
        from byol_tpu.core.config import resolve
        from byol_tpu.parallel.compile_plan import build_plan
        from byol_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                            shard_batch_to_mesh)
        from byol_tpu.training.build import setup_training
        from benchmarks.lib.weights import make_weights

        conf, chips = ctx.config, ctx.chips
        self.cfg = program_config(conf, seed=ctx.seed, chips=chips)
        self.mesh = build_mesh(MeshSpec(data=chips), ctx.devices)
        batch = conf["per_chip_batch"] * chips
        image = conf["image_size"]
        rcfg = resolve(
            self.cfg,
            num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
            num_test_samples=batch, output_size=conf["num_classes"],
            input_shape=(image, image, 3))
        plan = build_plan(self.mesh)
        _, state, step, _, _ = setup_training(
            rcfg, self.mesh, jax.random.PRNGKey(0), plan=plan)
        shardings = jax.tree_util.tree_map(
            lambda x: x.sharding,
            (state.params, state.target_params, state.batch_stats))
        params, target, stats = make_weights(
            state.params, state.batch_stats, ctx.seed, copies=2,
            shardings=shardings)
        self.state = state.replace(params=params, target_params=target,
                                   batch_stats=stats)
        del state, params, target, stats
        self._shard = lambda b: shard_batch_to_mesh(dict(b), self.mesh)
        self.global_batch = batch
        self.pool = host_batches(ctx.seed, ctx.cell["traffic"]["pool"],
                                 batch, image, conf["num_classes"])
        t0 = time.perf_counter()
        with self.mesh:
            self.compiled = step.__wrapped__.lower(
                self.state, self._shard(self.pool[0])).compile()
        self.compile_s = time.perf_counter() - t0
        mem = self.compiled.memory_analysis()
        self.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
        self.program_bytes = self.temp_bytes + int(
            getattr(mem, "argument_size_in_bytes", 0) or 0) + int(
            getattr(mem, "output_size_in_bytes", 0) or 0) - int(
            getattr(mem, "alias_size_in_bytes", 0) or 0)

    def step(self, host_batch):
        """The window's own call: feed, then the compiled step."""
        self.state, metrics = self.compiled(self.state,
                                            self._shard(host_batch))
        return metrics

    def momentum(self):
        from byol_tpu.optim.factory import extract_sgdm_state
        return extract_sgdm_state(self.state.opt_state)[0]

    def release(self):
        self.__dict__.clear()
        gc.collect()


def _host(tree):
    import jax
    return jax.device_get(tree)


def first_steps(prog: Program, k: int) -> dict:
    """Drive the program through its first ``k`` steps; keep what the
    comparison reads."""
    got = {"losses": [], "params0": _host(prog.state.params)}
    for i in range(k):
        metrics = prog.step(prog.pool[i % len(prog.pool)])
        got["losses"].append(float(metrics["loss_mean"]))
        if i == 0:
            got["first_trace"] = _host(prog.momentum())
    got["params"] = _host(prog.state.params)
    return got


def window(prog: Program, seconds: float, max_in_flight: int, annotate):
    """Run steps for ``seconds``; returns the counters of the window."""
    import jax
    jax.block_until_ready(prog.state)
    losses, feed_s, done_at = [], [], []
    pool, n = prog.pool, len(prog.pool)
    with annotate("bench/window"):
        t_start = time.perf_counter()
        i = 0
        while time.perf_counter() - t_start < seconds:
            if i >= max_in_flight:
                with annotate("bench/wait_device"):
                    jax.block_until_ready(losses[i - max_in_flight])
                done_at.append(time.perf_counter())
            t_h = time.perf_counter()
            with annotate("bench/feed_and_dispatch"):
                losses.append(prog.step(pool[i % n])["loss_mean"])
            feed_s.append(time.perf_counter() - t_h)
            i += 1
        with annotate("bench/wait_device"):
            jax.block_until_ready((prog.state, losses))
        t_end = time.perf_counter()
    values = [float(x) for x in jax.device_get(losses)]
    return {"steps": i, "window_s": t_end - t_start,
            "nonfinite": sum(not math.isfinite(v) for v in values),
            "last_loss": values[-1] if values else float("nan"),
            "feed_s": feed_s,
            "step_s": [b - a for a, b in zip(done_at, done_at[1:])]}


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps."""
    import jax
    from benchmarks.lib import reference
    from benchmarks.lib.weights import make_weights
    conf, chips = ctx.config, ctx.chips
    like_p, like_s = ctx.scratch["like"]
    params, _ = make_weights(like_p, like_s, ctx.seed)
    pool = ctx.scratch["pool"]             # the program's own host batches
    out = reference.train_steps(
        params, [pool[i % len(pool)] for i in range(k)],
        hyperparameters(conf, chips), image_size=conf["image_size"],
        vit_heads=conf.get("num_heads", 0), precision=precision,
        device_budget_bytes=int(ctx.cell.get("reference", {}).get(
            "device_budget_bytes", 3 << 30)))
    return {"losses": out["losses"], "first_trace": _host(out["first_trace"]),
            "params": _host(out["params"])}


def control(ctx, precision: str) -> dict:
    """The control: the reference in ``precision``, put in the program's
    place, against the float32 reference of the run just made."""
    from benchmarks.lib import check
    got, ref = ctx.scratch["compared"]
    ctl = reference_steps(ctx, int(ctx.cell["check"]["steps"]), precision)
    return check.training_numbers(ctl, ref, got["params0"])


def run(ctx) -> dict:
    import jax
    from benchmarks.lib import check
    traffic = ctx.cell["traffic"]
    k = int(ctx.cell["check"]["steps"])
    ctx.say("train_loop: building the program")
    prog = Program(ctx)
    ctx.say(f"train_loop: step compiled in {prog.compile_s:.1f}s; program "
            f"{prog.program_bytes / 2**30:.2f} GiB by the compiler "
            f"(temp {prog.temp_bytes / 2**30:.2f})")
    ctx.scratch["like"] = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (prog.state.params, prog.state.batch_stats))
    got = first_steps(prog, k)
    ctx.say(f"train_loop: first {k} losses {got['losses']}")
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    jax.block_until_ready(prog.state)
    compiles_before = ctx.compile_count()
    ctx.start_trace()
    setup_s = time.perf_counter() - ctx.t0
    w = window(prog, seconds, int(traffic["max_in_flight"]), ctx.annotate)
    ctx.stop_trace()
    compiles = ctx.compile_count() - compiles_before
    memory = ctx.memory_peak(extra_temp_bytes=prog.temp_bytes)
    chips, batch = ctx.chips, prog.global_batch
    ctx.scratch["pool"] = prog.pool
    prog.release()

    t_ref = time.perf_counter()
    ref = reference_steps(ctx, k)
    ctx.say(f"train_loop: reference followed {k} steps in "
            f"{time.perf_counter() - t_ref:.1f}s, losses {ref['losses']}")
    numbers = check.training_numbers(got, ref, got["params0"])
    ctx.scratch["compared"] = (got, ref)
    rate = batch * w["steps"] / w["window_s"] / chips
    counters = {
        "steps": w["steps"], "window_s": w["window_s"],
        "global_batch": batch, "chips": chips,
        "train_images_per_s_per_chip": rate,
        "step_ms": [s * 1e3 for s in w["step_s"]],
        "host_feed_ms": [s * 1e3 for s in w["feed_s"]],
        "compiles_in_window": compiles, "last_loss": w["last_loss"],
    }
    ctx.say(f"train_loop: {w['steps']} steps in {w['window_s']:.3f}s, "
            f"{rate:.2f} images/s/chip, median step "
            f"{statistics.median(counters['step_ms'] or [float('nan')]):.2f}"
            f" ms, last loss {w['last_loss']:.4f}, compiles in window "
            f"{compiles}")
    slow = sorted(enumerate(counters["step_ms"]), key=lambda kv: -kv[1])[:3]
    ctx.say("train_loop: slowest step intervals (index, ms) "
            f"{[(i, round(ms, 1)) for i, ms in slow]}; slowest feeds "
            f"{[round(x, 1) for x in sorted(counters['host_feed_ms'])[-3:]]}")
    return {
        "attempted": w["steps"],
        "failed": w["nonfinite"] + compiles,
        "setup_s": setup_s,
        "end_to_end": {"train_images_per_s_per_chip":
                       (rate, "images/s/chip")},
        "numbers": numbers,
        "counters": counters,
        "memory_peak_bytes": memory,
    }
