"""Time-budgeted BYOL train loop over TOKEN sequences: the driver of a cell
whose encoder is a decoder trunk (``byol_tpu/models/decoder_trunk.py``).

The loop, the checked first steps, the window and the control are
``train_loop.py``'s, by its own functions; what differs is what a sample
is.  Set-up builds the program's jitted train step the way ``train.py
--task synth_tokens`` does (CLI flags -> Config -> resolve with an
``(S,)`` input -> mesh -> compile plan -> ``setup_training``), swaps in
the benchmark's seeded weights (lib/weights_decoder_trunk.py), and drives
it through ``check_steps`` optimizer steps on the first batches of the
pool; afterwards the float32 reference (lib/reference_decoder_trunk.py)
follows the same steps from the same weights.

Feed: a pool of ``pool`` seeded host batches — per sequence two views of
``seq_len`` ids, each drawn independently and uniformly from the
vocabulary rows this chip holds, and a label — through
``shard_batch_to_mesh`` in the dispatch thread, ``max_in_flight`` steps
ahead of the device.

One sequence is one "image" of ``train_images_per_s_per_chip``.  The rate
goes into ``counters`` as ``train_sequences_per_s_per_chip`` only: the
readers that key on ``train_images_per_s_per_chip`` (``train_step.mfu``
counts an image encoder's operations) have nothing to read here.  Every
step's routing counters (rows routed to held experts, largest and mean
load, rows dropped) come back with its metrics; a dropped row counts as a
failed step.
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmarks.drivers import train_loop as base

ROUTING = ("rows_held", "load_max", "load_mean", "rows_dropped")


def host_batches(seed: int, n: int, batch: int, seq_len: int, vocab: int,
                 classes: int):
    """``n`` host batches from ``seed``."""
    rng = np.random.default_rng(seed)
    draw = lambda: rng.integers(0, vocab, size=(batch, seq_len),
                                dtype=np.int32)
    return [{"view1": draw(), "view2": draw(),
             "label": rng.integers(0, classes, size=(batch,)).astype(
                 np.int32)} for _ in range(n)]


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
    dense = conf["first_k_dense_replace"]
    stated = {"arch": cfg.model.arch, "seq_len": cfg.task.seq_len,
              "layer_share": cfg.model.layer_share,
              "trunk_depth": cfg.model.trunk_depth,
              "remat_policy": cfg.model.remat_policy,
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
    if conf["trunk_depth"] != f"{dense}+{conf['num_hidden_layers'] - dense}":
        raise ValueError(
            f"configuration {conf['name']}: trunk_depth "
            f"{conf['trunk_depth']!r} is not its {dense} dense of "
            f"{conf['num_hidden_layers']} layers")
    return cfg


class Program(base.Program):
    """The compiled step with its state; ``step``, ``momentum`` and
    ``release`` are the image driver's."""

    def __init__(self, ctx):
        import jax
        from byol_tpu.core.config import resolve
        from byol_tpu.parallel.compile_plan import build_plan
        from byol_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                            shard_batch_to_mesh)
        from byol_tpu.training.build import setup_training
        from benchmarks.lib.weights_decoder_trunk import make_weights

        conf, chips = ctx.config, ctx.chips
        self.cfg = program_config(conf, seed=ctx.seed, chips=chips)
        self.mesh = build_mesh(MeshSpec(data=chips), ctx.devices)
        batch, seq_len = conf["per_chip_batch"] * chips, conf["seq_len"]
        rcfg = resolve(
            self.cfg,
            num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
            num_test_samples=batch, output_size=conf["num_classes"],
            input_shape=(seq_len,))
        _, state, step, _, _ = setup_training(
            rcfg, self.mesh, jax.random.PRNGKey(0),
            plan=build_plan(self.mesh))
        shardings = jax.tree_util.tree_map(
            lambda x: x.sharding,
            (state.params, state.target_params, state.batch_stats))
        like = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (state.params, state.batch_stats))
        # the program's own initial values go before the seeded ones come:
        # two whole parameter sets more would not fit beside the state
        state = state.replace(params=None, target_params=None)
        params, target, stats = make_weights(
            *like, ctx.seed, copies=2, shardings=shardings)
        self.state = state.replace(params=params, target_params=target,
                                   batch_stats=stats)
        del state, params, target, stats
        self._shard = lambda b: shard_batch_to_mesh(dict(b), self.mesh)
        self.global_batch = batch
        self.pool = host_batches(ctx.seed, ctx.cell["traffic"]["pool"],
                                 batch, seq_len, conf["vocab_size"],
                                 conf["num_classes"])
        self.routing = []
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
        metrics = super().step(host_batch)
        self.routing.append([metrics[f"_moe_{name}"] for name in ROUTING])
        return metrics


# The hyper-connection maps whose gradient is structurally ZERO, so that what
# the optimizer gets is rounding noise which LARS then scales to full size:
# in the first sub-layer every stream is the embedding, so H_res X = X for any
# doubly stochastic H_res and the norm after H_pre X forgets H_pre's size; at
# the exit the streams are summed, and H_res's columns sum to one.
_NO_GRADIENT = {"first": ("attn_hc", ("phi_pre", "phi_res")),
                "last": ("ffn_hc", ("phi_res",))}


def comparable_tree(tree):
    """The tree as ``lib/check.py`` should read it: a stacked expert kernel
    as one leaf per expert, so that every expert's gradient direction and
    update is compared alone; ``b_res`` — n x n, but a bias LARS leaves
    untouched — flat, among the 1-D leaves whose NORM is compared; and so
    the maps of ``_NO_GRADIENT``, whose direction is noise in any
    precision."""
    if not isinstance(tree, dict):
        return tree
    layers = sorted((k for k in tree if k.startswith("layer")),
                    key=lambda k: int(k[5:]))
    flat = {}
    if layers:
        flat = {(layers[0],) + _NO_GRADIENT["first"],
                (layers[-1],) + _NO_GRADIENT["last"]}

    def walk(node, path):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if k == "experts":
                out[k] = {f"{name}{i}": np.asarray(leaf)[i]
                          for name, leaf in v.items()
                          for i in range(len(leaf))}
            elif k == "b_res" or any(
                    path == (layer, module) and k in names
                    for layer, module, names in flat):
                out[k] = np.asarray(v).reshape(-1)
            else:
                out[k] = walk(v, path + (k,))
        return out
    return {k: (walk(v, (k,)) if k in layers else comparable_tree(v))
            for k, v in tree.items()}


def worst_leaves(got: dict, ref: dict, count: int = 3) -> str:
    """The leaves behind the two gradient numbers, for the log of a run
    that is over a limit: a number names no leaf by itself.  Leaf by leaf
    (lib/check.py's arithmetic holds whole float64 trees; the host has room
    for one such at a time)."""
    from benchmarks.lib import check
    direction, norm = [], []
    for (name, g), (_, r) in zip(check._leaves(got["first_trace"]),
                                 check._leaves(ref["first_trace"])):
        gn, rn = float(np.linalg.norm(g)), float(np.linalg.norm(r))
        if r.ndim > 1:
            direction.append((1.0 - float(np.vdot(g, r)) / max(
                gn * rn, 1e-30), name))
        else:
            norm.append((abs(gn - rn), name, rn, gn))
    top = lambda rows: "; ".join(
        " ".join(f"{x:.3g}" if isinstance(x, float) else str(x) for x in r)
        for r in sorted(rows, reverse=True)[:count])
    return (f"worst directions: {top(direction)} | largest 1-D norm "
            f"differences (|program - reference|, leaf, reference, "
            f"program): {top(norm)}")


def host_gib() -> str:
    """This process's memory on the host, now and at its peak: three whole
    float32 trees of 619 M parameters a side, and lib/check.py's float64
    copies of them, have to stay under the machine's 40 GiB."""
    import resource
    with open("/proc/self/statm") as f:
        now = int(f.read().split()[1]) * resource.getpagesize()
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return f"host memory {now / 2**30:.1f} GiB (peak {peak / 2**30:.1f})"


def change_norms(after, before):
    """``||after - before||`` of every leaf, in float64, as a tree of
    one-element arrays.  ``update_norm_gap`` reads nothing of a leaf's
    change but its norm, and a one-element array has its value's norm:
    ``lib/check.py`` computes from these exactly what it would compute from
    the whole changes, without two whole float64 trees on the host."""
    import jax
    return jax.tree_util.tree_map(
        lambda a, b: np.asarray([np.linalg.norm(
            np.asarray(a, np.float64) - np.asarray(b, np.float64))]),
        comparable_tree(after), comparable_tree(before))


def followed(out: dict, params0) -> dict:
    """What one side of the comparison keeps of the steps it followed:
    losses, the momentum after the first step, and the norm of every leaf's
    CHANGE (the parameters themselves go: whole float32 trees of 619 M
    parameters are 2.5 GB each, ``lib/check.py`` works in float64, and the
    host has 40 GiB for the whole process)."""
    return {"losses": out["losses"],
            "first_trace": comparable_tree(out["first_trace"]),
            "change": change_norms(out["params"], params0)}


def _kernel_groups(tree, prefix=()):
    """The momentum's leaves, as ``(path, leaf)``, with the top-level group
    a kernel belongs to (a layer, the embedding, a head)."""
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _kernel_groups(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _regroup(leaves):
    out = {}
    for path, leaf in leaves:
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def compare(got: dict, ref: dict, limits: dict, say) -> dict:
    """``lib/check.py``'s four numbers, from calls that each hold one group
    of kernels in float64: ``grad_norm_gap`` reads the 1-D leaves only and
    ``grad_dir_gap`` is a maximum over kernels, so every call gets ALL the
    1-D leaves of the momentum (same median, same number in every call)
    and the kernels of one group; ``update_norm_gap`` comes from the
    changes' norms (handed over as parameters after zero parameters)."""
    import jax
    from benchmarks.lib import check
    side = lambda x, **kw: dict({"losses": x["losses"], "first_trace": {},
                                 "params": {}}, **kw)
    group_of = lambda path: path[:2] if path[0] == "backbone" else path[:1]
    leaves = {"got": list(_kernel_groups(got["first_trace"])),
              "ref": list(_kernel_groups(ref["first_trace"]))}
    flat = {k: [(p, v) for p, v in rows if np.ndim(v) <= 1]
            for k, rows in leaves.items()}
    numbers = {}
    for group in sorted({group_of(p) for p, v in leaves["ref"]
                         if np.ndim(v) > 1}):
        pick = lambda rows: _regroup(
            [(p, v) for p, v in rows
             if np.ndim(v) > 1 and group_of(p) == group])
        part = check.training_numbers(
            side(got, first_trace={"kernels": pick(leaves["got"]),
                                   "flat": _regroup(flat["got"])}),
            side(ref, first_trace={"kernels": pick(leaves["ref"]),
                                   "flat": _regroup(flat["ref"])}), {})
        for name in ("loss_rel_gap", "grad_norm_gap", "grad_dir_gap"):
            numbers[name] = max(numbers.get(name, 0.0), part[name])
    if any(v > limits.get(k, float("inf")) for k, v in numbers.items()):
        say("train_tokens: " + worst_leaves(got, ref))
    zeros = jax.tree_util.tree_map(lambda _: np.zeros(()), ref["change"])
    numbers["update_norm_gap"] = check.training_numbers(
        side(got, params=got["change"]), side(ref, params=ref["change"]),
        zeros)["update_norm_gap"]
    gc.collect()
    return numbers


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps."""
    from benchmarks.lib import reference_decoder_trunk as reference
    from benchmarks.lib.weights_decoder_trunk import make_weights
    params, _ = make_weights(*ctx.scratch["like"], ctx.seed)
    params0 = base._host(params)           # the seeded values: the start
    pool = ctx.scratch["pool"]             # the program's own host batches
    out = reference.train_steps(
        params, [pool[i % len(pool)] for i in range(k)],
        base.hyperparameters(ctx.config, ctx.chips), conf=ctx.config,
        precision=precision)
    out["params"] = base._host(out["params"])
    return followed(out, params0)


def control(ctx, precision: str) -> dict:
    """The control: the reference in ``precision``, put in the program's
    place, against the float32 reference of the run just made."""
    ctl = reference_steps(ctx, int(ctx.cell["check"]["steps"]), precision)
    return compare(ctl, ctx.scratch["reference"], {}, ctx.say)


def run(ctx) -> dict:
    import jax
    traffic = ctx.cell["traffic"]
    k = int(ctx.cell["check"]["steps"])
    ctx.say("train_tokens: building the program")
    prog = Program(ctx)
    ctx.say(f"train_tokens: step compiled in {prog.compile_s:.1f}s; program "
            f"{prog.program_bytes / 2**30:.2f} GiB by the compiler "
            f"(temp {prog.temp_bytes / 2**30:.2f})")
    ctx.scratch["like"] = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (prog.state.params, prog.state.batch_stats))
    got = base.first_steps(prog, k)
    got = followed(got, got["params0"])
    ctx.say(f"train_tokens: first {k} losses {got['losses']}; "
            + host_gib())
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    jax.block_until_ready(prog.state)
    prog.routing.clear()
    compiles_before = ctx.compile_count()
    ctx.start_trace()
    setup_s = time.perf_counter() - ctx.t0
    w = base.window(prog, seconds, int(traffic["max_in_flight"]),
                    ctx.annotate)
    ctx.stop_trace()
    compiles = ctx.compile_count() - compiles_before
    routing = np.asarray(jax.device_get(prog.routing), np.float64).reshape(
        -1, len(ROUTING))
    memory = ctx.memory_peak(extra_temp_bytes=prog.temp_bytes)
    chips, batch = ctx.chips, prog.global_batch
    ctx.scratch["pool"] = prog.pool
    prog.release()

    t_ref = time.perf_counter()
    ref = reference_steps(ctx, k)
    ctx.say(f"train_tokens: reference followed {k} steps in "
            f"{time.perf_counter() - t_ref:.1f}s, losses {ref['losses']}; "
            + host_gib())
    numbers = compare(got, ref, ctx.cell["check"]["limits"], ctx.say)
    ctx.say("train_tokens: compared; " + host_gib())
    ctx.scratch["reference"] = ref
    del got
    rate = batch * w["steps"] / w["window_s"] / chips
    dropped = int(routing[:, 3].sum())
    counters = {
        "steps": w["steps"], "window_s": w["window_s"],
        "global_batch": batch, "chips": chips,
        "train_sequences_per_s_per_chip": rate,
        "train_tokens_per_s_per_chip": rate * 2 * ctx.config["seq_len"],
        "step_ms": [s * 1e3 for s in w["step_s"]],
        "host_feed_ms": [s * 1e3 for s in w["feed_s"]],
        "compiles_in_window": compiles, "last_loss": w["last_loss"],
        "moe_rows_dropped": dropped,
        **{f"moe_{name}": routing[:, i].tolist()
           for i, name in enumerate(ROUTING[:3])},
    }
    ctx.say(f"train_tokens: {w['steps']} steps in {w['window_s']:.3f}s, "
            f"{rate:.3f} sequences/s/chip, median step "
            f"{statistics.median(counters['step_ms'] or [float('nan')]):.2f}"
            f" ms, last loss {w['last_loss']:.4f}, compiles in window "
            f"{compiles}")
    if len(routing):
        ctx.say("train_tokens: rows routed to held experts a step (median) "
                f"{statistics.median(routing[:, 0]):.0f}, largest / mean "
                f"load {statistics.median(routing[:, 1]):.0f} / "
                f"{statistics.median(routing[:, 2]):.1f}, rows dropped "
                f"{dropped}")
    return {
        "attempted": w["steps"],
        "failed": w["nonfinite"] + compiles + dropped,
        "setup_s": setup_s,
        "end_to_end": {"train_images_per_s_per_chip":
                       (rate, "images/s/chip")},
        "numbers": numbers,
        "counters": counters,
        "memory_peak_bytes": memory,
    }
