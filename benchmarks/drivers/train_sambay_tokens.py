"""Time-budgeted BYOL train loop over TOKEN sequences for a
DECODER-HYBRID-DECODER trunk (Mamba's selective scan, differential attention
under a band, in full and as cross attention on an earlier layer's keys and
values, gated memory units on an earlier layer's scan output, every layer
dense: ``--arch phi4_mini_flash``).

The run IS ``train_tokens.run`` — the program built the way ``train.py
--task synth_tokens`` builds it, the feed, the checked first steps, the
window — as ``train_blockdiff_tokens.py`` calls it, with this trunk's names
swapped in:

* the seeded weights and the reference are this trunk's
  (lib/weights_sambay_trunk.py; lib/reference_sambay_trunk.py: the scan a
  step a position, the softmaxes over whole rows, the band and the triangle
  as whole ``[S, S]`` rules, what a layer hands on passed by name);
* the configuration's cut names PUBLISHED layers (``--trunk-depth 15-19``),
  which ``train_tokens.program_config``'s depth check does not read:
  :func:`program_config` is that function with this check in its place;
* the trunk has NO expert layer, so a step's metrics hold no routing
  counter: :class:`Program` keeps what the trunk's layers do sow — the
  scan's step sizes and differential attention's ``lambda`` — a step;
* **the gradient is compared IN FRONT OF THE HEADS** (:func:`compare`), as
  ``train_blockdiff_tokens.py`` does and for its reason: a step's batch is 2
  samples x 2 views, the heads' BatchNorm sees FOUR near-equal rows, and
  through it every gradient number reads rounding blown up.  After the
  window the program's TRUNK is run once more with ONE seeded cotangent on
  its pooled representations (:func:`program_probe`) and the reference's
  trunk with the same.  Compared: the final norm's output at the first
  ``EARLY`` positions of every row (``early_hidden_gap``: the first two
  tiles of keys, so that the band's edge at 512 lies inside), and
  ``lib/check.py``'s ``grad_norm_gap`` and ``grad_dir_gap`` over the trunk's
  leaves of that gradient as LARS holds them.  From the three optimizer
  steps come ``loss_rel_gap`` and ``update_norm_gap``, the latter over the
  leaves LARS scales.  What the momentum's kernels read THROUGH the heads
  is printed beside them without a limit (``grad_dir_gap_through_heads``);
* afterwards the rate's counter is renamed from
  ``train_sequences_per_s_per_chip`` to ``RATE_COUNTER``, so that the other
  trunks' readers find nothing; this cell's readers (``ssm.*``, ``diff.*``,
  ``train_step.ssm_*`` ...) dispatch on the configuration's ``arch``
  (lib/trace_sambay_trunk.py).  One SAMPLE — two views of ``seq_len``
  positions — is one "image".
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from benchmarks.drivers import train_blockdiff_tokens as blockdiff
from benchmarks.drivers import train_hybrid_tokens as hybrid
from benchmarks.drivers import train_loop as base
from benchmarks.drivers import train_tokens as tokens
from benchmarks.lib.reference_sambay_trunk import UNADAPTED
from benchmarks.lib.trace_sambay_trunk import RATE_COUNTER

EARLY = 1024        # positions a row: its first two tiles of keys
# what the trunk's layers sow a step, as the step's metrics name it
SOWN = ("_ssm_dt_max", "_ssm_dt_mean", "_ssm_decay_min", "_diff_lambda_mean")


def program_config(conf: dict, *, seed: int, chips: int):
    """``train_tokens.program_config`` for a cut that names published
    layers: the flags as ``train.py`` parses them, every stated key against
    its flag, and ``trunk_depth`` against ``kept_layers``."""
    first, last = conf["kept_layers"]
    if conf["trunk_depth"] != f"{first}-{last}" \
            or last - first + 1 != conf["num_hidden_layers"]:
        raise ValueError(
            f"configuration {conf['name']}: trunk_depth "
            f"{conf['trunk_depth']!r} is not its kept layers {first}-{last}, "
            f"{conf['num_hidden_layers']} of them")
    # the depth check there speaks 'D+S' alone: every kept layer is dense
    plain = f"{conf['num_hidden_layers']}+0"
    flags = [plain if flag == conf["trunk_depth"] else flag
             for flag in conf["flags"]]
    cfg = _program_config(dict(
        conf, flags=flags, trunk_depth=plain,
        first_k_dense_replace=conf["num_hidden_layers"]),
        seed=seed, chips=chips)
    return dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, trunk_depth=conf["trunk_depth"]))


_program_config = tokens.program_config     # while it is swapped


class Program(tokens.Program):
    """``train_tokens.Program`` with this trunk's seeded weights and depth
    check; the resolved configuration is kept for :func:`program_probe`; a
    step keeps the counters the trunk's layers sow (``ctx.scratch["sown"]``)
    and reads no routing counter."""

    def __init__(self, ctx):
        from benchmarks.lib import weights_decoder_trunk, weights_sambay_trunk
        from byol_tpu.core import config as config_lib
        resolve = config_lib.resolve

        def kept(cfg, **kw):
            rcfg = resolve(cfg, **kw)
            ctx.scratch["rcfg"] = rcfg
            return rcfg
        with hybrid._swapped(weights_decoder_trunk,
                             make_weights=weights_sambay_trunk.make_weights), \
                hybrid._swapped(tokens, program_config=program_config), \
                hybrid._swapped(config_lib, resolve=kept):
            super().__init__(ctx)
        self.sown = ctx.scratch["sown"] = []
        # what set-up built (traces, module trees: millions of objects) is
        # kept out of the collector's passes while the window runs: a full
        # pass over it is a second of host time, two steps in flight hide one
        # and a half (one window in ten lost 1.4 s: PERF.md section 6, PR 48)
        gc.collect()
        gc.freeze()

    def release(self):
        gc.unfreeze()
        super().release()

    def step(self, host_batch):
        metrics = base.Program.step(self, host_batch)
        self.sown.append([metrics[name] for name in SOWN])
        return metrics


def probed(out: dict) -> dict:
    """A probe as ``lib/check.py`` should read it."""
    return {"hidden": np.asarray(out["hidden"], np.float32),
            "grads": {"backbone": base._host(out["grads"])}}


def program_probe(ctx) -> dict:
    """The PROGRAM's trunk in front of the heads, once the window is over
    and its buffers are gone: ``BYOLNet.backbone`` built from the step's own
    resolved configuration (so the kernels the step lowers to), at the
    seeded weights, jitted for the device: the final norm's output at the
    first ``EARLY`` positions of ``probe_inputs``' rows (flax's
    ``capture_intermediates``) and the gradient under its cotangent."""
    import jax
    from benchmarks.lib.weights_sambay_trunk import make_weights
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from byol_tpu.training.build import build_net
    trunk = build_net(ctx.scratch["rcfg"]).backbone
    rows, cotangent = blockdiff.probe_inputs(ctx)
    params, _ = make_weights(*ctx.scratch["like"], ctx.seed)

    def forward(backbone, rows):
        features, kept = trunk.apply(
            {"params": backbone}, rows, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "final_norm")
        hidden, = kept["intermediates"]["final_norm"]["__call__"]
        return features, hidden[:, :EARLY]

    @jax.jit
    def probe(backbone, rows, cotangent):
        features, vjp, hidden = jax.vjp(
            functools.partial(forward, rows=rows), backbone, has_aux=True)
        return hidden, vjp(cotangent.astype(features.dtype))[0]

    with build_mesh(MeshSpec(data=ctx.chips), ctx.devices):
        hidden, grads = probe(params["backbone"], rows, cotangent)
    return probed({"hidden": hidden, "grads": grads})


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps, then its trunk
    under the probe."""
    from benchmarks.lib import reference_sambay_trunk as reference
    from benchmarks.lib.weights_sambay_trunk import make_weights
    params, _ = make_weights(*ctx.scratch["like"], ctx.seed)
    params0 = base._host(params)           # the seeded values: the start
    del params              # the reference's backward wants the device's room
    pool = ctx.scratch["pool"]             # the program's own host batches
    out = reference.train_steps(
        params0, [pool[i % len(pool)] for i in range(k)],
        base.hyperparameters(ctx.config, ctx.chips), conf=ctx.config,
        precision=precision)
    out["params"] = base._host(out["params"])
    kept = tokens.followed(out, params0)
    del out                         # three whole trees: the host has 40 GiB
    kept["probe"] = probed(reference.probe(
        params0, *blockdiff.probe_inputs(ctx), conf=ctx.config, first=EARLY,
        precision=precision))
    return kept


def early_gap(got, ref, say) -> float:
    """The mean, over the first ``EARLY`` positions of every row, of ``||h -
    h_ref|| / ||h_ref||`` (``got``, ``ref``: ``(rows, EARLY, D)``): in
    front of the POOLING, because a mean over 8,192 positions hides what a
    rule does to some of them."""
    got, ref = (np.asarray(x, np.float64) for x in (got, ref))
    gap = np.linalg.norm(got - ref, axis=-1) / np.maximum(
        np.linalg.norm(ref, axis=-1), 1e-30)
    if not np.isfinite(gap).all():
        return float("inf")
    say("train_sambay_tokens: the first positions' gap: median "
        f"{np.median(gap):.4g}, 90% {np.quantile(gap, 0.9):.4g}, worst "
        f"{gap.max():.4g}; the mean over positions under / from "
        f"{gap.shape[1] // 2}: {gap[:, :gap.shape[1] // 2].mean():.4g} / "
        f"{gap[:, gap.shape[1] // 2:].mean():.4g}")
    return float(gap.mean())


def compare(got: dict, ref: dict, limits: dict, say) -> dict:
    """``loss_rel_gap`` and ``update_norm_gap`` from the optimizer steps —
    the latter over the leaves LARS scales —, ``early_hidden_gap``,
    ``grad_norm_gap`` and ``grad_dir_gap`` from the probe in front of the
    heads; and, printed without a limit, what the momentum's kernels read
    THROUGH the heads."""
    def side(x):
        scaled = [(path, norm) for path, norm in tokens._kernel_groups(
            x["change"]) if path[-1] not in UNADAPTED]
        flat = {path for path, leaf in tokens._kernel_groups(
            x["first_trace"]) if np.ndim(leaf) <= 1}
        return {"losses": x["losses"], "first_trace": x["probe"]["grads"],
                "change": tokens._regroup(
                    [(path, norm) for path, norm in scaled
                     if path not in flat])}
    numbers = _compare(side(got), side(ref), limits, say)
    numbers["early_hidden_gap"] = early_gap(
        got["probe"]["hidden"], ref["probe"]["hidden"], say)
    numbers["grad_dir_gap_through_heads"] = _compare(
        got, ref, {}, say)["grad_dir_gap"]
    return numbers


_compare = tokens.compare               # while it is swapped


def control(ctx, precision: str) -> dict:
    """The control: the reference in ``precision``, put in the program's
    place, against the float32 reference of the run just made."""
    ctl = reference_steps(ctx, int(ctx.cell["check"]["steps"]), precision)
    return compare(ctl, ctx.scratch["reference"], {}, ctx.say)


def tile_counts(conf: dict) -> dict:
    """What the program's own rule forms: the pairs of its band's list and
    of the causal list, at the cell's sizes."""
    from byol_tpu.models.registry import get_backbone
    from byol_tpu.ops import attention
    sizes = get_backbone(conf["arch"])[0].sizes.hybrid_decoder
    blocks = -(-conf["seq_len"] // sizes.block)
    return {"diff_band_tiles": len(attention.window_tiles(
        blocks, sizes.window, sizes.block).q_of),
        "diff_causal_tiles": len(attention.causal_tiles(blocks).q_of)}


def run(ctx) -> dict:
    import jax

    def after_window(ctx, k):
        """What ``train_tokens.run`` calls once the program's buffers are
        dropped: the program's trunk under the probe, then the reference."""
        t0 = time.perf_counter()
        ctx.scratch["probe"] = program_probe(ctx)
        ctx.say("train_sambay_tokens: the program's trunk probed in "
                f"{time.perf_counter() - t0:.1f}s")
        return reference_steps(ctx, k)
    with hybrid._swapped(
            tokens, Program=Program, reference_steps=after_window,
            compare=lambda got, ref, limits, say: compare(
                dict(got, probe=ctx.scratch.pop("probe")), ref, limits, say)):
        result = tokens.run(ctx)
    counters = result["counters"]
    counters[RATE_COUNTER] = counters.pop("train_sequences_per_s_per_chip")
    # the window's steps: the checked first steps come before them
    steps = int(counters["steps"])
    sown = np.asarray(jax.device_get(ctx.scratch.pop("sown")),
                      np.float64).reshape(-1, len(SOWN))[-steps:]
    counters.update({name[1:]: sown[:, i].tolist()
                     for i, name in enumerate(SOWN)})
    counters.update(tile_counts(ctx.config))
    if len(sown):
        ctx.say("train_sambay_tokens: a step's (median) " + ", ".join(
            f"{name[1:]} {np.median(sown[:, i]):.4g}"
            for i, name in enumerate(SOWN)))
    return result
