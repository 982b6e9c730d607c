"""Time-budgeted BYOL train loop over TOKEN sequences for a BLOCK-DIFFUSION
decoder trunk (grouped-query attention under the block-diffusion training
mask over rows ``[noised | clean]``, softmax-routed experts, no shared
expert: ``--arch sdar_30b_a3b``).

The run IS ``train_tokens.run`` — the program built the way ``train.py
--task synth_tokens`` builds it, the feed, the checked first steps, the
window — as ``train_sparse_tokens.py`` calls it, with this trunk's names
swapped in:

* the seeded weights are lib/weights_blockdiff_trunk.py's: the
  sparse-attention trunk's rules, which cover every leaf this tree has, and
  the mask id's row of the embedding small, so that masked positions route
  by their context;
* a sample's view is ONE ROW of ``2 x seq_len`` ids, ``[noised | clean]``
  (:func:`host_batches`): the program is resolved with that input shape, as
  ``data/loader._token_loader`` hands it to ``train.py``;
* the reference is this trunk's (lib/reference_blockdiff_trunk.py: the mask
  as a whole ``[2L, 2L]`` rule over whole rows);
* **the gradient is compared IN FRONT OF THE HEADS** (:func:`compare`).  A
  step's batch is 2 samples x 2 views: the heads' BatchNorm sees FOUR rows,
  two pairs of noisings of one sample, each a mean over 4,096 positions of
  uniformly drawn ids — nearly one row.  It divides by a spread of a percent
  or two of the features' size, so bfloat16's rounding of the features comes
  back from the heads as a cotangent tens of percent off the reference's,
  and every gradient a step's momentum holds reads 0.1-1.5 from it in SOUND
  runs, the fp8 control the same (PR 45's first chip runs; PERF.md section
  2): a limit there refuses nothing.  So after the window the program's
  TRUNK — ``BYOLNet.backbone`` as the step builds it, the kernels it
  lowers to, the seeded weights, the rows of the first step — is run once
  more with ONE seeded cotangent on its pooled representations
  (:func:`program_probe`), and the reference's trunk with the same
  (``reference.probe``).  Compared: the final norm's output at the
  first 64 noised positions of every row (``early_hidden_gap``,
  :func:`early_gap`: in front of the POOLING too, because a mean over 4,096
  positions hides what a mask does to a few — under seeded weights the
  twin's leak moves the pooled representation by less than bfloat16 does,
  0.0091 against 0.0074 on the chip, and the first rows by half), and
  ``lib/check.py``'s ``grad_norm_gap`` and ``grad_dir_gap`` over the
  trunk's leaves of that gradient AS THE PROGRAM HOLDS THEM: a layer's
  sixteen held experts are one stacked kernel, the leaf LARS gives one
  trust ratio, so its direction is what the update follows.  With every
  expert a leaf of its own (``train_tokens.comparable_tree``, as the other
  trunks' drivers read the momentum) the worst of 240 expert leaves is
  ONE expert's over the few rows that carry its gradient: in a sound run
  on the chip (PR 45, the driver's seed 1857578033) layer 0's expert 10
  read 0.0985 in its three kernels — it is sent 1,531 of the probe's
  32,768 positions and only 66 of the masked ones, whose small streams
  the norms' backward weighs most — where six other sound runs' worst
  leaf read 0.007-0.031 (PERF.md section 2).  That reading is printed
  beside the other without a limit (``grad_dir_gap_by_expert``).  From the
  three optimizer steps come ``loss_rel_gap`` and ``update_norm_gap``, the
  latter over the leaves LARS scales (more than one dimension): their
  change's norm is the trust ratio's, whatever the gradient's direction, so
  it reads rounding in a sound run and 1 where a state was left as it
  was.  What the momentum's kernels read THROUGH the heads is printed
  beside them without a limit (``grad_dir_gap_through_heads``), so that
  every run shows the two side by side;
* afterwards the rate's counter is renamed from
  ``train_sequences_per_s_per_chip`` to ``RATE_COUNTER``, so that the
  latent-attention trunk's readers, which key on the old name and count that
  trunk's operations from keys this configuration does not have, find
  nothing; this cell's readers (``blockdiff.*``, ``train_step.blockdiff_*``)
  dispatch on the configuration's ``arch`` (lib/trace_blockdiff_trunk.py).
  One SAMPLE — two views of ``2 x seq_len`` positions — is one "image".
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks.drivers import train_hybrid_tokens as hybrid
from benchmarks.drivers import train_loop as base
from benchmarks.drivers import train_tokens as tokens
from benchmarks.lib.trace_blockdiff_trunk import RATE_COUNTER


def noised(ids, rng, mask_id: int, block_length: int):
    """``ids (N, L)`` with every position of block ``p // block_length``
    replaced by ``mask_id`` with probability ``t``, one ``t ~ U(0, 1)`` a
    block and row."""
    n, length = ids.shape
    rate = np.repeat(rng.random((n, -(-length // block_length))),
                     block_length, axis=1)[:, :length]
    return np.where(rng.random((n, length)) < rate, np.int32(mask_id), ids)


def host_batches(seed: int, n: int, batch: int, seq_len: int, vocab: int,
                 classes: int, *, block_length: int):
    """``n`` host batches from ``seed``: per sample ``seq_len`` clean ids
    uniform over the ``vocab - 1`` usable held rows (the last is the mask
    id and is never drawn), and two views ``[noised | clean]`` of them, each
    noised independently."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        clean = rng.integers(0, vocab - 1, size=(batch, seq_len),
                             dtype=np.int32)
        view = lambda: np.concatenate(
            [noised(clean, rng, vocab - 1, block_length), clean],
            axis=1).astype(np.int32)
        out.append({"view1": view(), "view2": view(),
                    "label": rng.integers(0, classes, size=(batch,)).astype(
                        np.int32)})
    return out


class Program(tokens.Program):
    """``train_tokens.Program`` with this trunk's seeded weights, its rows
    (its constructor looks ``make_weights`` and ``host_batches`` up when it
    runs) and the input shape ``(2 x seq_len,)`` those rows have; the
    resolved configuration is kept for :func:`program_probe`."""

    def __init__(self, ctx):
        from benchmarks.lib import (weights_blockdiff_trunk,
                                    weights_decoder_trunk)
        from byol_tpu.core import config as config_lib
        resolve = config_lib.resolve

        def rows(cfg, *, input_shape, **kw):
            rcfg = resolve(cfg, input_shape=(2 * input_shape[0],), **kw)
            ctx.scratch["rcfg"] = rcfg
            return rcfg
        with hybrid._swapped(weights_decoder_trunk,
                             make_weights=weights_blockdiff_trunk.make_weights), \
                hybrid._swapped(tokens, host_batches=functools.partial(
                    host_batches,
                    block_length=ctx.config["block_length"])), \
                hybrid._swapped(config_lib, resolve=rows):
            super().__init__(ctx)


def probe_inputs(ctx):
    """What both trunks are probed with: the first step's rows, both views
    stacked as the fused pass stacks them, and one seeded cotangent on their
    pooled representations."""
    first = ctx.scratch["pool"][0]
    rows = np.concatenate([first["view1"], first["view2"]])
    cotangent = np.random.default_rng(ctx.seed).standard_normal(
        (len(rows), ctx.config["hidden_size"]), dtype=np.float32)
    return rows, cotangent


def probed(out: dict) -> dict:
    """A probe as ``lib/check.py`` should read it: the noised positions'
    hidden states ``(rows, L, D)`` and the trunk's gradient LEAF BY LEAF AS
    THE PROGRAM HOLDS IT — a layer's sixteen held experts one stacked
    kernel, the leaf LARS gives one trust ratio (``grads``) — and, beside
    it, with every expert a leaf of its own (``by_expert``)."""
    grads = {"backbone": base._host(out["grads"])}
    return {"hidden": np.asarray(out["hidden"], np.float32), "grads": grads,
            "by_expert": tokens.comparable_tree(grads)}


def program_probe(ctx) -> dict:
    """The PROGRAM's trunk in front of the heads, once the window is over
    and its buffers are gone: ``BYOLNet.backbone`` built from the step's own
    resolved configuration (so the kernels the step lowers to), at the
    seeded weights, jitted for the device: the final norm's output at the
    noised positions of :func:`probe_inputs`' rows (flax's
    ``capture_intermediates``: the trunk itself hands out the pooled mean
    alone) and the gradient under its cotangent."""
    import jax
    from benchmarks.lib.weights_blockdiff_trunk import make_weights
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from byol_tpu.training.build import build_net
    trunk = build_net(ctx.scratch["rcfg"]).backbone
    rows, cotangent = probe_inputs(ctx)
    params, _ = make_weights(*ctx.scratch["like"], ctx.seed)

    def forward(backbone, rows):
        features, kept = trunk.apply(
            {"params": backbone}, rows, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "final_norm")
        hidden, = kept["intermediates"]["final_norm"]["__call__"]
        return features, hidden[:, :hidden.shape[1] // 2]

    @jax.jit
    def probe(backbone, rows, cotangent):
        features, vjp, hidden = jax.vjp(
            functools.partial(forward, rows=rows), backbone, has_aux=True)
        return hidden, vjp(cotangent.astype(features.dtype))[0]

    with build_mesh(MeshSpec(data=ctx.chips), ctx.devices):
        hidden, grads = probe(params["backbone"], rows, cotangent)
    return probed({"hidden": hidden, "grads": base._host(grads)})


_compare = tokens.compare               # while it is swapped


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps, then its trunk
    under the probe."""
    from benchmarks.lib import reference_blockdiff_trunk as reference
    from benchmarks.lib.weights_blockdiff_trunk import make_weights
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
        params0, *probe_inputs(ctx), conf=ctx.config, precision=precision))
    return kept


EARLY = 64      # positions a row: its first 16 blocks


def early_gap(got, ref, say) -> float:
    """The mean, over the first ``EARLY`` noised positions of every row, of
    ``||h - h_ref|| / ||h_ref||`` (``got``, ``ref``: ``(rows, L, D)``).
    EARLY, because there a position reads few keys and every key the mask
    admits or hides is a large share of them: the twin's four leaked keys
    move rows 0-3 by 0.57 and rows 16-63 by 0.18, the fp8 control moves them
    0.09-0.11, bfloat16 0.008-0.011 (PR 45's chip runs); a MEAN over those,
    and not the worst of all positions, because far down a row a masked
    position's stream is so small that one router flip replaces it: the
    worst position of a SOUND run reads 0.93-1.09 and 7% of them over 0.02,
    which the log line shows in every run."""
    got, ref = (np.asarray(x, np.float64) for x in (got, ref))
    gap = np.linalg.norm(got - ref, axis=-1) / np.maximum(
        np.linalg.norm(ref, axis=-1), 1e-30)
    if not np.isfinite(gap).all():
        return float("inf")
    say("train_blockdiff_tokens: the noised positions' gap, all of them: "
        f"median {np.median(gap):.4g}, 90% {np.quantile(gap, 0.9):.4g}, 99% "
        f"{np.quantile(gap, 0.99):.4g}, worst {gap.max():.4g}")
    return float(gap[:, :EARLY].mean())


def compare(got: dict, ref: dict, limits: dict, say) -> dict:
    """``loss_rel_gap`` and ``update_norm_gap`` from the optimizer steps —
    the latter over the leaves LARS scales —, ``early_hidden_gap``,
    ``grad_norm_gap`` and ``grad_dir_gap`` from the probe in front of the
    heads, the direction over the leaves as the program holds them; and,
    printed without a limit, the probe's direction with every expert a leaf
    and what the momentum's kernels read THROUGH the heads."""
    def side(x, grads="grads"):
        flat = {path for path, leaf in tokens._kernel_groups(
            x["first_trace"]) if np.ndim(leaf) <= 1}
        return {"losses": x["losses"], "first_trace": x["probe"][grads],
                "change": tokens._regroup(
                    [(path, norm) for path, norm in tokens._kernel_groups(
                        x["change"]) if path not in flat])}
    numbers = _compare(side(got), side(ref), limits, say)
    numbers["early_hidden_gap"] = early_gap(
        got["probe"]["hidden"], ref["probe"]["hidden"], say)
    numbers["grad_dir_gap_by_expert"] = _compare(
        side(got, "by_expert"), side(ref, "by_expert"), {},
        say)["grad_dir_gap"]
    numbers["grad_dir_gap_through_heads"] = _compare(
        got, ref, {}, say)["grad_dir_gap"]
    return numbers


def control(ctx, precision: str) -> dict:
    """The control: the reference in ``precision``, put in the program's
    place, against the float32 reference of the run just made."""
    ctl = reference_steps(ctx, int(ctx.cell["check"]["steps"]), precision)
    return compare(ctl, ctx.scratch["reference"], {}, ctx.say)


def run(ctx) -> dict:
    def after_window(ctx, k):
        """What ``train_tokens.run`` calls once the program's buffers are
        dropped: the program's trunk under the probe, then the reference."""
        t0 = time.perf_counter()
        ctx.scratch["probe"] = program_probe(ctx)
        ctx.say("train_blockdiff_tokens: the program's trunk probed in "
                f"{time.perf_counter() - t0:.1f}s")
        return reference_steps(ctx, k)
    with hybrid._swapped(
            tokens, Program=Program, reference_steps=after_window,
            compare=lambda got, ref, limits, say: compare(
                dict(got, probe=ctx.scratch.pop("probe")), ref, limits, say)):
        result = tokens.run(ctx)
    counters = result["counters"]
    counters[RATE_COUNTER] = counters.pop("train_sequences_per_s_per_chip")
    return result
