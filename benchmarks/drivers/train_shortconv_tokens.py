"""Time-budgeted BYOL train loop over TOKEN sequences for a
SHORT-CONVOLUTION decoder trunk (gated short convolutions beside plain
grouped-query attention in a listed pattern, a leading dense layer,
sigmoid-routed experts with a selection bias and no shared expert: ``--arch
lfm2_24b_a2b``).

The run IS ``train_tokens.run`` — the program built the way ``train.py
--task synth_tokens`` builds it, the feed, the checked first steps, the
window — as ``train_hybrid_tokens.py`` calls it, whose ``followed`` (a bias
in front of a BatchNorm is not compared) and ``compare`` this takes as they
are, with this trunk's names swapped in:

* the seeded weights and the reference are this trunk's
  (lib/weights_shortconv_trunk.py; lib/reference_shortconv_trunk.py: the
  convolution as three shifted adds, the softmax over whole rows, the router
  by a full sort);
* afterwards the rate's counter is renamed from
  ``train_sequences_per_s_per_chip`` to ``RATE_COUNTER``, so that the
  latent-attention trunk's readers, which key on the old name and count
  that trunk's operations from keys this configuration does not have, find
  nothing; this cell's readers (``shortconv.*``, ``shortconv_gqa.*``,
  ``train_step.shortconv_*``) dispatch on the configuration's ``arch``
  (lib/trace_shortconv_trunk.py).
"""
from __future__ import annotations

from benchmarks.drivers import train_hybrid_tokens as hybrid
from benchmarks.drivers import train_loop as base
from benchmarks.drivers import train_tokens as tokens
from benchmarks.lib.trace_shortconv_trunk import RATE_COUNTER


class Program(tokens.Program):
    """``train_tokens.Program`` with this trunk's seeded weights (its
    constructor looks ``make_weights`` up when it runs)."""

    def __init__(self, ctx):
        from benchmarks.lib import (weights_decoder_trunk,
                                    weights_shortconv_trunk)
        with hybrid._swapped(
                weights_decoder_trunk,
                make_weights=weights_shortconv_trunk.make_weights):
            super().__init__(ctx)


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps."""
    from benchmarks.lib import reference_shortconv_trunk as reference
    from benchmarks.lib.weights_shortconv_trunk import make_weights
    params, _ = make_weights(*ctx.scratch["like"], ctx.seed)
    params0 = base._host(params)           # the seeded values: the start
    pool = ctx.scratch["pool"]             # the program's own host batches
    out = reference.train_steps(
        params, [pool[i % len(pool)] for i in range(k)],
        base.hyperparameters(ctx.config, ctx.chips), conf=ctx.config,
        precision=precision)
    out["params"] = base._host(out["params"])
    return hybrid.followed(out, params0)


def control(ctx, precision: str) -> dict:
    """The control: the reference in ``precision``, put in the program's
    place, against the float32 reference of the run just made."""
    ctl = reference_steps(ctx, int(ctx.cell["check"]["steps"]), precision)
    return hybrid.compare(ctl, ctx.scratch["reference"], {}, ctx.say)


def run(ctx) -> dict:
    with hybrid._swapped(tokens, Program=Program,
                         reference_steps=reference_steps,
                         followed=hybrid.followed, compare=hybrid.compare):
        result = tokens.run(ctx)
    counters = result["counters"]
    counters[RATE_COUNTER] = counters.pop("train_sequences_per_s_per_chip")
    return result
