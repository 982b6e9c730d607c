"""Time-budgeted BYOL train loop over TOKEN sequences for a
SPARSE-ATTENTION decoder trunk (grouped-query attention behind a learned
indexer, softmax-routed experts, no shared expert: ``--arch
keye_vl2_30b_a3b``).

The run IS ``train_tokens.run`` — the program built the way ``train.py
--task synth_tokens`` builds it, the feed, the checked first steps, the
window — as ``train_hybrid_tokens.py`` calls it, whose ``followed`` (a bias
in front of a BatchNorm is not compared) and ``compare`` this takes as they
are, with this trunk's names swapped in:

* the seeded weights and the reference are this trunk's
  (lib/weights_sparse_trunk.py; lib/reference_sparse_trunk.py: index scores
  and softmax over whole rows, the selection by a full sort).  The losses
  compared are the step's TOTAL: BYOL's, the probe's and the layers' index
  loss;
* every step's key-selection counters (``_sel_causal_pairs``,
  ``_sel_selected_pairs``: the online pass, summed over the layers) come
  back with its metrics and go into ``counters`` as ``sel_*``;
* afterwards the rate's counter is renamed from
  ``train_sequences_per_s_per_chip`` to ``RATE_COUNTER``, so that the
  latent-attention trunk's readers, which key on the old name and count
  that trunk's operations from keys this configuration does not have, find
  nothing; this cell's readers (``dsa.*``, ``train_step.dsa_*``,
  ``train_step.sparse_*``) dispatch on the configuration's ``arch``
  (lib/trace_sparse_trunk.py).
"""
from __future__ import annotations

from benchmarks.drivers import train_hybrid_tokens as hybrid
from benchmarks.drivers import train_loop as base
from benchmarks.drivers import train_tokens as tokens
from benchmarks.lib.trace_sparse_trunk import RATE_COUNTER

SELECTION = ("causal_pairs", "selected_pairs")


class Program(tokens.Program):
    """``train_tokens.Program`` with this trunk's seeded weights (its
    constructor looks ``make_weights`` up when it runs), keeping every
    step's selection counters."""

    def __init__(self, ctx):
        from benchmarks.lib import weights_decoder_trunk, weights_sparse_trunk
        with hybrid._swapped(weights_decoder_trunk,
                             make_weights=weights_sparse_trunk.make_weights):
            super().__init__(ctx)
        self.selection = ctx.scratch["selection"] = []

    def step(self, host_batch):
        metrics = super().step(host_batch)
        self.selection.append([metrics[f"_sel_{name}"] for name in SELECTION])
        return metrics


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps."""
    from benchmarks.lib import reference_sparse_trunk as reference
    from benchmarks.lib.weights_sparse_trunk import make_weights
    params, _ = make_weights(*ctx.scratch["like"], ctx.seed)
    params0 = base._host(params)           # the seeded values: the start
    pool = ctx.scratch["pool"]             # the program's own host batches
    out = reference.train_steps(
        params, [pool[i % len(pool)] for i in range(k)],
        base.hyperparameters(ctx.config, ctx.chips), conf=ctx.config,
        precision=precision)
    ctx.say(f"train_sparse_tokens: the reference's index losses "
            f"({precision}) {out['index_losses']}")
    out["params"] = base._host(out["params"])
    return hybrid.followed(out, params0)


def control(ctx, precision: str) -> dict:
    """The control: the reference in ``precision``, put in the program's
    place, against the float32 reference of the run just made."""
    ctl = reference_steps(ctx, int(ctx.cell["check"]["steps"]), precision)
    return hybrid.compare(ctl, ctx.scratch["reference"], {}, ctx.say)


def run(ctx) -> dict:
    import jax
    import numpy as np
    with hybrid._swapped(tokens, Program=Program,
                         reference_steps=reference_steps,
                         followed=hybrid.followed, compare=hybrid.compare):
        result = tokens.run(ctx)
    counters = result["counters"]
    counters[RATE_COUNTER] = counters.pop("train_sequences_per_s_per_chip")
    # the window's steps are the last ones the program ran
    pairs = np.asarray(jax.device_get(
        ctx.scratch.pop("selection")[-counters["steps"]:]),
        np.float64).reshape(-1, len(SELECTION))
    counters.update({f"sel_{name}": pairs[:, i].tolist()
                     for i, name in enumerate(SELECTION)})
    if len(pairs):
        ctx.say("train_sparse_tokens: selected / causal pairs a step "
                f"(median) {np.median(pairs[:, 1]):.0f} / "
                f"{np.median(pairs[:, 0]):.0f}")
    return result
