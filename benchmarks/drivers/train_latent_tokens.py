"""Time-budgeted BYOL train loop over TOKEN sequences for a ONE-STREAM
LATENT-ATTENTION decoder trunk (latent attention with a plain residual and
plain rotary embedding, a leading dense layer, sigmoid-routed experts with
the ``noaux_tc`` bias and one shared expert: ``--arch joyai_llm_flash``).

The run IS ``train_tokens.run`` — the program built the way ``train.py
--task synth_tokens`` builds it, the feed, the checked first steps, the
window — as ``train_shortconv_tokens.py`` calls it, with this trunk's names
swapped in:

* the reference is this trunk's (lib/reference_latent_trunk.py: the softmax
  over whole rows); the seeded weights are lib/weights_shortconv_trunk.py's,
  whose rules cover every leaf this tree has (embedding N(0, 1), gains off
  their start, a fixed selection bias 0.01 N(0, 1));
* ``followed`` and ``compare`` are ``train_hybrid_tokens``'s: a bias in
  front of a BatchNorm is not compared, and this trunk has no
  hyper-connection map for ``train_tokens.comparable_tree`` to flatten;
* the rate STAYS under ``train_sequences_per_s_per_chip``, ``train_tokens``'s
  name: this configuration has every key the latent-attention trunk's
  readers count from (``train_step.seq_mfu``, ``.moe_ms``, ``.mla_ms``,
  ``.update_share``, ``moe.expert_matmul_roofline``), so they answer here
  and no further set of twins is needed (``train_step.mhc_ms`` finds no op
  under ``mhc`` and says nothing).
"""
from __future__ import annotations

from benchmarks.drivers import train_hybrid_tokens as hybrid
from benchmarks.drivers import train_loop as base
from benchmarks.drivers import train_tokens as tokens
from benchmarks.drivers.train_shortconv_tokens import Program  # the weights


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps."""
    from benchmarks.lib import reference_latent_trunk as reference
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
        return tokens.run(ctx)
