"""Time-budgeted BYOL train loop over TOKEN sequences for a PATTERNED
decoder trunk (Gated DeltaNet / gated attention layers, softmax-routed
experts: ``--arch qwen3_next_80b_a3b``).

The run IS ``train_tokens.run`` — the program built the way ``train.py
--task synth_tokens`` builds it, the feed, the checked first steps, the
window, ``followed`` and ``compare``, and ``train_loop.py``'s below them —
with a few of its names swapped for the call:

* the seeded weights and the reference are this trunk's
  (lib/weights_hybrid_trunk.py; lib/reference_hybrid_trunk.py: the delta
  rule token by token, the softmax unblocked over the keys);
* the leaves whose gradient is structurally zero (``NO_GRADIENT``) leave
  the comparison, and ``compare`` names the leaves behind
  ``update_norm_gap`` where it is over its limit;
* afterwards the rate's counter is renamed from
  ``train_sequences_per_s_per_chip`` to ``RATE_COUNTER``: the
  latent-attention trunk's readers (``train_step.seq_mfu``, ``moe_ms``,
  ``mla_ms``, ``mhc_ms``, ``update_share``, ``moe.expert_matmul_roofline``)
  key on the old name and count that trunk's operations from keys this
  configuration does not have; here they find nothing and stay silent, and
  this cell's twins (``train_step.hybrid_*``, ``gdn.*``, ``gqa.*``) read the
  new one.
"""
from __future__ import annotations

import contextlib
import statistics

from benchmarks.drivers import train_loop as base
from benchmarks.drivers import train_tokens as tokens

RATE_COUNTER = "train_hybrid_sequences_per_s_per_chip"
# A bias in front of a BatchNorm: the batch mean takes it out again, so its
# true gradient is ZERO and what the optimizer gets is rounding noise — a
# norm of 2.7e-6 in the float32 reference, 0.029 under bfloat16 — which LARS
# leaves unscaled (1-D).  Compared, that ONE leaf set ``grad_norm_gap``
# (0.40-0.50) and ``update_norm_gap`` (1.21-1.46: its noise against the
# median leaf's change) in every seed, while every other leaf stayed under
# 0.08 (my chip runs, PR 31; PERF.md section 2).  A number that reads noise
# can catch no fault: these leaves are not compared.
NO_GRADIENT = (("projector", "dense1", "bias"), ("predictor", "dense1", "bias"))


@contextlib.contextmanager
def _swapped(module, **names):
    """``module``'s ``names`` replaced while the block runs."""
    kept = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(module, k, v)


class Program(tokens.Program):
    """``train_tokens.Program`` with this trunk's seeded weights (its
    constructor looks ``make_weights`` up when it runs)."""

    def __init__(self, ctx):
        from benchmarks.lib import weights_decoder_trunk, weights_hybrid_trunk
        with _swapped(weights_decoder_trunk,
                      make_weights=weights_hybrid_trunk.make_weights):
            super().__init__(ctx)


def followed(out: dict, params0) -> dict:
    """``train_tokens.followed`` without the ``NO_GRADIENT`` leaves."""
    kept = _followed(out, params0)
    for tree in (kept["first_trace"], kept["change"]):
        for *path, leaf in NO_GRADIENT:
            node = tree
            for key in path:
                node = node[key]
            del node[leaf]
    return kept


_followed = tokens.followed


def reference_steps(ctx, k: int, precision: str = "float32") -> dict:
    """The plain reference over the same first ``k`` steps."""
    from benchmarks.lib import reference_hybrid_trunk as reference
    from benchmarks.lib.weights_hybrid_trunk import make_weights
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
    return tokens.compare(ctl, ctx.scratch["reference"], {}, ctx.say)


def compare(got: dict, ref: dict, limits: dict, say) -> dict:
    """``train_tokens.compare``, and the leaves behind ``update_norm_gap``
    where it is over its limit (a number names no leaf by itself)."""
    from benchmarks.lib import check
    numbers = _compare(got, ref, limits, say)
    if numbers["update_norm_gap"] > limits.get("update_norm_gap",
                                               float("inf")):
        norms = [(name, float(g[0]), float(r[0])) for (name, g), (_, r) in
                 zip(check._leaves(got["change"]),
                     check._leaves(ref["change"]))]
        floor = statistics.median([r for _, _, r in norms if r > 0.0] or [0])
        worst = sorted(norms, key=lambda n: -abs(n[1] - n[2])
                       / max(n[2], floor, 1e-30))[:4]
        say("train_hybrid_tokens: largest update-norm differences (leaf, "
            "program, reference; median leaf "
            f"{floor:.3g}): " + "; ".join(
                f"{name} {g:.3g} {r:.3g}" for name, g, r in worst))
    return numbers


_compare = tokens.compare


def run(ctx) -> dict:
    with _swapped(tokens, Program=Program, reference_steps=reference_steps,
                  followed=followed, compare=compare):
        result = tokens.run(ctx)
    counters = result["counters"]
    counters[RATE_COUNTER] = counters.pop("train_sequences_per_s_per_chip")
    return result
