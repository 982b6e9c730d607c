"""Optimizer factory / registry.

Mirrors reference ``build_optimizer`` (main.py:303-344):
- registry {rmsprop, adam, adadelta, sgd, momentum(0.9), lamb, lbfgs};
- linear LR scaling to global batch for sgd/momentum (main.py:333-334);
- ``lars_<name>`` prefix composes LARS around the base optimizer with eps=0
  (main.py:323,339-340);
- weight decay routed through ``add_weight_decay`` semantics: bias/BN params
  undecayed + excluded from LARS adaptation (SURVEY.md §2.3).  For non-LARS
  optimizers the reference passes wd to the torch optimizer's own decoupled-
  from-nothing L2 (torch adds wd*p to the grad) — reproduced with
  ``optax.add_decayed_weights`` before the base transform.
- grad VALUE clipping before everything when ``clip > 0``
  (main.py:619-622: ``clip_grad_value_``).

The apex FusedLAMB path (main.py:324-326) maps to ``optax.lamb`` — XLA fuses
the update; no custom CUDA needed (SURVEY.md §2.4).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import optax

from byol_tpu.optim import lars as lars_lib
from byol_tpu.optim import schedules as sched_lib

# the 'momentum' registry entry's decay (reference main.py:311)
MOMENTUM_DECAY = 0.9


def _base_optimizer(name: str, learning_rate) -> optax.GradientTransformation:
    if name == "rmsprop":
        # torch RMSprop defaults: alpha=0.99, eps=1e-8, no momentum.
        return optax.rmsprop(learning_rate, decay=0.99, eps=1e-8)
    if name == "adam":
        return optax.adam(learning_rate)
    if name == "adadelta":
        return optax.adadelta(learning_rate)
    if name == "sgd":
        return optax.sgd(learning_rate)
    if name == "momentum":
        return optax.sgd(learning_rate, momentum=MOMENTUM_DECAY)
    if name == "lamb":
        return optax.lamb(learning_rate)
    if name == "lbfgs":
        # Memory-limited BFGS direction with the schedule LR.  The torch
        # closure/zoom-line-search driver (reference main.py:317) cannot run
        # inside a jitted step; the direction update itself is jit-native.
        return optax.chain(optax.scale_by_lbfgs(),
                           optax.scale_by_learning_rate(learning_rate))
    raise ValueError(f"unknown optimizer {name!r}")


def is_lars_optimizer(opt_name: str) -> bool:
    """Does this optimizer string build the LARS wrapper chain?  The ONE
    predicate shared by the factory and the telemetry plumbing (build.py
    ``StepConfig.lars_in_chain``) — a second copy that normalized the
    string differently would make the health vector report identity trust
    ratios for a run where LARS is actually scaling updates."""
    return opt_name.lower().strip().startswith("lars_")


def extract_sgdm_state(opt_state: Any) -> Tuple[Any, Any]:
    """``(momentum_trace_tree, schedule_count)`` out of the lars_momentum
    chain state — located by node TYPE (TraceState / ScaleByScheduleState),
    not by tuple position, so an optax version reshuffling the chain
    nesting fails loudly here instead of silently reading the wrong slot.
    Read by what compares a step's momentum with a reference
    (benchmarks/drivers/train_loop.py, chip_smoke.py, tests)."""
    traces, counts = [], []

    def walk(node):
        if isinstance(node, optax.TraceState):
            traces.append(node.trace)
        elif isinstance(node, optax.ScaleByScheduleState):
            counts.append(node.count)
        elif isinstance(node, tuple):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(traces) != 1 or len(counts) != 1:
        raise ValueError(
            "opt_state is not the lars_momentum chain: found "
            f"{len(traces)} TraceState / {len(counts)} "
            "ScaleByScheduleState nodes")
    return traces[0], counts[0]


def build_optimizer(opt_name: str, *,
                    base_lr: float,
                    global_batch_size: int,
                    weight_decay: float,
                    total_units: int,
                    warmup_units: int,
                    lr_schedule_kind: str = "cosine",
                    steps_per_epoch: Optional[int] = None,
                    clip: float = 0.0,
                    trust_coefficient: float = lars_lib.TRUST_COEFFICIENT_DEFAULT,
                    lars_eps: float = lars_lib.LARS_EPS_DEFAULT,
                    adapt_mask: Optional[Any] = None,
                    ) -> Tuple[optax.GradientTransformation, optax.Schedule]:
    """Build the full gradient transformation + the lr schedule (returned
    separately so the driver can log lr per epoch, main.py:763-764).

    ``total_units``/``warmup_units`` are in schedule units; pass epochs and
    set ``steps_per_epoch`` for reference-parity epoch-granular stepping
    (Quirk Q5), or pass steps directly with ``steps_per_epoch=None``.

    ``adapt_mask``: optional PRECOMPUTED bias/BN exclusion mask tree for
    LARS adaptation / weight decay.  The default (None) derives the mask
    from leaf ndim at update time — correct on the shaped param tree, but
    under ZeRO-1 the transforms see the FLAT leaf-partitioned trees
    (parallel/zero1.py) where every leaf is 1-D, so the caller must pass
    the mask computed on the real shapes.
    """
    full = opt_name.lower().strip()
    if full == "lars":
        raise ValueError(
            "bare 'lars' is a wrapper, not an optimizer; use lars_<base>, "
            "e.g. 'lars_momentum' (the reference default, main.py:88-89)")
    is_lars = is_lars_optimizer(full)
    name = full.split("_")[-1] if is_lars else full

    lr = sched_lib.linear_scaled_lr(base_lr, global_batch_size, name)
    schedule = sched_lib.warmup_cosine(lr, warmup_units, total_units,
                                       kind=lr_schedule_kind)
    if steps_per_epoch is not None:
        schedule = sched_lib.epoch_granular(schedule, steps_per_epoch)

    base = _base_optimizer(name, schedule)

    chain = []
    if clip > 0.0:
        chain.append(optax.clip(clip))
    if is_lars:
        chain.append(lars_lib.lars(
            base, weight_decay=weight_decay,
            trust_coefficient=trust_coefficient, eps=lars_eps,
            mask=adapt_mask))
    else:
        if weight_decay > 0.0:
            # torch-style L2: grad += wd*p for every param (torch applies wd
            # to ALL params when passed per-group; add_weight_decay gives the
            # no-decay group wd=0, so mask bias/BN here identically).
            chain.append(optax.add_decayed_weights(
                weight_decay,
                mask=(adapt_mask if adapt_mask is not None
                      else lars_lib.decay_mask)))
        chain.append(base)

    return optax.chain(*chain), schedule
