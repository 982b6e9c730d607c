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

# the 'momentum' registry entry's decay (reference main.py:311) — also the
# momentum the fused update kernel ticks (training/steps.py), so the
# number has exactly one home
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


def fused_update_unsupported_reason(opt_name: str, clip: float = 0.0,
                                    adapt_mask: Optional[Any] = None
                                    ) -> Optional[str]:
    """Why ``--fused-update on`` cannot serve this optimizer config —
    ``None`` when the fused Pallas kernel (ops/fused_update.py) computes
    exactly the chain :func:`build_optimizer` would.  The ONE gating
    predicate, shared by config resolve() (fail fast at the CLI) and the
    step builder (fail fast for programmatic callers).  ``adapt_mask``
    (``optim.lars.default_exclusion_mask`` of the parameter tree, once it
    exists) names a tree the kernel cannot take."""
    full = opt_name.lower().strip()
    if not is_lars_optimizer(full):
        return (f"optimizer {opt_name!r} does not build the LARS wrapper "
                "chain; the fused kernel implements wd fold-in + trust "
                "ratio + momentum (use lars_momentum)")
    if full.split("_")[-1] != "momentum":
        return (f"inner optimizer {full.split('_')[-1]!r} is not the sgd-"
                "momentum trace the fused kernel ticks (use lars_momentum)")
    if clip > 0.0:
        return ("--clip > 0 value-clips gradients before LARS; the fused "
                "kernel does not replicate the clip")
    if adapt_mask is not None and lars_lib.has_expert_axis(adapt_mask):
        return ("the parameter tree stacks expert kernels on a leading "
                "axis and LARS adapts every expert alone; the fused kernel "
                "has one segment, one trust ratio, per leaf")
    return None


def extract_sgdm_state(opt_state: Any) -> Tuple[Any, Any]:
    """``(momentum_trace_tree, schedule_count)`` out of the lars_momentum
    chain state — located by node TYPE (TraceState / ScaleByScheduleState),
    not by tuple position, so an optax version reshuffling the chain
    nesting fails loudly here instead of silently reading the wrong slot.
    The fused update reads these, ticks them in-kernel, and writes them
    back via :func:`replace_sgdm_state`; the opt_state PYTREE STRUCTURE is
    never changed (checkpoints, shardings, and the zero1 codec all key on
    it)."""
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
            f"opt_state is not the lars_momentum chain the fused update "
            f"expects: found {len(traces)} TraceState / {len(counts)} "
            "ScaleByScheduleState nodes (fused_update_unsupported_reason "
            "should have rejected this config)")
    return traces[0], counts[0]


def replace_sgdm_state(opt_state: Any, new_trace: Any,
                       new_count: Any) -> Any:
    """Rebuild the chain state with a fresh momentum trace + schedule
    count — the exact inverse of :func:`extract_sgdm_state` (every other
    node, including the empty wd/LARS states, passes through untouched)."""

    def rebuild(node):
        if isinstance(node, optax.TraceState):
            return optax.TraceState(trace=new_trace)
        if isinstance(node, optax.ScaleByScheduleState):
            return optax.ScaleByScheduleState(count=new_count)
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[rebuild(c) for c in node])
        if isinstance(node, tuple):
            return tuple(rebuild(c) for c in node)
        return node

    return rebuild(opt_state)


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
