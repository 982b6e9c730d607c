"""Train state: online params, target EMA tree, optimizer state, counters.

Replaces the reference's CosEMA buffer + parameter-vector swap machinery
(main.py:133-164, 214-227): the target network is a plain second pytree.

State facts mirrored from the reference:
- the EMA covers the FULL parameter tree incl. heads and probe
  (``parameters_to_vector(self.parameters())``, main.py:211-212,255);
- ``ema_step`` is persisted in the checkpoint — the reference loses it on
  resume because CosEMA.step is a plain attribute, resetting the tau
  schedule (Quirk Q6, fixed here);
- target initialization defaults to a COPY of the online params (the paper's
  init); ``ema_init_mode='reference'`` reproduces the reference's
  near-zero init: the ctor tick runs with mean=0 and step 0 => tau=0.996 =>
  mean = 0.004 * theta, and the step counter starts at 1 (Quirk Q1,
  main.py:156-162,211-212).
"""
from __future__ import annotations

from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray                    # global optimizer step
    params: Any                          # online tree (backbone+heads+probe)
    batch_stats: Any                     # BN running stats (fp32)
    target_params: Any                   # EMA tree (fp32)
    ema_step: jnp.ndarray                # persisted tau-schedule counter (Q6 fix)
    opt_state: Any
    polyak_params: Optional[Any] = None  # --polyak-ema tree (main.py:76,625-626)


def create_train_state(variables: Any,
                       tx: Optional[optax.GradientTransformation],
                       *, ema_init_mode: str = "copy",
                       polyak_ema: float = 0.0) -> TrainState:
    """``tx=None`` leaves ``opt_state`` empty: the ZeRO-1 compile plan
    re-initializes it on the FLAT params in ``prepare_state`` — allocating
    the full replicated momentum tree here first would raise the setup-time
    HBM high water by ~1 params-tree for nothing."""
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if ema_init_mode == "copy":
        target = jax.tree_util.tree_map(jnp.array, params)
        ema_step = jnp.zeros((), jnp.int32)
    elif ema_init_mode == "reference":
        # Quirk Q1: mean = (1 - tau0)|_{tau(0)=0.996} * theta = 0.004 * theta,
        # and the schedule counter starts at 1.
        target = jax.tree_util.tree_map(lambda p: 0.004 * p, params)
        ema_step = jnp.ones((), jnp.int32)
    else:
        raise ValueError(f"unknown ema_init_mode {ema_init_mode!r}")
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        target_params=target,
        ema_step=ema_step,
        opt_state=tx.init(params) if tx is not None else None,
        polyak_params=(jax.tree_util.tree_map(jnp.array, params)
                       if polyak_ema > 0.0 else None),
    )
    return _dedupe_buffers(state)


def _dedupe_buffers(state: TrainState) -> TrainState:
    """Copy any leaf that aliases an earlier leaf's buffer.

    Some optimizer inits store the PARAM ARRAYS THEMSELVES in their state
    (optax.scale_by_lbfgs keeps the previous-params tree as the very objects
    passed in), so the flattened TrainState would contain one buffer twice —
    and the train step's ``donate_argnums=(0,)`` then fails with "Attempt to
    donate the same buffer twice".  A one-time copy at setup breaks the
    aliasing."""
    seen: set = set()

    def uniq(x):
        if isinstance(x, jax.Array):
            if id(x) in seen:
                return jnp.array(x)
            seen.add(id(x))
        return x

    return jax.tree_util.tree_map(uniq, state)
