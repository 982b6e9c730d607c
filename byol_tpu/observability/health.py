"""In-graph training-health diagnostics — the packed telemetry vector.

BYOL's signature failure mode is SILENT: the loss keeps falling while the
target network degenerates (representation collapse), trust ratios explode,
or the EMA target drifts — and the five epoch-mean scalars the trainer
reports would show nothing.  This module computes the per-step health
signals INSIDE the jitted train step (training/steps.py, gated by
``StepConfig.telemetry``) and packs them into one small float32 vector, so
observing a run costs a handful of reductions fused into the step and adds
ZERO host syncs — the readback is deferred and asynchronous
(observability/telemetry.py reads the vector back with >= interval-step
lag).

The packed layout is a versioned contract: ``HEALTH_FIELDS`` names every
slot, ``pack``/``unpack`` are the only writers/readers, and the JSONL run
log (observability/events.py) records the unpacked dict per sampled step.

Signals (one float32 each, ``len(HEALTH_FIELDS)`` total):

- ``grad_norm`` / ``update_norm`` / ``param_norm``: global l2 norms of the
  accumulated gradient, the post-LARS optimizer update, and the post-step
  online params — exploding/vanishing updates and parameter blowup.
- ``ema_drift`` / ``ema_drift_rel``: global l2 distance between the online
  and EMA target trees (and relative to ``param_norm``) — a target that
  stops tracking (tau pinned ~1 by a bad EMA-scaling config) or never
  lags (tau ~0) is visible immediately.
- ``trust_min`` / ``trust_median`` / ``trust_max``: LARS trust-ratio
  spread over the adapted layer groups (optim/lars.py
  ``trust_ratio_vector`` — the same per-leaf ratio the optimizer applies),
  the large-batch early-warning signal (LARS exists because per-layer
  |p|/|g| diverges at scale; a runaway max is how that failure starts).
- ``collapse_feature_std`` / ``collapse_cosine_mean``: the BYOL collapse
  signature on the STOP-GRAD target projections — mean per-feature std
  over the batch (collapse -> 0) and mean pairwise cosine similarity
  (collapse -> 1).
- ``nonfinite_count``: number of non-finite values in the gradient tree +
  the loss — the per-step in-graph replacement for blanket
  ``jax_debug_nans`` (which syncs every op); the host-side
  ``--nan-policy {warn,halt}`` keys off this slot.
- ``loss``: the step loss, so a sampled telemetry record is
  self-contained.
- ``moe_rows_held`` / ``moe_load_max`` / ``moe_load_mean`` /
  ``moe_rows_dropped``: rows routed to the experts this chip holds, the
  largest and the mean load of a held expert, rows the expert product did
  not cover (always 0: the layer has no capacity) — each summed over the
  layers that route; 0 for a backbone without sparse experts.
- ``ssm_dt_max`` / ``ssm_dt_mean`` / ``ssm_decay_min``: a selective scan's
  step ``delta`` after its softplus (what decides whether the state
  forgets) and the smallest ``exp(delta A)`` (underflow shows here first),
  the mean over the state-space layers; ``diff_lambda_mean``: differential
  attention's ``lambda``, the mean over its layers; 0 for a backbone with
  neither.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The packed-vector layout contract.  Append-only: downstream readers
# (telemetry sink, run-log consumers) index by name via pack/unpack, and
# events.py stamps every record with the schema version.
HEALTH_FIELDS: Tuple[str, ...] = (
    "grad_norm",
    "update_norm",
    "param_norm",
    "ema_drift",
    "ema_drift_rel",
    "trust_min",
    "trust_median",
    "trust_max",
    "collapse_feature_std",
    "collapse_cosine_mean",
    "nonfinite_count",
    "loss",
    # routing of a backbone with sparse experts, summed over the layers
    # that route (models/decoder_trunk.py ROUTING_FIELDS); 0 elsewhere
    "moe_rows_held",
    "moe_load_max",
    "moe_load_mean",
    "moe_rows_dropped",
    # a selective scan's step sizes and differential attention's lambda,
    # means over the layers that have them (STATE_SPACE_FIELDS,
    # DIFFERENTIAL_FIELDS); 0 elsewhere
    "ssm_dt_max",
    "ssm_dt_mean",
    "ssm_decay_min",
    "diff_lambda_mean",
)

# fields a packer may leave out (they read 0): what only some backbones have
OPTIONAL_FIELDS = frozenset(
    k for k in HEALTH_FIELDS if k.startswith(("moe_", "ssm_", "diff_")))

_EPS = 1e-12


def global_norm(tree: Any) -> jnp.ndarray:
    """Global l2 norm over every leaf of a pytree (fp32 accumulation)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    total = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    return jnp.sqrt(total)


def nonfinite_count(tree: Any) -> jnp.ndarray:
    """Number of non-finite (NaN/inf) scalars across a pytree, as fp32."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(~jnp.isfinite(l)).astype(jnp.float32)
               for l in leaves)


def collapse_stats(proj: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The BYOL collapse signature of a (B, D) projection batch.

    Returns ``(feature_std, cosine_mean)``:

    - ``feature_std``: mean over features of the per-feature std over the
      batch.  A collapsed representation (every input mapped to the same
      vector) drives this to 0.
    - ``cosine_mean``: mean pairwise cosine similarity between the B
      row-normalized projections, computed in closed form from the norm of
      the summed unit rows — O(B*D), no BxB similarity matrix:
      ``(||sum_i u_i||^2 - B) / (B * (B - 1))``.  Collapse drives it to 1.

    Computed on the STOP-GRAD target projections in the train step, so the
    diagnostic can never leak into the gradient.
    """
    p = proj.astype(jnp.float32)
    feature_std = jnp.mean(jnp.std(p, axis=0))
    b = p.shape[0]
    if b < 2:
        return feature_std, jnp.ones((), jnp.float32)
    u = p / (jnp.linalg.norm(p, axis=1, keepdims=True) + _EPS)
    s = jnp.sum(u, axis=0)
    cosine_mean = (jnp.sum(jnp.square(s)) - b) / (b * (b - 1))
    return feature_std, cosine_mean


def pack(values: Dict[str, Any]) -> jnp.ndarray:
    """Pack the named signals into the (len(HEALTH_FIELDS),) fp32 vector."""
    missing = set(HEALTH_FIELDS) - set(values) - OPTIONAL_FIELDS
    extra = set(values) - set(HEALTH_FIELDS)
    if missing or extra:
        raise ValueError(
            f"health vector fields mismatch: missing={sorted(missing)} "
            f"extra={sorted(extra)}")
    return jnp.stack([jnp.asarray(values.get(k, 0.0), jnp.float32).reshape(())
                      for k in HEALTH_FIELDS])


def unpack(vec: Any) -> Dict[str, float]:
    """Host-side inverse of :func:`pack`: vector -> {field: python float}."""
    arr = np.asarray(vec, np.float64).reshape(-1)
    if arr.shape[0] != len(HEALTH_FIELDS):
        raise ValueError(
            f"health vector has {arr.shape[0]} slots; schema expects "
            f"{len(HEALTH_FIELDS)} ({HEALTH_FIELDS})")
    return {k: float(arr[i]) for i, k in enumerate(HEALTH_FIELDS)}


def health_stats(*, grads: Any, updates: Any, params: Any,
                 target_params: Any, loss: jnp.ndarray,
                 collapse: Tuple[jnp.ndarray, jnp.ndarray],
                 trust_ratios: jnp.ndarray,
                 routing: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
    """Assemble the packed health vector from one optimizer step's tensors.

    All inputs are traced values inside the jitted step; the result is a
    fresh (len(HEALTH_FIELDS),) fp32 array — a step OUTPUT, never an alias
    of the donated state (graphlint GL104 corpus pins the call pattern).

    ``collapse`` is ``collapse_stats(...)`` of the stop-grad target
    projections (computed per microbatch next to the forward, then
    mean-accumulated — recomputing it here would need the projections kept
    live across the accumulation scan, defeating the scan's memory win).
    ``trust_ratios`` is ``optim.lars.trust_ratio_vector(grads, params_pre)``
    — the per-layer-group ratios the LARS transform applies.
    ``routing`` maps the ``OPTIONAL_FIELDS`` (``moe_*``, ``ssm_*``,
    ``diff_*``) to the counters the online forward's layers sowed
    (training/steps.py); a backbone without such layers passes none and
    they read 0.
    """
    param_norm = global_norm(params)
    drift = global_norm(jax.tree_util.tree_map(
        lambda p, t: p.astype(jnp.float32) - t.astype(jnp.float32),
        params, target_params))
    feature_std, cosine_mean = collapse
    tr = trust_ratios.astype(jnp.float32)
    return pack({
        "grad_norm": global_norm(grads),
        "update_norm": global_norm(updates),
        "param_norm": param_norm,
        "ema_drift": drift,
        "ema_drift_rel": drift / (param_norm + _EPS),
        "trust_min": jnp.min(tr),
        "trust_median": jnp.median(tr),
        "trust_max": jnp.max(tr),
        "collapse_feature_std": feature_std,
        "collapse_cosine_mean": cosine_mean,
        "nonfinite_count": nonfinite_count((grads, loss)),
        "loss": loss,
        **(routing or {}),
    })
