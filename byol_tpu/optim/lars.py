"""LARS (layer-wise adaptive rate scaling) as an optax transform.

Reference: /root/reference/optimizers/lars.py:8-127, a wrapper over an
arbitrary torch optimizer.  Exact semantics reproduced (order matters):

1. weight decay is folded into the gradient BEFORE the trust ratio
   (lars.py:96-97: ``p.grad += weight_decay * p``), for every group whose
   ``weight_decay > 0`` — bias/BN groups carry wd=0 so are untouched;
2. the trust ratio ``trust_coef * |p| / (|g| + eps)`` multiplies the gradient
   only for groups not flagged ``ignore`` (lars.py:100-108), i.e. only
   matrix/conv kernels — bias and BN params are excluded (the
   ``helpers.layers.add_weight_decay`` contract, SURVEY.md §2.3);
3. the ratio is applied only when both norms are > 0, else 1.0
   (lars.py:105-107);
4. the inner optimizer then runs with its own lr and wd forced to 0
   (lars.py:116-126) — here that is simply "don't add another wd transform".

Defaults mirror the factory at reference main.py:339-340: ``eps=0.0``,
``trust_coef=1e-3``.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax

MaskOrFn = Union[Any, Callable[[Any], Any]]

# Factory defaults (reference main.py:339-340) — the ONE home for these
# numbers: optim/factory.py's signature reads them here.
TRUST_COEFFICIENT_DEFAULT = 1e-3
LARS_EPS_DEFAULT = 0.0


# A mask leaf is False (excluded), True (one layer group) or PER_EXPERT:
# the leaf stacks one kernel per expert on its leading axis and every
# expert is a layer group of its own.  With ONE ratio for the stack, an
# expert's update would depend on which other experts share its chip.
PER_EXPERT = "per_expert"
# the module whose leaves carry that axis (models/decoder_trunk.py)
EXPERT_MODULE = "experts"
# leaves that are gains, biases or buffers whatever their rank: the
# hyper-connection scalars and static maps (``b_res`` is n x n), the
# router's selection bias, every norm's scale; a selective state-space
# layer's ``A_log`` (channels x state: the logarithm of a decay rate, which
# Mamba's own training neither decays nor rescales) and the taps of its
# depthwise convolution (taps x channels: a few numbers a channel)
UNADAPTED_LEAVES = frozenset({
    "scale", "bias", "alpha_pre", "alpha_post", "alpha_res", "b_pre",
    "b_post", "b_res", "e_score_correction_bias", "A_log", "taps"})


def default_exclusion_mask(params) -> Any:
    """Truthy where LARS adaptation / weight decay applies.

    Reproduces the bias/BN exclusion of ``add_weight_decay``: 1-D parameters
    (biases, BN scale/bias) are excluded; kernels (ndim >= 2) are adapted —
    per expert (``PER_EXPERT``) below a module named ``EXPERT_MODULE``, and
    never a leaf named in ``UNADAPTED_LEAVES``.
    """
    def rule(path, p):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if p.ndim <= 1 or names[-1] in UNADAPTED_LEAVES:
            return False
        return PER_EXPERT if EXPERT_MODULE in names[:-1] else True
    return jax.tree_util.tree_map_with_path(rule, params)


def has_expert_axis(mask) -> bool:
    return any(m == PER_EXPERT for m in jax.tree_util.tree_leaves(mask))


def decay_mask(params) -> Any:
    """The default mask as booleans (weight decay is elementwise: an
    expert axis changes nothing)."""
    return jax.tree_util.tree_map(bool, default_exclusion_mask(params))


def _resolve_mask(mask: Optional[MaskOrFn], params):
    if mask is None:
        return default_exclusion_mask(params)
    if callable(mask):
        return mask(params)
    return mask


class LarsState(NamedTuple):
    pass


def trust_ratio_from_norms(param_norm: jnp.ndarray, grad_norm: jnp.ndarray,
                           trust_coefficient: float = TRUST_COEFFICIENT_DEFAULT,
                           eps: float = LARS_EPS_DEFAULT) -> jnp.ndarray:
    """Steps 2-3 on PRECOMPUTED norms (lars.py:100-108), elementwise.

    The ONE trust-ratio formula: :func:`_leaf_trust_ratio` (the optax
    transform + per-leaf telemetry) applies it to a leaf's scalar norms and
    to a stacked expert kernel's per-expert norms.  ``grad_norm`` must be of
    the POST-weight-decay gradient (step 1 folds wd in first).
    """
    return jnp.where(
        (param_norm > 0.0) & (grad_norm > 0.0),
        trust_coefficient * param_norm / (grad_norm + eps),
        jnp.ones((), jnp.float32))


def _leaf_trust_ratio(g: jnp.ndarray, p: jnp.ndarray,
                      trust_coefficient: float, eps: float,
                      per_expert: bool = False) -> jnp.ndarray:
    """The per-layer-group LARS trust ratio (lars.py:100-108), fp32 scalar
    — or, ``per_expert``, one ratio per slice of the leading axis, shaped
    ``(E, 1, ...)`` to broadcast against the leaf.

    ONE implementation shared by the optimizer transform below and the
    telemetry stats (:func:`trust_ratio_vector`), so the health vector can
    never report a different ratio than the update applied.
    """
    g32 = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    if per_expert:
        axes = tuple(range(1, p.ndim))
        norm = lambda x: jnp.sqrt(
            jnp.sum(jnp.square(x), axis=axes, keepdims=True))
        return trust_ratio_from_norms(norm(p32), norm(g32),
                                      trust_coefficient, eps)
    return trust_ratio_from_norms(jnp.linalg.norm(p32),
                                  jnp.linalg.norm(g32),
                                  trust_coefficient, eps)


def trust_ratio_vector(updates: Any, params: Any,
                       trust_coefficient: float = TRUST_COEFFICIENT_DEFAULT,
                       eps: float = LARS_EPS_DEFAULT,
                       mask: Optional[MaskOrFn] = None) -> jnp.ndarray:
    """Per-layer-group trust ratios as one stacked fp32 vector.

    The optional stats output alongside :func:`scale_by_lars_trust_ratio`:
    the same per-leaf ratio the transform multiplies in, for every ADAPTED
    leaf (the default bias/BN exclusion mask), in flattened-tree order —
    the health vector reports its min/median/max (observability/health.py).
    Pure function of (updates, params): usable in-graph without touching
    optimizer state.  Defaults mirror the factory (trust_coef=1e-3, eps=0).
    NB ``updates`` must be whatever the transform actually sees at its
    position in the chain — :func:`lars` folds weight decay into the
    gradient FIRST, so callers replicate that fold-in (training/steps.py
    does) or the reported ratios drift from the applied ones.
    """
    m = _resolve_mask(mask, params)
    g_leaves = jax.tree_util.tree_leaves(updates)
    p_leaves = jax.tree_util.tree_leaves(params)
    m_leaves = jax.tree_util.tree_leaves(m)
    ratios = [_leaf_trust_ratio(g, p, trust_coefficient, eps,
                                use == PER_EXPERT)
              for g, p, use in zip(g_leaves, p_leaves, m_leaves) if use]
    if not ratios:       # nothing adapted (all-1D tree): ratio is identity
        return jnp.ones((1,), jnp.float32)
    if has_expert_axis(m):
        return jnp.concatenate([r.reshape(-1) for r in ratios])
    return jnp.stack(ratios)


def scale_by_lars_trust_ratio(trust_coefficient: float = TRUST_COEFFICIENT_DEFAULT,
                              eps: float = LARS_EPS_DEFAULT,
                              mask: Optional[MaskOrFn] = None
                              ) -> optax.GradientTransformation:
    """Step 2-3 above: multiply masked gradients by the trust ratio."""

    def init_fn(params):
        del params
        return LarsState()

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("LARS requires params")
        m = _resolve_mask(mask, params)

        def scale(g, p, use):
            if not use:
                return g
            ratio = _leaf_trust_ratio(g, p, trust_coefficient, eps,
                                      use == PER_EXPERT)
            return (g.astype(jnp.float32) * ratio).astype(g.dtype)

        updates = jax.tree_util.tree_map(scale, updates, params, m)
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)


def lars_weight_decay(weight_decay: float,
                      mask: Optional[MaskOrFn] = None
                      ) -> optax.GradientTransformation:
    """Step 1 above: fold wd into the gradient before adaptation
    (lars.py:96-97).  Masked like the adaptation — bias/BN undecayed."""
    if weight_decay <= 0.0:
        return optax.identity()
    as_bools = lambda m: jax.tree_util.tree_map(bool, m)
    return optax.add_decayed_weights(
        weight_decay,
        mask=(lambda p: as_bools(_resolve_mask(mask, p)))
        if mask is None or callable(mask) else as_bools(mask))


def lars(inner: optax.GradientTransformation,
         weight_decay: float = 0.0,
         trust_coefficient: float = TRUST_COEFFICIENT_DEFAULT,
         eps: float = LARS_EPS_DEFAULT,
         mask: Optional[MaskOrFn] = None) -> optax.GradientTransformation:
    """Compose wd fold-in + trust ratio + inner optimizer — the analog of
    ``LARS(optimizer=...)`` wrapping at reference main.py:339-340."""
    return optax.chain(
        lars_weight_decay(weight_decay, mask),
        scale_by_lars_trust_ratio(trust_coefficient, eps, mask),
        inner,
    )
