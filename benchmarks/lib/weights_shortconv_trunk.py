"""The benchmark's seeded weights for a short-convolution decoder trunk
(gated short convolutions beside plain grouped-query attention, leading
dense layers, sigmoid-routed experts with a selection bias and no shared
expert) under the BYOL heads: one jitted call from ``--seed``, as
``lib/weights_sparse_trunk.py`` makes them for the sparse-attention trunk,
whose ``make_weights`` this is with this trunk's rule for a leaf's VALUES:

* ``kernel`` (dense, ``(in, out)``), ``router`` and the convolution's taps
  ``conv`` (``(3, D)``: fan-in 3): LeCun normal, fan-in = rows;
* a leaf below ``experts`` (``(E, in, out)``): LeCun normal with the fan-in
  of ONE expert;
* ``embedding``: N(0, 1) — the scale of every term the layers add to the
  residual stream, so that a token's own row stays the larger part of the
  stream the routers read (PR 33 learned it: at N(0, 0.02^2) the deeper
  routers starved held experts);
* ``e_score_correction_bias`` (the router's selection bias, ``expert_bias``
  in the source: a buffer no gradient reaches): ``BIAS_STD`` N(0, 1), held at
  that value — NON-ZERO, so that a bias that leaked into the weights would
  show, and small beside the gaps between sigmoid scores, so that no held
  expert is starved or flooded;
* the trunk's norm gains (plain ``x^ w``): ``1 + 0.1 N(0, 1)``, off their
  starting point; the heads' BatchNorm ``scale`` 1, ``bias`` 0;
* running mean 0 / variance 1.

The only leaf with a structurally zero gradient is the selection bias, and
nothing adapts or decays it.  Each of these is an assumption the
configuration file lists.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_sparse_trunk

BIAS_STD = 0.01
_sparse_leaf = weights_sparse_trunk._leaf


def _leaf(names, shape, key) -> jnp.ndarray:
    leaf = names[-1]
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    if leaf == "conv":
        return normal(math.sqrt(1.0 / shape[0]))
    if leaf == "e_score_correction_bias":
        return normal(BIAS_STD)
    return _sparse_leaf(names, shape, key)


def make_weights(like_params, like_stats, seed: int, *, copies: int = 1,
                 shardings=None):
    """``(params x copies, batch_stats)`` on the device, in one jitted call
    (``copies=2``: the EMA target as buffers of its own, because the train
    step donates its state)."""
    weights_sparse_trunk._leaf = _leaf
    try:
        return weights_sparse_trunk.make_weights(
            like_params, like_stats, seed, copies=copies,
            shardings=shardings)
    finally:
        weights_sparse_trunk._leaf = _sparse_leaf
