"""The benchmark's seeded weights for a decoder-hybrid-decoder trunk (Mamba,
differential attention under a band, in full and as cross attention, gated
memory units, dense SwiGLU) under the BYOL heads: one jitted call from
``--seed``, as ``lib/weights_sparse_trunk.py`` makes them.

The tree's STRUCTURE (names and shapes) is the program's; the VALUES are
drawn here by leaf name:

* ``kernel`` (dense, ``(in, out)``) and a Mamba layer's ``taps`` (``(taps,
  channels)``): LeCun normal, fan-in = rows;
* ``embedding``: N(0, 1) — the scale of every term the layers add to the
  residual stream, as the other trunks' files draw it;
* a Mamba layer's own, as Mamba initialises them: ``A_log = log(1 .. N)`` a
  channel; ``dt_bias`` the inverse softplus of a step ``~ logU(1e-3, 0.1)``;
  ``D`` and ``conv_bias`` drawn OFF their starting point (``1 + 0.1 N(0,
  1)``, ``0.1 N(0, 1)``);
* differential attention's four ``lambda`` vectors: N(0, 0.1^2), the
  published initialiser;
* the trunk's LayerNorm gains and the sub-norm's: ``1 + 0.1 N(0, 1)``, its
  LayerNorm biases ``0.1 N(0, 1)``, off their starting point, so that no
  leaf has a structurally small gradient there; the heads' BatchNorm
  ``scale`` 1, ``bias`` 0;
* running mean 0 / variance 1.

Each of these is an assumption the configuration file lists.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights_hybrid_trunk import _names

DT_MIN, DT_MAX = 1e-3, 0.1


def _leaf(names, shape, key) -> jnp.ndarray:
    leaf, trunk = names[-1], names[0] == "backbone"
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    if leaf in ("kernel", "taps"):
        return normal(math.sqrt(1.0 / shape[-2]))
    if leaf == "embedding":
        return normal(1.0)
    if leaf == "A_log":
        return jnp.log(jnp.broadcast_to(
            jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape))
    if leaf == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (
            math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        return step + jnp.log(-jnp.expm1(-step))
    if leaf.startswith("lambda_") or leaf == "conv_bias":
        return normal(0.1)
    if leaf == "D" or (leaf == "scale" and trunk):
        return 1.0 + normal(0.1)
    if leaf == "bias" and trunk:
        return normal(0.1)
    if leaf in ("scale", "var"):
        return jnp.ones(shape, jnp.float32)
    if leaf in ("bias", "mean"):
        return jnp.zeros(shape, jnp.float32)
    raise KeyError(f"no initialiser for leaf {'/'.join(names)}")


def make_weights(like_params, like_stats, seed: int, *, copies: int = 1,
                 shardings=None):
    """``(params x copies, batch_stats)`` on the device, in one jitted call
    (``copies=2``: the EMA target as buffers of its own, because the train
    step donates its state)."""
    p_leaves, p_def = jax.tree_util.tree_flatten_with_path(like_params)
    s_leaves, s_def = jax.tree_util.tree_flatten_with_path(like_stats)
    p_spec = [(_names(p), tuple(x.shape)) for p, x in p_leaves]
    s_spec = [(_names(p), tuple(x.shape)) for p, x in s_leaves]

    def build(key):
        def tree(spec, treedef, offset):
            return jax.tree_util.tree_unflatten(treedef, [
                _leaf(n, s, jax.random.fold_in(key, offset + i))
                for i, (n, s) in enumerate(spec)])
        out = [tree(p_spec, p_def, 0) for _ in range(copies)]
        return tuple(out) + (tree(s_spec, s_def, len(p_spec)),)

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return jax.jit(build, out_shardings=shardings)(key)
