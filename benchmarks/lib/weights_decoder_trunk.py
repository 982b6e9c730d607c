"""The benchmark's seeded weights for a decoder trunk under the BYOL heads:
one jitted call from ``--seed``, as ``lib/weights.py`` makes them for the
image encoders.

The tree's STRUCTURE (names and shapes) is the program's; the VALUES are
drawn here by leaf name:

* ``kernel`` (dense, ``(in, out)``), the router and the hyper-connection
  maps ``phi_*``: LeCun normal, fan-in = rows;
* a leaf below ``experts`` (``(E, in, out)``): LeCun normal with the fan-in
  of ONE expert (its own ``in`` rows, not ``E x in``);
* ``embedding``: N(0, 0.02^2); norm ``scale``: 1; ``bias``: 0;
* ``e_score_correction_bias`` (the router's ``noaux_tc`` selection bias, a
  buffer nothing trains): 0.01 N(0, 1), held at that value;
* the hyper-connections, drawn AWAY from the symmetric starting point
  (``alpha`` 0.01, ``b_pre = b_post = 0``, ``b_res`` a large multiple of
  the identity), at which all streams stay copies of one another and the
  stream-to-stream map gets no gradient a comparison could read:
  ``alpha_* = 0.1``; ``b_pre``, ``b_post`` ~ 0.5 N(0, 1);
  ``b_res = 2 I + 0.5 N(0, 1)``;
* running mean 0 / variance 1.

Each of these is an assumption the configuration file lists.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _names(path) -> list:
    return [getattr(k, "key", getattr(k, "name", str(k))) for k in path]


def _leaf(names, shape, key) -> jnp.ndarray:
    leaf = names[-1]
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    if leaf == "kernel" or leaf == "router" or leaf.startswith("phi_") \
            or "experts" in names[:-1]:
        return normal(math.sqrt(1.0 / shape[-2]))
    if leaf == "embedding":
        return normal(0.02)
    if leaf == "e_score_correction_bias":
        return normal(0.01)
    if leaf.startswith("alpha_"):
        return jnp.full(shape, 0.1, jnp.float32)
    if leaf in ("b_pre", "b_post"):
        return normal(0.5)
    if leaf == "b_res":
        return 2.0 * jnp.eye(shape[0], dtype=jnp.float32) + normal(0.5)
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
