"""The benchmark's seeded weights for a sparse-attention decoder trunk
(grouped-query attention behind an indexer, softmax-routed experts, no
shared expert) under the BYOL heads: one jitted call from ``--seed``, as
``lib/weights_hybrid_trunk.py`` makes them for the patterned trunk.

The tree's STRUCTURE (names and shapes) is the program's; the VALUES are
drawn here by leaf name:

* ``kernel`` (dense, ``(in, out)`` — the indexer's three projections among
  them) and ``router``: LeCun normal, fan-in = rows;
* a leaf below ``experts`` (``(E, in, out)``): LeCun normal with the fan-in
  of ONE expert;
* ``embedding``: N(0, 1) — the scale of every term the layers add to the
  residual stream (LeCun-normal ``o`` and ``down`` kernels on unit-variance
  inputs), so that a token's own row stays the larger part of the stream the
  routers read, layer after layer.  (At N(0, 0.02^2), the checkpoint
  initialiser's value, the attention output drowned it from layer 1 on:
  every token looked alike to the deeper routers, which sent held experts
  1 to 8 rows of 32,768 where 2,048 are nominal; PERF.md section 6.);
* the trunk's norm gains (plain ``x^ w``): ``1 + 0.1 N(0, 1)``, off their
  starting point; the heads' BatchNorm ``scale`` 1, ``bias`` 0;
* running mean 0 / variance 1.

No leaf of this trunk has a structurally zero gradient at these values: the
indexer's leaves take theirs from its own loss.  Each of these is an
assumption the configuration file lists.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights_hybrid_trunk import _names


def _leaf(names, shape, key) -> jnp.ndarray:
    leaf = names[-1]
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    if leaf in ("kernel", "router") or "experts" in names[:-1]:
        return normal(math.sqrt(1.0 / shape[-2]))
    if leaf == "embedding":
        return normal(1.0)
    if leaf == "scale" and names[0] == "backbone":
        return 1.0 + normal(0.1)
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
