"""The benchmark's own weights: one jitted call from ``--seed``.

The program and the plain reference both start from these, so the
reference takes nothing the program has made.  The tree's STRUCTURE (names
and shapes) is the program's, read with ``jax.eval_shape`` or from a state
it has already built; the VALUES follow the recipe's initialisers by leaf
name: He-normal convolution kernels, LeCun-normal dense kernels, unit
scales, zero biases, the last normalisation scale of every residual block
zero (``zero_init_residual``), position embeddings N(0, 0.02), running
mean 0 / variance 1.  (Plain normals where flax truncates: an assumption
noted in each configuration file.)
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp

_BLOCK = re.compile(r"^stage\d+_block\d+$")


def _names(path) -> list:
    return [getattr(k, "key", getattr(k, "name", str(k))) for k in path]


def _zero_scale_paths(params) -> set:
    """``(block, bn)`` of the last BatchNorm in every residual block."""
    last = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = _names(path)
        for i, n in enumerate(names[:-1]):
            m = re.match(r"^bn(\d+)$", names[i + 1]) if _BLOCK.match(n) else None
            if m:
                key = tuple(names[:i + 1])
                last[key] = max(last.get(key, 0), int(m.group(1)))
    return {k + (f"bn{v}",) for k, v in last.items()}


def _leaf(names, shape, key, zero_scales) -> jnp.ndarray:
    leaf = names[-1]
    if leaf == "kernel":
        fan_in = math.prod(shape[:-1])
        gain = 2.0 if len(shape) == 4 else 1.0     # He for conv, LeCun dense
        return jax.random.normal(key, shape, jnp.float32) * math.sqrt(
            gain / fan_in)
    if leaf == "scale":
        zero = tuple(names[:-1]) in zero_scales
        return jnp.zeros(shape, jnp.float32) if zero else jnp.ones(
            shape, jnp.float32)
    if leaf == "pos_embedding":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if leaf in ("bias", "cls_token", "mean"):
        return jnp.zeros(shape, jnp.float32)
    if leaf == "var":
        return jnp.ones(shape, jnp.float32)
    raise KeyError(f"no initialiser for leaf {'/'.join(names)}")


def make_weights(like_params, like_stats, seed: int, *, copies: int = 1,
                 shardings=None, zero_init_residual: bool = True):
    """``(params x copies, batch_stats)`` on the device, in one jitted call.

    ``like_*`` give names and shapes only (arrays or ShapeDtypeStructs).
    ``copies=2`` also returns the EMA target as buffers of its own (the
    train step donates its state, so no two leaves may share a buffer).
    ``zero_init_residual=False`` gives every normalisation scale 1: a
    trained encoder, as a server holds, has no zero scales, and with them
    a served forward would skip every residual branch.
    """
    zero_scales = (_zero_scale_paths(like_params) if zero_init_residual
                   else set())
    p_leaves, p_def = jax.tree_util.tree_flatten_with_path(like_params)
    s_leaves, s_def = jax.tree_util.tree_flatten_with_path(like_stats)
    p_spec = [(_names(p), tuple(x.shape)) for p, x in p_leaves]
    s_spec = [(_names(p), tuple(x.shape)) for p, x in s_leaves]

    def build(key):
        def tree(spec, treedef, offset):
            return jax.tree_util.tree_unflatten(treedef, [
                _leaf(n, s, jax.random.fold_in(key, offset + i), zero_scales)
                for i, (n, s) in enumerate(spec)])
        out = [tree(p_spec, p_def, 0)]
        # a copy XLA cannot alias to the first output: built again
        out += [tree(p_spec, p_def, 0) for _ in range(copies - 1)]
        return tuple(out) + (tree(s_spec, s_def, len(p_spec)),)

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return jax.jit(build, out_shardings=shardings)(key)
