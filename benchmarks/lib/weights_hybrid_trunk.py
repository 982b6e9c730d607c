"""The benchmark's seeded weights for a patterned decoder trunk (Gated
DeltaNet / gated attention / softmax-routed experts) under the BYOL heads:
one jitted call from ``--seed``, as ``lib/weights_decoder_trunk.py`` makes
them for the latent-attention trunk.

The tree's STRUCTURE (names and shapes) is the program's; the VALUES are
drawn here by leaf name:

* ``kernel`` (dense, ``(in, out)``), ``router`` and the convolution's taps
  ``conv`` (``(4, C)``: fan-in 4): LeCun normal, fan-in = rows;
* a leaf below ``experts`` (``(E, in, out)``): LeCun normal with the fan-in
  of ONE expert;
* ``embedding``: N(0, 0.02^2);
* ``A_log = log A``, ``A ~ U(0.016, 16)`` (the published code's ``U(0,
  16)``, kept off zero where its logarithm has no value); ``dt_bias``: 1;
* norm gains, drawn off their starting point so that ``1 + w`` and ``w``
  are told apart: a zero-centred ``scale`` ~ 0.1 N(0, 1); the DeltaNet's
  plain output gain (``gdn/scale``) ~ 1 + 0.1 N(0, 1); the heads'
  BatchNorm ``scale`` 1, ``bias`` 0;
* running mean 0 / variance 1.

No leaf of this trunk has a structurally zero gradient at these values.
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
    if leaf in ("kernel", "router", "conv") or "experts" in names[:-1]:
        return normal(math.sqrt(1.0 / shape[-2]))
    if leaf == "embedding":
        return normal(0.02)
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          0.016, 16.0))
    if leaf == "dt_bias":
        return jnp.ones(shape, jnp.float32)
    if leaf == "scale" and names[0] == "backbone":
        return normal(0.1) + (1.0 if names[-2] == "gdn" else 0.0)
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
