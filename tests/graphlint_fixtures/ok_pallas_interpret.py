"""GL109 near-miss: pallas_call WITH the interpret= fallback plumbed.

The in-tree pattern (ops/packed_attention.py, ops/fused_update.py): the
caller-facing wrapper resolves ``interpret`` from config/backend detection
and passes it through, so CPU environments run the identical kernel under
the Pallas interpreter.
"""
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def double(x, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        _double_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)
