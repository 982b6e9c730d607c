"""Shared Pallas-kernel plumbing: interpret resolution, the shard_map
wrapper and the facts the kernels share.

Every in-tree kernel (ops/packed_attention.py, ops/causal_attention.py,
ops/fused_augment.py, ...) follows the same conventions, hoisted here so
they cannot drift per kernel:

1. **Interpret resolution** (:func:`resolve_interpret`): ``interpret=``
   defaults to "on iff no TPU backend", so CPU tier-1 and CI execute the
   REAL kernel code under the Pallas interpreter instead of skipping it —
   the discipline graphlint GL109 enforces tree-wide.
2. **shard_map wrapper** (:func:`shard_map_unchecked`): GSPMD cannot
   partition a ``pallas_call``, so every kernel that meets a multi-device
   mesh wraps itself in ``shard_map`` — through one helper, not a copy
   per kernel.
3. **One number, one name**: the VMEM a program may ask for, the masked
   score, the three contractions of a 2-D product and the product itself.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# TPU vector-lane width.
LANES = 128

# What a program may take of VMEM (a v5e holds 128 MiB, the compiler's
# default scope is 16): the largest count a cell has is the tiled causal
# kernels' 39 MiB backward, at heads of 256 and 8 query heads a key head.
VMEM_LIMIT_BYTES = 48 * 2 ** 20

# A score no softmax sees.  Finite: exp(MASKED - max) is 0, never inf - inf,
# and a float32 score added to it is lost whole under its rounding.
MASKED = -1e30

NT = ((1,), (1,))       # a @ b^T
NN = ((1,), (0,))       # a @ b
TN = ((0,), (0,))       # a^T @ b


def dot(a, b, dims):
    """A 2-D product, operands as they are, float32 accumulation; float32
    operands at full precision."""
    exact = a.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret off-TPU (tier-1/CI run the real kernel under
    the Pallas interpreter), explicit bool wins."""
    return (jax.default_backend() != "tpu" if interpret is None
            else interpret)


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off —
    pallas_call has no replication rule, and every cross-shard value in
    the in-tree kernels (and in ring attention) is an explicit
    collective."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
