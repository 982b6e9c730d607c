"""Shared Pallas-kernel plumbing: interpret resolution + the shard_map wrapper.

Every in-tree kernel (ops/flash_attention.py, ops/packed_attention.py,
ops/fused_augment.py) follows the same two conventions, hoisted here so
they cannot drift per kernel:

1. **Interpret resolution** (:func:`resolve_interpret`): ``interpret=``
   defaults to "on iff no TPU backend", so CPU tier-1 and CI execute the
   REAL kernel code under the Pallas interpreter instead of skipping it —
   the discipline graphlint GL109 enforces tree-wide.
2. **shard_map wrapper** (:func:`shard_map_unchecked`): GSPMD cannot
   partition a ``pallas_call``, so every kernel that meets a multi-device
   mesh wraps itself in ``shard_map`` — through one helper, not a copy
   per kernel.
"""
from __future__ import annotations

from typing import Optional

import jax

# TPU vector-lane width.
LANES = 128


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
