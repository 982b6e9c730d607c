"""Shared Pallas-kernel plumbing: interpret resolution + grid sizing.

Every in-tree kernel (ops/flash_attention.py, ops/fused_update.py,
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
3. **Grid sizing** (:func:`resolve_block_rows` / :func:`fat_tile`): the
   interpreter pays per GRID STEP (each step re-stages its operands, so a
   fine grid is quadratic in buffer size — measured 0.75 s -> 0.06 s at
   1M elements when fused_update coarsened its interpreter grid), while
   compiled TPU kernels want VMEM-sized tiles.  ``resolve_block_rows`` is
   the (rows, 128)-layout instance fused_update ships; ``fat_tile`` is
   the bare few-fat-tiles heuristic for kernels gridding over other units
   (fused_augment grids over images).

The numeric behavior here is regression-pinned by
tests/test_fused_update.py::TestSegmentMap::test_resolve_block_rows —
moving the helpers must not move the grids.
"""
from __future__ import annotations

from typing import Optional

import jax

# TPU vector-lane width: flat buffers are viewed as (rows, LANES).
LANES = 128
# Compiled-mode tile height for (rows, 128) fp32 buffers: 256 x 128 x 4 B
# = 128 KiB per operand — seven operands stay under ~1 MiB of the ~16 MiB
# VMEM (the fused_update apply pass sizing).
TPU_BLOCK_ROWS = 256
# Interpreter grids aim for ~this many steps regardless of buffer size.
INTERPRET_GRID = 16


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


def fat_tile(count: int, *, align: int = 1,
             target_steps: int = INTERPRET_GRID) -> int:
    """Tile size giving ~``target_steps`` grid steps over ``count`` units,
    rounded up to ``align`` (8 = the fp32 sublane count for row-tiled
    buffers; 1 for unit grids like images)."""
    target = -(-count // target_steps)                      # ceil
    return max(align, -(-target // align) * align)


def resolve_block_rows(num_rows: int, interpret: bool,
                       block_rows: Optional[int] = None) -> int:
    """Grid tile height for (rows, 128) buffers: explicit override, else
    VMEM-sized on TPU and ~:data:`INTERPRET_GRID` fat tiles under the
    interpreter (multiple of 8, the fp32 sublane count)."""
    if block_rows is not None:
        if block_rows % 8:
            raise ValueError(f"block_rows {block_rows} not a multiple of 8")
        return block_rows
    if not interpret:
        return TPU_BLOCK_ROWS
    return fat_tile(num_rows, align=8)
