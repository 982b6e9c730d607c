"""The sum over a token's copies as a segment sum over the held rows.

``out[t] = sum of the rows that are token t's copies`` is the expert layer's
combine and the transpose of its dispatch (``models/decoder_trunk.py``).
The ``jax.numpy`` body there, ``_sum_copies(rows, pos, ok)``, gathers a whole
``(tokens, D)`` array a SLOT — k of them — although the sorted window holds
only ``cap`` rows: at 32,768 tokens, top-10 and 40,960 rows it fetched
327,680 rows of 4 KB to use at most 40,960, at 36–43 ns a row whatever the
row holds (PERF.md section 5, PR 32).  Here the cost follows the rows held:

- :func:`by_token` — once a window: a stable ``argsort`` of the rows' tokens
  (a row ``valid`` leaves out sorts last, under the token id ``tokens``) and,
  from the sorted ids, the WORK ITEMS of the kernel;
- ``rows[order]`` — ONE gather of ``cap`` rows, plain XLA: a token's copies
  are now neighbours, in expert order, and the rows of a block of
  ``BLOCK`` tokens are one contiguous range ``[start[b], start[b + 1])``;
- the kernel — for a token block, the aligned windows of ``WINDOW`` rows
  that cover its range: ``acc(BLOCK, D) += onehot(BLOCK, WINDOW) @
  rows(WINDOW, D)`` with ``onehot[i, p] = (token of row p == t0 + i)``, on
  the matrix unit, float32 accumulation, rounded ONCE to the rows' dtype.
  A row of a neighbouring block, or one ``valid`` left out, matches no token
  of the block and adds an exact zero: the windows need no mask.

Design (see /opt/skills/guides/pallas_guide.md; the work-item grid is the one
``jax.experimental.pallas.ops.tpu.megablox.gmm`` gives its group tiles):
- the grid is STATIC, ``tokens / BLOCK + cap / WINDOW`` items (a block's
  windows are its own but for the first, which it may share with the block
  before: never more), and which (block, window) an item is goes in as
  scalar prefetch, so the ``BlockSpec`` pipeline fetches the windows and no
  copy is written by hand.  The trip count is data, the shapes are not: no
  capacity, no dropped row, no overflow branch;
- items of one block are neighbours; the block's output stays resident
  across them and is written once, at its last item.  A block whose range is
  empty gets one item and writes zeros (whatever window it reads matches
  none of its tokens).  Two neighbouring items that read the same window —
  the end of one block, the start of the next — fetch it once;
- the items past the last real one repeat its block and window, and do
  nothing;
- ``1.0 x row`` is exact on the matrix unit (float32 operands multiply at
  ``Precision.HIGHEST``), so the addends are the ≤ k rows themselves, added
  in float32: only the ORDER of the additions differs from the ``jax.numpy``
  body (expert order, not slot order).

``interpret=True`` (default off-TPU) runs the same kernel under the Pallas
interpreter so CPU tests exercise identical code paths.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.common import LANES, VMEM_LIMIT_BYTES

BLOCK = 128         # tokens an output block
WINDOW = 256        # rows a fetched window
# Tiles read on the chip (PR 37, chains at the three cells' shapes, ms a
# call): 128 x 256 0.48 / 0.62 / 0.36, 256 x 256 0.53 / 0.70 / 0.39, 128 x 128
# 0.52 / 0.70 / 0.38, 512 x 512 0.68 / 0.85 / 0.55.
# At rows of 3,584 bf16 :func:`_vmem_bytes` counts 9 MiB of the 48 a program
# may ask for (``VMEM_LIMIT_BYTES``).

_FIRST, _LAST, _REAL = 1, 2, 4          # an item's flags


class ByToken(NamedTuple):
    """A window's rows in token order, and the kernel's work items."""

    order: jax.Array        # (cap,) the window's rows, sorted by token
    token: jax.Array        # (1, cap) their tokens; ``tokens`` = left out
    block: jax.Array        # (items,) the token block of an item
    window: jax.Array       # (items,) the window of rows it reads
    flags: jax.Array        # (items,) _FIRST | _LAST of its block, _REAL


def _vmem_bytes(dim: int, itemsize: int) -> int:
    """The blocks twice (double buffering), the float32 accumulator and a
    product beside it, the one-hot square and what it is compared from."""
    blocks = (WINDOW + BLOCK) * dim * itemsize + 8 * WINDOW * 4
    return (2 * blocks + 2 * 4 * BLOCK * dim
            + BLOCK * WINDOW * (2 * 4 + itemsize))


def supported(tokens: int, cap: int, dim: int, itemsize: int = 2) -> bool:
    """Shapes the kernel takes: whole token blocks and row windows, rows that
    fill whole 128-lane tiles, and a working set that fits."""
    return (tokens > 0 and tokens % BLOCK == 0 and cap > 0
            and cap % WINDOW == 0 and dim > 0 and dim % LANES == 0
            and _vmem_bytes(dim, itemsize) <= VMEM_LIMIT_BYTES)


def applies(tokens: int, copies: int, cap: int, dim: int,
            dtype=jnp.bfloat16, *, backend: Optional[str] = None) -> bool:
    """Whether the combine runs as one gather and the kernel — decided from
    what the code can see, never by a flag: the program lowers for a TPU, the
    shapes are ones the kernel takes, and the window is SHORTER than every
    copy (``cap < tokens x copies``).  Where the window is every copy, a
    gather a slot touches the same number of rows and the ``jax.numpy`` body
    stays."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and cap < tokens * copies
            and supported(tokens, cap, dim, jnp.dtype(dtype).itemsize))


def by_token(idx, valid, tokens: int) -> ByToken:
    """Token order of a window of ``cap`` sorted copies — ``idx (cap,)`` their
    tokens, ``valid (cap,)`` the rows that count — and the kernel's items:
    per token block the aligned windows over ``[start[b], start[b + 1])``,
    at least one."""
    cap, blocks, windows = idx.shape[0], tokens // BLOCK, idx.shape[0] // WINDOW
    key = jnp.where(valid, idx, tokens).astype(jnp.int32)
    order = jnp.argsort(key).astype(jnp.int32)               # stable
    token = key[order]
    # (every query against every element in one pass: a loop of log2(cap)
    # dependent steps is all latency on the chip)
    start = jnp.searchsorted(
        token, jnp.arange(0, tokens + 1, BLOCK, dtype=jnp.int32),
        method="compare_all").astype(jnp.int32)
    lo, hi = start[:-1], start[1:]
    first = jnp.minimum(lo // WINDOW, windows - 1)
    count = jnp.where(hi > lo, (hi - 1) // WINDOW - first + 1, 1)
    end = jnp.cumsum(count)
    item = jnp.arange(blocks + windows, dtype=jnp.int32)
    real = item < end[-1]
    block = jnp.minimum(
        jnp.searchsorted(end, item, side="right", method="compare_all"),
        blocks - 1).astype(jnp.int32)
    # an item past the last real one: the last block's last window again
    nth = jnp.minimum(item - (end - count)[block], count[block] - 1)
    flags = jnp.where(real, _REAL | jnp.where(nth == 0, _FIRST, 0)
                      | jnp.where(nth == count[block] - 1, _LAST, 0), 0)
    return ByToken(order, token[None, :], block,
                   (first[block] + nth).astype(jnp.int32),
                   flags.astype(jnp.int32))


def _kernel(block_ref, window_ref, flags_ref, token_ref, rows_ref, out_ref,
            acc_ref):
    item = pl.program_id(0)
    flags = flags_ref[item]
    first, last = (flags & _FIRST) != 0, (flags & _LAST) != 0

    @pl.when((flags & _REAL) != 0)
    def _():
        t0 = block_ref[item] * BLOCK
        mine = (jax.lax.broadcasted_iota(jnp.int32, (BLOCK, WINDOW), 0) + t0
                == token_ref[...])
        rows = rows_ref[...]
        exact = rows.dtype == jnp.float32
        part = jax.lax.dot_general(
            mine.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if exact else None,
            preferred_element_type=jnp.float32)

        # a block's one window is the common case: no pass over the
        # accumulator then
        @pl.when(first & last)
        def _():
            out_ref[...] = part.astype(out_ref.dtype)

        @pl.when(first & ~last)
        def _():
            acc_ref[...] = part

        @pl.when(~first & ~last)
        def _():
            acc_ref[...] += part

        @pl.when(~first & last)
        def _():
            out_ref[...] = (acc_ref[...] + part).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _call(tokens, interpret, rows, token, block, window, flags):
    """One ``pallas_call`` over the work items.  ``rows``: ``(cap, D)`` in
    token order.  Jitted so that a model's layers share one trace and
    lowering."""
    cap, d = rows.shape
    items = block.shape[0]
    itemsize = rows.dtype.itemsize
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(items,),
            in_specs=[
                pl.BlockSpec((1, WINDOW), lambda i, b, w, f: (0, w[i])),
                pl.BlockSpec((WINDOW, d), lambda i, b, w, f: (w[i], 0))],
            out_specs=pl.BlockSpec((BLOCK, d), lambda i, b, w, f: (b[i], 0)),
            scratch_shapes=[pltpu.VMEM((BLOCK, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * items * BLOCK * WINDOW * d, transcendentals=0,
            bytes_accessed=(cap + tokens) * d * itemsize + cap * 4),
        interpret=interpret,
        name="sum_copies",
    )(block, window, flags, token, rows)


def sum_copies(rows, plan: ByToken, tokens: int, *,
               interpret: Optional[bool] = None):
    """``out[t] = sum of rows[p] over the rows p that plan marks as token
    t's``, ``(tokens, D)`` in the rows' dtype: one gather of ``cap`` rows
    into token order, then the segment sum."""
    return _call(tokens, ops_common.resolve_interpret(interpret),
                 rows[plan.order], plan.token, plan.block, plan.window,
                 plan.flags)
