"""Tiled causal grouped-query attention — forward and backward kernels.

``attend(q, k, v, scale=, block=, selected=, shared=)`` is the kernel
lowering of both blockwise cores of ops/attention.py: of
``blockwise_causal_attention`` (every causal key) and, with ``selected``, of
``selected_attention`` (each query's softmax over the causal keys
``selected`` marks for it, the same set for every head).  Both return the
output and each row's log-sum-exp.  It exists because of what the compiler
does with the ``jax.numpy`` form (PERF.md section 5, PR 33 and PR 38): per
block pair the scores, the weights and, backward, ``d_weights`` and
``d_scores`` were each a whole float32 ``(B, Hkv, G, block, block)`` array
in HBM — 268 MB at the published sizes, some 650 GB a step for 27 TFLOP of
products under a selection, 159 of the plain core's 183 ms a step.  Here a
tile's squares live and die in VMEM.  Still MASKED-DENSE: every tile on or
under the diagonal is formed whatever it keeps, a tile above it never.

Design (see /opt/skills/guides/pallas_guide.md):
- grid ``(B, Hkv / n, causal pair)``: ``causal_pairs`` goes in as scalar
  prefetch and the PAIR is the innermost axis, so the grid has no step above
  the diagonal and the block index maps read ``i, j`` of a step from SMEM
  (pair ``(i, j)`` is tile ``i (i + 1) / 2 + j`` of ops/attention.py's TILE
  layout);
- a program holds ``n`` key heads, each with its ``G`` query heads: ``k`` and
  ``v`` are fetched once for a key head's ``G``, the ``n G`` heads traced
  side by side (a Python loop), so that one head's products overlap
  another's passes: 20.5 -> 18.6 ms a call of 8 sequences when ``G`` = 8
  were so held (chip runs, PR 34).  ``n`` is 1 wherever ``G`` fills a
  program — four heads and more run at one pace (PERF.md section 5) — and
  the blocks' head dimension is then squeezed away, the kernels those of
  PR 34–45 to the byte.  Where ``G`` alone leaves a program short
  (:func:`key_heads`: latent attention has ONE query head a key head, a
  lone ``[512, 512]`` tile a step whose chain of products and passes has
  nothing to overlap with) ``n`` is the largest divisor of ``Hkv`` with ``n
  G <= 4`` whose blocks fit VMEM by :func:`_vmem_bytes`, forward and
  backward each for itself (the backward's resident ``d_k, d_v`` grow with
  ``n``): every block's head extent grows from 1 to ``n`` through its
  ``BlockSpec``, nothing is reshaped or copied outside, and what has no
  head axis — the shared key, the pair's bias — is fetched or made ONCE for
  the ``n G`` heads.  A shape takes it in every call or in none: no flag
  (what the chip said is in PERF.md section 6, PR 49);
- what a tile masks is an additive float32 bias, ``0`` where the query sees
  the key, ``-1e30`` where not, which in float32 IS ``where(visible, score,
  -1e30)`` (a score is lost whole under ``1e30``'s rounding).  Where it
  comes from is decided while the kernel is TRACED, from whether there is a
  selection.  With one, ``selected`` arrives in the tile layout, ``(P, B,
  block, block)``, and a pair's tile by its own ``BlockSpec``: it has no
  head axis, so it crosses HBM once a key head, as ``int8`` (``bool``
  operands lower badly), and becomes the bias once a program.  Without one
  there is no mask operand: only a tile ON the diagonal (``j == i``) masks
  anything, and its mask is the same lower triangle every time, made from
  two iotas on those steps alone; a tile under the diagonal adds nothing;
- both kernels hold a tile's squares TRANSPOSED, ``[keys, queries]``: what
  is taken over a query's keys — the running max and sum — then runs down
  the sublanes, an elementwise pass of the vector unit, and a query's
  statistics are lane rows broadcast down the sublanes.  With scores
  ``[queries, keys]`` the forward made 128 cross-lane reductions a head
  and tile and took 18.6 ms a call; transposed 11.6 (PR 34);
- forward: running max, sum and the float32 accumulator of every head stay
  in VMEM scratch across the key blocks of a query block (``j = 0 .. i``:
  the innermost axis walks them in order), the output and the log-sum-exp
  are written at ``j == i``.  Only ``P V`` contracts the leading axis, and
  the accumulator ``[queries, Dv]`` is rescaled by the statistics' row
  turned into a column.  A row none of whose keys in a tile is kept
  carries ``-1e30`` as its max, weighs that tile's keys 1 each, and loses
  all of it to the ``exp(old max - new max) = 0`` of the first kept key —
  the arithmetic of ``ops/attention._selected_fwd``;
- backward: ONE kernel, five products a tile (the usual pair of kernels
  recomputes the scores in each: seven), no reduction at all: the rows'
  log-sum-exp and ``delta = rowsum(dO . O)`` (made outside: one fused pass
  over ``dO, O``) come in as lane rows, ``d_v = P^T dO`` and ``d_k = dS^T
  q`` are plain products, only ``d_q = dS k`` contracts the leading axis —
  16.4 ms a call, 96% of the matrix unit's peak (PR 34).  ``d_q`` of a
  query block accumulates in scratch over its key blocks and is rounded at
  ``j == i``; ``d_k, d_v`` of ONE key head's whole sequence stay resident as
  the kernel's float32 output blocks ``(S, D)``, ``(S, Dv)`` across all its
  pairs — summed there over the ``G`` query heads and the query blocks — and
  are rounded once outside.  Those blocks are what bounds the sequence
  (:func:`supported`): 16 bytes a token and lane column;
- head widths.  A block's last dimension is the array's whole head: 128 and
  256 fill lane tiles, 64 is half of one and is PADDED to a tile — in VMEM
  (:func:`_vmem_bytes` counts 128) and, by the TPU's tiled layout
  ``T(8,128)(2,1)``, in HBM too, where the ``jax.numpy`` body's arrays were
  as wide.  At 64 the products half-fill the 128-deep matrix unit: a tile
  costs what it costs at 128;
- a key width and a value width (PR 40).  ``q, k`` are ``dim`` wide, ``v``
  and the output ``vdim``; each follows the rule above alone (latent
  attention: 128 + 64 against 128).  And a SHARED part: ``attend(..,
  shared=(q_s, k_s))`` adds ``q_s k_s^T`` to a tile's scores, ``q_s (B, Hkv,
  G, S, r)`` a head's own, ``k_s (B, S, r)`` ONE for every key head (latent
  attention's rotary key) — a second product a tile, 64 deep, instead of a
  192-wide head padded to two lane tiles with that key copied to every head
  in HBM (what the chip said of the two is in PERF.md section 6, PR 40).
  Backward ``d_q_s`` is the head's own and ``d_k_s`` the SUM over every head:
  its float32 ``(S, r)`` block stays resident over a sequence's heads and
  pairs — added to by the ``n`` heads of a program, then by the next
  program's — so the head axis of that grid is walked in order;
- bf16 (the input dtype's) operands, float32 accumulation and statistics, the
  weights rounded before ``P V`` and ``d_scores`` before its products, as
  the ``jax.numpy`` bodies do; float32 inputs multiply at
  ``Precision.HIGHEST``.  Residuals ``q, k, v, out, lse`` (and the mask and
  the shared pair).

``interpret=True`` (default off-TPU) runs the same kernels under the Pallas
interpreter so CPU tests exercise identical code paths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byol_tpu.ops.attention import (BOUNDS, FULL, NOT_AFTER, VISIBLE,
                                    WITHIN, TilePairs, causal_tiles)
from byol_tpu.ops.common import (LANES, MASKED, NN, NT, TN, VMEM_LIMIT_BYTES,
                                 dot, resolve_interpret)


def _vmem_bytes(block: int, dim: int, seq_len: int, group: int,
                itemsize: int, forward: bool, *, vdim: Optional[int] = None,
                shared: int = 0, selected: bool = False,
                heads: int = 1) -> int:
    """A kernel's blocks twice (double buffering), its scratch and the
    float32 squares of the head in hand, at a key width ``dim`` (+
    ``shared``) and a value width ``vdim``, ``heads`` key heads a program
    (the shared key, its cotangent and the mask have no head axis: once); a
    width that does not fill its last 128 lanes takes the whole lane tile of
    VMEM; ``selected``: a pair's int8 mask among the blocks."""
    tiles = lambda d: -(-d // LANES) * LANES
    own_lanes, shared_lanes = tiles(dim), tiles(shared)
    value_lanes = tiles(dim if vdim is None else vdim)
    group *= heads                        # the query heads of a program
    q_rows = group * block * (own_lanes + shared_lanes)
    o_rows = group * block * value_lanes
    slab_lanes = heads * (own_lanes + value_lanes) + shared_lanes
    slabs = (block * slab_lanes * itemsize                  # k, v (, k_s)
             + (block * block if selected else 0))          # (, the mask)
    square = 4 * block * block       # one float32 (block, block) value
    if forward:
        blocks = (q_rows + o_rows) * itemsize + slabs \
            + 4 * group * block                             # q, o; lse
        scratch = 4 * o_rows + 2 * 4 * group * block        # acc; stats
        live = 3                          # scores, weights, their bf16 copy
    else:
        blocks = ((2 * q_rows + o_rows) * itemsize + slabs  # q, dq; dO
                  + 8 * group * block                       # lse, delta
                  + 4 * seq_len * slab_lanes)               # d_k, d_v,
        scratch = 4 * q_rows
        live = 5                          # ... and d_weights, d_scores
    return 2 * blocks + scratch + (1 + live) * square     # 1: the bias


# The heads a program wants side by side: the smallest group that runs at the
# grouped pace (PERF.md section 5: 1.26 us a head-tile at G = 4, 1.91 at 1).
HEADS_A_PROGRAM = 4


def key_heads(block: int, dim: int, seq_len: int, group: int, kv_heads: int,
              itemsize: int, forward: bool, *, vdim: Optional[int] = None,
              shared: int = 0, selected: bool = False) -> int:
    """How many KEY heads — each with its ``group`` query heads — a program
    of the forward (the backward) kernel holds: where the group alone leaves
    a program short of :data:`HEADS_A_PROGRAM` heads, the largest divisor
    ``n`` of ``kv_heads`` with ``n * group`` within it whose
    :func:`_vmem_bytes` fits; 1 for every group of 4 and more."""
    fits = lambda n: _vmem_bytes(
        block, dim, seq_len, group, itemsize, forward, vdim=vdim,
        shared=shared, selected=selected, heads=n) <= VMEM_LIMIT_BYTES
    return next((n for n in range(HEADS_A_PROGRAM // group, 1, -1)
                 if kv_heads % n == 0 and fits(n)), 1)


def _width_ok(dim: int) -> bool:
    """A head fills whole lane tiles or exactly half of one (64: what
    compiles, tests/test_tpu_compile.py)."""
    return dim > 0 and (dim % LANES == 0 or 2 * dim == LANES)


def supported(block: int, dim: int, seq_len: int, group: int = 1,
              itemsize: int = 2, *, vdim: Optional[int] = None,
              shared: int = 0, selected: bool = False) -> bool:
    """Shapes the kernels take: a block's tokens fill whole 128-lane tiles,
    each of the key width, the value width and the shared part (0 = none)
    one :func:`_width_ok` takes, whole blocks, and the backward's working
    set — the float32 ``d_k, d_v`` of one key head's sequence among it —
    fits."""
    vdim = dim if vdim is None else vdim
    return (block > 0 and block % LANES == 0
            and _width_ok(dim) and _width_ok(vdim)
            and (shared == 0 or _width_ok(shared))
            and seq_len > 0 and seq_len % block == 0 and group > 0
            and max(_vmem_bytes(block, dim, seq_len, group, itemsize, fwd,
                                vdim=vdim, shared=shared, selected=selected)
                    for fwd in (True, False)) <= VMEM_LIMIT_BYTES)


def applies(block: int, dim: int, seq_len: int, heads: int, kv_heads: int,
            dtype=jnp.bfloat16, *, vdim: Optional[int] = None,
            shared: int = 0, selected: bool = False,
            backend: Optional[str] = None) -> bool:
    """Whether ``blockwise_causal_attention`` (``selected``:
    ``selected_attention``) runs as the kernels — decided from what the code
    can see, never by a flag: the program lowers for a TPU, the query heads
    share the key heads evenly and the shapes (key width ``dim``, value
    width ``vdim``, a ``shared`` part or none) are ones the kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and kv_heads > 0 and heads % kv_heads == 0
            and supported(block, dim, seq_len, heads // kv_heads,
                          jnp.dtype(dtype).itemsize, vdim=vdim,
                          shared=shared, selected=selected))


# ---- the kernels -----------------------------------------------------------

FIRST, LAST = 4, 8      # beside a pair's kind in the third prefetched array


def _flags(tiles: TilePairs):
    """What a step of the grid is — its pair's kind, whether it is its query
    tile's first pair, its last — as one int a pair, or None where the list
    is the lower triangle (:func:`causal_tiles`): there the pair's own ``(i,
    j)`` say all three (``j == 0``, ``j == i``, ``NOT_AFTER`` at ``j ==
    i``), and the kernels read them from it."""
    q_of = tiles.q_of
    if tiles == causal_tiles(max(q_of) + 1):
        return None
    first = [n == 0 or q_of[n - 1] != i for n, i in enumerate(q_of)]
    last = first[1:] + [True]
    # a WITHIN pair's bounds come beside the flags: any masked kind does
    return tuple((NOT_AFTER if kind == WITHIN else kind)
                 | FIRST * a | LAST * z
                 for kind, a, z in zip(tiles.kind, first, last))


def _step(pair, q_of_ref, k_of_ref, flags_ref, bounds_refs=None):
    """``j, first, last, when, bounds``: the step's key tile, and as thunks
    (each use traces its own comparison) whether its pair is its query
    tile's first, its last, and ``when``: which of the tile's TWO bodies is
    the pair's — ``FULL``, or masked.  On the lower triangle the masked kind
    is known while the kernel is traced (``NOT_AFTER``: ``bounds`` None);
    elsewhere it is the pair's own and comes as ``bounds``, two scalars
    ``lo <= beta(query) - beta(key) <= hi`` — ONE masked body whatever the
    kinds in the list: a body a kind made the backward, five products a head
    each, 47.5 ms a call where this one takes 19.2 (PERF.md section 6).  A
    list with ``WITHIN`` pairs (a band) brings every pair's two scalars as
    two more prefetched arrays (``bounds_refs``)."""
    i, j = q_of_ref[pair], k_of_ref[pair]
    if flags_ref is None:            # the lower triangle: see _flags
        return j, lambda: j == 0, lambda: j == i, {
            FULL: lambda: j < i, NOT_AFTER: lambda: j == i}, None
    flags = flags_ref[pair]
    kind = flags & 3
    if bounds_refs is not None:
        lo, hi = (ref[pair] for ref in bounds_refs)
    else:
        lo, hi = (sum(jnp.where(kind == k, bounds[n], 0)
                      for k, bounds in BOUNDS.items()) for n in (0, 1))
    return (j, lambda: (flags & FIRST) != 0, lambda: (flags & LAST) != 0,
            {FULL: lambda: kind == FULL, None: lambda: kind != FULL},
            (lo, hi))


def _block_of(row, span: int):
    """The block of ``span`` rows a row lies in: a shift where ``span`` is a
    power of two (the vector unit has no integer division)."""
    if span == 1:
        return row
    shift = span.bit_length() - 1
    return row >> shift if 1 << shift == span else row // span


def _with_the_pairs_bias(when, bounds, span, keep_ref, bias_ref, tile):
    """``tile(bias)`` for the step's pair, ``bias`` a ``(bk, bq)`` float32
    ref, 0 where the query sees the key and ``MASKED`` where not, or None
    where it sees them all.  With a selection (``keep_ref (bq, bk)`` int8:
    1 = kept) every pair has one, its tile turned ``[keys, queries]``.
    Without (None) the pair's KIND says (:func:`_step`): a ``FULL`` tile
    adds nothing, any other compares two iotas — a key's row and a query's
    column of the tile, in blocks of ``span`` — on those steps alone."""
    if keep_ref is not None:
        bias_ref[...] = ((1.0 - keep_ref[...].astype(jnp.float32))
                         * MASKED).T
        return tile(bias_ref)
    for kind, here in when.items():
        if kind == FULL:
            pl.when(here())(lambda: tile(None))
            continue

        @pl.when(here())
        def _masked(kind=kind):
            key, query = (_block_of(jax.lax.broadcasted_iota(
                jnp.int32, bias_ref.shape, axis), span) for axis in (0, 1))
            if bounds is None:
                visible = VISIBLE[kind](key, query)
            else:
                ahead = query - key
                visible = (ahead >= bounds[0]) & (ahead <= bounds[1])
            bias_ref[...] = jnp.where(visible, 0.0, MASKED)
            tile(bias_ref)


def _scores(k_ref, q, scale, bias, shared=None, where=...):
    """``(bk, bq)`` float32; ``shared``: the head's ``q_s`` and the ``k_s``
    ref, whose product is the scores' second term; ``where``: the key
    head's block in ``k_ref``."""
    scores = dot(k_ref[where], q, NT)
    if shared is not None:
        q_s, ks_ref = shared
        scores = scores + dot(ks_ref[...], q_s, NT)
    scores = scores * scale
    return scores if bias is None else scores + bias[...]


def _in_key_head(heads: int):
    """``at(kh, *index)``: ``index`` into key head ``kh``'s part of a block
    or scratch ref — the index itself where a program holds ONE key head (a
    ref's head dimension is squeezed away), else behind the head's own (ONE
    index, not a view of a view: Mosaic slices no 64-wide ref)."""
    return lambda kh, *index: index if heads == 1 else (kh,) + index


def _taker(refs):
    """``take(n, present=True)``: the next ``n`` of a kernel's refs, or
    ``n`` Nones for operands this call does not have."""
    refs = iter(refs)
    return lambda n, present=True: [
        next(refs) if present else None for _ in range(n)]


def _fwd_kernel(q_of_ref, k_of_ref, *refs, scale: float, selected: bool,
                shared: bool, flagged: bool, span: int, bounded: bool = False,
                heads: int = 1):
    """Scores ``[keys, queries]``.  Refs: with a list that is not the lower
    triangle its ``flags`` and, ``bounded``, its pairs' ``lo`` and ``hi``
    (scalar prefetch); ``q (G, bq, D)``; ``k (bk, D)``;
    ``v (bk, Dv)``; with a selection ``keep (bq, bk)`` int8; with a shared
    part ``q_s (G, bq, r)``, ``k_s (bk, r)``; ``o (G, bq, Dv)``; ``lse (G,
    bq)``; scratch: every head's running max and sum, a lane row a head,
    ``(G, bq)``, the float32 accumulators ``(G, bq, Dv)`` and a tile's
    bias.  With ``heads`` > 1 key heads a program every one of them but
    ``keep``, ``k_s`` and the bias has that many as its leading axis."""
    take = _taker(refs)
    flags_ref, = take(1, flagged)
    bounds_refs = take(2) if bounded else None
    q_ref, k_ref, v_ref = take(3)
    keep_ref, = take(1, selected)
    qs_ref, ks_ref = take(2, shared)
    o_ref, lse_ref, top_ref, total_ref, acc_ref, bias_ref = take(6)
    _, first, last, when, bounds = _step(pl.program_id(2), q_of_ref,
                                         k_of_ref, flags_ref, bounds_refs)
    group, _, dim = acc_ref.shape[-3:]
    at = _in_key_head(heads)

    @pl.when(first())
    def _start():
        top_ref[...] = jnp.full_like(top_ref, MASKED)
        total_ref[...] = jnp.zeros_like(total_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def column(row):
        """``(1, bq)`` -> ``(bq, Dv)``, a row's value on every lane: its
        broadcast down a lane tile's worth of sublanes, turned."""
        lanes = max(dim, LANES)
        return jnp.broadcast_to(row, (lanes, row.shape[1])).T[:, :dim]

    def head(kh, h, bias):
        own, whole = at(kh, h), at(kh, ...)
        row = at(kh, pl.ds(h, 1), slice(None))
        scores = _scores(k_ref, q_ref[own], scale, bias,
                         (qs_ref[own], ks_ref) if shared else None, whole)
        top = top_ref[row]
        new_top = jnp.maximum(top, jnp.max(scores, axis=0, keepdims=True))
        weights = jnp.exp(scores - new_top)
        keep = jnp.exp(top - new_top)
        total_ref[row] = total_ref[row] * keep + jnp.sum(
            weights, axis=0, keepdims=True)
        top_ref[row] = new_top
        acc_ref[own] = acc_ref[own] * column(keep) + dot(
            weights.astype(v_ref.dtype), v_ref[whole], TN)

    def tile(bias):
        for kh in range(heads):     # side by side: the module docstring
            for h in range(group):
                head(kh, h, bias)

    _with_the_pairs_bias(when, bounds, span, keep_ref, bias_ref, tile)

    @pl.when(last())
    def _finish():
        lse_ref[...] = top_ref[...] + jnp.log(total_ref[...])
        for kh in range(heads):
            for h in range(group):
                o_ref[at(kh, h)] = (acc_ref[at(kh, h)] / column(
                    total_ref[at(kh, slice(h, h + 1), slice(None))])).astype(
                        o_ref.dtype)


def _bwd_kernel(q_of_ref, k_of_ref, *refs, scale: float, selected: bool,
                shared: bool, flagged: bool, span: int, bounded: bool = False,
                heads: int = 1):
    """Everything ``[keys, queries]``.  Refs: with a list that is not the
    lower triangle its ``flags``; ``q, dq (G, bq, D)``; ``dO (G,
    bq, Dv)``; ``k (bk, D)``; ``v (bk, Dv)``; with a selection ``keep (bq,
    bk)`` int8; ``lse, delta (G, bq)``; ``dk (S, D)``, ``dv (S, Dv)``
    float32, one key head's, resident over all its pairs; with a shared part
    ``q_s, dq_s (G, bq, r)``, ``k_s (bk, r)`` and ``dk_s (S, r)`` float32,
    ONE SEQUENCE's, resident over all its heads and pairs; scratch: the
    float32 ``dq`` (and ``dq_s``) of the query block and a tile's bias.  With
    ``heads`` > 1 key heads a program every one of them but ``keep``, ``k_s``,
    ``dk_s`` and the bias has that many as its leading axis."""
    take = _taker(refs)
    flags_ref, = take(1, flagged)
    bounds_refs = take(2) if bounded else None
    q_ref, k_ref, v_ref = take(3)
    keep_ref, = take(1, selected)
    qs_ref, ks_ref = take(2, shared)
    lse_ref, delta_ref, do_ref, dq_ref, dk_ref, dv_ref = take(6)
    dqs_ref, dks_ref = take(2, shared)
    dq_acc_ref, = take(1)
    dqs_acc_ref, = take(1, shared)
    bias_ref, = take(1)
    pair = pl.program_id(2)
    j, first, last, when, bounds = _step(pair, q_of_ref, k_of_ref, flags_ref,
                                         bounds_refs)
    group, bk = q_ref.shape[-3], k_ref.shape[-2]
    keys = pl.ds(pl.multiple_of(j * bk, bk), bk)
    at = _in_key_head(heads)

    @pl.when(pair == 0)
    def _start():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    if shared:
        @pl.when((pair == 0) & (pl.program_id(1) == 0))
        def _next_sequence():
            dks_ref[...] = jnp.zeros_like(dks_ref)

    @pl.when(first())
    def _next_rows():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        if shared:
            dqs_acc_ref[...] = jnp.zeros_like(dqs_acc_ref)

    def head(kh, h, bias):
        own, whole = at(kh, h), at(kh, ...)
        row, rows = at(kh, pl.ds(h, 1), slice(None)), at(kh, keys, slice(None))
        q, d_out = q_ref[own], do_ref[own]
        lse, delta = lse_ref[row], delta_ref[row]
        weights = jnp.exp(_scores(
            k_ref, q, scale, bias,
            (qs_ref[own], ks_ref) if shared else None, whole) - lse)
        dv_ref[rows] += dot(weights.astype(d_out.dtype), d_out, NN)
        d_weights = dot(v_ref[whole], d_out, NT)
        d_scores = (weights * (d_weights - delta) * scale).astype(q.dtype)
        dk_ref[rows] += dot(d_scores, q, NN)
        dq_acc_ref[own] += dot(d_scores, k_ref[whole], TN)
        if shared:
            dks_ref[keys, :] += dot(d_scores, qs_ref[own], NN)
            dqs_acc_ref[own] += dot(d_scores, ks_ref[...], TN)

    def tile(bias):
        for kh in range(heads):     # side by side: the module docstring
            for h in range(group):
                head(kh, h, bias)

    _with_the_pairs_bias(when, bounds, span, keep_ref, bias_ref, tile)

    @pl.when(last())
    def _finish():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)
        if shared:
            dqs_ref[...] = dqs_acc_ref[...].astype(dqs_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _call(forward, scale, block, interpret, tiles, q, k, v, keep, shared,
          *rest):
    """One ``pallas_call`` over ``(batch, key heads, pair of tiles)``, ``n``
    key heads a program (:func:`key_heads`).  ``q``:
    ``(B, Hkv, G, S, D)``; ``k``: ``(B, Hkv, S, D)``; ``v``: ``(B, Hkv, S,
    Dv)``; ``keep``: None or ``(P, B, block, block)`` int8; ``shared``:
    ``()`` or ``(q_s (B, Hkv, G, S, r), k_s (B, S, r))``; ``rest``, backward:
    ``lse, delta (B, Hkv, G, S)`` float32 and ``dO``, like the output ``(B,
    Hkv, G, S, Dv)``.  Jitted so that a model's passes share one trace and
    lowering of each kernel."""
    b, hkv, g, s, d = q.shape
    dv = v.shape[-1]
    selected, r = keep is not None, shared[0].shape[-1] if shared else 0
    heads = key_heads(block, d, s, g, hkv, q.dtype.itemsize, forward,
                      vdim=dv, shared=r, selected=selected)
    # a block's extent over the key heads: squeezed away where it is one
    of_heads = None if heads == 1 else heads
    flags = _flags(tiles)
    if selected and flags is not None:
        raise ValueError("a selection comes in the lower triangle's layout")
    lists = (tiles.q_of, tiles.k_of) + (() if flags is None else (flags,))
    if tiles.bounds:                    # a band: every pair's lo and hi
        lists += tuple(zip(*tiles.bounds))
    # index maps: (batch, n key heads, pair, q_of, k_of[, flags[, lo, hi]])
    rows = lambda w: pl.BlockSpec(
        (None, of_heads, g, block, w),
        lambda n, h, p, qo, ko, *_: (n, h, 0, qo[p], 0))
    slab = lambda w: pl.BlockSpec(
        (None, of_heads, block, w),
        lambda n, h, p, qo, ko, *_: (n, h, ko[p], 0))
    # the pair's mask: no head axis
    tile = pl.BlockSpec((None, None, block, block),
                        lambda n, h, p, qo, ko, *_: (p, n, 0, 0))
    # the shared key and its cotangent: no head axis
    slab_s = pl.BlockSpec((None, block, r),
                          lambda n, h, p, qo, ko, *_: (n, ko[p], 0))
    row_stat = pl.BlockSpec((None, of_heads, g, block),
                            lambda n, h, p, qo, ko, *_: (n, h, 0, qo[p]))
    stat = jax.ShapeDtypeStruct((b, hkv, g, s), jnp.float32)
    like = lambda w: jax.ShapeDtypeStruct((b, hkv, g, s, w), q.dtype)
    square = pltpu.VMEM((block, block), jnp.float32)
    a_head = lambda *shape: pltpu.VMEM(   # scratch: laid out as the blocks
        (() if heads == 1 else (heads,)) + shape, jnp.float32)
    per_head = lambda w: a_head(g, block, w)
    in_specs = [rows(d), slab(d), slab(dv)] + (
        [tile] if selected else []) + ([rows(r), slab_s] if shared else [])
    stem = "selected_attention" if selected else "causal_attention"
    if forward:
        kernel, name = _fwd_kernel, stem + "_fwd"
        outs = [(rows(dv), like(dv)), (row_stat, stat)]
        stats = a_head(g, block)
        scratch = [stats, stats, per_head(dv), square]
    else:
        kernel, name = _bwd_kernel, stem + "_bwd"
        in_specs += [row_stat, row_stat, rows(dv)]
        whole = lambda w: pl.BlockSpec(
            (None, of_heads, s, w), lambda n, h, p, qo, ko, *_: (n, h, 0, 0))
        outs = [(rows(d), like(d)),
                (whole(d), jax.ShapeDtypeStruct(k.shape, jnp.float32)),
                (whole(dv), jax.ShapeDtypeStruct(v.shape, jnp.float32))]
        scratch = [per_head(d)]
        if shared:
            outs += [(rows(r), like(r)),
                     (pl.BlockSpec((None, s, r),
                                   lambda n, h, p, qo, ko, *_: (n, 0, 0)),
                      jax.ShapeDtypeStruct((b, s, r), jnp.float32))]
            scratch += [per_head(r)]
        scratch += [square]
    arrays = (q, k, v) + ((keep,) if selected else ()) + shared + rest
    formed = b * hkv * g * len(tiles.q_of) * block * block  # every head
    depth = (d + r + dv) if forward else 3 * (d + r) + 2 * dv
    moved = sum(a.size * a.dtype.itemsize for a in arrays) + sum(
        out.size * out.dtype.itemsize for _, out in outs)
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, selected=selected,
                          shared=bool(shared), flagged=flags is not None,
                          span=tiles.span, bounded=bool(tiles.bounds),
                          heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(lists),
            grid=(b, hkv // heads, len(tiles.q_of)),
            in_specs=in_specs,
            out_specs=[spec for spec, _ in outs],
            scratch_shapes=scratch),
        out_shape=[out for _, out in outs],
        compiler_params=pltpu.CompilerParams(
            # d_k_s is summed over the heads in its resident block: that
            # grid walks a sequence's heads in order
            dimension_semantics=(
                "parallel", "arbitrary" if shared and not forward
                else "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * formed * depth, transcendentals=formed,
            bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(*(jnp.asarray(x, jnp.int32) for x in lists), *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attend(q, k, v, keep, shared, scale, block, interpret, tiles):
    """``(out, log-sum-exp)``; the second takes no cotangent."""
    return _attend_fwd(q, k, v, keep, shared, scale, block, interpret,
                       tiles)[0]


def _attend_fwd(q, k, v, keep, shared, scale, block, interpret, tiles):
    out, lse = _call(True, scale, block, interpret, tiles, q, k, v, keep,
                     shared)
    return (out, lse), (q, k, v, keep, shared, out, lse)


def _attend_bwd(scale, block, interpret, tiles, residuals, cotangents):
    q, k, v, keep, shared, out, lse = residuals
    d_out, _ = cotangents
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    d_q, d_k, d_v, *d_shared = _call(
        False, scale, block, interpret, tiles, q, k, v, keep, shared, lse,
        delta, d_out.astype(q.dtype))
    if shared:
        d_shared[1] = d_shared[1].astype(shared[1].dtype)
    return (d_q, d_k.astype(k.dtype), d_v.astype(v.dtype), None,
            tuple(d_shared))


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q, k, v, *, scale: float, block: int, selected=None, shared=None,
           tiles: Optional[TilePairs] = None,
           interpret: Optional[bool] = None):
    """``q``: ``(B, Hkv, G, S, D)``; ``k``: ``(B, Hkv, S, D)``; ``v``: ``(B,
    Hkv, S, Dv)``, ``S`` whole blocks; ``tiles``: the visibility rule as the
    list of tile pairs to form (None: ``causal_tiles``); ``selected``: None
    (every key the rule shows) or, under the causal rule, ``(P, B, block,
    block)`` bool, the tile layout; ``shared``: None or
    ``(q_s (B, Hkv, G, S, r), k_s (B, S, r))``.  Returns ``out (B, Hkv, G,
    S, Dv)`` and the rows' log-sum-exp ``(B, Hkv, G, S)`` float32 — what
    ``ops/attention._blockwise_causal`` and ``_selected`` return,
    differentiable w.r.t. ``q, k, v`` and the shared pair."""
    return _attend(q, k, v,
                   None if selected is None else selected.astype(jnp.int8),
                   () if shared is None else tuple(shared),
                   float(scale), int(block), resolve_interpret(interpret),
                   causal_tiles(q.shape[3] // block) if tiles is None
                   else tiles)
