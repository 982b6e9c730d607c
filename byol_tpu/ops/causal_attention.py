"""Causal grouped-query attention — forward and backward kernels.

``attend(q, k, v, scale=, block=)`` is the second lowering of
``ops/attention.blockwise_causal_attention``: the same block pairs, the same
running max and sum, the same five products backward, with a pair's scores,
weights, ``d_weights`` and ``d_scores`` in VMEM instead of four float32
``(B, Hkv, G, block, block)`` arrays in HBM (PERF.md section 5, PR 38: 159 of
the core's 183 ms a step were fusions over those tiles).  It is
ops/selected_attention.py (PR 34; its docstring has the design and the chip
timings behind each choice) WITHOUT a selection:

- grid ``(B, Hkv, causal pair)``, :func:`causal_pairs` as scalar prefetch: no
  step lies above the diagonal; the ``G`` query heads of a key head are one
  program, traced side by side; a tile's squares are held ``[keys,
  queries]``, so a query's max and sum run down the sublanes and its
  statistics are lane rows; ONE backward kernel of five products, ``d_k,
  d_v`` of a key head's whole sequence resident in float32;
- no mask operand.  Only a tile ON the diagonal (``j == i``) masks anything,
  and its mask is the same lower triangle every time: made from two iotas
  into a float32 bias (``0`` / ``-1e30``, which in float32 IS ``where(visible,
  score, -1e30)``) on those steps alone; a tile under the diagonal adds
  nothing;
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
  pairs, so the head axis of that grid is walked in order;
- bf16 (the input dtype's) operands, float32 accumulation and statistics, the
  weights rounded before ``P V`` and ``d_scores`` before its products, as
  the ``jax.numpy`` body does; float32 inputs multiply at
  ``Precision.HIGHEST``.  Residuals ``q, k, v, out, lse`` (and the shared
  pair).

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

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.attention import _MASKED, causal_pairs
from byol_tpu.ops.common import LANES
from byol_tpu.ops.selected_attention import (_NN, _NT, _TN, VMEM_LIMIT_BYTES,
                                             _dot)


def _vmem_bytes(block: int, dim: int, seq_len: int, group: int,
                itemsize: int, forward: bool, *, vdim: Optional[int] = None,
                shared: int = 0) -> int:
    """A kernel's blocks twice (double buffering), its scratch and the
    float32 squares of the head in hand — ``selected_attention``'s count
    without a mask, at a key width ``dim`` (+ ``shared``) and a value width
    ``vdim``; a width that does not fill its last 128 lanes takes the whole
    lane tile of VMEM."""
    tiles = lambda d: -(-d // LANES) * LANES
    key_lanes = tiles(dim) + tiles(shared)
    value_lanes = tiles(dim if vdim is None else vdim)
    q_rows, o_rows = group * block * key_lanes, group * block * value_lanes
    slabs = block * (key_lanes + value_lanes) * itemsize    # k, v (, k_s)
    square = 4 * block * block       # one float32 (block, block) value
    if forward:
        blocks = (q_rows + o_rows) * itemsize + slabs \
            + 4 * group * block                             # q, o; lse
        scratch = 4 * o_rows + 2 * 4 * group * block        # acc; stats
        live = 3                          # scores, weights, their bf16 copy
    else:
        blocks = ((2 * q_rows + o_rows) * itemsize + slabs  # q, dq; dO
                  + 8 * group * block                       # lse, delta
                  + 4 * seq_len * (key_lanes + value_lanes))  # d_k, d_v,
        scratch = 4 * q_rows
        live = 5                          # ... and d_weights, d_scores
    return 2 * blocks + scratch + (1 + live) * square     # 1: the bias


def _width_ok(dim: int) -> bool:
    """A head fills whole lane tiles or exactly half of one (64: what
    compiles, tests/test_tpu_compile.py)."""
    return dim > 0 and (dim % LANES == 0 or 2 * dim == LANES)


def supported(block: int, dim: int, seq_len: int, group: int = 1,
              itemsize: int = 2, *, vdim: Optional[int] = None,
              shared: int = 0) -> bool:
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
                                vdim=vdim, shared=shared)
                    for fwd in (True, False)) <= VMEM_LIMIT_BYTES)


def applies(block: int, dim: int, seq_len: int, heads: int, kv_heads: int,
            dtype=jnp.bfloat16, *, vdim: Optional[int] = None,
            shared: int = 0, backend: Optional[str] = None) -> bool:
    """Whether ``blockwise_causal_attention`` runs as the kernels — decided
    from what the code can see, never by a flag: the program lowers for a
    TPU, the query heads share the key heads evenly and the shapes (key
    width ``dim``, value width ``vdim``, a ``shared`` part or none) are ones
    the kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and kv_heads > 0 and heads % kv_heads == 0
            and supported(block, dim, seq_len, heads // kv_heads,
                          jnp.dtype(dtype).itemsize, vdim=vdim,
                          shared=shared))


# ---- the kernels -----------------------------------------------------------

def _on_and_under_the_diagonal(i, j, bias_ref, tile):
    """``tile(bias)`` for the step's pair: ``bias`` is None under the
    diagonal and, on it, ``bias_ref`` holding ``(bk, bq)`` float32, 0 where
    the query sees the key (same block: its row in the tile is not after the
    query's column), ``_MASKED`` where not."""
    @pl.when(j < i)
    def _under():
        tile(None)

    @pl.when(j == i)
    def _on():
        key = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 0)
        query = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 1)
        bias_ref[...] = jnp.where(key <= query, 0.0, _MASKED)
        tile(bias_ref)


def _scores(k_ref, q, scale, bias, shared=None):
    """``(bk, bq)`` float32; ``shared``: the head's ``q_s`` and the ``k_s``
    ref, whose product is the scores' second term."""
    scores = _dot(k_ref[...], q, _NT)
    if shared is not None:
        q_s, ks_ref = shared
        scores = scores + _dot(ks_ref[...], q_s, _NT)
    scores = scores * scale
    return scores if bias is None else scores + bias[...]


def _fwd_kernel(q_of_ref, k_of_ref, *refs, scale: float, shared: bool):
    """Scores ``[keys, queries]``.  Refs: ``q (G, bq, D)``; ``k (bk, D)``;
    ``v (bk, Dv)``; with a shared part ``q_s (G, bq, r)``, ``k_s (bk, r)``;
    ``o (G, bq, Dv)``; ``lse (G, bq)``; scratch: every head's running max
    and sum, a lane row a head, ``(G, bq)``, the float32 accumulators ``(G,
    bq, Dv)`` and a diagonal tile's bias."""
    q_ref, k_ref, v_ref, *refs = refs
    qs_ref, ks_ref = refs[:2] if shared else (None, None)
    o_ref, lse_ref, top_ref, total_ref, acc_ref, bias_ref = refs[-6:]
    pair = pl.program_id(2)
    i, j = q_of_ref[pair], k_of_ref[pair]
    group, _, dim = acc_ref.shape

    @pl.when(j == 0)
    def _start():
        top_ref[...] = jnp.full_like(top_ref, _MASKED)
        total_ref[...] = jnp.zeros_like(total_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def column(row):
        """``(1, bq)`` -> ``(bq, Dv)``, a row's value on every lane: its
        broadcast down a lane tile's worth of sublanes, turned."""
        lanes = max(dim, LANES)
        return jnp.broadcast_to(row, (lanes, row.shape[1])).T[:, :dim]

    def head(h, bias):
        at = pl.ds(h, 1)
        scores = _scores(k_ref, q_ref[h], scale, bias,
                         (qs_ref[h], ks_ref) if shared else None)
        top = top_ref[at, :]
        new_top = jnp.maximum(top, jnp.max(scores, axis=0, keepdims=True))
        weights = jnp.exp(scores - new_top)
        keep = jnp.exp(top - new_top)
        total_ref[at, :] = total_ref[at, :] * keep + jnp.sum(
            weights, axis=0, keepdims=True)
        top_ref[at, :] = new_top
        acc_ref[h] = acc_ref[h] * column(keep) + _dot(
            weights.astype(v_ref.dtype), v_ref[...], _TN)

    def tile(bias):
        for h in range(group):      # side by side: selected_attention.py
            head(h, bias)

    _on_and_under_the_diagonal(i, j, bias_ref, tile)

    @pl.when(j == i)
    def _finish():
        lse_ref[...] = top_ref[...] + jnp.log(total_ref[...])
        for h in range(group):
            o_ref[h] = (acc_ref[h] / column(total_ref[h:h + 1, :])).astype(
                o_ref.dtype)


def _bwd_kernel(q_of_ref, k_of_ref, *refs, scale: float, shared: bool):
    """Everything ``[keys, queries]``.  Refs: ``q, dq (G, bq, D)``; ``dO (G,
    bq, Dv)``; ``k (bk, D)``; ``v (bk, Dv)``; ``lse, delta (G, bq)``; ``dk
    (S, D)``, ``dv (S, Dv)`` float32, one key head's, resident over all its
    pairs; with a shared part ``q_s, dq_s (G, bq, r)``, ``k_s (bk, r)`` and
    ``dk_s (S, r)`` float32, ONE SEQUENCE's, resident over all its heads and
    pairs; scratch: the float32 ``dq`` (and ``dq_s``) of the query block and
    a diagonal tile's bias."""
    refs = iter(refs)
    take = lambda n, present=True: [
        next(refs) if present else None for _ in range(n)]
    q_ref, k_ref, v_ref = take(3)
    qs_ref, ks_ref = take(2, shared)
    lse_ref, delta_ref, do_ref, dq_ref, dk_ref, dv_ref = take(6)
    dqs_ref, dks_ref = take(2, shared)
    dq_acc_ref, = take(1)
    dqs_acc_ref, = take(1, shared)
    bias_ref, = take(1)
    pair = pl.program_id(2)
    i, j = q_of_ref[pair], k_of_ref[pair]
    group, bk = q_ref.shape[0], k_ref.shape[0]
    keys = pl.ds(pl.multiple_of(j * bk, bk), bk)

    @pl.when(pair == 0)
    def _start():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    if shared:
        @pl.when((pair == 0) & (pl.program_id(1) == 0))
        def _next_sequence():
            dks_ref[...] = jnp.zeros_like(dks_ref)

    @pl.when(j == 0)
    def _next_rows():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        if shared:
            dqs_acc_ref[...] = jnp.zeros_like(dqs_acc_ref)

    def head(h, bias):
        q, d_out = q_ref[h], do_ref[h]
        lse, delta = lse_ref[pl.ds(h, 1), :], delta_ref[pl.ds(h, 1), :]
        weights = jnp.exp(_scores(
            k_ref, q, scale, bias,
            (qs_ref[h], ks_ref) if shared else None) - lse)
        dv_ref[keys, :] += _dot(weights.astype(d_out.dtype), d_out, _NN)
        d_weights = _dot(v_ref[...], d_out, _NT)
        d_scores = (weights * (d_weights - delta) * scale).astype(q.dtype)
        dk_ref[keys, :] += _dot(d_scores, q, _NN)
        dq_acc_ref[h] += _dot(d_scores, k_ref[...], _TN)
        if shared:
            dks_ref[keys, :] += _dot(d_scores, qs_ref[h], _NN)
            dqs_acc_ref[h] += _dot(d_scores, ks_ref[...], _TN)

    def tile(bias):
        for h in range(group):      # side by side: selected_attention.py
            head(h, bias)

    _on_and_under_the_diagonal(i, j, bias_ref, tile)

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)
        if shared:
            dqs_ref[...] = dqs_acc_ref[...].astype(dqs_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3),
                   static_argnames=("shared",))
def _call(forward, scale, block, interpret, q, k, v, *rest,
          shared: bool = False):
    """One ``pallas_call`` over ``(batch, key head, causal pair)``.  ``q``:
    ``(B, Hkv, G, S, D)``; ``k``: ``(B, Hkv, S, D)``; ``v``: ``(B, Hkv, S,
    Dv)``; ``dO`` and the output ``(B, Hkv, G, S, Dv)``; ``lse, delta``:
    ``(B, Hkv, G, S)`` float32; ``shared``: ``rest`` starts with ``q_s (B,
    Hkv, G, S, r)`` and ``k_s (B, S, r)``.  Jitted so that a model's passes
    share one trace and lowering of each kernel."""
    b, hkv, g, s, d = q.shape
    dv = v.shape[-1]
    r = rest[0].shape[-1] if shared else 0
    q_of, k_of = causal_pairs(s // block)
    # index maps: (batch, key head, pair, q_of, k_of)
    rows = lambda w: pl.BlockSpec((None, None, g, block, w),
                                  lambda n, h, p, qo, ko: (n, h, 0, qo[p], 0))
    slab = lambda w: pl.BlockSpec((None, None, block, w),
                                  lambda n, h, p, qo, ko: (n, h, ko[p], 0))
    # the shared key and its cotangent: no head axis
    slab_s = pl.BlockSpec((None, block, r),
                          lambda n, h, p, qo, ko: (n, ko[p], 0))
    row_stat = pl.BlockSpec((None, None, g, block),
                            lambda n, h, p, qo, ko: (n, h, 0, qo[p]))
    stat = jax.ShapeDtypeStruct((b, hkv, g, s), jnp.float32)
    like = lambda w: jax.ShapeDtypeStruct((b, hkv, g, s, w), q.dtype)
    square = pltpu.VMEM((block, block), jnp.float32)
    per_head = lambda w: pltpu.VMEM((g, block, w), jnp.float32)
    in_specs = [rows(d), slab(d), slab(dv)] + (
        [rows(r), slab_s] if shared else [])
    if forward:
        kernel, name = _fwd_kernel, "causal_attention_fwd"
        outs = [(rows(dv), like(dv)), (row_stat, stat)]
        stats = pltpu.VMEM((g, block), jnp.float32)
        scratch = [stats, stats, per_head(dv), square]
    else:
        kernel, name = _bwd_kernel, "causal_attention_bwd"
        in_specs += [row_stat, row_stat, rows(dv)]
        whole = lambda w: pl.BlockSpec((None, None, s, w),
                                       lambda n, h, p, qo, ko: (n, h, 0, 0))
        outs = [(rows(d), like(d)),
                (whole(d), jax.ShapeDtypeStruct(k.shape, jnp.float32)),
                (whole(dv), jax.ShapeDtypeStruct(v.shape, jnp.float32))]
        scratch = [per_head(d)]
        if shared:
            outs += [(rows(r), like(r)),
                     (pl.BlockSpec((None, s, r),
                                   lambda n, h, p, qo, ko: (n, 0, 0)),
                      jax.ShapeDtypeStruct((b, s, r), jnp.float32))]
            scratch += [per_head(r)]
        scratch += [square]
    arrays = (q, k, v) + rest
    formed = b * hkv * g * len(q_of) * block * block      # pairs, every head
    depth = (d + r + dv) if forward else 3 * (d + r) + 2 * dv
    moved = sum(a.size * a.dtype.itemsize for a in arrays) + sum(
        out.size * out.dtype.itemsize for _, out in outs)
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, shared=shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, len(q_of)),
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
    )(jnp.asarray(q_of), jnp.asarray(k_of), *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attend(q, k, v, shared, scale, block, interpret):
    return _call(True, scale, block, interpret, q, k, v, *shared,
                 shared=bool(shared))[0]


def _attend_fwd(q, k, v, shared, scale, block, interpret):
    out, lse = _call(True, scale, block, interpret, q, k, v, *shared,
                     shared=bool(shared))
    return out, (q, k, v, shared, out, lse)


def _attend_bwd(scale, block, interpret, residuals, d_out):
    q, k, v, shared, out, lse = residuals
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    d_q, d_k, d_v, *d_shared = _call(
        False, scale, block, interpret, q, k, v, *shared, lse, delta,
        d_out.astype(q.dtype), shared=bool(shared))
    if shared:
        d_shared[1] = d_shared[1].astype(shared[1].dtype)
    return d_q, d_k.astype(k.dtype), d_v.astype(v.dtype), tuple(d_shared)


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q, k, v, *, scale: float, block: int, shared=None,
           interpret: Optional[bool] = None):
    """``q``: ``(B, Hkv, G, S, D)``; ``k``: ``(B, Hkv, S, D)``; ``v``: ``(B,
    Hkv, S, Dv)``, ``S`` whole blocks; ``shared``: None or ``(q_s (B, Hkv,
    G, S, r), k_s (B, S, r))``.  Returns ``out (B, Hkv, G, S, Dv)`` — what
    ``ops/attention._blockwise_causal`` returns, differentiable w.r.t. ``q,
    k, v`` and the shared pair."""
    return _attend(q, k, v, tuple(shared) if shared is not None else (),
                   float(scale), int(block),
                   ops_common.resolve_interpret(interpret))
