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
- bf16 (the input dtype's) operands, float32 accumulation and statistics, the
  weights rounded before ``P V`` and ``d_scores`` before its two products, as
  the ``jax.numpy`` body does; float32 inputs multiply at
  ``Precision.HIGHEST``.  Residuals ``q, k, v, out, lse``.

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
from byol_tpu.ops.selected_attention import _vmem_bytes as _vmem_bytes_masked


def _vmem_bytes(block: int, dim: int, seq_len: int, group: int,
                itemsize: int, forward: bool) -> int:
    """``selected_attention``'s count less the mask's block (twice: double
    buffering); a head narrower than the 128 lanes takes a whole lane tile
    of VMEM."""
    return _vmem_bytes_masked(block, -(-dim // LANES) * LANES, seq_len,
                              group, itemsize, forward) - 2 * block * block


def supported(block: int, dim: int, seq_len: int, group: int = 1,
              itemsize: int = 2) -> bool:
    """Shapes the kernels take: a block's tokens fill whole 128-lane tiles, a
    head fills whole lane tiles or exactly half of one (64: what compiles,
    tests/test_tpu_compile.py), whole blocks, and the backward's working set
    — the float32 ``d_k, d_v`` of one key head's sequence among it — fits."""
    return (block > 0 and block % LANES == 0
            and dim > 0 and (dim % LANES == 0 or 2 * dim == LANES)
            and seq_len > 0 and seq_len % block == 0 and group > 0
            and max(_vmem_bytes(block, dim, seq_len, group, itemsize, fwd)
                    for fwd in (True, False)) <= VMEM_LIMIT_BYTES)


def applies(block: int, dim: int, seq_len: int, heads: int, kv_heads: int,
            dtype=jnp.bfloat16, *, backend: Optional[str] = None) -> bool:
    """Whether ``blockwise_causal_attention`` runs as the kernels — decided
    from what the code can see, never by a flag: the program lowers for a
    TPU, the query heads share the key heads evenly and the shapes are ones
    the kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and kv_heads > 0 and heads % kv_heads == 0
            and supported(block, dim, seq_len, heads // kv_heads,
                          jnp.dtype(dtype).itemsize))


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


def _scores(k_ref, q, scale, bias):
    scores = _dot(k_ref[...], q, _NT) * scale
    return scores if bias is None else scores + bias[...]


def _fwd_kernel(q_of_ref, k_of_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                top_ref, total_ref, acc_ref, bias_ref, *, scale: float):
    """Scores ``[keys, queries]``.  Refs: ``q, o (G, bq, D)``; ``k, v (bk,
    D)``; ``lse (G, bq)``; scratch: every head's running max and sum, a lane
    row a head, ``(G, bq)``, the float32 accumulators ``(G, bq, D)`` and a
    diagonal tile's bias."""
    pair = pl.program_id(2)
    i, j = q_of_ref[pair], k_of_ref[pair]
    group, _, dim = acc_ref.shape

    @pl.when(j == 0)
    def _start():
        top_ref[...] = jnp.full_like(top_ref, _MASKED)
        total_ref[...] = jnp.zeros_like(total_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def column(row):
        """``(1, bq)`` -> ``(bq, D)``, a row's value on every lane: its
        broadcast down a lane tile's worth of sublanes, turned."""
        lanes = max(dim, LANES)
        return jnp.broadcast_to(row, (lanes, row.shape[1])).T[:, :dim]

    def head(h, bias):
        at = pl.ds(h, 1)
        scores = _scores(k_ref, q_ref[h], scale, bias)
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


def _bwd_kernel(q_of_ref, k_of_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dq_acc_ref, bias_ref, *,
                scale: float):
    """Everything ``[keys, queries]``.  Refs: ``q, dO, dq (G, bq, D)``; ``k, v
    (bk, D)``; ``lse, delta (G, bq)``; ``dk, dv (S, D)`` float32, one key
    head's, resident over all its pairs; scratch: the float32 ``dq`` of the
    query block and a diagonal tile's bias."""
    pair = pl.program_id(2)
    i, j = q_of_ref[pair], k_of_ref[pair]
    group, bk = q_ref.shape[0], k_ref.shape[0]
    keys = pl.ds(pl.multiple_of(j * bk, bk), bk)

    @pl.when(pair == 0)
    def _start():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j == 0)
    def _next_rows():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def head(h, bias):
        q, d_out = q_ref[h], do_ref[h]
        lse, delta = lse_ref[pl.ds(h, 1), :], delta_ref[pl.ds(h, 1), :]
        weights = jnp.exp(_scores(k_ref, q, scale, bias) - lse)
        dv_ref[keys, :] += _dot(weights.astype(d_out.dtype), d_out, _NN)
        d_weights = _dot(v_ref[...], d_out, _NT)
        d_scores = (weights * (d_weights - delta) * scale).astype(q.dtype)
        dk_ref[keys, :] += _dot(d_scores, q, _NN)
        dq_acc_ref[h] += _dot(d_scores, k_ref[...], _TN)

    def tile(bias):
        for h in range(group):      # side by side: selected_attention.py
            head(h, bias)

    _on_and_under_the_diagonal(i, j, bias_ref, tile)

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _call(forward, scale, block, interpret, q, k, v, *rest):
    """One ``pallas_call`` over ``(batch, key head, causal pair)``.  ``q``
    (and ``dO``): ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``; ``lse,
    delta``: ``(B, Hkv, G, S)`` float32.  Jitted so that a model's passes
    share one trace and lowering of each kernel."""
    b, hkv, g, s, d = q.shape
    q_of, k_of = causal_pairs(s // block)
    # index maps: (batch, key head, pair, q_of, k_of)
    rows = pl.BlockSpec((None, None, g, block, d),
                        lambda n, h, p, qo, ko: (n, h, 0, qo[p], 0))
    slab = pl.BlockSpec((None, None, block, d),
                        lambda n, h, p, qo, ko: (n, h, ko[p], 0))
    row_stat = pl.BlockSpec((None, None, g, block),
                            lambda n, h, p, qo, ko: (n, h, 0, qo[p]))
    stat = jax.ShapeDtypeStruct((b, hkv, g, s), jnp.float32)
    square = pltpu.VMEM((block, block), jnp.float32)
    per_head = pltpu.VMEM((g, block, d), jnp.float32)
    if forward:
        kernel, name = _fwd_kernel, "causal_attention_fwd"
        in_specs = [rows, slab, slab]
        outs = [(rows, jax.ShapeDtypeStruct(q.shape, q.dtype)),
                (row_stat, stat)]
        stats = pltpu.VMEM((g, block), jnp.float32)
        scratch = [stats, stats, per_head, square]
    else:
        kernel, name = _bwd_kernel, "causal_attention_bwd"
        in_specs = [rows, slab, slab, row_stat, row_stat, rows]
        whole = pl.BlockSpec((None, None, s, d),
                             lambda n, h, p, qo, ko: (n, h, 0, 0))
        summed = jax.ShapeDtypeStruct(k.shape, jnp.float32)
        outs = [(rows, jax.ShapeDtypeStruct(q.shape, q.dtype)),
                (whole, summed), (whole, summed)]
        scratch = [per_head, square]
    arrays = (q, k, v) + rest
    formed = b * hkv * g * len(q_of) * block * block      # pairs, every head
    moved = sum(a.size * a.dtype.itemsize for a in arrays) + sum(
        out.size * out.dtype.itemsize for _, out in outs)
    return pl.pallas_call(
        functools.partial(kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, len(q_of)),
            in_specs=in_specs,
            out_specs=[spec for spec, _ in outs],
            scratch_shapes=scratch),
        out_shape=[out for _, out in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * (2 if forward else 5) * formed * d,
            transcendentals=formed, bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(jnp.asarray(q_of), jnp.asarray(k_of), *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, scale, block, interpret):
    return _call(True, scale, block, interpret, q, k, v)[0]


def _attend_fwd(q, k, v, scale, block, interpret):
    out, lse = _call(True, scale, block, interpret, q, k, v)
    return out, (q, k, v, out, lse)


def _attend_bwd(scale, block, interpret, residuals, d_out):
    q, k, v, out, lse = residuals
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    d_q, d_k, d_v = _call(False, scale, block, interpret, q, k, v, lse,
                          delta, d_out.astype(q.dtype))
    return d_q, d_k.astype(k.dtype), d_v.astype(v.dtype)


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q, k, v, *, scale: float, block: int,
           interpret: Optional[bool] = None):
    """``q``: ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``, ``S`` whole
    blocks.  Returns ``out`` like ``q`` — what ``ops/attention.
    _blockwise_causal`` returns, differentiable w.r.t. ``q, k, v``."""
    return _attend(q, k, v, float(scale), int(block),
                   ops_common.resolve_interpret(interpret))

