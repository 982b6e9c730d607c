"""Attention over a per-query set of keys — forward and backward kernels.

``attend(q, k, v, selected, scale=, block=)`` is the body of
``ops/attention.selected_attention``: grouped-query softmax attention in
which each query's softmax runs over the causal keys ``selected`` marks for
it, the same set for every head, returning the output and each row's
log-sum-exp.  It exists because of what the compiler does with the
``jax.numpy`` form (PERF.md section 5, PR 33): per block pair the scores,
the weights and, backward, ``d_weights`` and ``d_scores`` were each a whole
float32 ``(B, Hkv, G, block, block)`` array in HBM — 268 MB at the published
sizes, some 650 GB a step for 27 TFLOP of products.  Here a tile's squares
live and die in VMEM.  Still MASKED-DENSE: every tile on or under the
diagonal is formed whatever it keeps, a tile above it never.

Design (see /opt/skills/guides/pallas_guide.md):
- ``selected`` arrives in the TILE layout of ops/attention.py, ``(P, B,
  block, block)``, tile ``i (i + 1) / 2 + j`` for query block ``i`` and key
  block ``j``.  ``causal_pairs`` goes in as scalar prefetch and the PAIR is
  the innermost grid axis — ``(B, Hkv, P)`` — so the grid has no step above
  the diagonal and the block index maps read ``i, j`` of a step from SMEM;
- a program holds the ``G`` query heads of one key head: ``k``, ``v`` and
  the tile's mask are fetched once for the ``G`` of them (the mask has no
  head axis: it crosses HBM once a key head, as ``int8`` — ``bool`` operands
  lower badly), and becomes an additive float32 bias once a program:
  ``0`` where kept, ``-1e30`` where not, which in float32 IS ``where(keep,
  score, -1e30)`` (a score is lost whole under ``1e30``'s rounding);
- both kernels hold a tile's squares TRANSPOSED, ``[keys, queries]``: what
  is taken over a query's keys — the running max and sum — then runs down
  the sublanes, an elementwise pass of the vector unit, and a query's
  statistics are lane rows broadcast down the sublanes.  With scores
  ``[queries, keys]`` the forward made 128 cross-lane reductions a head
  and tile and took 18.6 ms a call of 8 sequences; transposed 11.6 (chip
  runs, PR 34).  A program's ``G`` heads are traced side by side (a Python
  loop), so that one head's products overlap another's passes: 20.5 ->
  18.6 ms with the first layout;
- forward: running max, sum and the float32 accumulator of every head stay
  in VMEM scratch across the key blocks of a query block (``j = 0 .. i``:
  the innermost axis walks them in order), the output and the log-sum-exp
  are written at ``j == i``.  Only ``P V`` contracts the leading axis, and
  the accumulator ``[queries, D]`` is rescaled by the statistics' row
  turned into a column.  A row none of whose keys in a tile is kept
  carries ``-1e30`` as its max, weighs that tile's keys 1 each, and loses
  all of it to the ``exp(old max - new max) = 0`` of the first kept key —
  the arithmetic of ``_selected_fwd``;
- backward: ONE kernel, five products a tile (the usual pair of kernels
  recomputes the scores in each: seven), no reduction at all: the rows'
  log-sum-exp and ``delta = rowsum(dO . O)`` (made outside: one fused pass
  over ``dO, O``) come in as lane rows, ``d_v = P^T dO`` and ``d_k = dS^T
  q`` are plain products, only ``d_q = dS k`` contracts the leading axis —
  16.4 ms a call, 96% of the matrix unit's peak.  ``d_q`` of a query block
  accumulates in scratch over its key blocks and is rounded at ``j == i``;
  ``d_k, d_v`` of ONE key head's whole sequence stay resident as the
  kernel's float32 output block ``(S, D)`` across all its pairs — summed
  there over the ``G`` query heads and the query blocks — and are rounded
  once outside.  That block is what bounds the sequence (:func:`supported`):
  16 bytes a token and lane column;
- bf16 (the input dtype's) operands, float32 accumulation and statistics,
  the weights rounded before ``P V`` and ``d_scores`` before its two
  products, as the ``jax.numpy`` body does; float32 inputs multiply at
  ``Precision.HIGHEST``.

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

# What a program may take of VMEM (a v5e holds 128 MiB, the compiler's
# default scope is 16): at blocks of 512, heads of 128, 8 query heads a key
# head and 4,096 tokens in bf16 the forward counts 11 MiB, the backward 23.
VMEM_LIMIT_BYTES = 48 * 2 ** 20

_NT = ((1,), (1,))      # a @ b^T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a^T @ b


def _vmem_bytes(block: int, dim: int, seq_len: int, group: int,
                itemsize: int, forward: bool) -> int:
    """A kernel's blocks twice (double buffering), its scratch and the
    float32 squares of the head in hand."""
    rows = group * block * dim                   # a query block, every head
    keys = 2 * block * dim * itemsize + block * block            # k, v, mask
    square = 4 * block * block       # one float32 (block, block) value
    if forward:
        blocks = 2 * rows * itemsize + keys + 4 * group * block  # q, o; lse
        scratch = 4 * rows + 2 * 4 * group * block               # acc; stats
        live = 3                          # scores, weights, their bf16 copy
    else:
        blocks = (3 * rows * itemsize + keys + 8 * group * block  # q, dO, dq
                  + 2 * 4 * seq_len * dim)                        # d_k, d_v
        scratch = 4 * rows
        live = 5                          # ... and d_weights, d_scores
    return 2 * blocks + scratch + (1 + live) * square     # 1: the bias


def supported(block: int, dim: int, seq_len: int, group: int = 1,
              itemsize: int = 2) -> bool:
    """Shapes the kernels take: a block's tokens and a head's width fill
    whole 128-lane tiles, whole blocks, and the backward's working set —
    the float32 ``d_k, d_v`` of one key head's sequence among it — fits."""
    return (block > 0 and block % LANES == 0 and dim > 0 and dim % LANES == 0
            and seq_len > 0 and seq_len % block == 0 and group > 0
            and max(_vmem_bytes(block, dim, seq_len, group, itemsize, fwd)
                    for fwd in (True, False)) <= VMEM_LIMIT_BYTES)


def applies(block: int, dim: int, seq_len: int, heads: int, kv_heads: int,
            dtype=jnp.bfloat16, *, backend: Optional[str] = None) -> bool:
    """Whether ``selected_attention`` runs as the kernels — decided from what
    the code can see, never by a flag: the program lowers for a TPU, the
    query heads share the key heads evenly and the shapes are ones the
    kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and kv_heads > 0 and heads % kv_heads == 0
            and supported(block, dim, seq_len, heads // kv_heads,
                          jnp.dtype(dtype).itemsize))


# ---- the kernels -----------------------------------------------------------

def _dot(a, b, dims):
    """Operands as they are, float32 accumulation; float32 operands at full
    precision."""
    exact = a.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _bias(keep_ref):
    """``(bq, bk)`` float32: 0 where the key is kept, ``_MASKED`` where not."""
    return (1.0 - keep_ref[...].astype(jnp.float32)) * _MASKED


def _fwd_kernel(q_of_ref, k_of_ref, q_ref, k_ref, v_ref, keep_ref, o_ref,
                lse_ref, top_ref, total_ref, acc_ref, bias_ref, *,
                scale: float):
    """Scores ``[keys, queries]``.  Refs: ``q, o (G, bq, D)``; ``k, v (bk,
    D)``; ``keep (bq, bk)`` int8; ``lse (G, bq)``; scratch: every head's
    running max and sum, a lane row a head, ``(G, bq)``, the float32
    accumulators ``(G, bq, D)`` and the tile's bias, transposed."""
    pair = pl.program_id(2)
    i, j = q_of_ref[pair], k_of_ref[pair]
    group = q_ref.shape[0]

    @pl.when(j == 0)
    def _start():
        top_ref[...] = jnp.full_like(top_ref, _MASKED)
        total_ref[...] = jnp.zeros_like(total_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias_ref[...] = _bias(keep_ref).T

    def column(row):
        """``(1, bq)`` -> ``(bq, D)``, a row's value on every lane: its
        broadcast down ``D`` sublanes, turned."""
        return jnp.broadcast_to(row, (acc_ref.shape[2], row.shape[1])).T

    def head(h):
        at = pl.ds(h, 1)
        scores = _dot(k_ref[...], q_ref[h], _NT) * scale + bias_ref[...]
        top = top_ref[at, :]
        new_top = jnp.maximum(top, jnp.max(scores, axis=0, keepdims=True))
        weights = jnp.exp(scores - new_top)
        keep = jnp.exp(top - new_top)
        total_ref[at, :] = total_ref[at, :] * keep + jnp.sum(
            weights, axis=0, keepdims=True)
        top_ref[at, :] = new_top
        acc_ref[h] = acc_ref[h] * column(keep) + _dot(
            weights.astype(v_ref.dtype), v_ref[...], _TN)

    for h in range(group):          # side by side: the module docstring
        head(h)

    @pl.when(j == i)
    def _finish():
        lse_ref[...] = top_ref[...] + jnp.log(total_ref[...])
        for h in range(group):
            o_ref[h] = (acc_ref[h] / column(total_ref[h:h + 1, :])).astype(
                o_ref.dtype)


def _bwd_kernel(q_of_ref, k_of_ref, q_ref, k_ref, v_ref, keep_ref, lse_ref,
                delta_ref, do_ref, dq_ref, dk_ref, dv_ref, dq_acc_ref,
                bias_ref, *, scale: float):
    """Everything ``[keys, queries]``.  Refs: ``q, dO, dq (G, bq, D)``; ``k, v
    (bk, D)``; ``keep (bq, bk)`` int8; ``lse, delta (G, bq)``; ``dk, dv (S,
    D)`` float32, one key head's, resident over all its pairs; scratch: the
    float32 ``dq`` of the query block and the tile's bias, transposed."""
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

    bias_ref[...] = _bias(keep_ref).T

    def head(h):
        q, d_out = q_ref[h], do_ref[h]
        lse, delta = lse_ref[pl.ds(h, 1), :], delta_ref[pl.ds(h, 1), :]
        scores = _dot(k_ref[...], q, _NT) * scale + bias_ref[...]
        weights = jnp.exp(scores - lse)
        dv_ref[keys, :] += _dot(weights.astype(d_out.dtype), d_out, _NN)
        d_weights = _dot(v_ref[...], d_out, _NT)
        d_scores = (weights * (d_weights - delta) * scale).astype(q.dtype)
        dk_ref[keys, :] += _dot(d_scores, q, _NN)
        dq_acc_ref[h] += _dot(d_scores, k_ref[...], _TN)

    for h in range(group):          # side by side: the module docstring
        head(h)

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _call(forward, scale, block, interpret, q, k, v, keep, *rest):
    """One ``pallas_call`` over ``(batch, key head, causal pair)``.  ``q``
    (and ``dO``): ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``;
    ``keep``: ``(P, B, block, block)`` int8; ``lse, delta``: ``(B, Hkv, G,
    S)`` float32.  Jitted so that a model's layers share one trace and
    lowering of each kernel."""
    b, hkv, g, s, d = q.shape
    q_of, k_of = causal_pairs(s // block)
    # index maps: (batch, key head, pair, q_of, k_of)
    rows = pl.BlockSpec((None, None, g, block, d),
                        lambda n, h, p, qo, ko: (n, h, 0, qo[p], 0))
    slab = pl.BlockSpec((None, None, block, d),
                        lambda n, h, p, qo, ko: (n, h, ko[p], 0))
    tile = pl.BlockSpec((None, None, block, block),
                        lambda n, h, p, qo, ko: (p, n, 0, 0))
    row_stat = pl.BlockSpec((None, None, g, block),
                            lambda n, h, p, qo, ko: (n, h, 0, qo[p]))
    stat = jax.ShapeDtypeStruct((b, hkv, g, s), jnp.float32)
    square = pltpu.VMEM((block, block), jnp.float32)
    per_head = pltpu.VMEM((g, block, d), jnp.float32)
    if forward:
        kernel, name = _fwd_kernel, "selected_attention_fwd"
        in_specs = [rows, slab, slab, tile]
        outs = [(rows, jax.ShapeDtypeStruct(q.shape, q.dtype)),
                (row_stat, stat)]
        stats = pltpu.VMEM((g, block), jnp.float32)
        scratch = [stats, stats, per_head, square]
    else:
        kernel, name = _bwd_kernel, "selected_attention_bwd"
        in_specs = [rows, slab, slab, tile, row_stat, row_stat, rows]
        whole = pl.BlockSpec((None, None, s, d),
                             lambda n, h, p, qo, ko: (n, h, 0, 0))
        summed = jax.ShapeDtypeStruct(k.shape, jnp.float32)
        outs = [(rows, jax.ShapeDtypeStruct(q.shape, q.dtype)),
                (whole, summed), (whole, summed)]
        scratch = [per_head, square]
    arrays = (q, k, v, keep) + rest
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attend(q, k, v, keep, scale, block, interpret):
    """``(out, log-sum-exp)``; the second takes no cotangent."""
    return tuple(_call(True, scale, block, interpret, q, k, v, keep))


def _attend_fwd(q, k, v, keep, scale, block, interpret):
    out, lse = _call(True, scale, block, interpret, q, k, v, keep)
    return (out, lse), (q, k, v, keep, out, lse)


def _attend_bwd(scale, block, interpret, residuals, cotangents):
    q, k, v, keep, out, lse = residuals
    d_out, _ = cotangents
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    d_q, d_k, d_v = _call(False, scale, block, interpret, q, k, v, keep, lse,
                          delta, d_out.astype(q.dtype))
    return d_q, d_k.astype(k.dtype), d_v.astype(v.dtype), None


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q, k, v, selected, *, scale: float, block: int,
           interpret: Optional[bool] = None):
    """``q``: ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``;
    ``selected``: ``(P, B, block, block)`` bool, the tile layout.  Returns
    ``out`` like ``q`` and the rows' log-sum-exp ``(B, Hkv, G, S)`` float32
    — what ``ops/attention._selected`` returns, differentiable w.r.t. ``q,
    k, v``."""
    return _attend(q, k, v, selected.astype(jnp.int8), float(scale),
                   int(block), ops_common.resolve_interpret(interpret))
