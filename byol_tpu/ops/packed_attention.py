"""Fused self-attention over the packed ``qkv`` — forward and backward.

``packed_self_attention(qkv[B,S,3*H*D], num_heads) -> out[B,S,H*D]`` is
``softmax(q k^T / sqrt(D)) v`` over all keys (no mask, no dropout) for
sequences that fit VMEM whole: ViT-B/16's 197 tokens.  It exists because of
what the compiler does with the einsum form at that length (PERF.md §5,
PR 28): half of the ViT-B/16 train step's bytes were ``[B,H,S,S]`` scores and
weights crossing HBM and relayout copies of ``qkv``, of its q/k/v slices and
of the output, for 4% of the step's operations.

Design (see /opt/skills/guides/pallas_guide.md; times are chip runs, PR 28):
- the kernels read q, k and v where the ``qkv`` projection wrote them —
  static 128-lane column slices of one ``[block_b, S, 3*H*D]`` block — and
  write ``out`` / ``dqkv`` where ``proj`` / the projection's backward read
  them.  No slice, transpose or reshape of an activation outside the kernel;
- a program holds ``block_b`` whole images (all heads), so a call is
  ``B / block_b`` grid steps (a step costs about 0.35 us);
- a 128-lane column block holds ``128 / D`` heads.  A head is selected by
  zeroing the other heads' lanes of ONE operand of each product: the MXU is
  128 deep and 128 wide, so a contraction or an output of ``D`` lanes costs
  what 128 cost, and no lane is ever shifted.  The masked copies of a block's
  heads are STACKED along the rows, so each product is one ``dot`` for all
  heads of the block against the other, unmasked operand, whose tiles are
  latched once (two dots a block forward, five backward: 17% / 16% faster
  than a dot per head);
- the sequence axis is padded to a multiple of 128 by the BLOCK: rows past
  ``S`` are outside the array (uninitialised in VMEM), so every tile is
  zeroed there after the load and key positions ``>= S`` are masked before
  the softmax; rows past ``S`` of the outputs are never written back.  The
  axis that is streamed through the MXU as rows — queries forward, keys
  backward — is padded to 16 only (197 -> 208, not 256);
- scores and softmax statistics are float32; the weights are cast to the
  input dtype for the second product (as ``dense_attention`` does);
- the backward recomputes scores and statistics in VMEM from ``qkv`` — the
  only residual, and one the ``qkv`` projection's backward holds anyway.  It
  works on the TRANSPOSED scores ``[keys, queries]``: the softmax sums and
  ``rowsum(P * dP)`` then run down the sublanes, and ``dv = P^T dO`` and
  ``dk = dS^T q`` are plain products; only ``dq = dS k`` contracts the
  leading axis.  The forward keeps ``[queries, keys]``: transposed it would
  need that contraction for its one output product, and was 40% slower.

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
from jax.sharding import PartitionSpec as P

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.common import (LANES, MASKED, NN, NT, TN,
                                 VMEM_LIMIT_BYTES, dot)
from byol_tpu.parallel.mesh import DATA_AXIS

# [S,S] float32 tiles and the double-buffered row blocks fit the VMEM a
# program may ask for (``VMEM_LIMIT_BYTES``)
MAX_PADDED_SEQ = 512
_BLOCK_BYTES = 12 * 2 ** 20      # budget for the double-buffered row blocks
ROW_ALIGN = 16                   # rows of a streamed tile: bf16 packs 16


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def supported(seq_len: int, num_heads: int, head_dim: int) -> bool:
    """Shapes the kernels take: whole ``[S,S]`` tiles in VMEM, heads that
    tile the 128 lanes, whole 128-lane column blocks."""
    return (0 < seq_len and _round_up(seq_len, LANES) <= MAX_PADDED_SEQ
            and head_dim > 0 and LANES % head_dim == 0
            and (num_heads * head_dim) % LANES == 0)


def _block_b(batch: int, row_bytes: int) -> int:
    """Images a program holds: the largest divisor of ``batch`` (at most 8)
    whose double-buffered row blocks stay inside the budget."""
    for bb in (8, 4, 2):
        if batch % bb == 0 and 2 * bb * row_bytes <= _BLOCK_BYTES:
            return bb
    return 1


def _head_masks(rows: int, head_dim: int):
    """Per head of a 128-lane column block, its lanes — ``[None]`` when the
    block is one head."""
    if head_dim == LANES:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return [(lane >= h * head_dim) & (lane < (h + 1) * head_dim)
            for h in range(LANES // head_dim)]


def _only(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _stack(x, heads):
    """``[rows, 128] -> [heads * rows, 128]``: a copy per head with the
    other heads' lanes zero.  Against the UNMASKED other operand, block
    ``h`` of the product is head ``h``'s; the operand's tiles are latched
    into the MXU once for all heads of the block."""
    return jnp.concatenate([_only(head, x) for head in heads], axis=0)


def _pick(y, heads):
    """``[heads * rows, 128] -> [rows, 128]``: head ``h``'s lanes from
    block ``h`` (the other lanes of a block hold products across heads)."""
    rows = y.shape[0] // len(heads)
    out = y[:rows]
    for h, head in enumerate(heads[1:], 1):
        out = jnp.where(head, y[h * rows:(h + 1) * rows], out)
    return out


def _tiles(ref, i, cols, rows_ok, rows):
    """Image ``i``'s first ``rows`` rows of a column block, zero past the
    sequence (those rows lie outside the array: uninitialised VMEM)."""
    return _only(rows_ok[:rows], ref[i, :rows, cols])


def _fwd_kernel(qkv_ref, o_ref, *, seq_len: int, width: int, head_dim: int,
                scale: float):
    """Scores as ``[heads x queries, keys]``: the queries padded to the
    sublane packing only (they are the rows streamed through the MXU)."""
    block_b, sp, _ = qkv_ref.shape
    qr = _round_up(seq_len, ROW_ALIGN)
    heads = _head_masks(qr, head_dim)
    rows_ok = jax.lax.broadcasted_iota(jnp.int32, (sp, LANES), 0) < seq_len
    key_ok = jax.lax.broadcasted_iota(
        jnp.int32, (len(heads) * qr, sp), 1) < seq_len

    def image(i, carry):
        for j in range(width // LANES):
            cq, ck, cv = (pl.ds(part * width + j * LANES, LANES)
                          for part in range(3))
            q = _tiles(qkv_ref, i, cq, rows_ok, qr)
            k = _tiles(qkv_ref, i, ck, rows_ok, sp)
            v = _tiles(qkv_ref, i, cv, rows_ok, sp)
            s = dot(_stack(q, heads), k, NT) * scale
            s = jnp.where(key_ok, s, MASKED)
            e = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
            inv = 1.0 / jnp.sum(e, axis=1, keepdims=True)
            out = _pick(dot(e.astype(v.dtype), v, NN) * inv, heads)
            o_ref[i, :qr, cq] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_b, image, 0, unroll=True)


def _bwd_kernel(qkv_ref, do_ref, dqkv_ref, *, seq_len: int, width: int,
                head_dim: int, scale: float):
    """Everything ``[heads, keys, queries]``: the keys padded to the
    sublane packing only; sums over the keys run down the sublanes."""
    block_b, sp, _ = qkv_ref.shape
    kr = _round_up(seq_len, ROW_ALIGN)
    heads = _head_masks(kr, head_dim)
    tile = (len(heads), kr, sp)
    rows_ok = jax.lax.broadcasted_iota(jnp.int32, (sp, LANES), 0) < seq_len
    key_ok = jax.lax.broadcasted_iota(jnp.int32, tile, 1) < seq_len

    def image(i, carry):
        for j in range(width // LANES):
            cq, ck, cv = (pl.ds(part * width + j * LANES, LANES)
                          for part in range(3))
            q = _tiles(qkv_ref, i, cq, rows_ok, sp)
            do = _tiles(do_ref, i, cq, rows_ok, sp)
            k_h = _stack(_tiles(qkv_ref, i, ck, rows_ok, kr), heads)
            v_h = _stack(_tiles(qkv_ref, i, cv, rows_ok, kr), heads)
            s = (dot(k_h, q, NT) * scale).reshape(tile)
            s = jnp.where(key_ok, s, MASKED)
            e = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
            p = e * (1.0 / jnp.sum(e, axis=1, keepdims=True))
            dp = dot(v_h, do, NT).reshape(tile)
            delta = jnp.sum(p * dp, axis=1, keepdims=True)
            ds = (p * (dp - delta) * scale).astype(q.dtype).reshape(-1, sp)
            p = p.astype(do.dtype).reshape(-1, sp)
            dq = dot(ds, k_h, TN)            # sums over the heads too
            dk = _pick(dot(ds, q, NN), heads)
            dv = _pick(dot(p, do, NN), heads)
            dqkv_ref[i, :, cq] = dq.astype(dqkv_ref.dtype)
            dqkv_ref[i, :kr, ck] = dk.astype(dqkv_ref.dtype)
            dqkv_ref[i, :kr, cv] = dv.astype(dqkv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_b, image, 0, unroll=True)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _call(kernel, name, out_width, num_heads, interpret, *arrays):
    """One ``pallas_call`` over row blocks of ``block_b`` whole images.
    Jitted so that a model's layers share ONE trace and lowering of each
    kernel: 36 separate ones added 4.5 s to the ViT-B/16 step's lowering,
    which every start pays, compile cache or not."""
    qkv = arrays[0]
    b, s, width3 = qkv.shape
    width = width3 // 3
    head_dim = width // num_heads
    sp = _round_up(s, LANES)
    itemsize = qkv.dtype.itemsize
    columns = sum(a.shape[2] for a in arrays) + out_width    # in and out
    bb = _block_b(b, sp * itemsize * columns)

    def spec(w):
        return pl.BlockSpec((bb, sp, w), lambda i: (i, 0, 0))

    heads_work = b * num_heads * sp * sp
    n_dots = 2 if kernel is _fwd_kernel else 5
    return pl.pallas_call(
        functools.partial(kernel, seq_len=s, width=width, head_dim=head_dim,
                          scale=head_dim ** -0.5),
        grid=(b // bb,),
        in_specs=[spec(a.shape[2]) for a in arrays],
        out_specs=spec(out_width),
        out_shape=jax.ShapeDtypeStruct((b, s, out_width), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_dots * heads_work * head_dim,
            transcendentals=heads_work,
            bytes_accessed=b * s * itemsize * columns),
        interpret=interpret,
        name=name,
    )(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _attend(qkv, num_heads, interpret):
    return _call(_fwd_kernel, "packed_attention_fwd", qkv.shape[2] // 3,
                 num_heads, interpret, qkv)


def _attend_fwd(qkv, num_heads, interpret):
    return _attend(qkv, num_heads, interpret), qkv


def _attend_bwd(num_heads, interpret, qkv, dout):
    return (_call(_bwd_kernel, "packed_attention_bwd", qkv.shape[2],
                  num_heads, interpret, qkv, dout.astype(qkv.dtype)),)


_attend.defvjp(_attend_fwd, _attend_bwd)


def packed_self_attention(qkv: jnp.ndarray, num_heads: int, *, mesh=None,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """``[B, S, 3*H*D]`` (q | k | v, heads major inside each) ->
    ``[B, S, H*D]``; differentiable w.r.t. ``qkv``.  Same arithmetic as
    :func:`byol_tpu.ops.attention.dense_attention` on the unpacked heads,
    with the scores kept in float32.  ``mesh`` spanning >1 device wraps the
    kernels in a ``shard_map`` over the data axis (GSPMD cannot partition a
    ``pallas_call``): the batch is split, everything else replicated."""
    b, s, width3 = qkv.shape
    if width3 % (3 * num_heads):
        raise ValueError(f"qkv width {width3} is not 3 x {num_heads} heads")
    head_dim = width3 // (3 * num_heads)
    if not supported(s, num_heads, head_dim):
        raise ValueError(
            f"packed_self_attention does not take {s} tokens x {num_heads} "
            f"heads of {head_dim} (see packed_attention.supported)")
    call = functools.partial(_attend, num_heads=num_heads,
                             interpret=ops_common.resolve_interpret(interpret))
    if mesh is not None and mesh.size > 1:
        call = ops_common.shard_map_unchecked(
            call, mesh, in_specs=(P(DATA_AXIS),), out_specs=P(DATA_AXIS))
    return call(qkv)
