"""Pallas flash attention — blockwise online-softmax, no S x S in HBM.

TPU-native long-sequence attention for the ViT path (``attn_impl='flash'``).
The reference has no attention anywhere (ResNet-only hot path,
/root/reference/main.py:190-193); this kernel exists because long-context is
first-class in the rebuild and XLA's dense softmax attention materializes
the (S, S) score matrix in HBM for large S.

Kernel design (see /opt/skills/guides/pallas_guide.md):
- grid over (batch*heads, S/block_q, S/block_k) with the KEY loop as the
  INNERMOST grid dimension: per program instance only ONE (block_q, d) query
  tile and ONE (block_k, d) key/value tile are VMEM-resident, so sequence
  length is bounded by HBM, not VMEM.  (An earlier revision kept the whole
  padded K/V resident per program — grid-level K streaming is the fix.)
- the online-softmax running max ``m``, normalizer ``l`` and fp32 output
  accumulator live in VMEM scratch, which persists across the sequential
  innermost grid steps; state is initialized at k==0 and the normalized
  output is written at the last k step;
- the two matmuls per tile hit the MXU with
  ``preferred_element_type=float32`` (bf16-safe statistics);
- HBM traffic is O(S*D) per q tile instead of O(S^2) resident;
- non-block-aligned sequences are zero-padded; padded KEY positions are
  masked to -inf inside the kernel, padded QUERY rows are sliced away.

``interpret=True`` (default off-TPU) runs the same kernel under the Pallas
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

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() well-defined
                 # when an entire tile is masked (all-padding tail block)

# m/l scratch carries one value per query row, stored over a full 128-lane
# vector register (the minor-dim tiling the TPU vector unit requires; a
# (block_q, 1) scratch would not lower).
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_k: int, seq_len: int, n_k: int):
    kv_i = pl.program_id(2)          # innermost grid dim: sequential K walk

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                      # (block_q, d)
    k = k_ref[0]                                      # (block_k, d)
    v = v_ref[0]
    block_q = q.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (block_q, block_k)
    # mask key positions beyond the true sequence length
    kpos = kv_i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    s = jnp.where(kpos < seq_len, s, NEG_INF)

    m_prev = m_ref[:, :1]                             # (block_q, 1)
    l_prev = l_ref[:, :1]
    m_curr = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_curr)
    p = jnp.exp(s - m_next)                           # fp32
    alpha = jnp.exp(m_prev - m_next)                  # (block_q, 1)
    l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (block_q, d)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    @pl.when(kv_i == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """(B, H, S, D) x3 -> (B, H, S, D); same contract as dense_attention."""
    interpret = ops_common.resolve_interpret(interpret)
    b, h, s, d = q.shape
    scale = d ** -0.5

    q = _pad_to(q, 2, block_q)
    k = _pad_to(k, 2, block_k)
    v = _pad_to(v, 2, block_k)
    s_pad_q, s_pad_k = q.shape[2], k.shape[2]
    n_k = s_pad_k // block_k

    qr = q.reshape(b * h, s_pad_q, d)
    kr = k.reshape(b * h, s_pad_k, d)
    vr = v.reshape(b * h, s_pad_k, d)

    kernel = functools.partial(_flash_kernel, scale=scale, block_k=block_k,
                               seq_len=s, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        # K innermost: sequential on-core walk, scratch carries persist
        grid=(b * h, s_pad_q // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),        # fp32 accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(b, h, s_pad_q, d)[:, :, :s, :]
