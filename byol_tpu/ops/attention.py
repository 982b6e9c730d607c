"""Attention ops — the pluggable compute seam for the ViT path.

All implementations share one signature::

    fn(q, k, v) -> out      # (B, H, S, D) x3 -> (B, H, S, D)

so the model swaps between them by name without re-plumbing:
  ``dense``   — exact softmax attention over the whole sequence, written as
                two einsums.  XLA does NOT fuse them: at ViT-B/16's 197
                tokens the ``[B,H,S,S]`` scores and weights cross HBM and
                q, k, v and the output are relaid out by copies — half of
                the train step's bytes (compiler and trace, PERF.md §5,
                PR 28).  Where the shapes allow and the program lowers for
                a TPU, ``models/vit.SelfAttention`` therefore hands the
                packed ``qkv`` to ``ops/packed_attention.py`` instead
                (:func:`packed_kernel_applies`): same arithmetic, one
                kernel forward and one backward;
  ``blockwise`` (:func:`blockwise_causal_attention`, the decoder trunk's
                gated grouped-query layers) — the same arithmetic, causal,
                over blocks of keys with a running max and sum, forward and
                backward, so that no ``[S, S]`` array exists at any length;
  ``flash``   — Pallas blockwise-softmax kernel (ops/flash_attention.py),
                for long sequences where the S x S score matrix shouldn't hit
                HBM;
  ``ring``    — sequence-parallel blockwise attention over the mesh's
                ``sequence`` axis (parallel/ring_attention.py), for sequences
                sharded across chips.

The reference has no attention at all (ResNet path, main.py:190-193); this
module exists because long-context support is first-class in the rebuild.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from byol_tpu.ops import packed_attention
from byol_tpu.parallel.mesh import DATA_AXIS


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: Optional[float] = None, causal: bool = False
                    ) -> jnp.ndarray:
    """Standard softmax attention. (B, H, S, D) -> (B, H, S, Dv).

    Softmax statistics in fp32 regardless of compute dtype (bf16-safe),
    matmuls in the input dtype (MXU-friendly).  ``scale`` defaults to
    ``1/sqrt(D)``; ``causal`` masks key positions after the query's (the
    decoder trunk, models/decoder_trunk.py, whose value heads are also
    narrower than its query/key heads)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        visible = jnp.tril(jnp.ones(scores.shape[-2:], bool))
        scores = jnp.where(visible, scores, jnp.finfo(scores.dtype).min)
    weights = jnp.exp(
        scores.astype(jnp.float32)
        - jnp.max(scores, axis=-1, keepdims=True).astype(jnp.float32))
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


_MASKED = -1e30      # finite: exp(_MASKED - max) is 0, never inf - inf


def _block_bounds(seq_len: int, block: int):
    return [(lo, min(lo + block, seq_len))
            for lo in range(0, seq_len, block)]


def _block_scores(q_blk, k_blk, scale, q_lo, k_lo):
    """``(B, Hkv, G, bq, bk)`` float32 scores of one block pair, keys after
    the query masked where the pair touches the diagonal."""
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                        preferred_element_type=jnp.float32) * scale
    bq, bk = q_blk.shape[-2], k_blk.shape[-2]
    if k_lo + bk - 1 > q_lo:                    # some key lies after a query
        visible = (q_lo + jnp.arange(bq))[:, None] >= \
            (k_lo + jnp.arange(bk))[None, :]
        scores = jnp.where(visible, scores, _MASKED)
    return scores


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blockwise_causal(q, k, v, scale, block):
    return _blockwise_causal_fwd(q, k, v, scale, block)[0]


def _blockwise_causal_fwd(q, k, v, scale, block):
    """``q``: ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``.  One
    query block at a time over the key blocks it can see, with a running
    max and sum; a block pair wholly above the diagonal is never formed."""
    bounds = _block_bounds(q.shape[-2], block)
    outs, lses = [], []
    for q_lo, q_hi in bounds:
        q_blk = q[..., q_lo:q_hi, :]
        top = total = acc = None
        for k_lo, k_hi in bounds:
            if k_lo >= q_hi:
                break
            scores = _block_scores(q_blk, k[..., k_lo:k_hi, :], scale,
                                   q_lo, k_lo)
            here = jnp.max(scores, axis=-1)
            new_top = here if top is None else jnp.maximum(top, here)
            weights = jnp.exp(scores - new_top[..., None])
            part = jnp.einsum("bhgqk,bhkd->bhgqd", weights.astype(v.dtype),
                              v[..., k_lo:k_hi, :],
                              preferred_element_type=jnp.float32)
            if top is None:
                total, acc = jnp.sum(weights, axis=-1), part
            else:
                keep = jnp.exp(top - new_top)
                total = total * keep + jnp.sum(weights, axis=-1)
                acc = acc * keep[..., None] + part
            top = new_top
        outs.append((acc / total[..., None]).astype(q.dtype))
        lses.append(top + jnp.log(total))
    out = jnp.concatenate(outs, axis=-2)
    return out, (q, k, v, out, jnp.concatenate(lses, axis=-1))


def _blockwise_causal_bwd(scale, block, residuals, d_out):
    """The same block pairs again: scores recomputed from ``q, k`` and the
    saved log-sum-exp, five products a pair."""
    q, k, v, out, lse = residuals
    bounds = _block_bounds(q.shape[-2], block)
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    d_k, d_v, d_q = [None] * len(bounds), [None] * len(bounds), []
    add = lambda old, new: new if old is None else old + new
    for q_lo, q_hi in bounds:
        q_blk, do_blk = q[..., q_lo:q_hi, :], d_out[..., q_lo:q_hi, :]
        dq_blk = None
        for j, (k_lo, k_hi) in enumerate(bounds):
            if k_lo >= q_hi:
                break
            k_blk, v_blk = k[..., k_lo:k_hi, :], v[..., k_lo:k_hi, :]
            weights = jnp.exp(
                _block_scores(q_blk, k_blk, scale, q_lo, k_lo)
                - lse[..., q_lo:q_hi, None])
            d_v[j] = add(d_v[j], jnp.einsum(
                "bhgqk,bhgqd->bhkd", weights.astype(v.dtype), do_blk,
                preferred_element_type=jnp.float32))
            d_weights = jnp.einsum("bhgqd,bhkd->bhgqk", do_blk, v_blk,
                                   preferred_element_type=jnp.float32)
            d_scores = (weights * (d_weights - delta[..., q_lo:q_hi, None])
                        * scale).astype(q.dtype)
            dq_blk = add(dq_blk, jnp.einsum(
                "bhgqk,bhkd->bhgqd", d_scores, k_blk,
                preferred_element_type=jnp.float32))
            d_k[j] = add(d_k[j], jnp.einsum(
                "bhgqk,bhgqd->bhkd", d_scores, q_blk,
                preferred_element_type=jnp.float32))
        d_q.append(dq_blk.astype(q.dtype))
    together = lambda blocks, like: jnp.concatenate(
        blocks, axis=-2).astype(like.dtype)
    return (jnp.concatenate(d_q, axis=-2), together(d_k, k),
            together(d_v, v))


_blockwise_causal.defvjp(_blockwise_causal_fwd, _blockwise_causal_bwd)


def blockwise_causal_attention(q: jnp.ndarray, k: jnp.ndarray,
                               v: jnp.ndarray, *,
                               scale: Optional[float] = None,
                               block: int = 512) -> jnp.ndarray:
    """Causal softmax attention whose memory is linear in S, forward and
    backward: ``(B, Hq, S, D)`` queries on ``(B, Hkv, S, D)`` keys and
    values, each key/value head shared by ``Hq / Hkv`` consecutive query
    heads and never repeated in memory.  Blockwise over the keys with a
    running max and sum; nothing larger than one ``(B, Hq, block, block)``
    tile of scores is ever held, block pairs above the diagonal are
    skipped, and the backward recomputes the tiles from ``q, k`` and the
    saved log-sum-exp (``jax.custom_vjp``).  Plain ``jax.numpy``, not a
    kernel: every tile crosses HBM once (ROADMAP R2).  Statistics in
    float32, products in the input dtype."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not share {hkv} key heads")
    if scale is None:
        scale = d ** -0.5
    out = _blockwise_causal(q.reshape(b, hkv, hq // hkv, s, d), k, v,
                            float(scale), int(block))
    return out.reshape(b, hq, s, v.shape[-1])


def packed_kernel_applies(batch: int, seq_len: int, num_heads: int,
                          head_dim: int, *, causal: bool = False,
                          masked: bool = False, mesh=None,
                          backend: Optional[str] = None) -> bool:
    """Whether ``dense`` self-attention runs as the fused kernel over the
    packed ``qkv`` (ops/packed_attention.py) — decided from what the code
    can see, never by a flag: the program lowers for a TPU, nothing is
    masked, the padded sequence's ``[S,S]`` float32 tiles and row blocks fit
    VMEM, the head width tiles the 128 lanes, and the mesh in scope (if any)
    shards nothing but the batch."""
    backend = jax.default_backend() if backend is None else backend
    if backend != "tpu" or causal or masked:
        return False
    if mesh is not None and (
            batch % mesh.shape.get(DATA_AXIS, 1)
            or mesh.size != mesh.shape.get(DATA_AXIS, 1)):
        return False
    return packed_attention.supported(seq_len, num_heads, head_dim)


def get_attention_fn(impl: str) -> Callable:
    if impl == "dense":
        return dense_attention
    if impl == "flash":
        from byol_tpu.ops.flash_attention import flash_attention
        return flash_attention
    if impl == "ring":
        from byol_tpu.parallel.ring_attention import ring_attention
        return ring_attention
    raise ValueError(f"unknown attention impl {impl!r}; "
                     f"known: dense, flash, ring")
