"""Attention ops — the pluggable compute seam for the ViT path.

All implementations share one signature::

    fn(q, k, v) -> out      # (B, H, S, D) x3 -> (B, H, S, D)

so the model swaps between them by name without re-plumbing:
  ``dense``   — straightforward XLA softmax attention (fused by the compiler;
                right answer for ViT-B's 197 tokens, SURVEY.md §5.7);
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

from typing import Callable, Dict, Optional

import jax.numpy as jnp


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
