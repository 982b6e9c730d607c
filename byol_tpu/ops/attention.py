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
