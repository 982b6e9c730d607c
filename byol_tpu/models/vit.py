"""Vision Transformer backbone — the BN-free encoder path.

The reference's backbone story is "any torchvision arch minus its last
module" (main.py:190-193), which silently breaks for ViT (Quirk Q8:
``children()[:-1]`` assumes a resnet-shaped module list).  Here ViT is a
first-class feature extractor behind the same registry contract as ResNet
(``__call__(x, train) -> (B, feature_dim)``), and the no-BatchNorm property
is declared in its registry spec so LARS/weight-decay BN-exclusion masks and
SyncBN machinery skip cleanly (SURVEY.md §7 hard part 6; BASELINE.json
config 5 is ViT-B/16).

TPU-native choices:
- patch embedding as a strided Conv (one big MXU matmul per image);
- pre-LN blocks, LayerNorm/softmax statistics in fp32 under bf16 compute;
- attention behind :func:`byol_tpu.ops.attention.get_attention_fn`:
  ``dense`` (exact softmax attention over the whole sequence) for 224px
  ViT-B — 197 tokens, no sequence parallelism warranted (SURVEY.md §5.7) —
  or ``ring`` (sequence-parallel over the mesh) for long-sequence configs.  ``dense`` as two XLA einsums is NOT the right
  answer at 197 tokens: the scores cross HBM and every head layout is a
  copy, half of the step's bytes (PERF.md §5, PR 28).  So where
  :func:`byol_tpu.ops.attention.packed_kernel_applies` (a TPU, a sequence
  that fits VMEM, heads that tile the lanes) it runs as one fused kernel
  over the packed ``qkv``, forward and backward; elsewhere as the einsums;
- optional ``remat`` per block (jax.checkpoint) to trade FLOPs for HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from byol_tpu.core import remat as remat_lib
from byol_tpu.ops.attention import get_attention_fn, packed_kernel_applies
from byol_tpu.ops.packed_attention import packed_self_attention
from byol_tpu.parallel.mesh import ambient_mesh


class MlpBlock(nn.Module):
    hidden_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        out_dim = x.shape[-1]
        x = nn.Dense(self.hidden_dim, dtype=self.dtype, name="fc1")(x)
        x = nn.gelu(x)
        x = nn.Dense(out_dim, dtype=self.dtype, name="fc2")(x)
        return x


class SelfAttention(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        assert d % self.num_heads == 0, (d, self.num_heads)
        head_dim = d // self.num_heads
        qkv = nn.Dense(3 * d, dtype=self.dtype, name="qkv")(x)
        mesh = ambient_mesh()
        if self.attn_impl == "dense" and packed_kernel_applies(
                b, s, self.num_heads, head_dim, mesh=mesh):
            out = packed_self_attention(qkv, self.num_heads, mesh=mesh)
        else:
            qkv = qkv.reshape(b, s, 3, self.num_heads, head_dim)
            q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
            out = get_attention_fn(self.attn_impl)(q, k, v)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, d)
        return nn.Dense(d, dtype=self.dtype, name="proj")(out)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"

    @nn.compact
    def __call__(self, x):
        # LayerNorm keeps fp32 stats under bf16 compute (param_dtype fp32;
        # reductions promoted) — the BN-free analog of the fp32-BN rule.
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + SelfAttention(self.num_heads, self.dtype, self.attn_impl,
                              name="attn")(y)
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        x = x + MlpBlock(self.mlp_ratio * x.shape[-1], self.dtype,
                         name="mlp")(y)
        return remat_lib.tag_block_out(x)


class ViT(nn.Module):
    """Feature extractor: (B, H, W, C) -> (B, width)."""

    width: int = 768
    depth: int = 12
    num_heads: int = 12
    patch_size: int = 16
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32
    pooling: str = "cls"                 # 'cls' | 'gap'
    attn_impl: str = "dense"
    remat: bool = False                  # legacy alias for remat_policy='full'
    remat_policy: str = "none"           # named selective checkpoint policy
                                         # (core/remat.py POLICY_NAMES)

    @property
    def feature_dim(self) -> int:
        return self.width

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train  # no BN, no dropout (BYOL uses none; delta documented)
        b, h, w, c = x.shape
        if h % self.patch_size or w % self.patch_size:
            raise ValueError(
                f"image size {(h, w)} not divisible by patch size "
                f"{self.patch_size}")
        x = x.astype(self.dtype)
        x = nn.Conv(self.width, (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    padding="VALID", dtype=self.dtype,
                    name="patch_embed")(x)
        x = x.reshape(b, -1, self.width)           # (B, S, D)
        s = x.shape[1]
        if self.pooling == "cls":
            cls = self.param("cls_token", nn.initializers.zeros,
                             (1, 1, self.width), jnp.float32)
            x = jnp.concatenate(
                [jnp.broadcast_to(cls, (b, 1, self.width)).astype(self.dtype),
                 x], axis=1)
            s += 1
        pos = self.param("pos_embedding",
                         nn.initializers.normal(stddev=0.02),
                         (1, s, self.width), jnp.float32)
        x = x + pos.astype(self.dtype)

        block = remat_lib.wrap_block(
            EncoderBlock,
            remat_lib.resolve_policy_name(self.remat, self.remat_policy))
        for i in range(self.depth):
            x = block(num_heads=self.num_heads, mlp_ratio=self.mlp_ratio,
                      dtype=self.dtype, attn_impl=self.attn_impl,
                      name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_final")(x)
        if self.pooling == "cls":
            feat = x[:, 0]
        elif self.pooling == "gap":
            feat = jnp.mean(x, axis=1)
        else:
            raise ValueError(f"unknown pooling {self.pooling!r}")
        return feat.astype(self.dtype)
