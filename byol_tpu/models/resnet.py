"""ResNet backbone family, NHWC / TPU-native.

Replaces the reference's torchvision backbone zoo (reference main.py:30-32,
190-193: ``models.__dict__[args.arch]`` with the final FC stripped via
``children()[:-1]``).  Instead of truncating an opaque module list (Quirk Q8),
every backbone here IS a feature extractor: ``__call__`` returns the pooled
representation, and the registry (:mod:`byol_tpu.models.registry`) exposes the
feature dimension so ``--representation-size`` no longer needs hand-matching.

Architecture follows torchvision ResNet v1 semantics (7x7/2 stem, 3x3/2
max-pool, post-activation residual blocks, global average pool) so trained
behavior is comparable, but the implementation is JAX-idiomatic: NHWC layout
(TPU-native), batch statistics computed over the GLOBAL batch under GSPMD jit
— the sharded batch axis makes every BN a SyncBN (reference's opt-in
``--convert-to-sync-bn``, main.py:77-78,433) with zero extra code.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from byol_tpu.core import remat as remat_lib

ModuleDef = Any


class SpaceToDepthStem(nn.Module):
    """The 7x7/2 stem conv, computed as a 4x4/1 conv on space-to-depth input.

    Mathematically IDENTICAL to ``Conv(width, (7,7), (2,2), padding=3)`` —
    the kernel is zero-padded to 8x8 and rearranged so each output position
    reads the same input window — but far friendlier to the TPU: the
    stride-2 7x7 conv over 3 input channels starves the MXU (3 channels
    against 128 lanes, and the stride halves useful overlap), while the
    rearranged form is a dense stride-1 conv over 12 channels on half the
    spatial extent.  This is the standard MLPerf ResNet trick, built here
    as a reparametrization: the PARAM is still the (7,7,C,width) kernel
    (same init distribution, same checkpoint tree as the plain stem —
    ``params/stem_conv/kernel``), and the rearrangement happens at apply
    time where XLA folds it into the conv.
    """

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        if h % 2 or w % 2:
            raise ValueError(
                f"space_to_depth stem needs even spatial dims, got {(h, w)}")
        kernel = self.param("kernel", nn.initializers.he_normal(),
                            (7, 7, c, self.width), jnp.float32)
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        # Zero row/col at the FRONT: output i of the original conv reads
        # input rows 2i-3..2i+3; over 2x2 subpixel blocks that window is
        # rows -1..6 of an 8x8 kernel whose first row/col never fires.
        k = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        # (8,8,C,O) -> (R,pr,S,pc,C,O) -> (R,S,pr,pc,C,O) -> (4,4,4C,O)
        k = k.reshape(4, 2, 4, 2, c, self.width).transpose(0, 2, 1, 3, 4, 5)
        k = k.reshape(4, 4, 4 * c, self.width)
        # input space-to-depth with the matching (pr,pc,c) channel order
        x = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, h // 2, w // 2, 4 * c)
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class BasicBlock(nn.Module):
    """2x conv3x3 residual block (resnet18/34)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    zero_init_last_bn: bool = True

    @nn.compact
    def __call__(self, x):
        last_scale = (nn.initializers.zeros_init() if self.zero_init_last_bn
                      else nn.initializers.ones_init())
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides, padding=1,
                      name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), padding=1, name="conv2")(y)
        y = self.norm(scale_init=last_scale, name="bn2")(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return remat_lib.tag_block_out(nn.relu(y + residual))


class Bottleneck(nn.Module):
    """1x1 -> 3x3 -> 1x1(x4) residual block (resnet50+).

    ``inner_multiplier`` widens only the two inner convs — torchvision's
    wide_resnet convention (width_per_group=128), where the block's OUTPUT
    width (and so the backbone feature dim) stays filters x expansion.
    The paper-style "2x" variants (resnet50w2 etc.) instead widen every
    layer via ResNet.width.
    """

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    expansion: int = 4
    zero_init_last_bn: bool = True
    inner_multiplier: int = 1

    @nn.compact
    def __call__(self, x):
        last_scale = (nn.initializers.zeros_init() if self.zero_init_last_bn
                      else nn.initializers.ones_init())
        residual = x
        inner = self.filters * self.inner_multiplier
        y = self.conv(inner, (1, 1), name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = self.conv(inner, (3, 3), self.strides, padding=1,
                      name="conv2")(y)
        y = self.norm(name="bn2")(y)
        y = nn.relu(y)
        out_filters = self.filters * self.expansion
        y = self.conv(out_filters, (1, 1), name="conv3")(y)
        # zero-init the last BN scale so blocks start as identity — standard
        # large-batch trick (Goyal et al.); torchvision offers the same via
        # zero_init_residual (off there by default — gate for parity).
        y = self.norm(scale_init=last_scale, name="bn3")(y)
        if residual.shape != y.shape:
            residual = self.conv(out_filters, (1, 1), self.strides,
                                 name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return remat_lib.tag_block_out(nn.relu(y + residual))


class ResNet(nn.Module):
    """Feature-extractor ResNet: ``(B, H, W, C) -> (B, feature_dim)``."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    width: int = 64                      # base width; 128 for the w2 variants
    dtype: jnp.dtype = jnp.float32
    bn_momentum: float = 0.9             # = 1 - torch momentum 0.1
    bn_epsilon: float = 1e-5
    small_inputs: bool = False           # CIFAR stem: 3x3/1, no max-pool
    zero_init_residual: bool = True      # False = torchvision/reference init
    remat: bool = False                  # legacy alias for remat_policy='full'
    remat_policy: str = "none"           # named selective checkpoint policy
                                         # (core/remat.py POLICY_NAMES);
                                         # wins over the bool when not 'none'
    stem: str = "conv"                   # 'conv' | 'space_to_depth' (identical
                                         # numerics, MXU-friendly layout;
                                         # ignored for the CIFAR stem)
    inner_multiplier: int = 1            # torchvision wide_resnet*_2: widen
                                         # only the bottleneck inner convs
                                         # (feature dim unchanged)
    bn_axis_name: Optional[str] = None   # named axis for BN statistics (the
                                         # accum_bn_mode='global' vmap axis;
                                         # SyncBN-over-microbatches)

    @property
    def feature_dim(self) -> int:
        exp = getattr(self.block_cls, "expansion", 1)
        return self.width * (2 ** (len(self.stage_sizes) - 1)) * exp

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 kernel_init=nn.initializers.he_normal())
        # BN params/stats stay fp32 (param_dtype default), and flax reduces
        # the statistics and normalises in fp32 whatever dtype is — the
        # apex-O2 "BN in fp32" rule (SURVEY.md §2.4) by construction.  dtype
        # only chooses the ONE cast of the result: the compute dtype, so the
        # ReLU, the residual sum and the skip path's cotangent do not cross
        # HBM in fp32 under the bf16 policy (as vit.py's LayerNorms).
        norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                 momentum=self.bn_momentum,
                                 epsilon=self.bn_epsilon, dtype=self.dtype,
                                 axis_name=self.bn_axis_name)
        if self.small_inputs:
            x = conv(self.width, (3, 3), padding=1, name="stem_conv")(x)
        elif self.stem == "space_to_depth":
            x = SpaceToDepthStem(self.width, dtype=self.dtype,
                                 name="stem_conv")(x)
        elif self.stem == "conv":
            x = conv(self.width, (7, 7), (2, 2), padding=3, name="stem_conv")(x)
        else:
            raise ValueError(f"unknown stem {self.stem!r}; "
                             "'conv' | 'space_to_depth'")
        x = norm(name="stem_bn")(x)
        x = nn.relu(x)
        if not self.small_inputs:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        block_cls = remat_lib.wrap_block(
            self.block_cls,
            remat_lib.resolve_policy_name(self.remat, self.remat_policy))
        # BasicBlock has no inner width to widen; only pass the knob where
        # it exists (wide variants are bottleneck-only, as in torchvision)
        wide_kw = ({"inner_multiplier": self.inner_multiplier}
                   if self.inner_multiplier != 1 else {})
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(filters=self.width * 2 ** i,
                              strides=strides, conv=conv, norm=norm,
                              zero_init_last_bn=self.zero_init_residual,
                              name=f"stage{i + 1}_block{j + 1}",
                              **wide_kw)(x)
        x = jnp.mean(x, axis=(1, 2))     # global average pool
        return x.astype(self.dtype)


STAGE_SIZES = {
    "resnet18": [2, 2, 2, 2],
    "resnet34": [3, 4, 6, 3],
    "resnet50": [3, 4, 6, 3],
    "resnet101": [3, 4, 23, 3],
    "resnet152": [3, 8, 36, 3],
    "resnet200": [3, 24, 36, 3],
}
BASIC = {"resnet18", "resnet34"}


def make_resnet(name: str, *, dtype=jnp.float32, width_multiplier: int = 1,
                small_inputs: bool = False,
                zero_init_residual: bool = True,
                remat: bool = False, remat_policy: str = "none",
                stem: str = "conv",
                bn_axis_name: Optional[str] = None) -> ResNet:
    """Two widening conventions, both first-class:

    - ``resnetNNw2`` (paper-style "x2", the BYOL paper's RN50(2x)): EVERY
      layer twice as wide, feature dim doubles (4096 for resnet50w2);
    - ``wide_resnetNN_2`` (the torchvision names the reference's arch flag
      accepts, main.py:30-32): only the two bottleneck inner convs widen
      (width_per_group=128), feature dim stays 2048.
    """
    inner_multiplier = 1
    if name.startswith("wide_") and name.endswith("_2"):
        base = name[len("wide_"):-len("_2")]
        if base in BASIC or base not in STAGE_SIZES:
            raise ValueError(f"unknown wide arch {name!r}; wide variants "
                             "exist for bottleneck resnets only")
        inner_multiplier = 2
    else:
        base = name.replace("w2", "")
        if base not in STAGE_SIZES:
            raise ValueError(f"unknown resnet arch {name!r}; "
                             f"known: {sorted(STAGE_SIZES)} (+'w2' suffix, "
                             "+ torchvision 'wide_resnetNN_2' names)")
        if name.endswith("w2"):
            width_multiplier = 2
    block = BasicBlock if base in BASIC else Bottleneck
    return ResNet(stage_sizes=STAGE_SIZES[base], block_cls=block,
                  width=64 * width_multiplier, dtype=dtype,
                  small_inputs=small_inputs,
                  zero_init_residual=zero_init_residual,
                  remat=remat, remat_policy=remat_policy, stem=stem,
                  inner_multiplier=inner_multiplier,
                  bn_axis_name=bn_axis_name)
