"""Operations the algorithm needs, counted from a configuration's sizes.

Multiply-accumulates of one forward pass over one image, from the shapes
alone (convolutions and matrix products; normalisation, activations and
pooling are not counted), and from them the operations of one BYOL step
per image.  The benchmark's MFU is ``rate x this / peak``; no later PR can
move it by changing the program.

One BYOL training step forwards both views through the online network and
through the target network and back-propagates the online pass: per image
2 online forwards + 2 target forwards + a backward worth 2 x the 2 online
forwards = 8 forward passes of one image.  Recomputed operations (remat)
are not counted.
"""
from __future__ import annotations

RESNET_STAGES = {
    "resnet18": ([2, 2, 2, 2], "basic"),
    "resnet34": ([3, 4, 6, 3], "basic"),
    "resnet50": ([3, 4, 6, 3], "bottleneck"),
    "resnet101": ([3, 4, 23, 3], "bottleneck"),
}
VIT_SIZES = {            # width, depth, heads, patch
    "vit_s16": (384, 12, 6, 16),
    "vit_b16": (768, 12, 12, 16),
    "vit_l16": (1024, 24, 16, 16),
}
FORWARDS_PER_TRAIN_IMAGE = 8


def resnet_forward_macs(arch: str, image: int, width: int = 64) -> float:
    stages, kind = RESNET_STAGES[arch]
    small = image <= 64                      # the program's CIFAR stem
    macs = 0.0
    if small:
        hw, cin = image, width
        macs += hw * hw * 9 * 3 * width
    else:
        hw = image // 2
        macs += hw * hw * 49 * 3 * width
        hw, cin = hw // 2, width             # 3x3/2 max-pool
    for i, blocks in enumerate(stages):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            out_hw = hw // stride
            if kind == "bottleneck":
                cout = 4 * f
                macs += hw * hw * cin * f                 # 1x1
                macs += out_hw * out_hw * 9 * f * f       # 3x3 (strided)
                macs += out_hw * out_hw * f * cout        # 1x1
            else:
                cout = f
                macs += out_hw * out_hw * 9 * cin * f
                macs += out_hw * out_hw * 9 * f * f
            if cin != cout or stride != 1:
                macs += out_hw * out_hw * cin * cout      # downsample 1x1
            hw, cin = out_hw, cout
    return macs


def vit_forward_macs(arch: str, image: int) -> float:
    d, depth, _, patch = VIT_SIZES[arch]
    n = (image // patch) ** 2
    s = n + 1                                # + class token
    macs = n * patch * patch * 3 * d         # patch embedding
    macs += depth * (4 * s * d * d + 2 * s * s * d + 8 * s * d * d)
    return float(macs)


def feature_dim(arch: str) -> int:
    if arch in VIT_SIZES:
        return VIT_SIZES[arch][0]
    return 512 if RESNET_STAGES[arch][1] == "basic" else 2048


def forward_flops_per_image(arch: str, image: int, *, head_hidden: int = 0,
                            projection: int = 0) -> float:
    """2 x MACs of encoder (+ projector and predictor when their sizes are
    given) for one image; an unknown architecture raises."""
    if arch in VIT_SIZES:
        macs = vit_forward_macs(arch, image)
    elif arch in RESNET_STAGES:
        macs = resnet_forward_macs(arch, image)
    else:
        raise KeyError(f"no operation count for architecture {arch!r}")
    if head_hidden and projection:
        d = feature_dim(arch)
        macs += d * head_hidden + head_hidden * projection       # projector
        macs += projection * head_hidden + head_hidden * projection
    return 2.0 * macs


def train_flops_per_image(arch: str, image: int, **heads) -> float:
    return FORWARDS_PER_TRAIN_IMAGE * forward_flops_per_image(
        arch, image, **heads)
