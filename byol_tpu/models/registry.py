"""Backbone registry with explicit feature-extractor contracts.

Replaces the reference's "any lowercase callable in torchvision.models"
discovery (main.py:30-32) + manual ``--representation-size`` matching
(main.py:59-60, Quirk Q8).  Each entry yields a module whose ``__call__(x,
train)`` returns pooled features, plus its feature dimension, plus whether
the arch contains BatchNorm (drives LARS/weight-decay exclusion masks and
lets the ViT path skip BN machinery cleanly).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import flax.linen as nn
import jax.numpy as jnp

from byol_tpu.models import resnet as resnet_lib


@dataclasses.dataclass(frozen=True)
class BackboneSpec:
    factory: Callable[..., nn.Module]    # (dtype, small_inputs) -> module
    feature_dim: int
    has_batchnorm: bool = True
    # what one sample is: 'image' = (H, W, C) float pixels in [0, 1];
    # 'tokens' = (S,) int32 ids below ``vocab_size / (chips sharing a
    # layer)`` (models/decoder_trunk.py)
    input_kind: str = "image"
    vocab_size: int = 0                  # published vocabulary ('tokens')
    # 'tokens': > 0 = a sample is ``[noised | clean]``, 2 x seq_len ids, the
    # noised half masked at one rate a block of this many ids
    diffusion_block: int = 0


_REGISTRY: Dict[str, BackboneSpec] = {}


def register(name: str, spec: BackboneSpec) -> None:
    if name in _REGISTRY:
        raise ValueError(f"backbone {name!r} already registered")
    _REGISTRY[name] = spec


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_spec(name: str) -> BackboneSpec:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown arch {name!r}; available: {available()}")
    return _REGISTRY[name]


def get_backbone(name: str, *, dtype=jnp.float32, small_inputs: bool = False,
                 **kwargs) -> Tuple[nn.Module, int]:
    spec = get_spec(name)
    module = spec.factory(dtype=dtype, small_inputs=small_inputs, **kwargs)
    return module, spec.feature_dim


def _register_resnets() -> None:
    for name in ("resnet18", "resnet34", "resnet50", "resnet101",
                 "resnet152", "resnet200", "resnet50w2", "resnet200w2",
                 # torchvision spellings (the reference's --arch accepts
                 # any torchvision callable, main.py:30-32); these widen
                 # only the bottleneck inner convs — feature dim 2048
                 "wide_resnet50_2", "wide_resnet101_2"):
        def factory(dtype=jnp.float32, small_inputs=False, _n=name, **kw):
            return resnet_lib.make_resnet(_n, dtype=dtype,
                                          small_inputs=small_inputs, **kw)
        # single source of truth: the module computes its own feature dim
        # from stage_sizes/width/expansion (resnet.py ResNet.feature_dim).
        register(name, BackboneSpec(
            factory=factory,
            feature_dim=resnet_lib.make_resnet(name).feature_dim,
            has_batchnorm=True))


_register_resnets()


def _register_vit() -> None:
    # Deferred import keeps resnet-only users off the ViT module path.
    from byol_tpu.models import vit as vit_lib
    for name, (width, depth, heads, patch) in {
            "vit_b16": (768, 12, 12, 16),
            "vit_l16": (1024, 24, 16, 16),
            "vit_s16": (384, 12, 6, 16),
    }.items():
        def factory(dtype=jnp.float32, small_inputs=False, _w=width, _d=depth,
                    _h=heads, _p=patch, **kw):
            del small_inputs  # BN-free path: no resnet stem knobs apply
            # kw passes through ViT-specific knobs: attn_impl ('dense' |
            # 'ring'), remat, pooling.
            return vit_lib.ViT(width=_w, depth=_d, num_heads=_h, patch_size=_p,
                               dtype=dtype, **kw)
        register(name, BackboneSpec(factory=factory, feature_dim=width,
                                    has_batchnorm=False))


try:
    _register_vit()
except ImportError:  # pragma: no cover - vit module lands in a later commit
    pass


def _register_decoder_trunks() -> None:
    from byol_tpu.models import decoder_trunk as trunk_lib
    # xing4_29b_a4b: huggingface.co/XingChen-AGI/Xing4.0-29B-A4B, config.json
    # qwen3_next_80b_a3b:
    # huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, config.json
    # keye_vl2_30b_a3b (the language model):
    # huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json
    # lfm2_24b_a2b: huggingface.co/LiquidAI/LFM2-24B-A2B, config.json
    # joyai_llm_flash: huggingface.co/jdopensource/JoyAI-LLM-Flash, config.json
    # sdar_30b_a3b: huggingface.co/JetLM/SDAR-30B-A3B-Chat, config.json
    # phi4_mini_flash:
    # huggingface.co/microsoft/Phi-4-mini-flash-reasoning, config.json
    for name, sizes in (("xing4_29b_a4b", trunk_lib.XING4_29B_A4B),
                        ("decoder_trunk_tiny", trunk_lib.TINY),
                        ("qwen3_next_80b_a3b", trunk_lib.QWEN3_NEXT_80B_A3B),
                        ("hybrid_trunk_tiny", trunk_lib.HYBRID_TINY),
                        ("keye_vl2_30b_a3b", trunk_lib.KEYE_VL2_30B_A3B),
                        ("sparse_trunk_tiny", trunk_lib.SPARSE_TINY),
                        ("lfm2_24b_a2b", trunk_lib.LFM2_24B_A2B),
                        ("shortconv_trunk_tiny", trunk_lib.SHORTCONV_TINY),
                        ("joyai_llm_flash", trunk_lib.JOYAI_LLM_FLASH),
                        ("latent_trunk_tiny", trunk_lib.LATENT_TINY),
                        ("sdar_30b_a3b", trunk_lib.SDAR_30B_A3B),
                        ("blockdiff_trunk_tiny", trunk_lib.BLOCKDIFF_TINY),
                        ("phi4_mini_flash", trunk_lib.PHI4_MINI_FLASH),
                        ("sambay_tiny", trunk_lib.SAMBAY_TINY)):
        def factory(dtype=jnp.float32, small_inputs=False, _z=sizes,
                    layer_share="0/1", trunk_depth="", **kw):
            del small_inputs
            if trunk_depth:
                _z = _z.cut(trunk_depth)
            return trunk_lib.DecoderTrunk(
                sizes=_z, share=trunk_lib.LayerShare.parse(layer_share),
                dtype=dtype, **kw)
        register(name, BackboneSpec(
            factory=factory, feature_dim=sizes.hidden_size,
            has_batchnorm=False, input_kind="tokens",
            vocab_size=sizes.vocab_size,
            diffusion_block=sizes.diffusion_block))


_register_decoder_trunks()


def held_vocab_rows(arch: str, layer_share: str) -> int:
    """Ids a token task may draw for ``arch`` on this chip: the rows of the
    embedding it holds."""
    from byol_tpu.models.decoder_trunk import LayerShare
    spec = get_spec(arch)
    if spec.input_kind != "tokens":
        raise ValueError(f"arch {arch!r} takes {spec.input_kind} input, "
                         "not token ids")
    return LayerShare.parse(layer_share).held(spec.vocab_size,
                                              "vocabulary rows")[1]
