"""Decoder trunk over token sequences — the integer-input encoder path.

A causal decoder stack read as a BYOL encoder: ``(B, S) int32 -> (B,
hidden)``, the mean over positions of the final-norm hidden states.  Named
by its mechanisms, sized by :class:`TrunkSizes`; a published model is one
registered instance (models/registry.py).  The shell (embedding, layers
under remat, final norm, mean pooling) and the expert layer are one; a
trunk's sizes say which token mixer each layer has and how its residual
travels.  Mechanisms nothing else in ``models/`` has:

- **latent attention** (MLA, as the DeepSeek-V3 modelling code writes it):
  low-rank query and key/value paths with an RMSNorm on each latent, a
  rotary part (YaRN-scaled) shared by all heads beside a per-head
  un-rotated part, value heads narrower than query/key heads, causal mask,
  softmax scale ``1/sqrt(d_qk) * m^2`` with ``m`` YaRN's ``mscale``; past
  two blocks of keys the core is blockwise (ops/attention.py), the rotary
  key handed over ONCE for all heads;
- **an expert layer that is told its share**: the router scores ALL
  published experts (sigmoid scores, ``noaux_tc`` selection bias, top-k,
  normalised and scaled weights); this chip holds experts ``[lo, lo + E)``
  and computes their part for every row routed to them — rows sorted by
  expert, one ragged product per matrix over the held experts, no capacity
  and no dropped row — plus the shared expert whole.  What the absent
  experts would add is left out (the other chips of the layer add it in a
  deployment; on one chip the layer runs without its exchange);
- **hyper-connected residual streams** (manifold-constrained, arXiv
  2512.24880): ``n`` streams per token, mixed round every sub-layer by maps
  computed from the streams themselves; the stream-to-stream map is made
  doubly stochastic by Sinkhorn-Knopp iterations.  A trunk with ONE stream
  has a plain residual instead (``x + F(norm(x))``);
- **a layer pattern of two mixers** (``full_attention_interval = n``): layer
  ``i`` is gated grouped-query softmax attention (:class:`GatedAttention`:
  an output gate beside every query head, RMSNorm on query and key heads,
  rotary on the first part of the head, the core blockwise over the keys —
  ops/attention.py) where ``(i + 1) % n == 0``, and Gated DeltaNet
  (models/gated_delta.py: a recurrence over the sequence, run in chunks)
  otherwise; with it come zero-centred norm gains (``x^ (1 + w)``), a
  softmax router without selection bias, and a shared expert behind a
  sigmoid gate;
- **sparse attention behind a learned indexer** (:class:`SparseAttention`,
  every layer of a trunk whose sizes carry ``sparse_attention``):
  grouped-query softmax attention whose softmax runs over a per-query SET
  of keys — the ``topk`` causal keys a second, small scorer ranks highest
  (ops/key_selection.py) — and which sows a loss of its own, the KL that
  teaches the scorer the core's attention (``LAYER_LOSS``; the train step
  adds whatever a layer sows there); with it an expert layer WITHOUT a
  shared expert;
- **a decoder-hybrid-decoder stack** (SambaY, arXiv 2507.06607: sizes that
  carry ``hybrid_decoder``): five mixers by a layer's PUBLISHED index —
  :class:`SelectiveStateSpace` (Mamba-1: a diagonal recurrence over the
  sequence on a ``[channels, state]`` state a row, ops/selective_scan.py);
  :class:`DifferentialAttention` (arXiv 2410.05258: the difference of two
  softmaxes, ``lambda`` learned) under a band of ``window`` keys
  (``window_tiles``: only the tiles the band touches are formed), under
  the full triangle, and as CROSS attention on an earlier layer's keys and
  values; :class:`GatedMemoryUnit`, a gate on an earlier layer's scan
  output.  With it **layers that hand tensors to later, non-adjacent
  layers** (``TrunkLayer`` takes and returns what is ``carried`` beside the
  streams), ``LayerNorm`` with a bias, no positional encoding at all, and a
  trunk WITHOUT an expert layer (``first_k_dense_replace =
  num_hidden_layers``: nothing sows ``ROUTING``).

:class:`LayerShare` states ONCE which of the ``of`` chips that share a layer
this one is; heads, experts and vocabulary rows held follow from it — and
where a part divides over fewer chips than the experts do (a vocabulary
over 8 of 16, heads over none), it says so there too.

Device-trace scopes (``DecoderTrunk.trace_scopes``; ``TRACE_SCOPES`` for a
latent-attention trunk: ``mla`` with ``core``; ``HYBRID_SCOPES`` for a
patterned one: ``gdn`` with ``proj``, ``conv``, ``core``, ``gate_norm``;
``gqa`` with ``core``; the
``moe`` scopes; ``SPARSE_SCOPES`` for a sparse-attention one: ``dsa`` with
``index``, ``select``, ``core``, ``index_loss``; ``SHORTCONV_SCOPES`` for one
with short convolutions: ``shortconv`` with ``proj``, ``core``; ``gqa`` with
``core``; ``ffn``; ``BLOCKDIFF_SCOPES`` for a block-diffusion one:
``blockdiff`` with ``core``): ``mla``, ``moe/route``,
``moe/experts`` (and in it ``combine``: the sum of a token's copies, forward
and as the dispatch's backward), ``moe/shared``, ``mhc`` (and ``ffn`` for a
leading dense layer) inside every layer; the train step stamps them beside
its phases so the compile cache keys them (training/steps.py).  Each routing
layer sows ``[rows held, largest load, mean load, rows dropped]`` into the
``ROUTING`` collection; the train step sums them over layers.  A
sparse-attention layer sows ``[causal pairs, selected pairs]`` into
``SELECTION`` beside them, a state-space layer ``STATE_SPACE_FIELDS`` into
``STATE_SPACE`` and a differential-attention layer ``DIFFERENTIAL_FIELDS``
into ``DIFFERENTIAL`` (each with a count of the layers that wrote, so that
the step's sum over layers becomes their mean).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from byol_tpu.core import remat as remat_lib
from byol_tpu.models.gated_delta import (GatedDeltaNet, GatedDeltaSizes,
                                          causal_conv)
from byol_tpu.ops import (expert_routing, key_selection, selective_scan,
                          sum_copies)
from byol_tpu.ops.attention import (block_diffusion_tiles,
                                    blockwise_causal_attention,
                                    dense_attention, kept_probabilities,
                                    selected_attention, window_tiles)

_MOE_SCOPES = ("moe/route", "moe/experts", "moe/experts/combine",
               "moe/shared")
TRACE_SCOPES = ("mla", "mla/core") + _MOE_SCOPES + ("mhc", "ffn")
HYBRID_SCOPES = ("gdn", "gdn/proj", "gdn/conv", "gdn/core", "gdn/gate_norm",
                 "gqa", "gqa/core") + _MOE_SCOPES
SPARSE_SCOPES = ("dsa", "dsa/index", "dsa/select", "dsa/core",
                 "dsa/index_loss") + _MOE_SCOPES
SHORTCONV_SCOPES = ("shortconv", "shortconv/proj", "shortconv/core", "gqa",
                    "gqa/core") + _MOE_SCOPES + ("ffn",)
BLOCKDIFF_SCOPES = ("blockdiff", "blockdiff/core") + _MOE_SCOPES
SAMBAY_SCOPES = ("ssm", "ssm/proj", "ssm/conv", "ssm/scan", "ssm/gate",
                 "diff", "diff/core", "gmu", "ffn")
# a trunk's scopes by the mixers its layers may have: the first set that
# holds them all
SCOPES_BY_MIXERS = (
    (frozenset({"mla"}), TRACE_SCOPES),
    (frozenset({"shortconv", "gqa"}), SHORTCONV_SCOPES),
    (frozenset({"gdn", "gqa"}), HYBRID_SCOPES),
    (frozenset({"dsa"}), SPARSE_SCOPES),
    (frozenset({"blockdiff"}), BLOCKDIFF_SCOPES),
    (frozenset({"ssm", "swa", "diff", "gmu", "xattn"}), SAMBAY_SCOPES))
# the expert layer's fallback (a step whose load passes twice the nominal
# one) forms its rows whole under this size and in slabs from it on
WHOLE_FALLBACK_BYTES = 1 << 29
ROUTING = "routing"                  # flax collection of the routing counters
ROUTING_FIELDS = ("rows_held", "load_max", "load_mean", "rows_dropped")
SELECTION = "selection"              # ... of the key-selection counters
SELECTION_FIELDS = ("causal_pairs", "selected_pairs")
LAYER_LOSS = "layer_loss"            # ... of the scalar losses layers add
STATE_SPACE = "state_space"          # ... of a selective scan's step sizes
STATE_SPACE_FIELDS = ("dt_max", "dt_mean", "decay_min", "layers")
DIFFERENTIAL = "differential"        # ... of differential attention's lambda
DIFFERENTIAL_FIELDS = ("lambda_mean", "layers")


@dataclasses.dataclass(frozen=True)
class GatedAttentionSizes:
    """One grouped-query attention layer, with an output gate or plain."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int                  # leading dims of a head that rotate
    rope_theta: float
    block: int = 512                 # the program's own: keys a block
    output_gate: bool = True         # sigmoid(gate) on the core's output
    # the program's own, and the core's ``jax.numpy`` lowering's alone (the
    # kernels of ops/causal_attention.py take the whole batch): sequences a
    # pass (0 = all).  There a score tile is ``(group, H, block, block)``
    # float32 in HBM and the compiler keeps some twenty of them alive: at 32
    # heads and 8 sequences 5 GB
    group: int = 0


@dataclasses.dataclass(frozen=True)
class SparseAttentionSizes:
    """One grouped-query attention layer behind an indexer."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    index_heads: int
    index_head_dim: int              # of the indexer's ONE key head too
    topk: int                        # keys a query keeps
    block: int = 512                 # q_chunk_size = kv_chunk_size


@dataclasses.dataclass(frozen=True)
class HybridDecoderSizes:
    """The mixers of a decoder-hybrid-decoder stack (SambaY): differential
    attention, and Mamba-1 at ``expand x hidden`` channels."""

    num_heads: int                   # query heads; PAIRS of them attend
    num_kv_heads: int
    head_dim: int
    window: int                      # keys a self-decoder attention sees
    state: int                       # d_state
    conv_taps: int                   # d_conv
    expand: int
    dt_rank: int
    block: int = 512                 # the program's own: keys a tile
    chunk: int = selective_scan.CHUNK    # ... steps between border states


@dataclasses.dataclass(frozen=True, kw_only=True)
class TrunkSizes:
    """The sizes of one decoder trunk, as its published config names them.
    A token mixer the trunk does not have keeps its zeros."""

    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int       # leading layers with a dense FFN
    # latent attention (every layer, unless a pattern is given below)
    num_attention_heads: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    attention_block: int = 512       # the program's own: keys a block
    intermediate_size: int           # dense FFN width
    n_routed_experts: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool
    norm_topk_eps: float = 1e-20     # top-k weights / (their sum + this)
    vocab_size: int
    hc_mult: int = 1                 # residual streams; 1 = plain residual
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: float = 0.0            # |logit| bound before the exp
    # the pattern: layer i is gated attention where (i + 1) % interval == 0
    # and Gated DeltaNet otherwise; 0 = latent attention everywhere
    full_attention_interval: int = 0
    gated_attention: Optional[GatedAttentionSizes] = None
    gated_delta: Optional[GatedDeltaSizes] = None
    # sparse attention behind an indexer, every layer (no pattern)
    sparse_attention: Optional[SparseAttentionSizes] = None
    # the pattern as a list: the mixer ('shortconv' | 'gqa') of every layer
    # BUILT, in order; () = one of the rules above
    layer_mixers: Tuple[str, ...] = ()
    conv_taps: int = 0               # of a 'shortconv' layer's convolution
    # block diffusion (arXiv 2503.09573): ids a block; 0 = none.  Every
    # layer's mixer is then ``gated_attention`` under the block-diffusion
    # training mask ('blockdiff') and a row of ids is ``[noised | clean]``
    diffusion_block: int = 0
    # a decoder-hybrid-decoder stack: ``layer_mixers`` lists 'ssm' | 'swa' |
    # 'diff' | 'gmu' | 'xattn' (:func:`hybrid_decoder_mixers`), every norm a
    # LayerNorm with a bias, no position enters anywhere
    hybrid_decoder: Optional[HybridDecoderSizes] = None
    # the PUBLISHED index of every layer built, in order; () = 0, 1, 2, ...
    # (a cut keeps it: differential attention's lambda_0 reads it)
    layer_index: Tuple[int, ...] = ()
    scoring_func: str = "sigmoid"    # 'sigmoid' (noaux_tc bias) | 'softmax'
    shared_expert_gate: bool = False     # sigmoid(x w_s) on the shared expert
    zero_centred_norm: bool = False      # gains are 1 + w, w from zeros
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0         # YaRN; 1 = plain rotary
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def mixer(self, layer: int) -> str:
        """The token mixer of layer ``layer``: its scope's name."""
        if self.layer_mixers:
            return self.layer_mixers[layer]
        if self.diffusion_block:
            return "blockdiff"
        if self.sparse_attention is not None:
            return "dsa"
        if not self.full_attention_interval:
            return "mla"
        return "gqa" if (layer + 1) % self.full_attention_interval == 0 \
            else "gdn"

    def published_index(self, layer: int) -> int:
        return self.layer_index[layer] if self.layer_index else layer

    def with_depth(self, dense: int, sparse: int) -> "TrunkSizes":
        """The same trunk cut to its first ``dense`` dense layers and its
        first ``sparse`` expert layers (``--trunk-depth D+S``)."""
        first = self.first_k_dense_replace
        return self._kept(tuple(range(dense))
                          + tuple(range(first, first + sparse)))

    def with_layers(self, first: int, last: int) -> "TrunkSizes":
        """The same trunk cut to its published layers ``first`` to ``last``,
        both counted (``--trunk-depth A-B``)."""
        return self._kept(tuple(range(first, last + 1)))

    def cut(self, text: str) -> "TrunkSizes":
        """``--trunk-depth``: ``D+S`` (:meth:`with_depth`) or ``A-B``
        (:meth:`with_layers`)."""
        try:
            a, b = (int(t) for t in text.split("+" if "+" in text else "-"))
        except ValueError:
            raise ValueError(
                f"trunk depth {text!r} is not 'D+S' (the first D dense and "
                "the first S expert layers) or 'A-B' (published layers A to "
                "B)") from None
        return self.with_depth(a, b) if "+" in text else self.with_layers(a, b)

    def _kept(self, kept: Tuple[int, ...]) -> "TrunkSizes":
        """The layers ``kept`` (published indices of an uncut trunk).  A
        listed pattern keeps the mixer of each layer kept, by its PUBLISHED
        index; every layer keeps that index (``layer_index``) and whether it
        is dense."""
        if self.layer_index or not kept or any(
                not 0 <= i < self.num_hidden_layers for i in kept):
            raise ValueError(
                f"layers {kept} are not layers of an uncut trunk of "
                f"{self.num_hidden_layers}")
        return dataclasses.replace(
            self, num_hidden_layers=len(kept),
            first_k_dense_replace=sum(
                i < self.first_k_dense_replace for i in kept),
            layer_index=kept,
            layer_mixers=tuple(self.layer_mixers[i] for i in kept)
            if self.layer_mixers else ())


def hybrid_decoder_mixers(layers: int, period: int = 2) -> Tuple[str, ...]:
    """The mixer of every published layer of a decoder-hybrid-decoder stack
    of ``layers`` layers (the public ``phi4flash`` modelling code,
    ``mb_per_layer = period``): layer ``i`` is of the Mamba kind where ``i %
    period == 0`` and of the attention kind elsewhere.  The first half is the
    SELF-DECODER: Mamba ('ssm'), attention under the band ('swa').  Layer
    ``layers / 2`` is the Mamba layer whose scan output is kept, the
    attention layer after it the ONE full attention layer ('diff'), whose
    keys and values are kept.  From there on the CROSS-DECODER: a gated
    memory unit on the kept scan output ('gmu': no scan), cross attention on
    the kept keys and values ('xattn': a query projection only)."""
    half = layers // 2

    def mixer(i):
        if i % period == 0:
            return "ssm" if i <= half else "gmu"
        return "swa" if i < half else "diff" if i < half + period \
            else "xattn"
    return tuple(mixer(i) for i in range(layers))


@dataclasses.dataclass(frozen=True)
class LayerShare:
    """Chip ``index`` of the ``of`` chips that share every layer (expert- and
    head-parallel, vocabulary rows split the same way).  A part that
    divides over FEWER chips is named in ``over``: ``0/16,vocab=8,heads=1``
    is chip 0 of 16 expert-parallel chips whose vocabulary is split over 8
    of them (chip ``i`` holds slice ``i % 8``) and whose heads are whole on
    every chip."""

    index: int = 0
    of: int = 1
    over: Tuple[Tuple[str, int], ...] = ()     # (part, chips it divides over)

    PARTS = {"vocab": "vocabulary rows", "heads": "attention heads"}

    @classmethod
    def parse(cls, text: str) -> "LayerShare":
        chip, *parts = text.split(",")
        try:
            index, of = (int(t) for t in chip.split("/"))
            over = tuple((cls.PARTS[name], int(n)) for name, n in
                         (part.split("=") for part in parts))
        except (ValueError, KeyError):
            raise ValueError(
                f"layer share {text!r} is not 'i/n' (chip i of the n that "
                "share a layer), optionally ',vocab=m' and ',heads=m' for "
                "a part that divides over m of them") from None
        if not 0 <= index < of or any(n < 1 or of % n for _, n in over):
            raise ValueError(f"layer share {text!r}: need 0 <= i < n, and "
                             "every m a divisor of n")
        return cls(index, of, over)

    def held(self, total: int, what: str) -> Tuple[int, int]:
        """``(first, count)`` of ``total`` heads / experts / rows held."""
        of = dict(self.over).get(what, self.of)
        if total % of:
            raise ValueError(
                f"{total} {what} do not divide over {of} chips")
        count = total // of
        return self.index % of * count, count


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(sizes: TrunkSizes) -> list:
    """Rotary inverse frequencies of the ``qk_rope_head_dim`` part under
    YaRN: interpolated (``/ factor``) below the ``beta_slow`` correction
    dimension, untouched above ``beta_fast``, a linear ramp between.
    Plain Python floats: the sizes are static, nothing here is traced."""
    dim, base = sizes.qk_rope_head_dim, sizes.rope_theta
    extra = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    if sizes.rope_factor <= 1.0:
        return extra

    def correction_dim(rotations):
        return dim * math.log(sizes.rope_original_max_position
                              / (rotations * 2 * math.pi)) / (
                                  2 * math.log(base))
    low = max(math.floor(correction_dim(sizes.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(sizes.rope_beta_slow)), dim - 1)
    ramp = [min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
            for i in range(dim // 2)]
    return [f / sizes.rope_factor * r + f * (1.0 - r)
            for f, r in zip(extra, ramp)]


@functools.lru_cache(maxsize=8)
def _rotary_values(sizes: TrunkSizes, seq_len: int):
    """``cos, sin`` as rows of Python floats (angles in double precision);
    every layer of every pass asks for the same ones."""
    freqs = yarn_inv_freq(sizes)
    scale = yarn_mscale(sizes.rope_factor, sizes.rope_mscale) / yarn_mscale(
        sizes.rope_factor, sizes.rope_mscale_all_dim)
    rows = lambda fn: tuple(tuple(fn(p * f) * scale for f in freqs)
                            for p in range(seq_len))
    return rows(math.cos), rows(math.sin)


def rotary_tables(sizes: TrunkSizes, seq_len: int):
    """``cos, sin`` of shape ``(S, rope_dim / 2)``, float32 constants."""
    cos, sin = _rotary_values(sizes, seq_len)
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def apply_rotary(x, cos, sin):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by the
    position's angle; ``x`` is ``(B, S, ..., rope_dim)``, the tables
    ``(S, rope_dim / 2)``.  The result holds the first components then the
    second (the published code's layout; a dot product of two vectors
    rotated this way does not depend on the layout)."""
    shape = x.shape
    pairs = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    extra = (1,) * (x.ndim - 3)
    cos = cos.reshape((1, shape[1]) + extra + (cos.shape[-1],))
    sin = sin.reshape((1, shape[1]) + extra + (sin.shape[-1],))
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    """``x / rms(x) * scale``; ``zero_centred``: ``x / rms(x) * (1 +
    scale)`` with ``scale`` from zeros."""

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.zero_centred
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.zero_centred:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * scale + bias``, statistics in
    float32."""

    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        y = centred * jax.lax.rsqrt(
            jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + self.eps)
        return (y * scale + bias).astype(self.dtype)


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def _trunk_norm(sizes, dtype, name):
    """The norm of a trunk's residual stream: RMSNorm, or, in a
    decoder-hybrid-decoder stack, LayerNorm with a bias."""
    if sizes.hybrid_decoder is not None:
        return LayerNorm(sizes.rms_norm_eps, dtype, name=name)
    return RMSNorm(sizes.rms_norm_eps, dtype, sizes.zero_centred_norm,
                   name=name)


class LatentAttention(nn.Module):
    """MLA over the heads this chip holds; ``q_a`` / ``kv_a`` are whole,
    ``q_b`` / ``kv_b`` / ``o`` are the held heads' slices.

    The core (scope ``core``: ``mla/attn/core`` on the device), by a rule on
    shapes alone.  A sequence of MORE than two blocks of keys (``2 x
    sizes.attention_block``) takes ``blockwise_causal_attention``: ``q_nope,
    k_nope`` a head, values narrower than keys, and the rotary part as its
    ``shared`` pair — the ONE rotary key of all heads handed over as ``(B, S,
    d_rope)``, never copied a head; no ``[.., S, S]`` array at any length
    (all 32 heads at 4,096 keys: 17 GB of scores a layer otherwise).  Up to
    two blocks it stays the dense form, op for op the program it was: a
    row's scores are then at most four tiles, and ``xing4_train_b8_s1024``
    (4 held heads, 1,024 keys: 256 MiB of scores, the core about 1% of its
    operations) was accepted and tuned on that program — its lowered step
    differs from PR 39's by the stamped scope name alone, so nothing there
    can slow, and no cell sits between the two sides of the rule to say
    where the blockwise core starts to win (ROADMAP Queue 2, item 12)."""

    sizes: TrunkSizes
    heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        z, heads = self.sizes, self.heads
        b, s, _ = h.shape
        dn, dr, dv = z.qk_nope_head_dim, z.qk_rope_head_dim, z.v_head_dim
        norm = lambda name: RMSNorm(z.rms_norm_eps, self.dtype, name=name)
        c_q = norm("q_norm")(_dense(z.q_lora_rank, self.dtype, "q_a")(h))
        q = _dense(heads * (dn + dr), self.dtype, "q_b")(c_q)
        q = q.reshape(b, s, heads, dn + dr)
        kv_a = _dense(z.kv_lora_rank + dr, self.dtype, "kv_a")(h)
        c_kv = norm("kv_norm")(kv_a[..., :z.kv_lora_rank])
        k_rope = kv_a[..., z.kv_lora_rank:]                 # one, all heads
        kv = _dense(heads * (dn + dv), self.dtype, "kv_b")(c_kv)
        kv = kv.reshape(b, s, heads, dn + dv)
        cos, sin = rotary_tables(z, s)
        rotated = lambda x: apply_rotary(x, cos, sin)
        scale = z.qk_head_dim ** -0.5 * yarn_mscale(
            z.rope_factor, z.rope_mscale_all_dim) ** 2
        by_head = lambda x: x.transpose(0, 2, 1, 3)
        if s > 2 * z.attention_block:
            q_rope = rotated(q[..., dn:])
            k_rope = rotated(k_rope[:, :, None, :])[:, :, 0]
            core = lambda: blockwise_causal_attention(
                by_head(q[..., :dn]), by_head(kv[..., :dn]),
                by_head(kv[..., dn:]), scale=scale, block=z.attention_block,
                shared=(by_head(q_rope), k_rope))
        else:              # op for op the program it was: see the docstring
            q = jnp.concatenate([q[..., :dn], rotated(q[..., dn:])], axis=-1)
            k_rope = rotated(k_rope[:, :, None, :])
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, heads, dr))],
                axis=-1)
            core = lambda: dense_attention(
                by_head(q), by_head(k), by_head(kv[..., dn:]), scale=scale,
                causal=True)
        with jax.named_scope("core"):
            out = core()
        out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * dv)
        return _dense(z.hidden_size, self.dtype, "o")(out)


@functools.lru_cache(maxsize=8)
def _half_rotary_values(theta: float, dim: int, seq_len: int):
    """``cos, sin`` as rows of Python floats (angles in double precision),
    as :func:`_rotary_values`: plain rotary at base ``theta``."""
    freqs = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    rows = lambda fn: tuple(tuple(fn(p * f) for f in freqs)
                            for p in range(seq_len))
    return rows(math.cos), rows(math.sin)


def half_rotary_tables(theta: float, dim: int, seq_len: int):
    """``cos, sin`` of shape ``(S, dim / 2)``, float32 constants."""
    cos, sin = _half_rotary_values(theta, dim, seq_len)
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def apply_half_rotary(x, cos, sin):
    """Rotate the pairs ``(x[i], x[i + r/2])`` of the FIRST ``r = 2 x
    cos.shape[-1]`` dims of the last axis by the position's angle (the
    rotate-half layout), and leave the rest of the head alone.  ``x`` is
    ``(B, S, H, D)``."""
    half = cos.shape[-1]
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:2 * half], x32[..., 2 * half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1).astype(x.dtype)


class GatedAttention(nn.Module):
    """Grouped-query softmax attention (the public ``qwen3_next`` modelling
    code; ``lfm2_moe``'s without the gate): ``q`` and ``k`` heads are
    RMS-normalised (``zero_centred``: the gain is ``1 + w``), the first
    ``rotary_dim`` dims of a head rotate, ``kv_heads`` key/value heads serve
    ``heads`` query heads, and the core's output goes through ``o``.  With
    ``sizes.output_gate`` ``q`` comes with a gate of its own width per head
    and ``out = softmax(.) v * sigmoid(gate)``.

    ``diffusion_block`` > 0: the block-diffusion training forward.  A row is
    ``[noised | clean]``, ``S = 2 L``; the n-th noised and the n-th clean
    row both stand at position ``n`` (the rotary tables repeat), and the
    core runs under ``block_diffusion_tiles`` instead of the causal rule:
    a clean query sees the clean keys of its own and earlier blocks, a
    noised one the clean keys of earlier blocks and its own noised block."""

    sizes: GatedAttentionSizes
    heads: int
    kv_heads: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    zero_centred: bool = True
    diffusion_block: int = 0

    @nn.compact
    def __call__(self, h):
        z, dt = self.sizes, self.dtype
        b, s, d = h.shape
        dh = z.head_dim
        gate = None
        if z.output_gate:
            q = _dense(self.heads * dh * 2, dt, "q")(h).reshape(
                b, s, self.heads, 2 * dh)
            q, gate = q[..., :dh], q[..., dh:]
        else:
            q = _dense(self.heads * dh, dt, "q")(h).reshape(
                b, s, self.heads, dh)
        k = _dense(self.kv_heads * dh, dt, "k")(h).reshape(
            b, s, self.kv_heads, dh)
        v = _dense(self.kv_heads * dh, dt, "v")(h).reshape(
            b, s, self.kv_heads, dh)
        norm = lambda name: RMSNorm(self.eps, dt, self.zero_centred,
                                    name=name)
        tiles = None                                       # causal
        if self.diffusion_block:
            if s % (2 * z.block):
                raise ValueError(
                    f"a block-diffusion row is [noised | clean], each half "
                    f"whole tiles of {z.block}; got {s} ids")
            cos, sin = (jnp.tile(t, (2, 1)) for t in half_rotary_tables(
                z.rope_theta, z.rotary_dim, s // 2))
            tiles = block_diffusion_tiles(s // (2 * z.block),
                                          self.diffusion_block)
        else:
            cos, sin = half_rotary_tables(z.rope_theta, z.rotary_dim, s)
        q = apply_half_rotary(norm("q_norm")(q), cos, sin)
        k = apply_half_rotary(norm("k_norm")(k), cos, sin)
        with jax.named_scope("core"):
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            out = blockwise_causal_attention(
                q, k, v, scale=dh ** -0.5, block=z.block, group=z.group,
                tiles=tiles)
        out = out.transpose(0, 2, 1, 3)
        if gate is not None:
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
        return _dense(d, dt, "o")(out.reshape(b, s, self.heads * dh))


class ShortConv(nn.Module):
    """A gated short convolution (the public ``lfm2`` modelling code):
    ``[B, C, u] = split3(x W_in)``, ``z[t] = sum_j taps[j] (B * u)[t - (K-1)
    + j]`` (depthwise, causal, nothing before the sequence's start, no
    bias), ``(C * z) W_out``.  No activation anywhere in it."""

    taps: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        d, dt = h.shape[-1], self.dtype
        with jax.named_scope("proj"):
            mixed = _dense(3 * d, dt, "in_proj")(h)
        with jax.named_scope("core"):
            taps = self.param("conv", nn.initializers.lecun_normal(),
                              (self.taps, d), jnp.float32)
            gate_in, gate_out, u = jnp.split(mixed, 3, axis=-1)
            out = gate_out * causal_conv(gate_in * u, taps.astype(dt))
        with jax.named_scope("proj"):
            return _dense(d, dt, "out_proj")(out)


class SparseAttention(nn.Module):
    """Grouped-query softmax attention over the keys an indexer picks
    (Keye-VL-2.0's ``sa_config``; the indexer as the DeepSeek-V3.2-Exp
    report describes it).  ``q, k`` heads RMS-normalised with a gain, rotary
    over the whole head.  The indexer reads ``stop_gradient(h)``: ``J`` small
    query heads and a weight each against ONE key head, ``I[t, s] = sum_j
    w[t, j] relu(qI[t, j] . kI[s]) / sqrt(d_I J)``, rotary over its whole
    head too.  Query ``t`` attends the ``min(t + 1, topk)`` causal keys of
    largest ``I[t, .]``, all heads the same set, and the layer sows ``mean_t
    KL(p_t || softmax_{S_t} I[t, .])`` with ``p_t`` the core's head-mean
    probabilities under stop-gradient — so the trunk takes no gradient from
    that loss and the indexer none from any other."""

    sizes: SparseAttentionSizes
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        z, dt = self.sizes, self.dtype
        b, s, d = h.shape
        dh, di, blk = z.head_dim, z.index_head_dim, z.block
        heads = lambda x, n: x.reshape(b, s, n, x.shape[-1] // n)
        norm = lambda name: RMSNorm(self.eps, dt, name=name)
        # the block passes below take whole blocks: a last, short one is
        # filled with rows after every real one, which no real query sees
        whole = lambda x: jnp.pad(
            x, [(0, 0), (0, -s % blk)] + [(0, 0)] * (x.ndim - 2))
        cos, sin = half_rotary_tables(z.rope_theta, dh, s)
        q = heads(_dense(z.num_heads * dh, dt, "q")(h), z.num_heads)
        k = heads(_dense(z.num_kv_heads * dh, dt, "k")(h), z.num_kv_heads)
        v = heads(_dense(z.num_kv_heads * dh, dt, "v")(h), z.num_kv_heads)
        q, k, v = (whole(x).transpose(0, 2, 1, 3) for x in (
            apply_half_rotary(norm("q_norm")(q), cos, sin),
            apply_half_rotary(norm("k_norm")(k), cos, sin), v))
        with jax.named_scope("index"):
            seen = jax.lax.stop_gradient(h)
            cos_i, sin_i = half_rotary_tables(z.rope_theta, di, s)
            q_i = apply_half_rotary(heads(_dense(
                z.index_heads * di, dt, "index_q")(seen), z.index_heads),
                cos_i, sin_i)
            k_i = apply_half_rotary(heads(_dense(
                di, dt, "index_k")(seen), 1), cos_i, sin_i)[:, :, 0]
            w = _dense(z.index_heads, dt, "index_w")(seen)
            scores = key_selection.index_scores(
                whole(q_i), whole(k_i), whole(w), block=blk,
                scale=(di * z.index_heads) ** -0.5)
        with jax.named_scope("select"):
            selected = key_selection.select_top_keys(scores, z.topk,
                                                     block=blk)
            self.sow(SELECTION, "pairs",
                     key_selection.pair_counts(selected, s))
        with jax.named_scope("core"):
            out, lse = selected_attention(q, k, v, selected,
                                          scale=dh ** -0.5, block=blk)
        with jax.named_scope("index_loss"):
            self.sow(LAYER_LOSS, "index", key_selection.index_loss(
                scores, kept_probabilities(
                    *jax.lax.stop_gradient((q, k, lse)), selected,
                    scale=dh ** -0.5, block=blk), selected, s))
        out = out[:, :, :s].transpose(0, 2, 1, 3)
        return _dense(d, dt, "o")(out.reshape(b, s, z.num_heads * dh))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba's own: the inverse softplus of a step ``~ logU(1e-3, 0.1)``."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype) * (
        math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return step + jnp.log(-jnp.expm1(-step))


class SelectiveStateSpace(nn.Module):
    """A Mamba-1 mixer (arXiv 2312.00752; the public ``phi4flash`` modelling
    code): ``[a | z] = x W_in``; ``u = silu(conv(a) + b_c)`` (depthwise,
    causal, zeros before the row); ``[dt | B | C] = u W_x``; ``delta =
    softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
    (ops/selective_scan.py) gives ``m``; the mixer's output is ``(m *
    silu(z)) W_out``.  Returns the output AND ``m``, before its gate: what
    the gated memory units of later layers read.  ``delta``, ``A``, the
    state and the scan's sums are float32."""

    sizes: HybridDecoderSizes
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        z, dt = self.sizes, self.dtype
        inner, n = z.expand * x.shape[-1], z.state
        f32 = lambda t: t.astype(jnp.float32)
        with jax.named_scope("proj"):
            mixed = _dense(2 * inner, dt, "in_proj")(x)
        with jax.named_scope("conv"):
            taps = self.param("taps", nn.initializers.lecun_normal(),
                              (z.conv_taps, inner), jnp.float32)
            conv_bias = self.param("conv_bias", nn.initializers.zeros,
                                   (inner,), jnp.float32)
            u = nn.silu(f32(causal_conv(mixed[..., :inner], taps.astype(dt)))
                        + conv_bias).astype(dt)
        with jax.named_scope("proj"):
            low = _dense(z.dt_rank + 2 * n, dt, "x_proj")(u)
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,),
                                 jnp.float32)
            delta = jax.nn.softplus(f32(_dense(inner, dt, "dt_proj")(
                low[..., :z.dt_rank])) + dt_bias)
        with jax.named_scope("scan"):
            a_log = self.param(
                "A_log", lambda *_: jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (inner, n))))
            skip = self.param("D", nn.initializers.ones, (inner,),
                              jnp.float32)
            a = -jnp.exp(a_log)
            m = selective_scan.selective_scan(
                u, delta, a, f32(low[..., z.dt_rank:z.dt_rank + n]),
                f32(low[..., z.dt_rank + n:]), skip, chunk=z.chunk)
            # the smallest exp(delta A): where a state forgets at once
            fastest = jnp.min(delta * jnp.min(a, axis=1))
            self.sow(STATE_SPACE, "steps", jnp.stack([
                jnp.max(delta), jnp.mean(delta), jnp.exp(fastest),
                jnp.ones((), jnp.float32)]))
        with jax.named_scope("gate"):
            gated = (m * nn.silu(f32(mixed[..., inner:]))).astype(dt)
        with jax.named_scope("proj"):
            return _dense(x.shape[-1], dt, "out_proj")(gated), m.astype(dt)


class GatedMemoryUnit(nn.Module):
    """``(m * silu(x W_in)) W_out``, ``m`` an earlier layer's scan output,
    position by position (SambaY, arXiv 2507.06607 section 2): no scan, no
    convolution."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, m):
        dt = self.dtype
        gate = _dense(m.shape[-1], dt, "in_proj")(x)
        gated = (m.astype(jnp.float32)
                 * nn.silu(gate.astype(jnp.float32))).astype(dt)
        return _dense(x.shape[-1], dt, "out_proj")(gated)


def lambda_init(layer: int) -> float:
    """Differential attention's ``lambda_0`` of a layer, by its PUBLISHED
    index (arXiv 2410.05258 section 2.1)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class DifferentialAttention(nn.Module):
    """Differential attention (arXiv 2410.05258, as the public ``phi4flash``
    modelling code runs it on grouped heads): the ``H`` query heads are ``H /
    2`` PAIRS ``(q1_i, q2_i)``, the ``Hkv`` key heads ``Hkv / 2`` pairs, the
    value heads ``Hkv / 2`` heads twice as wide, ``[v1_j | v2_j]``; pair
    ``i`` reads key/value pair ``i // (H / Hkv)``; ``o_i = softmax(q1_i
    k1_j^T / sqrt(d)) v_j - lambda softmax(q2_i k2_j^T / sqrt(d)) v_j``,
    ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_0``; ``o_i <- (1 -
    lambda_0) rmsnorm(o_i)`` with a gain of the doubled width; the pairs'
    outputs are read as ``H`` heads again and go through ``o``.  No rotary,
    no position.  ``window`` > 0: a query sees the ``window`` latest keys
    (``window_tiles``); 0: every causal key.  ``kv``: None — this layer
    projects its own keys and values and hands them on — or the ``(k, v)``
    an earlier layer handed on (CROSS attention: a query projection only).

    The core is ONE call of ``blockwise_causal_attention``: the two
    softmaxes of a pair are two of its key heads (``Hkv`` of them, keys as
    they lie) on the pair's value head, repeated; the query heads are turned
    ``(pair, softmax, query of the group)`` for it.  Statistics and
    ``lambda`` float32."""

    sizes: HybridDecoderSizes
    layer: int                       # PUBLISHED index: lambda_0 reads it
    window: int = 0
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, kv=None):
        z, dt = self.sizes, self.dtype
        b, s, d = x.shape
        h, hkv, dh = z.num_heads, z.num_kv_heads, z.head_dim
        pairs, group = hkv // 2, h // hkv
        if kv is None:
            qkv = _dense((h + 2 * hkv) * dh, dt, "qkv")(x)
            q = qkv[..., :h * dh]
            k = qkv[..., h * dh:(h + hkv) * dh].reshape(
                b, s, hkv, dh).transpose(0, 2, 1, 3)
            v = qkv[..., (h + hkv) * dh:].reshape(
                b, s, pairs, 2 * dh).transpose(0, 2, 1, 3)
        else:
            q = _dense(h * dh, dt, "q")(x)
            k, v = kv
        vector = lambda name: self.param(
            name, nn.initializers.normal(stddev=0.1), (dh,), jnp.float32)
        lambda_0 = lambda_init(self.layer)
        lam = (jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
               - jnp.exp(jnp.sum(vector("lambda_q2") * vector("lambda_k2")))
               + lambda_0)
        self.sow(DIFFERENTIAL, "lambda",
                 jnp.stack([lam, jnp.ones((), jnp.float32)]))
        with jax.named_scope("core"):
            # query head 2 (group j + g) + c -> (j, c, g)
            q = q.reshape(b, s, pairs, group, 2, dh).transpose(
                0, 2, 4, 3, 1, 5).reshape(b, h, s, dh)
            tiles = None if not self.window or self.window >= s else \
                window_tiles(-(-s // z.block), self.window, z.block)
            out = blockwise_causal_attention(
                q, k, jnp.repeat(v, 2, axis=1), scale=dh ** -0.5,
                block=z.block, tiles=tiles)
            out = out.reshape(b, pairs, 2, group, s, 2 * dh).astype(
                jnp.float32)
            out = out[:, :, 0] - lam * out[:, :, 1]
        out = RMSNorm(self.eps, dt, name="subln")(out) * jnp.asarray(
            1.0 - lambda_0, dt)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * dh)
        return _dense(d, dt, "o")(out), (k, v)


class GatedMLP(nn.Module):
    """SwiGLU: ``down(silu(gate x) * up x)``."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.width, self.dtype, "gate")(x)
        up = _dense(self.width, self.dtype, "up")(x)
        return _dense(x.shape[-1], self.dtype, "down")(nn.silu(gate) * up)


def _sum_copies(rows, pos, ok, by_token=None):
    """``out[t] = sum_j rows[pos[t, j]]`` over the copies ``ok`` marks: a
    token's row back from the (up to k) sorted rows that are its copies,
    added in float32 and rounded once.  Two lowerings of the one sum.  With
    the window's rows ``by_token`` (``ops/sum_copies.py``): one gather of the
    ``cap`` rows into token order and a segment sum over them on the matrix
    unit, so the cost follows the rows held.  Without: one gather of
    ``(tokens, D)`` a slot, the k of them added in slot order in one pass
    (gathered as one ``(tokens, k, D)`` array, k lands on the tiled minor
    dimensions and the TPU compiler relays out all k copies of the hidden
    states before it sums them: PERF.md section 6, PR 30)."""
    with jax.named_scope("combine"):
        if by_token is not None:
            return sum_copies.sum_copies(rows, by_token, pos.shape[0])
        total = None
        for j in range(pos.shape[1]):
            copy = jnp.where(ok[:, j, None], rows[pos[:, j]],
                             0).astype(jnp.float32)
            total = copy if total is None else total + copy
        return total.astype(rows.dtype)


# Dispatch and combine are each other's transpose: ``rows[p] = x[idx[p]]``
# one way, ``out[t] = sum of the rows that are t's copies`` the other.  Both
# are GATHERS here (a scatter-add of the same rows took five times as long
# on the chip), which autodiff cannot know: it would transpose either gather
# into a scatter-add.
@jax.custom_vjp
def _take_rows(x, idx, pos, ok, by_token=None):
    return x[idx]


def _take_fwd(x, idx, pos, ok, by_token):
    return x[idx], (pos, ok, by_token)


def _take_bwd(res, g):
    return _sum_copies(g, *res), None, None, None, None


_take_rows.defvjp(_take_fwd, _take_bwd)


@jax.custom_vjp
def _put_rows(rows, idx, pos, ok, by_token=None):
    return _sum_copies(rows, pos, ok, by_token)


def _put_fwd(rows, idx, pos, ok, by_token):
    return _sum_copies(rows, pos, ok, by_token), idx


def _put_bwd(idx, g):
    return g[idx], None, None, None, None


_put_rows.defvjp(_put_fwd, _put_bwd)


class ExpertWeights(nn.Module):
    """The held experts' three matrices, each stacked on a leading expert
    axis.  The module is named ``experts``: ``optim/lars.py`` gives every
    slice of a leaf below that name a trust ratio of its own."""

    held: int
    hidden: int
    width: int

    @nn.compact
    def __call__(self):
        init = nn.initializers.variance_scaling(     # fan-in: its own rows
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        e, d, f = self.held, self.hidden, self.width
        return (self.param("gate", init, (e, d, f), jnp.float32),
                self.param("up", init, (e, d, f), jnp.float32),
                self.param("down", init, (e, f, d), jnp.float32))


class ExpertLayer(nn.Module):
    """Routed experts ``[lo, lo + held)`` of ``n_routed_experts`` plus the
    shared expert.  Every row routed to a held expert is computed.  The
    sizes name the scoring rule (sigmoid scores with a selection bias, or a
    softmax over all experts); from the chosen experts down there is one
    path.

    ``route`` hands on the k choices of a token (``chosen``, ``weight``) and
    the dispatch tables of its ``tokens x k`` copies in EXPERT ORDER
    (``ops/expert_routing.py``: counting kernels on a TPU, ``top_k`` and
    ``argsort`` elsewhere, the same values).  DEFINED: ``group_sizes`` and
    ``rows_held`` everywhere; ``place[t, j]``, a copy's sorted row, where a
    held expert owns the copy (``here_2d``); ``token_of[r]`` and
    ``weight_of[r]``, a sorted row's token and routing weight, for ``r <
    rows_held``.  Elsewhere they are only kept in bounds (``weight_of`` is 0
    there): every use of ``place`` is under ``here_2d &`` or clipped into
    the window, and rows at or past ``rows_held`` are masked on the way in
    and out (``valid``)."""

    sizes: TrunkSizes
    lo: int
    held: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        z, dt = self.sizes, self.dtype
        b, s, d = h.shape
        k, f = z.num_experts_per_tok, z.moe_intermediate_size
        x = h.reshape(b * s, d)
        tokens = x.shape[0]
        with jax.named_scope("route"):
            router = self.param(
                "router", nn.initializers.lecun_normal(),
                (d, z.n_routed_experts), jnp.float32)
            logits = jnp.dot(x.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            # which lowering the k choices and the tables take: from the
            # shapes (ops/expert_routing.py)
            kernel = expert_routing.applies(tokens, z.n_routed_experts, k,
                                            self.held)
            with jax.named_scope("choose"):
                if z.scoring_func == "softmax":
                    # the k largest ARE the weights
                    weight, chosen = expert_routing.choose(
                        jax.nn.softmax(logits, axis=-1), None, k,
                        kernel=kernel)
                else:
                    # noaux_tc: a selection bias that takes no gradient (it
                    # moves which experts are chosen, never their weights)
                    bias = self.param("e_score_correction_bias",
                                      nn.initializers.zeros,
                                      (z.n_routed_experts,), jnp.float32)
                    scores = jax.nn.sigmoid(logits)
                    weight, chosen = expert_routing.choose(
                        scores + bias, scores, k, kernel=kernel)
            if z.norm_topk_prob and k > 1:
                weight = weight / (jnp.sum(weight, -1, keepdims=True)
                                   + z.norm_topk_eps)
            weight = weight * z.routed_scaling_factor
            local = chosen - self.lo
            here_2d = (local >= 0) & (local < self.held)
            with jax.named_scope("tables"):
                place, token_of, weight_of, group_sizes = (
                    expert_routing.tables(chosen, weight, self.lo, self.held,
                                          kernel=kernel))
            rows_held = jnp.sum(group_sizes)
        with jax.named_scope("experts"):
            w_gate, w_up, w_down = ExpertWeights(
                self.held, d, f, name="experts")()
            every = tokens * k

            def held_experts(idx, ok, pos, valid, sizes, weights):
                """The held experts over a window of the sorted copies:
                ``idx`` their tokens, ``ok`` / ``pos`` which copies of a
                token lie in it and where, ``sizes`` the experts' rows in
                it, ``weights()`` the copies' routing weights.  A ragged
                product writes no row beyond its groups: rows ``valid``
                leaves out are masked on the way in AND out, so neither
                they nor their cotangents reach a token."""
                ragged = lambda lhs, w: jax.lax.ragged_dot(
                    lhs, w.astype(dt), sizes)
                # which lowering the two sums over a token's copies take
                # (the combine, the dispatch's backward): from the shapes
                by_token = None
                if sum_copies.applies(tokens, k, idx.shape[0], d, dt):
                    with jax.named_scope("combine"):
                        by_token = sum_copies.by_token(idx, valid[:, 0],
                                                       tokens)
                rows = jnp.where(
                    valid, _take_rows(x, idx, pos, ok, by_token), 0)
                act = nn.silu(ragged(rows, w_gate)) * ragged(rows, w_up)
                # the copy's routing weight goes on BEFORE the last product
                # (it is linear): (cap, f) to scale, not (cap, d)
                act = (act * weights()).astype(dt)
                out = jnp.where(valid, ragged(act, w_down), 0)
                return _put_rows(out, idx, pos, ok, by_token)

            def product(cap):
                """The first ``cap`` sorted copies."""
                return held_experts(
                    idx=token_of[:cap], ok=here_2d & (place < cap),
                    pos=jnp.minimum(place, cap - 1),
                    valid=(jnp.arange(cap) < rows_held)[:, None],
                    sizes=group_sizes, weights=lambda: weight_of[:cap, None])

            def slab(cap, start):
                """``cap`` sorted copies from ``start`` (traced) on.  The
                last slab ends with the copies: it starts early, and the
                rows it shares with the one before are masked."""
                first = jnp.minimum(start, every - cap)
                window = lambda a: jax.lax.dynamic_slice_in_dim(a, first, cap)
                ends = jnp.cumsum(group_sizes)
                row = first + jnp.arange(cap)
                return held_experts(
                    idx=window(token_of),
                    ok=here_2d & (place >= start) & (place < first + cap),
                    pos=jnp.clip(place - first, 0, cap - 1),
                    valid=((row >= start) & (row < rows_held))[:, None],
                    sizes=jnp.clip(
                        jnp.minimum(ends, first + cap)
                        - jnp.maximum(ends - group_sizes, first), 0, None),
                    weights=lambda: window(weight_of)[:, None])

            def in_slabs(cap):
                """Every copy, ``cap`` at a time, each slab under
                ``jax.checkpoint``: the memory of one slab, whatever the
                load."""
                one = jax.checkpoint(lambda start: slab(cap, start))
                total, _ = jax.lax.scan(
                    lambda total, start: (
                        total + one(start).astype(jnp.float32), None),
                    jnp.zeros((tokens, d), jnp.float32),
                    jnp.arange(0, every, cap))
                return total.astype(dt)

            # Shapes are static, loads are not.  At the nominal load a chip
            # gets ``k x held / published`` copies per token; gathers and
            # scatters over twice that many rows serve every step whose
            # load stays under it, and a step whose load does not takes
            # the same product over ALL ``tokens x k`` copies: no capacity,
            # no dropped row, and the common step does not pay for the
            # worst one.  Where one ``(every, D)`` array of that fallback
            # would reach ``WHOLE_FALLBACK_BYTES`` (a 64-way router's 131,072
            # copies of 2,048 — beside them experts 1,536 wide — a 128-way
            # router's 262,144, a 512-way router's 327,680: the branch not
            # taken would hold 3.5 to 4.5 GB of the step's memory) it runs
            # in slabs of the usual size.
            usual = min(every, -(-2 * every * self.held
                                 // z.n_routed_experts))
            whole = every * d * jnp.dtype(dt).itemsize < WHOLE_FALLBACK_BYTES
            if usual == every:
                routed = product(every)
            else:
                routed = jax.lax.cond(
                    rows_held <= usual, lambda: product(usual),
                    (lambda: product(every)) if whole
                    else (lambda: in_slabs(usual)))
        shared = None
        if z.n_shared_experts:                  # a trunk may have none
            with jax.named_scope("shared"):
                shared = GatedMLP(f * z.n_shared_experts, dt,
                                  name="shared")(x)
                if z.shared_expert_gate:
                    shared = shared * jax.nn.sigmoid(_dense(
                        1, dt, "shared_gate")(x).astype(
                            jnp.float32)).astype(dt)
        load = group_sizes.astype(jnp.float32)
        self.sow(ROUTING, "stats", jnp.stack([
            rows_held.astype(jnp.float32), jnp.max(load), jnp.mean(load),
            (jnp.sum(here_2d) - rows_held).astype(jnp.float32)]))
        if shared is not None:
            routed = routed + shared
        return routed.reshape(b, s, d)


class HyperConnection(nn.Module):
    """The three maps of one sub-layer, from the ``n`` streams (a tuple of
    ``(..., D)`` arrays): ``h_pre (..., n)``, ``h_post (..., n)``, ``h_res
    (..., n, n)``, float32.

    ``x~ = RMSNorm(vec(X))``; ``h_pre = sigmoid(a_pre x~ Phi_pre + b_pre)``,
    ``h_post = 2 sigmoid(a_post x~ Phi_post + b_post)``, ``h_res`` =
    Sinkhorn-Knopp of ``exp(clip(a_res mat(x~ Phi_res) + B_res))``.  The
    norm's scale is folded into the maps' matrices and its division applied
    after the product, and ``vec(X) Phi`` is the sum over the streams of
    each stream's rows of ``Phi``: neither the normalised nor the
    concatenated streams are ever written out."""

    sizes: TrunkSizes
    dtype: jnp.dtype = jnp.float32
    alpha_init: float = 0.01
    res_init: float = 4.0

    @nn.compact
    def __call__(self, streams):
        z = self.sizes
        n, d = len(streams), streams[0].shape[-1]
        scale = self.param("scale", nn.initializers.ones, (n * d,),
                           jnp.float32)
        phi = [self.param(f"phi_{name}", nn.initializers.lecun_normal(),
                          (n * d, width), jnp.float32)
               for name, width in (("pre", n), ("post", n), ("res", n * n))]
        const = lambda v: nn.initializers.constant(v)
        a_pre, a_post, a_res = (
            self.param(f"alpha_{name}", const(self.alpha_init), (),
                       jnp.float32) for name in ("pre", "post", "res"))
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), jnp.float32)
        b_post = self.param("b_post", nn.initializers.zeros, (n,),
                            jnp.float32)
        b_res = self.param(
            "b_res", lambda *_: self.res_init * jnp.eye(n, dtype=jnp.float32))
        square_sum = sum(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1,
                                 keepdims=True) for x in streams)
        inv_rms = jax.lax.rsqrt(square_sum / (n * d) + z.rms_norm_eps)
        weights = (jnp.concatenate(phi, axis=1) * scale[:, None]).astype(
            self.dtype).reshape(n, d, -1)
        proj = sum(jnp.dot(x, weights[j], preferred_element_type=jnp.float32)
                   for j, x in enumerate(streams)) * inv_rms
        h_pre = jax.nn.sigmoid(a_pre * proj[..., :n] + b_pre)
        h_post = 2.0 * jax.nn.sigmoid(a_post * proj[..., n:2 * n] + b_post)
        logits = a_res * proj[..., 2 * n:].reshape(
            proj.shape[:-1] + (n, n)) + b_res
        m = jnp.exp(jnp.clip(logits, -z.hc_clamp, z.hc_clamp))
        for _ in range(z.hc_sinkhorn_iters):
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + z.hc_eps)
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + z.hc_eps)
        return h_pre, h_post, m


def _read_streams(streams, h_pre):
    """``H_pre X``: the sub-layer's input, one row per token."""
    return sum(h_pre[..., j, None] * x.astype(jnp.float32)
               for j, x in enumerate(streams)).astype(streams[0].dtype)


def _write_streams(streams, h_res, h_post, y):
    """``H_res X + H_post^T y``, stream by stream as plain multiply-adds:
    ONE elementwise pass over the streams (``n x n`` products per token
    would be lost on the matrix unit)."""
    rows, y = [x.astype(jnp.float32) for x in streams], y.astype(jnp.float32)
    return tuple(
        (sum(h_res[..., i, j, None] * rows[j] for j in range(len(rows)))
         + h_post[..., i, None] * y).astype(streams[0].dtype)
        for i in range(len(rows)))


class TrunkLayer(nn.Module):
    """A token mixer, then a dense FFN or the expert layer, each read from
    and written to the residual streams through its own hyper-connection —
    or, with one stream, added to it.  Beside the streams a layer takes and
    returns what is ``carried`` from layer to layer, a dict of tensors that
    LATER, non-adjacent layers read (a decoder-hybrid-decoder stack: ``m``, a
    state-space layer's scan output, which every gated memory unit after it
    reads; ``k`` and ``v``, the full attention layer's, which every cross
    attention layer after it reads).  Under the remat wrap a carried tensor
    is a block's output and later blocks' input: its cotangent is the sum
    over the layers that read it.  Every other trunk carries ``{}``."""

    sizes: TrunkSizes
    share: LayerShare
    dense: bool
    dtype: jnp.dtype = jnp.float32
    mixer: str = "mla"               # TrunkSizes.mixer(i)
    index: int = 0                   # TrunkSizes.published_index(i)

    @nn.compact
    def __call__(self, streams, carried):
        z, dt = self.sizes, self.dtype
        heads = lambda n: self.share.held(n, "attention heads")[1]
        handed = dict(carried)

        def taken(name):
            if name not in carried:
                raise ValueError(
                    f"published layer {self.index} ({self.mixer!r}) reads "
                    f"{name!r} of an earlier layer, and no layer built "
                    "before it hands one on: the cut leaves that layer out")
            return carried[name]

        def sublayer(streams, name, fn):
            norm = _trunk_norm(z, dt, f"{name}_norm")
            if len(streams) == 1:                   # plain residual
                return (streams[0] + fn(norm(streams[0])),)
            with jax.named_scope("mhc"):
                h_pre, h_post, h_res = HyperConnection(
                    z, dt, name=f"{name}_hc")(streams)
                x = _read_streams(streams, h_pre)
            y = fn(norm(x))
            with jax.named_scope("mhc"):
                return _write_streams(streams, h_res, h_post, y)

        def attention(x):
            # ``gdn``, ``gqa``, ``blockdiff``, ``dsa``, ``shortconv``,
            # ``ssm``, ``diff`` and ``gmu`` are modules named after their
            # scope, as ``moe`` is
            if self.mixer == "gdn":
                d = z.gated_delta
                return GatedDeltaNet(
                    d, heads(d.num_key_heads), heads(d.num_value_heads),
                    z.rms_norm_eps, dt, name="gdn")(x)
            if self.mixer in ("gqa", "blockdiff"):
                a = z.gated_attention
                return GatedAttention(
                    a, heads(a.num_heads), heads(a.num_kv_heads),
                    z.rms_norm_eps, dt, z.zero_centred_norm,
                    z.diffusion_block if self.mixer == "blockdiff" else 0,
                    name=self.mixer)(x)
            if self.mixer == "shortconv":
                return ShortConv(z.conv_taps, dt, name="shortconv")(x)
            if self.mixer == "dsa":
                return SparseAttention(z.sparse_attention, z.rms_norm_eps,
                                       dt, name="dsa")(x)
            if self.mixer == "ssm":
                out, handed["m"] = SelectiveStateSpace(
                    z.hybrid_decoder, dt, name="ssm")(x)
                return out
            if self.mixer == "gmu":
                return GatedMemoryUnit(dt, name="gmu")(x, taken("m"))
            if self.mixer in ("swa", "diff", "xattn"):
                # one scope for band, full and cross: the layer is in the path
                a = z.hybrid_decoder
                out, kv = DifferentialAttention(
                    a, self.index, a.window if self.mixer == "swa" else 0,
                    z.rms_norm_eps, dt, name="diff")(
                        x, (taken("k"), taken("v"))
                        if self.mixer == "xattn" else None)
                if self.mixer == "diff":
                    handed["k"], handed["v"] = kv
                return out
            with jax.named_scope("mla"):
                return LatentAttention(z, heads(z.num_attention_heads), dt,
                                       name="attn")(x)

        def feed_forward(x):
            # flax names a module's scope after it: ``ffn/...``, and
            # ``moe/route``, ``moe/experts``, ``moe/shared``
            if self.dense:
                return GatedMLP(z.intermediate_size, dt, name="ffn")(x)
            lo, held = self.share.held(z.n_routed_experts, "routed experts")
            return ExpertLayer(z, lo, held, dt, name="moe")(x)

        streams = sublayer(streams, "attn", attention)
        streams = sublayer(streams, "ffn", feed_forward)
        return tuple(remat_lib.tag_block_out(x) for x in streams), handed


class DecoderTrunk(nn.Module):
    """Feature extractor: ``(B, S) int32 -> (B, hidden)``, the mean over the
    positions of the final-norm hidden states (a block-diffusion trunk: over
    the noised half of its ``[noised | clean]`` rows).  The embedding, then
    the layers the sizes list — each a token mixer and a dense FFN or the
    expert layer on the residual streams, under the remat wrap, and each
    handed what earlier layers carry forward for it (``TrunkLayer``) — then
    one more norm."""

    sizes: TrunkSizes
    share: LayerShare = LayerShare()
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "none"

    @property
    def trace_scopes(self) -> Tuple[str, ...]:
        z = self.sizes
        mixers = {z.mixer(i) for i in range(z.num_hidden_layers)}
        return next(scopes for known, scopes in SCOPES_BY_MIXERS
                    if mixers <= known)

    @property
    def feature_dim(self) -> int:
        return self.sizes.hidden_size

    @property
    def vocab_rows(self) -> int:
        return self.share.held(self.sizes.vocab_size, "vocabulary rows")[1]

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train                       # no BatchNorm, no dropout
        z = self.sizes
        if not jnp.issubdtype(tokens.dtype, jnp.integer):
            raise TypeError(
                f"a decoder trunk takes integer ids, got {tokens.dtype}")
        x = nn.Embed(self.vocab_rows, z.hidden_size, dtype=self.dtype,
                     embedding_init=nn.initializers.normal(stddev=0.02),
                     name="embed")(tokens)
        # entry: every stream starts as the embedding.  The streams travel
        # as a tuple of (B, S, D) arrays (of one, under a plain residual):
        # a stream axis of 4 beside D would sit on the tiled minor
        # dimensions, padded fourfold
        streams = (x,) * z.hc_mult
        layer = remat_lib.wrap_block(
            TrunkLayer,
            remat_lib.resolve_policy_name(self.remat, self.remat_policy))
        # what a layer hands to later, non-adjacent layers travels beside
        # the streams (TrunkLayer): nothing, in most trunks
        carried = {}
        for i in range(z.num_hidden_layers):
            streams, carried = layer(
                z, self.share, i < z.first_k_dense_replace, self.dtype,
                z.mixer(i), z.published_index(i),
                name=f"layer{i}")(streams, carried)
        # exit: the streams are summed
        hidden = sum(x.astype(jnp.float32) for x in streams).astype(
            self.dtype)
        hidden = _trunk_norm(z, self.dtype, "final_norm")(hidden)
        if z.diffusion_block:       # the NOISED half: what the loss reads
            hidden = hidden[:, :hidden.shape[1] // 2]
        return jnp.mean(hidden.astype(jnp.float32), axis=1).astype(self.dtype)


# Xing4.0-29B-A4B, from its public config.json (models/registry.py gives the
# source); LM head and multi-token-prediction module belong to a next-token
# loss and are not built.
XING4_29B_A4B = TrunkSizes(
    hidden_size=3584, num_hidden_layers=40, first_k_dense_replace=2,
    num_attention_heads=32, q_lora_rank=768, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    intermediate_size=9216, n_routed_experts=64, moe_intermediate_size=1024,
    num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=2.0,
    norm_topk_prob=True, vocab_size=131072, hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-6, hc_clamp=30.0, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_factor=64.0, rope_original_max_position=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0)

# The same module at test size (tests/test_decoder_trunk.py).
TINY = TrunkSizes(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=160, n_routed_experts=8, moe_intermediate_size=32,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.0,
    norm_topk_prob=True, vocab_size=128, hc_mult=2, hc_sinkhorn_iters=20,
    hc_eps=1e-6, hc_clamp=30.0, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_factor=64.0, rope_original_max_position=16, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0)

# JoyAI-LLM-Flash (48B-A2.7B), from its public config.json (``model_type:
# joyai_llm_flash``): 40 layers of latent attention on ONE residual stream —
# 32 heads, 128 + 64 wide query/key heads, 128-wide values, plain rotary at
# theta 3.2e7 on pairs (2i, 2i+1) (``rope_interleave``; ``rope_scaling``
# null) — the first dense (SwiGLU of 7,168), the others sparse: 256 experts
# of width 768, top-8, sigmoid scores with the ``noaux_tc`` bias (one group),
# ``norm_topk_prob``, ``routed_scaling_factor`` 2.5, one shared expert.  LM
# head and multi-token-prediction module are not built.
JOYAI_LLM_FLASH = TrunkSizes(
    hidden_size=2048, num_hidden_layers=40, first_k_dense_replace=1,
    num_attention_heads=32, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    intermediate_size=7168, n_routed_experts=256, moe_intermediate_size=768,
    num_experts_per_tok=8, n_shared_experts=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, vocab_size=129280, rms_norm_eps=1e-6,
    rope_theta=32e6)

# The one-stream latent-attention trunk at test size
# (tests/test_latent_trunk.py): value heads narrower than key heads, and at
# 20 tokens three blocks of keys a row, the last one short, so the CPU runs
# the blockwise core.
LATENT_TINY = TrunkSizes(
    hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=8,
    attention_block=8, intermediate_size=64, n_routed_experts=16,
    moe_intermediate_size=16, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, vocab_size=128,
    rms_norm_eps=1e-6, rope_theta=32e6)

# Qwen3-Next-80B-A3B-Instruct, from its public config.json: 48 layers, every
# fourth gated attention and the others Gated DeltaNet, every layer sparse
# (512 experts of width 512, top-10, softmax scores, one gated shared
# expert).  ``intermediate_size`` is the config's; no layer is dense.
QWEN3_NEXT_80B_A3B = TrunkSizes(
    hidden_size=2048, num_hidden_layers=48, first_k_dense_replace=0,
    intermediate_size=5120, n_routed_experts=512, moe_intermediate_size=512,
    num_experts_per_tok=10, n_shared_experts=1, norm_topk_prob=True,
    vocab_size=151936, full_attention_interval=4,
    gated_attention=GatedAttentionSizes(
        num_heads=16, num_kv_heads=2, head_dim=256, rotary_dim=64,
        rope_theta=1e7),
    # chunk 128, not the published code's 64: a C x C float32 array with
    # C = 64 pads to the 128 lanes anyway, and the scan is half as deep
    # (compiler, PR 31: 496 -> 437 GB a step under gdn/core, unpadded)
    gated_delta=GatedDeltaSizes(
        num_key_heads=16, num_value_heads=32, key_head_dim=128,
        value_head_dim=128, conv_kernel=4, chunk=128),
    scoring_func="softmax", shared_expert_gate=True, zero_centred_norm=True,
    rms_norm_eps=1e-6)

# The patterned trunk at test size (tests/test_hybrid_trunk.py): period 2.
HYBRID_TINY = TrunkSizes(
    hidden_size=32, num_hidden_layers=4, first_k_dense_replace=0,
    intermediate_size=64, n_routed_experts=8, moe_intermediate_size=16,
    num_experts_per_tok=3, n_shared_experts=1, norm_topk_prob=True,
    vocab_size=128, full_attention_interval=2,
    gated_attention=GatedAttentionSizes(
        num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=8,
        rope_theta=1e7, block=8),
    gated_delta=GatedDeltaSizes(
        num_key_heads=2, num_value_heads=4, key_head_dim=8,
        value_head_dim=8, conv_kernel=4, chunk=8, group=2),
    scoring_func="softmax", shared_expert_gate=True, zero_centred_norm=True,
    rms_norm_eps=1e-6)

# Keye-VL-2.0-30B-A3B's language model, from its public config.json
# (``text_config`` and ``sa_config``): 48 layers, every one grouped-query
# attention (32 query on 4 key/value heads of 128, rotary theta 1e7) behind
# an indexer (16 heads of 64 on one key head, 2,048 keys a query, blocks of
# 512) and sparse (128 experts of width 768, top-8, softmax scores, NO
# shared expert).  The vision tower belongs to image input and is not built.
KEYE_VL2_30B_A3B = TrunkSizes(
    hidden_size=2048, num_hidden_layers=48, first_k_dense_replace=0,
    intermediate_size=6144, n_routed_experts=128, moe_intermediate_size=768,
    num_experts_per_tok=8, n_shared_experts=0, norm_topk_prob=True,
    vocab_size=151936,
    sparse_attention=SparseAttentionSizes(
        num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e7,
        index_heads=16, index_head_dim=64, topk=2048, block=512),
    scoring_func="softmax", rms_norm_eps=1e-6)

# The sparse-attention trunk at test size (tests/test_sparse_trunk.py): at
# 20 tokens three blocks a row, the last one short, and ``topk`` inside the
# first block, so that a block's rows keep different numbers of keys.
SPARSE_TINY = TrunkSizes(
    hidden_size=32, num_hidden_layers=2, first_k_dense_replace=0,
    intermediate_size=64, n_routed_experts=8, moe_intermediate_size=16,
    num_experts_per_tok=3, n_shared_experts=0, norm_topk_prob=True,
    vocab_size=128,
    sparse_attention=SparseAttentionSizes(
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e7,
        index_heads=2, index_head_dim=8, topk=6, block=8),
    scoring_func="softmax", rms_norm_eps=1e-6)

# LFM2-24B-A2B, from its public config.json (``model_type: lfm2_moe``): 40
# layers, ``layer_types`` lists 30 gated short convolutions (3 taps, no bias)
# and 10 grouped-query attention layers (32 query on 8 key/value heads of
# 64 = hidden / heads, rotary theta 1e6, at ``i % 4 == 2``); the first 2
# dense (SwiGLU of 11,776), the others sparse (64 experts of width 1,536,
# top-4, sigmoid scores with a selection bias, ``norm_topk_prob`` over
# ``sum + 1e-6``, NO shared expert).
LFM2_24B_A2B = TrunkSizes(
    hidden_size=2048, num_hidden_layers=40, first_k_dense_replace=2,
    intermediate_size=11776, n_routed_experts=64, moe_intermediate_size=1536,
    num_experts_per_tok=4, n_shared_experts=0, routed_scaling_factor=1.0,
    norm_topk_prob=True, norm_topk_eps=1e-6, vocab_size=65536,
    layer_mixers=tuple("gqa" if i % 4 == 2 else "shortconv"
                       for i in range(40)),
    conv_taps=3,
    gated_attention=GatedAttentionSizes(
        num_heads=32, num_kv_heads=8, head_dim=64, rotary_dim=64,
        rope_theta=1e6, output_gate=False, group=2),
    scoring_func="sigmoid", rms_norm_eps=1e-5)

# SDAR-30B-A3B-Chat, from its public config.json (``model_type: sdar_moe``):
# 48 layers, every one grouped-query attention (32 query on 4 key/value
# heads of 128, per-head ``q``/``k`` RMS norms with plain gains, rotary theta
# 1e6 over the whole head, no gate) and sparse (128 experts of width 768,
# top-8, softmax scores, ``norm_topk_prob``, NO shared expert) — the
# Qwen3-30B-A3B block — TRAINED BY BLOCK DIFFUSION (arXiv 2503.09573 section
# 3, kept by arXiv 2510.06303): every sequence passes as ``[noised | clean]``
# under the three-part mask of ``block_diffusion_tiles``.  The block length
# is in no key of the config: 4, the release's generation default (an
# assumption the benchmark's configuration file lists).  The LM head is not
# built.
SDAR_30B_A3B = TrunkSizes(
    hidden_size=2048, num_hidden_layers=48, first_k_dense_replace=0,
    intermediate_size=6144, n_routed_experts=128, moe_intermediate_size=768,
    num_experts_per_tok=8, n_shared_experts=0, norm_topk_prob=True,
    vocab_size=151936, diffusion_block=4,
    gated_attention=GatedAttentionSizes(
        num_heads=32, num_kv_heads=4, head_dim=128, rotary_dim=128,
        rope_theta=1e6, output_gate=False),
    scoring_func="softmax", rms_norm_eps=1e-6)

# The block-diffusion trunk at test size (tests/test_blockdiff_trunk.py): at
# 16 ids a sample a row is 32, two tiles of 8 a half, two blocks of 4 a tile.
BLOCKDIFF_TINY = TrunkSizes(
    hidden_size=32, num_hidden_layers=2, first_k_dense_replace=0,
    intermediate_size=64, n_routed_experts=8, moe_intermediate_size=16,
    num_experts_per_tok=3, n_shared_experts=0, norm_topk_prob=True,
    vocab_size=128, diffusion_block=4,
    gated_attention=GatedAttentionSizes(
        num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=16,
        rope_theta=1e6, block=8, output_gate=False),
    scoring_func="softmax", rms_norm_eps=1e-6)

# The short-convolution trunk at test size (tests/test_shortconv_trunk.py):
# 7 published layers, 2 dense, attention at ``i % 4 == 2``; cut ``1+4`` it
# is published layers 0, 2, 3, 4, 5: conv | attention, conv, conv, conv.
SHORTCONV_TINY = TrunkSizes(
    hidden_size=32, num_hidden_layers=7, first_k_dense_replace=2,
    intermediate_size=64, n_routed_experts=8, moe_intermediate_size=16,
    num_experts_per_tok=2, n_shared_experts=0, routed_scaling_factor=1.0,
    norm_topk_prob=True, norm_topk_eps=1e-6, vocab_size=128,
    layer_mixers=tuple("gqa" if i % 4 == 2 else "shortconv"
                       for i in range(7)),
    conv_taps=3,
    gated_attention=GatedAttentionSizes(
        num_heads=4, num_kv_heads=2, head_dim=8, rotary_dim=8,
        rope_theta=1e6, block=8, output_gate=False, group=2),
    scoring_func="sigmoid", rms_norm_eps=1e-5)

# Phi-4-mini-flash-reasoning, from its public config.json (``model_type:
# phi4flash``; the architecture is SambaY, arXiv 2507.06607): 32 layers,
# hidden 2,560, every layer dense (SwiGLU of 10,240, no bias), LayerNorm
# with a bias at eps 1e-5, no positional encoding; ``mb_per_layer`` 2: even
# layers of the Mamba kind (inner 5,120, state 16, 4 taps, ``dt_rank`` 160),
# odd ones differential attention (40 query on 20 key/value heads of 64);
# layers 0-15 the self-decoder (the attention under a band of 512 keys),
# 16 and 17 the layers whose scan output and whose keys and values are kept,
# 18-31 the cross-decoder (``hybrid_decoder_mixers``).  The tied LM head is
# not built.
PHI4_MINI_FLASH = TrunkSizes(
    hidden_size=2560, num_hidden_layers=32, first_k_dense_replace=32,
    intermediate_size=10240, n_routed_experts=0, moe_intermediate_size=0,
    num_experts_per_tok=0, n_shared_experts=0, norm_topk_prob=False,
    vocab_size=200064, layer_mixers=hybrid_decoder_mixers(32),
    hybrid_decoder=HybridDecoderSizes(
        num_heads=40, num_kv_heads=20, head_dim=64, window=512, state=16,
        conv_taps=4, expand=2, dt_rank=160),
    rms_norm_eps=1e-5)

# The decoder-hybrid-decoder trunk at test size (tests/test_sambay_trunk.py):
# 12 published layers — (ssm, swa) x 3 | ssm, diff | (gmu, xattn) x 2 — cut
# ``5-9`` all five kinds, cut ``5-11`` two readers of each kept tensor; a
# window of 8 keys at tiles of 8, so that at 32 positions the band forms 2 of
# a row's 4 tiles.
SAMBAY_TINY = TrunkSizes(
    hidden_size=64, num_hidden_layers=12, first_k_dense_replace=12,
    intermediate_size=96, n_routed_experts=0, moe_intermediate_size=0,
    num_experts_per_tok=0, n_shared_experts=0, norm_topk_prob=False,
    vocab_size=128, layer_mixers=hybrid_decoder_mixers(12),
    hybrid_decoder=HybridDecoderSizes(
        num_heads=4, num_kv_heads=2, head_dim=16, window=8, state=4,
        conv_taps=4, expand=2, dt_rank=4, block=8, chunk=8),
    rms_norm_eps=1e-5)
