"""Decoder trunk over token sequences — the integer-input encoder path.

A causal decoder stack read as a BYOL encoder: ``(B, S) int32 -> (B,
hidden)``, the mean over positions of the final-norm hidden states.  Named
by its mechanisms, sized by :class:`TrunkSizes`; a published model is one
registered instance (models/registry.py).  Three mechanisms nothing else in
``models/`` has:

- **latent attention** (MLA, as the DeepSeek-V3 modelling code writes it):
  low-rank query and key/value paths with an RMSNorm on each latent, a
  rotary part (YaRN-scaled) shared by all heads beside a per-head
  un-rotated part, value heads narrower than query/key heads, causal mask,
  softmax scale ``1/sqrt(d_qk) * m^2`` with ``m`` YaRN's ``mscale``;
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
  doubly stochastic by Sinkhorn-Knopp iterations.

:class:`LayerShare` states ONCE which of the ``of`` chips that share a layer
this one is; heads, experts and vocabulary rows held follow from it.

Device-trace scopes (``TRACE_SCOPES``): ``mla``, ``moe/route``,
``moe/experts`` (and in it ``combine``: the sum of a token's copies, forward
and as the dispatch's backward), ``moe/shared``, ``mhc`` (and ``ffn`` for a
leading dense layer) inside every layer; the train step stamps them beside
its phases so the compile cache keys them (training/steps.py).  Each routing
layer sows ``[rows held, largest load, mean load, rows dropped]`` into the
``ROUTING`` collection; the train step sums them over layers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from byol_tpu.core import remat as remat_lib
from byol_tpu.ops.attention import dense_attention

TRACE_SCOPES = ("mla", "moe/route", "moe/experts", "moe/experts/combine",
                "moe/shared", "mhc", "ffn")
ROUTING = "routing"                  # flax collection of the routing counters
ROUTING_FIELDS = ("rows_held", "load_max", "load_mean", "rows_dropped")


@dataclasses.dataclass(frozen=True)
class TrunkSizes:
    """The sizes of one decoder trunk, as its published config names them."""

    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int       # leading layers with a dense FFN
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int           # dense FFN width
    n_routed_experts: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    vocab_size: int
    hc_mult: int                     # residual streams
    hc_sinkhorn_iters: int
    hc_eps: float
    hc_clamp: float                  # |logit| bound before the exp
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

    def with_depth(self, dense: int, sparse: int) -> "TrunkSizes":
        """The same trunk cut to ``dense`` leading dense layers and
        ``sparse`` expert layers."""
        return dataclasses.replace(self, num_hidden_layers=dense + sparse,
                                   first_k_dense_replace=dense)


@dataclasses.dataclass(frozen=True)
class LayerShare:
    """Chip ``index`` of the ``of`` chips that share every layer (expert- and
    head-parallel, vocabulary rows split the same way)."""

    index: int = 0
    of: int = 1

    @classmethod
    def parse(cls, text: str) -> "LayerShare":
        try:
            index, of = (int(t) for t in text.split("/"))
        except ValueError:
            raise ValueError(
                f"layer share {text!r} is not 'i/n' (chip i of the n that "
                "share a layer)") from None
        if not 0 <= index < of:
            raise ValueError(f"layer share {text!r}: need 0 <= i < n")
        return cls(index, of)

    def held(self, total: int, what: str) -> Tuple[int, int]:
        """``(first, count)`` of ``total`` heads / experts / rows held."""
        if total % self.of:
            raise ValueError(
                f"{total} {what} do not divide over {self.of} chips")
        count = total // self.of
        return self.index * count, count


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
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class LatentAttention(nn.Module):
    """MLA over the heads this chip holds; ``q_a`` / ``kv_a`` are whole,
    ``q_b`` / ``kv_b`` / ``o`` are the held heads' slices."""

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
        q = jnp.concatenate(
            [q[..., :dn], apply_rotary(q[..., dn:], cos, sin)], axis=-1)
        k_rope = apply_rotary(k_rope[:, :, None, :], cos, sin)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, heads, dr))],
            axis=-1)
        scale = z.qk_head_dim ** -0.5 * yarn_mscale(
            z.rope_factor, z.rope_mscale_all_dim) ** 2
        out = dense_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              kv[..., dn:].transpose(0, 2, 1, 3),
                              scale=scale, causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * dv)
        return _dense(z.hidden_size, self.dtype, "o")(out)


class GatedMLP(nn.Module):
    """SwiGLU: ``down(silu(gate x) * up x)``."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.width, self.dtype, "gate")(x)
        up = _dense(self.width, self.dtype, "up")(x)
        return _dense(x.shape[-1], self.dtype, "down")(nn.silu(gate) * up)


def _sum_copies(rows, pos, ok):
    """``out[t] = sum_j rows[pos[t, j]]`` over the copies ``ok`` marks: a
    token's row back from the (up to k) sorted rows that are its copies,
    added in float32 in slot order and rounded once.  One gather of
    ``(tokens, D)`` a slot, the k of them added in one pass: gathered as one
    ``(tokens, k, D)`` array, k lands on the tiled minor dimensions and the
    TPU compiler relays out all k copies of the hidden states before it
    sums them (PERF.md section 6, PR 30)."""
    with jax.named_scope("combine"):
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
def _take_rows(x, idx, pos, ok):
    return x[idx]


def _take_fwd(x, idx, pos, ok):
    return x[idx], (idx, pos, ok)


def _take_bwd(res, g):
    _, pos, ok = res
    return _sum_copies(g, pos, ok), None, None, None


_take_rows.defvjp(_take_fwd, _take_bwd)


@jax.custom_vjp
def _put_rows(rows, idx, pos, ok):
    return _sum_copies(rows, pos, ok)


def _put_fwd(rows, idx, pos, ok):
    return _sum_copies(rows, pos, ok), (idx, pos, ok)


def _put_bwd(res, g):
    return g[res[0]], None, None, None


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
    shared expert.  Every row routed to a held expert is computed."""

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
            # noaux_tc: a selection bias that takes no gradient (it moves
            # which experts are chosen, never their weights)
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros,
                              (z.n_routed_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))
            _, chosen = jax.lax.top_k(scores + bias, k)
            weight = jnp.take_along_axis(scores, chosen, axis=-1)
            if z.norm_topk_prob and k > 1:
                weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
            weight = weight * z.routed_scaling_factor
            local = chosen.reshape(-1) - self.lo
            here = (local >= 0) & (local < self.held)
            bucket = jnp.where(here, local, self.held)   # the rest sort last
            order = jnp.argsort(bucket)                  # stable
            token_of = order // k
            place = jnp.argsort(order).reshape(tokens, k)   # a copy's row
            here_2d = here.reshape(tokens, k)
            weight_of = jnp.where(here, weight.reshape(-1), 0.0)[order]
            group_sizes = jnp.sum(
                bucket[:, None] == jnp.arange(self.held,
                                              dtype=bucket.dtype),
                axis=0, dtype=jnp.int32)
            rows_held = jnp.sum(group_sizes)
        with jax.named_scope("experts"):
            w_gate, w_up, w_down = ExpertWeights(
                self.held, d, f, name="experts")()
            ragged = lambda lhs, w: jax.lax.ragged_dot(
                lhs, w.astype(dt), group_sizes)

            def product(cap):
                """The held experts over the first ``cap`` sorted copies.
                A ragged product writes no row beyond its groups: rows past
                ``rows_held`` are masked on the way in AND out, so neither
                they nor their cotangents reach a token."""
                idx = token_of[:cap]
                ok = here_2d & (place < cap)
                pos = jnp.minimum(place, cap - 1)
                valid = (jnp.arange(cap) < rows_held)[:, None]
                rows = jnp.where(valid, _take_rows(x, idx, pos, ok), 0)
                act = nn.silu(ragged(rows, w_gate)) * ragged(rows, w_up)
                # the copy's routing weight goes on BEFORE the last product
                # (it is linear): (cap, f) to scale, not (cap, d)
                act = (act * weight_of[:cap, None]).astype(dt)
                out = jnp.where(valid, ragged(act, w_down), 0)
                return _put_rows(out, idx, pos, ok)

            # Shapes are static, loads are not.  At the nominal load a chip
            # gets ``k x held / published`` copies per token; gathers and
            # scatters over twice that many rows serve every step whose
            # load stays under it, and a step whose load does not takes
            # the same product over ALL ``tokens x k`` copies: no capacity,
            # no dropped row, and the common step does not pay for the
            # worst one.
            every = tokens * k
            usual = min(every, -(-2 * every * self.held
                                 // z.n_routed_experts))
            if usual == every:
                routed = product(every)
            else:
                routed = jax.lax.cond(rows_held <= usual,
                                      lambda: product(usual),
                                      lambda: product(every))
        with jax.named_scope("shared"):
            shared = GatedMLP(f * z.n_shared_experts, dt, name="shared")(x)
        load = group_sizes.astype(jnp.float32)
        self.sow(ROUTING, "stats", jnp.stack([
            rows_held.astype(jnp.float32), jnp.max(load), jnp.mean(load),
            (jnp.sum(here) - rows_held).astype(jnp.float32)]))
        return (routed + shared).reshape(b, s, d)


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
    """Attention, then a dense FFN or the expert layer, each read from and
    written to the residual streams through its own hyper-connection."""

    sizes: TrunkSizes
    share: LayerShare
    dense: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, streams):
        z, dt = self.sizes, self.dtype
        _, heads = self.share.held(z.num_attention_heads, "attention heads")

        def sublayer(streams, name, fn):
            with jax.named_scope("mhc"):
                h_pre, h_post, h_res = HyperConnection(
                    z, dt, name=f"{name}_hc")(streams)
                x = _read_streams(streams, h_pre)
            y = fn(RMSNorm(z.rms_norm_eps, dt, name=f"{name}_norm")(x))
            with jax.named_scope("mhc"):
                return _write_streams(streams, h_res, h_post, y)

        def attention(x):
            with jax.named_scope("mla"):
                return LatentAttention(z, heads, dt, name="attn")(x)

        def feed_forward(x):
            # flax names a module's scope after it: ``ffn/...``, and
            # ``moe/route``, ``moe/experts``, ``moe/shared``
            if self.dense:
                return GatedMLP(z.intermediate_size, dt, name="ffn")(x)
            lo, held = self.share.held(z.n_routed_experts, "routed experts")
            return ExpertLayer(z, lo, held, dt, name="moe")(x)

        streams = sublayer(streams, "attn", attention)
        streams = sublayer(streams, "ffn", feed_forward)
        return tuple(remat_lib.tag_block_out(x) for x in streams)


class DecoderTrunk(nn.Module):
    """Feature extractor: ``(B, S) int32 -> (B, hidden)``."""

    sizes: TrunkSizes
    share: LayerShare = LayerShare()
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = "none"

    trace_scopes = TRACE_SCOPES

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
        # as a tuple of (B, S, D) arrays: a stream axis of 4 beside D
        # would sit on the tiled minor dimensions, padded fourfold
        streams = (x,) * z.hc_mult
        layer = remat_lib.wrap_block(
            TrunkLayer,
            remat_lib.resolve_policy_name(self.remat, self.remat_policy))
        for i in range(z.num_hidden_layers):
            streams = layer(z, self.share, i < z.first_k_dense_replace,
                            self.dtype, name=f"layer{i}")(streams)
        # exit: the streams are summed
        hidden = sum(x.astype(jnp.float32) for x in streams).astype(
            self.dtype)
        hidden = RMSNorm(z.rms_norm_eps, self.dtype,
                         name="final_norm")(hidden)
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
