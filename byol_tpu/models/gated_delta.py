"""Gated DeltaNet — a token mixer that is a RECURRENCE over the sequence.

Per value head, with a state ``S`` of ``d_k x d_v`` that starts at zero::

    S' = exp(g_t) S_{t-1}                    # the decay gate, g_t <= 0
    delta_t = beta_t (v_t - S'^T k_t)        # the delta rule: what S' gets wrong
    S_t = S' + k_t delta_t^T
    o_t = S_t^T q_t

(arXiv 2412.06464; the layer round it as the public ``qwen3_next`` modelling
code writes it: one projection to ``q, k, v, z`` laid out per key head and
one to ``b, a``; a depthwise causal convolution and SiLU over ``cat(q, k,
v)``; ``beta = sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)`` in
float32; ``q, k`` L2-normalised per head, each key head serving ``H_v / H_k``
value heads; the output RMS-normalised per head, gained, and gated by
``silu(z)``.)

A chip never runs it token by token.  :func:`chunked_delta_rule` is the
chunked (WY) form: inside a chunk of ``C`` tokens the rule is a unit lower
triangular system — ``(I + L) [U | W] = [beta V | beta K e^gamma]`` with ``L
= strict_tril(beta K K^T . decay)`` — whose inverse is built exactly
(forward substitution on small diagonal blocks, then block elimination:
nothing cancels where keys repeat, as a power series of ``L`` would);
between chunks the state is carried, ``S / C`` trips of four products
(:func:`_chunk_step`).

One algorithm, two lowerings, chosen from what the code can see
(``ops/delta_rule.applies``): where the program lowers for a TPU and
``chunk``, ``d_k``, ``d_v`` are multiples of 128 that fit VMEM, the kernels
of ``ops/delta_rule.py`` (:func:`_chunked_rule_kernels`) — ``delta_wy_fwd``
/ ``delta_wy_bwd`` within a chunk: its ``C x C`` matrices live and die in
VMEM, the keys are read per KEY head and never repeated; ``delta_scan_fwd``
/ ``delta_scan_bwd`` between chunks: :func:`_chunk_step` down a column of
chunks with the states in VMEM, no loop, and the output written as the
gated norm reads it, ``[B, S, H d_v]``; everywhere else (``HYBRID_TINY``,
every CPU run) plain ``jax.numpy``, :func:`_chunked_rule` —
:func:`unit_lower_inverse` and ``lax.scan(_chunk_step)`` — which is also
the tests' oracle for the kernels.  The projections are plain
``jax.numpy`` on both.

``qkvz`` is ONE product whose columns stand in the order the stages read
them: the kernel keeps the published per-key-head layout (the parameter
tree, the seeded weights and a checkpoint are the published ones) and its
columns are reordered on the WEIGHT to ``[q | k | v | z]``, each over all
key heads, so that ``mixed`` and ``gate`` are column ranges of the product
and no activation is cut per key head and put together again.

The two ELEMENTWISE stages round the rule — the convolution with its SiLU,
the gated norm — have two lowerings, chosen as the rule's are
(``ops/gdn_passes.applies``: a TPU, 4 taps, channels and a value head of
whole 128-lane tiles, a sequence's column block within VMEM, bf16 or
float32): the kernels ``conv_silu_fwd|bwd`` and ``gated_norm_fwd|bwd`` of
``ops/gdn_passes.py``, one pass over HBM each, which read their columns of
``qkvz`` where they lie; or :func:`causal_conv` with ``nn.silu`` and
:func:`gated_rms_norm` on those column ranges, plain ``jax.numpy``, the
tests' oracle (and ``decoder_trunk.ShortConv``'s only convolution).

Device-trace scopes, inside the layer's ``gdn``: ``proj``, ``conv``,
``core``, ``gate_norm``.
"""
from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from byol_tpu.ops import delta_rule, gdn_passes


@dataclasses.dataclass(frozen=True)
class GatedDeltaSizes:
    """One Gated DeltaNet layer, as a published config names its sizes
    (``linear_*``).  ``chunk`` is the program's own: tokens per chunk of
    the chunked rule."""

    num_key_heads: int
    num_value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_kernel: int
    chunk: int = 64
    group: int = 4       # the program's own: sequences the rule holds at once


HIGHEST = jax.lax.Precision.HIGHEST
# Side of the diagonal blocks that forward substitution inverts on the
# ``jax.numpy`` path (the kernels have their own: ops/delta_rule.py).  The
# TPU compiler's ``triangular_solve`` substitutes ROW BY ROW over every
# matrix at once, a pass over all of them a row: at 128 x 128 it took 41.6
# ms a call, 999 ms of a 2,793 ms step, at 32 x 32 8.3 ms, 200 ms of 2,334
# (PERF.md section 6, PR 31) — which is why a TPU takes the kernels since
# PR 32; its cost falls with the square of the side, and the matrix unit
# takes over above.
SUBSTITUTED = 32


@jax.custom_vjp
def unit_lower_inverse(lower):
    """``(I + L)^-1`` for strictly lower triangular ``L`` ``(..., C, C)``
    (what lies on or above the diagonal is not read), float32.

    The ``SUBSTITUTED``-sized diagonal blocks by forward substitution
    (``solve_triangular`` against the identity); then block elimination,
    bottom up: with ``T`` the inverse of the diagonal blocks of size ``s``
    (block diagonal, zero elsewhere), the inverse of the blocks of size
    ``2s`` is ``T - T (L . M) T``, ``M`` picking the lower-left ``s x s``
    quarter of every ``2s`` block — the block form of ``[[A, 0], [B, D]]^-1
    = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``.  Two products a round on whole
    ``C x C`` matrices.  The backward keeps the inverse alone: ``dL = -T^T
    dT T^T``."""
    c = lower.shape[-1]
    lower = lower.astype(jnp.float32)
    s = SUBSTITUTED if c % SUBSTITUTED == 0 else c
    blocks = c // s
    # the diagonal blocks, (..., blocks, s, s), and back on the diagonal
    same = jnp.eye(blocks, dtype=jnp.float32)
    tiled = lower.reshape(lower.shape[:-2] + (blocks, s, blocks, s))
    diagonal = jnp.einsum("...ipjq,ij->...ipq", tiled, same)
    small = jax.scipy.linalg.solve_triangular(
        diagonal, jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32),
                                   diagonal.shape),
        lower=True, unit_diagonal=True)
    inverse = jnp.einsum("...ipq,ij->...ipjq", small, same).reshape(
        lower.shape)
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    while s < c:
        quarter = ((rows // (2 * s) == cols // (2 * s))
                   & (rows % (2 * s) >= s) & (cols % (2 * s) < s))
        inverse = inverse - jnp.matmul(
            jnp.matmul(inverse, jnp.where(quarter, lower, 0.0),
                       precision=HIGHEST), inverse, precision=HIGHEST)
        s *= 2
    return inverse


def _inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _inverse_bwd(inverse, d_inverse):
    c = inverse.shape[-1]
    turned = jnp.swapaxes(inverse, -1, -2)
    d_lower = -jnp.matmul(jnp.matmul(turned, d_inverse, precision=HIGHEST),
                          turned, precision=HIGHEST)
    below = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    return (jnp.where(below, d_lower, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def causal_conv(x, taps):
    """Depthwise causal convolution: ``y[t] = sum_j taps[j] x[t - (K-1) +
    j]``, nothing before the sequence's start.  ``x``: ``(B, S, C)``,
    ``taps``: ``(K, C)`` — tap ``K-1`` meets the current token."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j] for j in range(k))


def gated_rms_norm(out, gate, gain, eps: float, dtype):
    """``rmsnorm(out) * gain * silu(gate)`` over the last axis, the
    statistics and the products float32, the result in ``dtype``."""
    out = out.astype(jnp.float32)
    out = out * jax.lax.rsqrt(
        jnp.mean(jnp.square(out), -1, keepdims=True) + eps)
    return (out * gain * nn.silu(gate.astype(jnp.float32))).astype(dtype)


def chunked_delta_rule(q, k, v, g, beta, *, chunk: int, dtype=jnp.float32,
                       group: int = 0):
    """The gated delta rule over whole sequences, in chunks.

    ``q, k``: ``(B, S, Hk, d_k)`` (normalised and scaled by the caller;
    ``Hk`` divides ``H``: key head ``h // (H / Hk)`` serves value head
    ``h``); ``v``: ``(B, S, H, d_v)``; ``g`` (log decay, <= 0) and ``beta``:
    ``(B, S, H)`` float32.  Returns ``o (B, S, H, d_v)`` in
    ``dtype``.  Matrix products take operands in ``dtype`` and accumulate
    in float32; gates, the triangular inverse and the carried state are
    float32.  A sequence that ``chunk`` does not divide is padded with
    tokens that write nothing (``beta = 0, g = 0``).

    ``group`` > 0 works on that many sequences at a time (where it divides
    ``B``), each group under ``jax.checkpoint``: the rule's intermediates —
    on the ``jax.numpy`` path several ``(B, H, S / C, C, C)`` and ``(B, H,
    S, d)`` float32 arrays, with the kernels the recurrence's operands, one
    kept inverse and the chunks' kept states — then exist for one group, in
    the forward and in the backward alike, at the price of one more forward
    of the rule."""
    b, s, hk, dk = q.shape
    if delta_rule.applies(min(chunk, s), dk, v.shape[-1], dtype):
        rule = _chunked_rule_kernels
    else:
        rule = _chunked_rule          # one q, k per VALUE head, repeated
        if hk != v.shape[2]:          # out here, before the groups
            q, k = (jnp.repeat(x, v.shape[2] // hk, axis=2) for x in (q, k))
    if not group or group >= b or b % group:
        out = rule(q, k, v, g, beta, chunk, dtype)
    else:
        grouped = lambda x: x.reshape((b // group, group) + x.shape[1:])
        out = jax.lax.map(
            jax.checkpoint(lambda xs: rule(*xs, chunk, dtype)),
            tuple(grouped(x) for x in (q, k, v, g, beta)))
    # the kernels' is (.., S, H d_v), a head's columns side by side as the
    # gated norm reads them: split out here, after the groups, the reshape
    # meets the norm's own and no array is ever laid out per head
    return out.reshape(v.shape)


def _pad_to_chunks(arrays, s, c):
    """Tokens that write nothing (``beta = 0, g = 0``) up to a whole chunk."""
    pad = -s % c
    if not pad:
        return arrays
    return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                 for x in arrays)


def _chunk_step(dtype):
    """One trip of the scan over chunk states: ``(state (B, H, d_k, d_v)
    float32, one chunk's (u, w, within, q_in, k_out, carry_decay)) ->
    (state, out (B, H, C, d_v))``; the products' operands in ``dtype``."""
    mm = lambda spec, x, y: jnp.einsum(
        spec, x.astype(dtype), y.astype(dtype),
        preferred_element_type=jnp.float32)

    def step(state, chunk_inputs):
        u_i, w_i, within_i, q_i, k_i, decay_i = chunk_inputs
        held = state.astype(dtype)
        delta = u_i - mm("bhcd,bhde->bhce", w_i, held)
        out = mm("bhcd,bhde->bhce", q_i, held) \
            + mm("bhij,bhje->bhie", within_i, delta)
        state = state * decay_i + mm("bhcd,bhce->bhde", k_i, delta)
        return state, out.astype(dtype)

    return step


def _chunked_rule_kernels(q, k, v, g, beta, chunk, dtype):
    """The rule as the kernels of ``ops/delta_rule.py``: ``q, k`` one per
    KEY head, no ``(.., C, C)`` float32 array outside the within-chunk
    pair; ``_chunk_step`` down the chunks with the states in VMEM, no loop.
    Returns ``(B, S, H d_v)``."""
    s, c = v.shape[1], min(chunk, v.shape[1])
    q, k, v, g, beta = _pad_to_chunks((q, k, v, g, beta), s, c)
    u, w, within, q_in, k_out, gamma = delta_rule.within_chunk(
        q, k, v, g, beta, chunk=c, dtype=dtype)
    return delta_rule.between_chunks(
        u, w, within, q_in, k_out, jnp.exp(gamma[..., -1:]))[:, :s]


def _chunked_rule(q, k, v, g, beta, chunk, dtype):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    q, k, v, g, beta = _pad_to_chunks((q, k, v, g, beta), s, c)
    n = q.shape[1] // c
    # (B, H, N, C, d): a chunk's tokens and a head's width on the minor axes
    split = lambda x: x.reshape((b, n, c) + x.shape[2:])
    heads = lambda x: jnp.moveaxis(split(x), 3, 1)
    q, k, v = (heads(x) for x in (q, k, v))     # float32 only where gated
    g, beta = (jnp.moveaxis(split(x.astype(jnp.float32)), 3, 1)
               for x in (g, beta))                      # (B, H, N, C)
    mm = lambda spec, x, y: jnp.einsum(
        spec, x.astype(dtype), y.astype(dtype),
        preferred_element_type=jnp.float32)

    gamma = jnp.cumsum(g, axis=-1)      # log decay since the chunk began
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # decay from token j to token i >= j; the exponent is masked BEFORE the
    # exp: above the diagonal it is positive and may overflow
    decay = jnp.exp(jnp.where(rows >= cols,
                              gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    lower = jnp.where(rows > cols,
                      mm("bhnid,bhnjd->bhnij", k_beta, k) * decay, 0.0)
    # u: the chunk's deltas were the state zero; w: what the incoming state
    # takes from them; one float32 product for both (they feed every later
    # chunk)
    solved = jnp.matmul(
        unit_lower_inverse(lower), jnp.concatenate(
            [v_beta, k_beta * jnp.exp(gamma)[..., None]], axis=-1),
        precision=HIGHEST)
    u, w = solved[..., :dv], solved[..., dv:]
    within = mm("bhnid,bhnjd->bhnij", q, k) * decay      # diagonal included
    q_in = q * jnp.exp(gamma)[..., None]                 # reads the incoming state
    total = gamma[..., -1:]                              # the whole chunk's log decay
    k_out = k * jnp.exp(total - gamma)[..., None]        # writes the outgoing state
    carry_decay = jnp.exp(total)[..., None]              # (B, H, N, 1, 1)

    # scan over N; the products' operands are rounded once, out here
    per_chunk = lambda x, kind: jnp.moveaxis(x.astype(kind), 2, 0)
    _, out = jax.lax.scan(
        _chunk_step(dtype), jnp.zeros((b, h, dk, dv), jnp.float32),
        (per_chunk(u, jnp.float32),) + tuple(
            per_chunk(x, dtype) for x in (w, within, q_in, k_out))
        + (per_chunk(carry_decay, jnp.float32),))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * c, dv)
    return jnp.moveaxis(out, 1, 2)[:, :s]


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log = log A``, ``A ~ U(0, 16)`` (the published code's)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class _Kernel(nn.Module):
    """``nn.Dense``'s kernel under ``nn.Dense``'s name and initializer,
    handed over as it is: for a product with its columns in another order."""

    features: int

    @nn.compact
    def __call__(self, width: int):
        return self.param("kernel", nn.linear.default_kernel_init,
                          (width, self.features), jnp.float32)


class GatedDeltaNet(nn.Module):
    """One Gated DeltaNet mixer over the heads this chip holds: ``(B, S, D)
    -> (B, S, D)``."""

    sizes: GatedDeltaSizes
    key_heads: int
    value_heads: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        z, dt = self.sizes, self.dtype
        b, s, d = x.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, z.key_head_dim,
                          z.value_head_dim)
        r = hv // hk                                  # value heads a key head
        convolved = 2 * hk * dk + hv * dv         # q, k, v: what ``conv`` meets
        fused = gdn_passes.applies(s, convolved, dv, dt, taps=z.conv_kernel)
        with jax.named_scope("proj"):
            # the kernel as published, per key head: q(d_k) k(d_k) v(r d_v)
            # z(r d_v); the product with its columns in the order the stages
            # read them — [q | k | v | z], each over all key heads — so that
            # ``mixed`` and ``gate`` are column ranges of it: the columns
            # move on the WEIGHT (50 MB), never on the activations
            heads = _Kernel(convolved + hv * dv, name="qkvz")(d).reshape(
                d, hk, -1)
            cuts = (0, dk, 2 * dk, 2 * dk + r * dv, 2 * dk + 2 * r * dv)
            qkvz = jnp.dot(x.astype(dt), jnp.concatenate(
                [heads[..., lo:hi].reshape(d, -1)
                 for lo, hi in zip(cuts, cuts[1:])], axis=1).astype(dt))
            # per key head: b(r) a(r)
            ba = _dense(2 * hv, dt, "ba")(x).reshape(b, s, hk, 2 * r)
            flat = lambda t: t.reshape(b, s, -1)
            b_, a_ = flat(ba[..., :r]), flat(ba[..., r:])    # (B, S, H_v)
        with jax.named_scope("conv"):
            taps = self.param("conv", nn.initializers.lecun_normal(),
                              (z.conv_kernel, convolved), jnp.float32)
            if fused:       # reads its columns of ``qkvz`` where they lie
                mixed = gdn_passes.conv_silu(qkvz, taps)
            else:
                mixed = nn.silu(causal_conv(qkvz[..., :convolved],
                                            taps.astype(dt)))
        with jax.named_scope("core"):
            a_log = self.param("A_log", _decay_init, (hv,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                                 jnp.float32)
            q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
            k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
            v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
            unit = lambda t: t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), -1, keepdims=True) + self.eps)
            q = (unit(q.astype(jnp.float32)) * dk ** -0.5).astype(dt)
            k = unit(k.astype(jnp.float32)).astype(dt)
            beta = jax.nn.sigmoid(b_.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                a_.astype(jnp.float32) + dt_bias)
            out = chunked_delta_rule(q, k, v, g, beta, chunk=z.chunk,
                                     dtype=dt, group=z.group)
        with jax.named_scope("gate_norm"):
            gain = self.param("scale", nn.initializers.ones, (dv,),
                              jnp.float32)
            if fused:
                out = gdn_passes.gated_norm(out, qkvz, gain, self.eps,
                                            column=convolved)
            else:
                out = gated_rms_norm(
                    out, qkvz[..., convolved:].reshape(b, s, hv, dv), gain,
                    self.eps, dt)
        with jax.named_scope("proj"):
            return _dense(d, dt, "o")(out.reshape(b, s, hv * dv))
