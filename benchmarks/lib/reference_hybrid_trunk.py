"""The plain reference of a PATTERNED decoder trunk under BYOL — Gated
DeltaNet layers with a gated grouped-query attention layer every
``full_attention_interval``-th, every layer sparse — one training step in
straightforward float32 ``jax.numpy``, matrix products at precision
``highest``.

It imports nothing of the program's models or ops.  It walks a parameter
tree with the program's NAMES (``embed``, ``layerN/gdn/qkvz`` ...,
``layerN/gqa/q`` ..., ``layerN/moe/experts/gate`` ..., ``projector/dense1``
...) holding the benchmark's own seeded values (lib/weights_hybrid_trunk.py)
and follows the public ``qwen3_next`` modelling code, sizes from the
configuration file's plain keys (the catalog row's ``config``).  All norms
are ``x / rms(x) * (1 + w)`` but the DeltaNet's output norm (plain gain).

* **layer** ``i``: ``x <- x + Mixer_i(norm(x))``; ``x <- x + MoE(norm(x))``;
  ``Mixer_i`` is attention where ``(i + 1) % full_attention_interval == 0``.
* **Gated DeltaNet**: ``[q, k, v, z] = x W_qkvz`` per key head, ``[b, a] = x
  W_ba``; ``cat(q, k, v)`` through a depthwise causal convolution — FOUR
  SHIFTED ADDS — then SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; ``q, k`` L2-normalised, ``q / sqrt(d_k)``; then
  THE PER-TOKEN RECURRENCE, one ``lax.scan`` step a token: ``S' = exp(g_t)
  S``; ``delta = beta_t (v_t - S'^T k_t)``; ``S = S' + k_t delta^T``; ``o_t =
  S^T q_t`` — no chunk, no triangular solve.  The scan runs in blocks of
  tokens under ``jax.checkpoint``, so that the backward keeps one state a
  block and not one a token (4,096 tokens x 32 heads x 128 x 128 floats
  are 8.6 GB a sequence and layer).  Output ``rmsnorm(o) w silu(z)`` per
  head, then ``W_o``.
* **gated attention**: ``[q, gate] = x W_q`` per head, ``k = x W_k``, ``v = x
  W_v``; norms on ``q`` and ``k`` heads; rotate-half rotary on the first
  ``partial_rotary_factor`` of the head; THE PLAIN MASKED SOFTMAX over all
  keys, computed a block of QUERIES at a time (each block under
  ``jax.checkpoint``) with the key/value heads repeated; ``out *
  sigmoid(gate)``; ``W_o``.
* **experts**: ``p = softmax(x W_r)`` over all published experts, top-k,
  ``p_j / sum_topk p``; A LOOP OVER THE HELD EXPERTS, each computing every
  token times its weight or zero (no sort, no ragged product); plus
  ``sigmoid(x w_s)`` times the shared expert.
* representation, heads, loss, probe, learning rate, EMA schedule and LARS
  as ``lib/reference_decoder_trunk.py`` (by import): every kernel adapted,
  each EXPERT of a stacked kernel alone, the convolution's taps as one
  kernel; 1-D leaves (gains, ``A_log``, ``dt_bias``) untouched.

Departures from the published code: no LM head and no multi-token-prediction
module (BYOL has no next-token loss); the sequence's representation is the
mean over positions of the final-norm hidden states; the top-k weights are
divided by ``sum + 1e-20`` (the published code adds nothing); one chip's
share of the experts and of the vocabulary (what the absent experts add is
left out, as in the program).

Memory: ONE SEQUENCE AT A TIME, as ``lib/reference_decoder_trunk.py``, and
each layer under ``jax.checkpoint`` (at 4,096 tokens a layer's float32
intermediates are over a gigabyte: all four at once would not fit beside
the parameters and their gradient).

``precision``: ``float32`` is the reference; ``bfloat16`` / ``fp8`` round
every matrix product's operands and result (the CONTROL, never a result).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import (HIGHEST, ema_decay, learning_rate,
                                      mlp_head, q, tail_loss)
from benchmarks.lib.reference_decoder_trunk import (_frozen, _layer_order,
                                                    _Z_CACHE, gated_mlp,
                                                    lars_momentum_ema, mm)

SCAN_BLOCK = 64          # tokens a checkpointed block of the recurrence
QUERY_BLOCK = 512        # queries a checkpointed block of the softmax


def sizes_of(conf: dict) -> dict:
    """What the trunk's equations need of a configuration file, hashable."""
    index, of = (int(t) for t in conf["layer_share"].split(",")[0].split("/"))
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    return dict(
        interval=int(conf["full_attention_interval"]),
        key_heads=int(conf["linear_num_key_heads"]),
        value_heads=int(conf["linear_num_value_heads"]),
        dk=int(conf["linear_key_head_dim"]),
        dv=int(conf["linear_value_head_dim"]),
        heads=int(conf["num_attention_heads"]),
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        rotary=int(conf["head_dim"] * conf["partial_rotary_factor"]),
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        top_k=int(conf["num_experts_per_tok"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        first_expert=index * (published // of))


def norm0(x, scale, eps):
    """``x / rms(x) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def shifted_conv(x, taps):
    """``y[t] = sum_j taps[j] x[t - 3 + j]``: four shifted adds.  ``x``:
    ``(S, C)``; ``taps``: ``(4, C)``."""
    s = x.shape[0]
    y = taps[-1] * x
    for back in range(1, taps.shape[0]):
        y = y + taps[-1 - back] * jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]], axis=0)
    return y


def delta_recurrence(qh, kh, vh, g, beta, precision):
    """The gated delta rule token by token.  ``qh, kh``: ``(S, H, d_k)``,
    ``vh``: ``(S, H, d_v)``, ``g, beta``: ``(S, H)`` -> ``(S, H, d_v)``."""
    s, h, dk = qh.shape
    dv = vh.shape[-1]
    ein = lambda spec, a, b: q(jnp.einsum(
        spec, q(a, precision), q(b, precision), precision=HIGHEST), precision)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, None, None]
        delta = b_t[:, None] * (v_t - ein("hde,hd->he", state, k_t))
        state = state + ein("hd,he->hde", k_t, delta)
        return state, ein("hde,hd->he", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -s % SCAN_BLOCK
    tail = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    blocks = lambda x: tail(x).reshape((-1, SCAN_BLOCK) + x.shape[1:])
    _, out = jax.lax.scan(
        block, jnp.zeros((h, dk, dv), jnp.float32),
        tuple(blocks(x) for x in (qh, kh, vh, g, beta)))
    return out.reshape(-1, h, dv)[:s]


def gated_delta_net(p, x, z, precision):
    """``x``: ``(S, D)`` of one sequence."""
    s = x.shape[0]
    hk, hv, dk, dv = z["key_heads"], z["value_heads"], z["dk"], z["dv"]
    r = hv // hk
    qkvz = mm(x, p["qkvz"]["kernel"], precision).reshape(
        s, hk, 2 * dk + 2 * r * dv)
    ba = mm(x, p["ba"]["kernel"], precision).reshape(s, hk, 2 * r)
    query, key = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    value = qkvz[..., 2 * dk:2 * dk + r * dv]
    gate = qkvz[..., 2 * dk + r * dv:].reshape(s, hv, dv)
    b, a = ba[..., :r].reshape(s, hv), ba[..., r:].reshape(s, hv)
    mixed = jnp.concatenate([query.reshape(s, -1), key.reshape(s, -1),
                             value.reshape(s, -1)], axis=-1)
    mixed = jax.nn.silu(shifted_conv(mixed, p["conv"]))
    query = mixed[:, :hk * dk].reshape(s, hk, dk)
    key = mixed[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    value = mixed[:, 2 * hk * dk:].reshape(s, hv, dv)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, -1, keepdims=True) + z["eps"])
    query = jnp.repeat(unit(query) * dk ** -0.5, r, axis=1)
    key = jnp.repeat(unit(key), r, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    out = delta_recurrence(query, key, value, g, beta, precision)
    out = out * jax.lax.rsqrt(
        jnp.mean(out * out, -1, keepdims=True) + z["eps"]) * p["scale"]
    out = out * jax.nn.silu(gate)
    return mm(out.reshape(s, hv * dv), p["o"]["kernel"], precision)


def half_rotary(x, z):
    """Rotate-half rotary on the first ``rotary`` dims of ``(S, H, D)``."""
    s, rot = x.shape[0], z["rotary"]
    freqs = z["theta"] ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    angles = np.arange(s, dtype=np.float64)[:, None] * freqs[None, :]
    both = lambda t: jnp.asarray(np.concatenate([t, t], -1),
                                 jnp.float32)[:, None, :]
    head, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-head[..., rot // 2:], head[..., :rot // 2]],
                             axis=-1)
    return jnp.concatenate(
        [head * both(np.cos(angles)) + turned * both(np.sin(angles)), rest],
        axis=-1)


def gated_attention(p, x, z, precision):
    s = x.shape[0]
    h, hkv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    qg = mm(x, p["q"]["kernel"], precision).reshape(s, h, 2 * dh)
    query, gate = qg[..., :dh], qg[..., dh:]
    key = mm(x, p["k"]["kernel"], precision).reshape(s, hkv, dh)
    value = mm(x, p["v"]["kernel"], precision).reshape(s, hkv, dh)
    query = half_rotary(norm0(query, p["q_norm"]["scale"], z["eps"]), z)
    key = half_rotary(norm0(key, p["k_norm"]["scale"], z["eps"]), z)
    key = jnp.repeat(key, h // hkv, axis=1)
    value = jnp.repeat(value, h // hkv, axis=1)

    @jax.checkpoint
    def rows(q_blk, first):
        scores = q(jnp.einsum("qhd,khd->hqk", q(q_blk, precision),
                              q(key, precision), precision=HIGHEST),
                   precision) * dh ** -0.5
        visible = (first + jnp.arange(q_blk.shape[0]))[:, None] >= \
            jnp.arange(s)[None, :]
        weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return q(jnp.einsum("hqk,khd->qhd", q(weights, precision),
                            q(value, precision), precision=HIGHEST),
                 precision)

    out = jnp.concatenate(
        [rows(query[i:i + QUERY_BLOCK], i)
         for i in range(0, s, QUERY_BLOCK)], axis=0)
    out = out * jax.nn.sigmoid(gate)
    return mm(out.reshape(s, h * dh), p["o"]["kernel"], precision)


def expert_layer(p, x, z, precision):
    """The held experts' part, one expert at a time over every token, plus
    the gated shared expert.  Also returns the held experts' loads."""
    probs = jax.nn.softmax(mm(x, p["router"], precision), axis=-1)
    weight, chosen = jax.lax.top_k(probs, z["top_k"])
    if z["norm_topk"] and z["top_k"] > 1:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    held = p["experts"]["gate"].shape[0]
    ids = z["first_expert"] + jnp.arange(held)
    hit = chosen[:, :, None] == ids[None, None, :]            # (T, k, E)
    per_expert = jnp.sum(jnp.where(hit, weight[:, :, None], 0.0), axis=1)

    def one(total, e):
        w_gate, w_up, w_down, w_tokens = e
        act = jax.nn.silu(mm(x, w_gate, precision)) * mm(x, w_up, precision)
        return total + w_tokens[:, None] * mm(act, w_down, precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"],
         per_expert.T))
    shared = gated_mlp(p["shared"], x, precision) * jax.nn.sigmoid(
        mm(x, p["shared_gate"]["kernel"], precision))
    return routed + shared, jnp.sum(hit, axis=(0, 1))


def trunk_layer(p, x, z, precision):
    h = norm0(x, p["attn_norm"]["scale"], z["eps"])
    if "gqa" in p:
        x = x + gated_attention(p["gqa"], h, z, precision)
    else:
        x = x + gated_delta_net(p["gdn"], h, z, precision)
    h = norm0(x, p["ffn_norm"]["scale"], z["eps"])
    return x + expert_layer(p["moe"], h, z, precision)[0]


def trunk(backbone, tokens, z, precision="float32"):
    """One sequence: ``(S,)`` ids -> ``(D,)`` its representation."""
    x = backbone["embed"]["embedding"][tokens]                # (S, D)
    for i, name in enumerate(_layer_order(backbone)):
        if ("gqa" in backbone[name]) != ((i + 1) % z["interval"] == 0):
            raise ValueError(f"{name}: not the mixer the pattern gives it")
        # a layer's intermediates live for that layer's backward alone
        x = jax.checkpoint(
            lambda p, h: trunk_layer(p, h, z, precision))(backbone[name], x)
    hidden = norm0(x, backbone["final_norm"]["scale"], z["eps"])
    return jnp.mean(hidden, axis=0)


# ---- one BYOL step --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("zkey", "precision"))
def _features(backbone, tokens, *, zkey, precision):
    return trunk(backbone, tokens, _Z_CACHE[zkey], precision)


@functools.partial(jax.jit, static_argnames=("zkey", "precision"),
                   donate_argnums=(1,))
def _accumulate(backbone, acc, tokens, ct, *, zkey, precision):
    _, vjp = jax.vjp(
        lambda p: trunk(p, tokens, _Z_CACHE[zkey], precision), backbone)
    return jax.tree_util.tree_map(jnp.add, acc, vjp(ct)[0])


def loss_and_grads(params, target_params, view1, view2, labels, *, z,
                   precision="float32"):
    """Loss and the online gradient of one BYOL step: trunk sequence by
    sequence, heads and loss over all rows."""
    zkey = _frozen(z)
    rows = [jnp.asarray(r, jnp.int32) for r in np.concatenate(
        [np.asarray(view1), np.asarray(view2)], axis=0)]
    feats = lambda p: jnp.stack([
        _features(p["backbone"], r, zkey=zkey, precision=precision)
        for r in rows])
    target_proj = jax.jit(functools.partial(mlp_head, precision=precision))(
        target_params["projector"], feats(target_params))
    heads = {k: params[k] for k in ("projector", "predictor", "probe")}
    tail = jax.jit(jax.value_and_grad(
        functools.partial(tail_loss, precision=precision), argnums=(0, 1)))
    loss, (g_heads, ct) = tail(heads, feats(params), target_proj,
                               jnp.asarray(labels))
    acc = jax.tree_util.tree_map(jnp.zeros_like, params["backbone"])
    for i, r in enumerate(rows):
        acc = _accumulate(params["backbone"], acc, r, ct[i], zkey=zkey,
                          precision=precision)
    return loss, dict(g_heads, backbone=acc)


def train_steps(params, batches, hp, *, conf, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from ``params`` (target = a
    copy, momentum zero, counters zero).  Returns per-step losses, the
    momentum after the FIRST step (host arrays) and the parameters after
    the last."""
    z = sizes_of(conf)
    params = jax.tree_util.tree_map(jnp.array, params)
    target = jax.tree_util.tree_map(jnp.array, params)
    trace = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, np.float32), params)
    losses, first_trace = [], None
    for k, b in enumerate(batches):
        loss, grads = loss_and_grads(params, target, b["view1"], b["view2"],
                                     b["label"], z=z, precision=precision)
        losses.append(float(loss))
        params, trace, target = lars_momentum_ema(
            params, grads, trace, target, learning_rate(k, hp),
            ema_decay(k, hp), wd=hp["weight_decay"])
        del grads
        if k == 0:
            first_trace = trace
    return {"losses": losses, "first_trace": first_trace, "params": params}
