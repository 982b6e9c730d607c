"""The plain reference of a decoder trunk under BYOL: one training step in
straightforward float32 ``jax.numpy``, matrix products at precision
``highest``.

It imports nothing of the program's models or ops.  It walks a parameter
tree with the program's NAMES (``embed``, ``layerN/attn/q_a`` ...,
``layerN/moe/experts/gate`` ..., ``projector/dense1`` ...) holding the
benchmark's own seeded values (lib/weights_decoder_trunk.py) and follows
the published equations, sizes from the configuration file's plain keys
(the catalog row's ``config``):

* **streams** (manifold-constrained hyper-connections, arXiv 2512.24880):
  ``X`` is ``n x D`` per token, entered by copying the embedding into every
  stream and left by summing the streams before the final RMSNorm.  Round
  each sub-layer ``F``: ``x~ = RMSNorm(vec(X))``; ``H_pre = sigmoid(a_pre
  x~ Phi_pre + b_pre)``; ``H_post = 2 sigmoid(a_post x~ Phi_post +
  b_post)``; ``H_res = Sinkhorn(exp(clip(a_res mat(x~ Phi_res) + B_res)))``
  (rows then columns normalised, ``hc_eps`` in the denominators,
  ``hc_sinkhorn_iters`` times); ``X <- H_res X + H_post^T F(RMSNorm(H_pre
  X))``.
* **latent attention** (the DeepSeek-V3 modelling code): ``c_q =
  RMSNorm(h W_qa)``, ``q = c_q W_qb`` per head ``[nope | rope]``; ``[c_kv |
  k_rope] = h W_kva``, ``c_kv <- RMSNorm(c_kv)``, ``[k_nope | v] = c_kv
  W_kvb`` per head; rotary embedding with YaRN frequencies on ``q_rope``
  and on the one ``k_rope`` all heads share; ``softmax(q k^T / sqrt(d_qk)
  m^2)`` under a causal mask, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  ``concat_heads(A v) W_o``.  The tree holds the heads of ONE chip's share;
  what the other heads add to ``W_o``'s sum is left out, as in the program.
* **experts** (``noaux_tc``): ``s = sigmoid(h W_g)``, the top-k of ``s +
  b``, weights ``s_i / sum_topk s`` times ``routed_scaling_factor``; the
  routed part is the sum over the chosen experts THIS SHARE HOLDS of ``w_i
  down_i(silu(gate_i h) * up_i h)``, by dense one-hot dispatch (every held
  expert over every token, times its weight or 0: no sort, no ragged
  product); plus the shared expert whole.
* a leading dense layer has a SwiGLU FFN in the experts' place.
* the sequence's representation is the mean over positions of the
  final-norm hidden states; BYOL's heads, loss, probe, learning rate and
  EMA schedule are ``lib/reference.py``'s; LARS adapts every kernel — each
  EXPERT of a stacked expert kernel alone — and leaves gains, biases and
  the hyper-connection scalars and static maps untouched.

Memory: the trunk runs ONE SEQUENCE AT A TIME (no layer couples
sequences); the heads' BatchNorm couples the batch, so the heads and the
loss run over all rows, and the trunk's gradient is accumulated sequence by
sequence from the features' cotangent.  The momentum lives on the host.

``precision``: as ``lib/reference.py`` — ``float32`` is the reference;
``bfloat16`` / ``fp8`` round every matrix product's operands and result
(the CONTROL that check.py must fail, never a result).
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import (HIGHEST, ema_decay, learning_rate,
                                      mlp_head, q, tail_loss)

# leaves LARS neither decays nor adapts, whatever their rank
UNADAPTED = frozenset({
    "scale", "bias", "alpha_pre", "alpha_post", "alpha_res", "b_pre",
    "b_post", "b_res", "e_score_correction_bias"})


def sizes_of(conf: dict) -> dict:
    """What the trunk's equations need of a configuration file, hashable."""
    rope = conf["rope_scaling"]
    index, of = (int(t) for t in conf["layer_share"].split("/"))
    published = conf.get("published", {})
    experts = published.get("n_routed_experts", conf["n_routed_experts"])
    return dict(
        nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
        v=conf["v_head_dim"], kv_rank=conf["kv_lora_rank"],
        top_k=conf["num_experts_per_tok"],
        scaling=float(conf["routed_scaling_factor"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        first_expert=index * (experts // of),
        eps=float(conf["rms_norm_eps"]), theta=float(conf["rope_theta"]),
        factor=float(rope["factor"]),
        original=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        mscale=float(rope["mscale"]),
        mscale_all_dim=float(rope["mscale_all_dim"]),
        iters=int(conf["hc_sinkhorn_iters"]), hc_eps=float(conf["hc_eps"]),
        clamp_min=float(conf["mhc_h_res_clamp_min"]),
        clamp_max=float(conf["mhc_h_res_clamp_max"]))


def mm(a, b, precision):
    return q(jnp.matmul(q(a, precision), q(b, precision), precision=HIGHEST),
             precision)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(factor, mscale):
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_angles(z, positions):
    """``(S, rope/2)`` angles: YaRN blends the interpolated frequency
    (``/ factor``) with the published one along a linear ramp between the
    correction dimensions of ``beta_fast`` and ``beta_slow``."""
    dim, base = z["rope"], z["theta"]
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq = base ** -exponent
    if z["factor"] > 1.0:
        def corr(rot):
            return dim * math.log(z["original"] / (rot * 2 * math.pi)) / (
                2 * math.log(base))
        low = max(math.floor(corr(z["beta_fast"])), 0)
        high = min(math.ceil(corr(z["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        freq = freq / z["factor"] * ramp + freq * (1.0 - ramp)
    return np.arange(positions, dtype=np.float64)[:, None] * freq[None, :]


def rotate(x, angles, amplitude):
    """``x``: ``(S, ..., rope)``, consecutive pairs rotated by the
    position's angles; first components, then second."""
    s = x.shape[0]
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    shape = (s,) + (1,) * (x.ndim - 2) + (angles.shape[-1],)
    cos = jnp.asarray(np.cos(angles) * amplitude, jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angles) * amplitude, jnp.float32).reshape(shape)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def latent_attention(p, h, z, precision):
    """``h``: ``(S, D)`` of one sequence."""
    s = h.shape[0]
    dn, dr, dv = z["nope"], z["rope"], z["v"]
    c_q = rms_norm(mm(h, p["q_a"]["kernel"], precision),
                   p["q_norm"]["scale"], z["eps"])
    qh = mm(c_q, p["q_b"]["kernel"], precision).reshape(s, -1, dn + dr)
    heads = qh.shape[1]
    kv_a = mm(h, p["kv_a"]["kernel"], precision)
    c_kv = rms_norm(kv_a[:, :z["kv_rank"]], p["kv_norm"]["scale"], z["eps"])
    k_rope = kv_a[:, z["kv_rank"]:]
    kv = mm(c_kv, p["kv_b"]["kernel"], precision).reshape(s, heads, dn + dv)
    angles = rotary_angles(z, s)
    amplitude = _mscale(z["factor"], z["mscale"]) / _mscale(
        z["factor"], z["mscale_all_dim"])
    q_full = jnp.concatenate(
        [qh[..., :dn], rotate(qh[..., dn:], angles, amplitude)], axis=-1)
    k_rot = rotate(k_rope, angles, amplitude)
    k_full = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rot[:, None, :], (s, heads, dr))],
        axis=-1)
    scale = (dn + dr) ** -0.5 * _mscale(z["factor"], z["mscale_all_dim"]) ** 2
    scores = q(jnp.einsum("qhd,khd->hqk", q(q_full, precision),
                          q(k_full, precision), precision=HIGHEST),
               precision) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = q(jnp.einsum("hqk,khd->qhd", q(weights, precision),
                       q(kv[..., dn:], precision), precision=HIGHEST),
            precision)
    return mm(out.reshape(s, heads * dv), p["o"]["kernel"], precision)


def gated_mlp(p, x, precision):
    gate = mm(x, p["gate"]["kernel"], precision)
    up = mm(x, p["up"]["kernel"], precision)
    return mm(jax.nn.silu(gate) * up, p["down"]["kernel"], precision)


def expert_layer(p, h, z, precision):
    """The held experts' part, by dense one-hot dispatch, plus the shared
    expert.  Also returns the held experts' loads."""
    scores = jax.nn.sigmoid(mm(h, p["router"], precision))
    _, chosen = jax.lax.top_k(scores + p["e_score_correction_bias"],
                              z["top_k"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if z["norm_topk"] and z["top_k"] > 1:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * z["scaling"]
    held = p["experts"]["gate"].shape[0]
    ids = z["first_expert"] + jnp.arange(held)
    hit = chosen[:, :, None] == ids[None, None, :]            # (T, k, E)
    per_expert = jnp.sum(jnp.where(hit, weight[:, :, None], 0.0), axis=1)
    gate = q(jnp.einsum("td,edf->etf", q(h, precision),
                        q(p["experts"]["gate"], precision),
                        precision=HIGHEST), precision)
    up = q(jnp.einsum("td,edf->etf", q(h, precision),
                      q(p["experts"]["up"], precision),
                      precision=HIGHEST), precision)
    down = q(jnp.einsum("etf,efd->etd", q(jax.nn.silu(gate) * up, precision),
                        q(p["experts"]["down"], precision),
                        precision=HIGHEST), precision)
    routed = jnp.einsum("etd,te->td", down, per_expert)
    load = jnp.sum(hit, axis=(0, 1))
    return routed + gated_mlp(p["shared"], h, precision), load


def hyper_maps(p, streams, z, precision):
    """``streams``: ``(T, n, D)`` -> ``h_pre (T, n)``, ``h_post (T, n)``,
    ``h_res (T, n, n)``."""
    t, n, d = streams.shape
    x = rms_norm(streams.reshape(t, n * d), p["scale"], z["eps"])
    pre = jax.nn.sigmoid(p["alpha_pre"] * mm(x, p["phi_pre"], precision)
                         + p["b_pre"])
    post = 2.0 * jax.nn.sigmoid(
        p["alpha_post"] * mm(x, p["phi_post"], precision) + p["b_post"])
    logits = p["alpha_res"] * mm(x, p["phi_res"], precision).reshape(
        t, n, n) + p["b_res"]
    m = jnp.exp(jnp.clip(logits, z["clamp_min"], z["clamp_max"]))
    for _ in range(z["iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + z["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + z["hc_eps"])
    return pre, post, m


def sublayer(p_hc, p_norm, streams, fn, z, precision):
    pre, post, res = hyper_maps(p_hc, streams, z, precision)
    x = jnp.einsum("tn,tnd->td", pre, streams)
    y = fn(rms_norm(x, p_norm["scale"], z["eps"]))
    return jnp.einsum("tij,tjd->tid", res, streams) \
        + post[:, :, None] * y[:, None, :]


def trunk_layer(p, streams, z, precision):
    streams = sublayer(
        p["attn_hc"], p["attn_norm"], streams,
        lambda x: latent_attention(p["attn"], x, z, precision), z, precision)
    if "ffn" in p:
        feed_forward = lambda x: gated_mlp(p["ffn"], x, precision)
    else:
        feed_forward = lambda x: expert_layer(p["moe"], x, z, precision)[0]
    return sublayer(p["ffn_hc"], p["ffn_norm"], streams, feed_forward, z,
                    precision)


def _layer_order(backbone):
    return sorted((k for k in backbone if k.startswith("layer")),
                  key=lambda k: int(re.findall(r"\d+", k)[0]))


def trunk(backbone, tokens, z, precision="float32"):
    """One sequence: ``(S,)`` ids -> ``(D,)`` its representation."""
    x = backbone["embed"]["embedding"][tokens]                # (S, D)
    n = backbone["layer0"]["attn_hc"]["b_pre"].shape[0]
    streams = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    for name in _layer_order(backbone):
        streams = trunk_layer(backbone[name], streams, z, precision)
    hidden = rms_norm(jnp.sum(streams, axis=1),
                      backbone["final_norm"]["scale"], z["eps"])
    return jnp.mean(hidden, axis=0)


# ---- one BYOL step --------------------------------------------------------

_Z_CACHE: dict = {}


def _frozen(z: dict):
    key = tuple(sorted(z.items()))
    _Z_CACHE[key] = z
    return key


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


def _adaptation(names, p) -> str:
    if p.ndim <= 1 or names[-1] in UNADAPTED:
        return "none"
    return "per_expert" if "experts" in names[:-1] else "whole"


@functools.partial(jax.jit, static_argnames=("kind", "wd", "trust"),
                   donate_argnums=(0, 2, 3))
def _update_leaf(p, g, m, t, lr, tau, *, kind, wd, trust):
    if kind != "none":                       # decayed and adapted
        g = g + wd * p
        axes = tuple(range(1, p.ndim)) if kind == "per_expert" else None
        norm = lambda x: jnp.sqrt(jnp.sum(x * x, axis=axes,
                                          keepdims=axes is not None))
        pn, gn = norm(p), norm(g)
        g = g * jnp.where((pn > 0) & (gn > 0), trust * pn / gn, 1.0)
    m_new = g + 0.9 * m
    p_new = p - lr * m_new
    return p_new, m_new, tau * t + (1.0 - tau) * p_new


def lars_momentum_ema(params, grads, trace, target, lr, tau, *, wd,
                      trust=1e-3):
    """Leaf by leaf (``trace`` arrives and leaves as host arrays)."""
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for (path, p), g, m, t in zip(
            flat_p, jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(trace),
            jax.tree_util.tree_leaves(target)):
        names = [getattr(k, "key", str(k)) for k in path]
        p_new, m_new, t_new = _update_leaf(
            p, g, jnp.asarray(m), t, lr, tau, kind=_adaptation(names, p),
            wd=wd, trust=trust)
        out.append((p_new, np.asarray(m_new), t_new))
    unflat = lambda i: jax.tree_util.tree_unflatten(
        treedef, [o[i] for o in out])
    return unflat(0), unflat(1), unflat(2)


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
