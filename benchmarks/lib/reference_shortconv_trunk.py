"""The plain reference of a SHORT-CONVOLUTION decoder trunk under BYOL —
gated short convolutions beside plain grouped-query attention in a listed
layer pattern, leading dense layers, then a sigmoid router with a selection
bias over experts with no shared expert — one training step in
straightforward float32 ``jax.numpy``, matrix products at precision
``highest``.

It imports nothing of the program's models or ops.  It walks a parameter
tree with the program's NAMES (``embed``, ``layerN/shortconv/in_proj`` ...,
``layerN/gqa/q`` ..., ``layerN/ffn/gate`` ..., ``layerN/moe/experts/gate``
..., ``projector/dense1`` ...) holding the benchmark's own seeded values
(lib/weights_shortconv_trunk.py), sizes from the configuration file's plain
keys (the catalog row's ``config``).  All norms are ``x / rms(x) * w``.
Block ``i``, input ``x``: ``h = x + M_i(norm(x))``, ``y = h + F_i(norm(h))``;
after the last block one more norm.

* **short convolution** (``layer_types[i] == "conv"``): ``[B, C, u] =
  split3(x W_in)``; ``z[t] = sum_j w[j] * (B * u)[t - 2 + j]`` AS
  ``conv_L_cache`` SHIFTED ADDS, zeros before the sequence, no bias;
  ``(C * z) W_out``.  No activation.
* **attention** (``"full_attention"``): ``q = x W_q`` (H heads of ``D /
  H``), ``k = x W_k``, ``v = x W_v`` (Hkv heads); ``q, k`` normalised per
  head with a gain; rotate-half rotary over the WHOLE head at
  ``rope_theta``; key/value heads repeated; THE PLAIN CAUSAL SOFTMAX over a
  query's whole row of keys at scale ``d^-1/2``; no gate; ``W_o``.
* **dense layers** (``i < num_dense_layers``): SwiGLU of
  ``intermediate_size``.
* **experts**: ``s = sigmoid(x W_r)`` over all published experts; the
  ``num_experts_per_tok`` of largest ``s + b`` BY A FULL STABLE SORT of every
  row (``b``, ``use_expert_bias``, moves the choice and nothing else: it
  takes no gradient); weights ``s`` of the chosen over ``(their sum +
  1e-6)`` (``norm_topk_prob``), times ``routed_scaling_factor``; A LOOP OVER
  THE HELD EXPERTS, each computing every token times its weight or zero (no
  dispatch, no ragged product); no shared expert.
* representation, heads, loss, probe, learning rate, EMA schedule and LARS
  as ``lib/reference_decoder_trunk.py`` (by import).

The softmax runs over WHOLE ROWS, a block of ``QUERY_BLOCK`` queries at a
time (``lax.map`` over the blocks, each under ``jax.checkpoint``: one
sequence's ``[32, 4096, 4096]`` float32 probabilities are 2 GB).

Departures from the published configuration (it states no training): no LM
head (BYOL over token ids trains none); the sequence's representation is the
mean over positions of the final-norm hidden states; the selection bias is a
fixed seeded buffer (the published training moves it by the experts' loads,
outside the gradient); one chip's share of the experts and of the vocabulary
(what the absent experts add is left out, as in the program).

Memory: ONE SEQUENCE AT A TIME, each layer under ``jax.checkpoint``;
consecutive layers that are alike (the three convolution layers after the
attention layer) run as ONE program under ``lax.scan`` over their stacked
weights.

``precision``: ``float32`` is the reference; ``bfloat16`` / ``fp8`` round
every matrix product's operands and result (the CONTROL, never a result).
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import (HIGHEST, ema_decay, learning_rate,
                                      mlp_head, q, tail_loss)
from benchmarks.lib.reference_decoder_trunk import (_frozen, _layer_order,
                                                    _Z_CACHE, gated_mlp,
                                                    lars_momentum_ema, mm,
                                                    rms_norm)
from benchmarks.lib.reference_sparse_trunk import rotary

QUERY_BLOCK = 512        # queries a checkpointed block of whole rows
MIXERS = {"conv": "shortconv", "full_attention": "gqa"}


def sizes_of(conf: dict) -> dict:
    """What the trunk's equations need of a configuration file, hashable."""
    index, of = (int(t) for t in conf["layer_share"].split(",")[0].split("/"))
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    if conf.get("conv_bias") or not conf.get("use_expert_bias", True):
        raise ValueError("the equations are written for a convolution "
                         "without bias and a router with a selection bias")
    return dict(
        heads=int(conf["num_attention_heads"]),
        kv_heads=int(conf["num_key_value_heads"]),
        taps=int(conf["conv_L_cache"]),
        mixers=tuple(MIXERS[t] for t in conf["layer_types"]),
        theta=float(conf["rope_parameters"]["rope_theta"]),
        eps=float(conf["norm_eps"]),
        top_k=int(conf["num_experts_per_tok"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        scaling=float(conf["routed_scaling_factor"]),
        first_expert=index * (published // of))


def short_conv(p, x, z, precision):
    """``x``: ``(S, D)`` of one sequence."""
    s = x.shape[0]
    gate_in, gate_out, u = jnp.split(
        mm(x, p["in_proj"]["kernel"], precision), 3, axis=-1)
    gated, taps = gate_in * u, p["conv"]
    if taps.shape[0] != z["taps"]:
        raise ValueError(f"{taps.shape[0]} taps, not conv_L_cache")
    mixed = taps[-1] * gated
    for back in range(1, z["taps"]):
        mixed = mixed + taps[-1 - back] * jnp.concatenate(
            [jnp.zeros((back, gated.shape[1]), gated.dtype),
             gated[:s - back]], axis=0)
    return mm(gate_out * mixed, p["out_proj"]["kernel"], precision)


def attention(p, x, z, precision):
    """``x``: ``(S, D)`` of one sequence."""
    s, d = x.shape
    h, hkv = z["heads"], z["kv_heads"]
    dh = d // h
    ein = lambda spec, a, b: q(jnp.einsum(
        spec, q(a, precision), q(b, precision), precision=HIGHEST), precision)
    query = mm(x, p["q"]["kernel"], precision).reshape(s, h, dh)
    key = mm(x, p["k"]["kernel"], precision).reshape(s, hkv, dh)
    value = mm(x, p["v"]["kernel"], precision).reshape(s, hkv, dh)
    query = rotary(rms_norm(query, p["q_norm"]["scale"], z["eps"]),
                   z["theta"])
    key = rotary(rms_norm(key, p["k_norm"]["scale"], z["eps"]), z["theta"])
    key = jnp.repeat(key, h // hkv, axis=1)
    value = jnp.repeat(value, h // hkv, axis=1)

    @jax.checkpoint
    def rows(block):
        q_blk, first = block
        scores = ein("qhd,khd->hqk", q_blk, key) * dh ** -0.5
        causal = (first + jnp.arange(q_blk.shape[0]))[:, None] >= \
            jnp.arange(s)[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return ein("hqk,khd->qhd", weights, value)

    size = min(QUERY_BLOCK, s)
    if s % size:
        raise ValueError(f"{s} queries do not come in blocks of {size}")
    out = jax.lax.map(rows, (query.reshape(s // size, size, h, dh),
                             jnp.arange(0, s, size)))
    return mm(out.reshape(s, h * dh), p["o"]["kernel"], precision)


def routing(p, x, z, precision):
    """``(T, k)`` chosen experts and their weights."""
    scores = jax.nn.sigmoid(mm(x, p["router"], precision))
    order = jnp.argsort(-(scores + p["e_score_correction_bias"]), axis=-1,
                        stable=True)
    chosen = order[:, :z["top_k"]]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if z["norm_topk"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
    return chosen, weight * z["scaling"]


def expert_layer(p, x, z, precision):
    """The held experts' part, one expert at a time over every token.  Also
    returns the held experts' loads."""
    chosen, weight = routing(p, x, z, precision)
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
    return routed, jnp.sum(hit, axis=(0, 1))


def trunk_layer(p, x, z, precision):
    """-> the layer's output and the rows each held expert was sent (none
    for a dense layer)."""
    h = rms_norm(x, p["attn_norm"]["scale"], z["eps"])
    if "shortconv" in p:
        x = x + short_conv(p["shortconv"], h, z, precision)
    else:
        x = x + attention(p["gqa"], h, z, precision)
    h = rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
    if "ffn" in p:
        return x + gated_mlp(p["ffn"], h, precision), None
    routed, rows = expert_layer(p["moe"], h, z, precision)
    return x + routed, rows


def _kind(p):
    """What makes two layers' programs the same: names and shapes."""
    return tuple((jax.tree_util.keystr(path), leaf.shape) for path, leaf in
                 jax.tree_util.tree_flatten_with_path(p)[0])


def trunk(backbone, tokens, z, precision="float32"):
    """One sequence: ``(S,)`` ids -> ``(D,)`` its representation.  A run of
    like layers is ONE layer's program under ``lax.scan`` over their
    stacked weights; every layer under ``jax.checkpoint``: its
    intermediates live for that layer's backward alone."""
    names = _layer_order(backbone)
    for name, want in zip(names, z["mixers"], strict=True):
        if want not in backbone[name]:
            raise ValueError(f"{name}: not the mixer layer_types gives it")
    layer = jax.checkpoint(
        lambda x, p: (trunk_layer(p, x, z, precision)[0], None))
    x = backbone["embed"]["embedding"][tokens]                # (S, D)
    for _, run in itertools.groupby(names,
                                    key=lambda n: _kind(backbone[n])):
        x, _ = jax.lax.scan(layer, x, jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *[backbone[n] for n in run]))
    hidden = rms_norm(x, backbone["final_norm"]["scale"], z["eps"])
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
