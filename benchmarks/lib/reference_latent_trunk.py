"""The plain reference of a ONE-STREAM LATENT-ATTENTION decoder trunk under
BYOL — the DeepSeek-V3 block without hyper-connections: latent attention
(MLA) with plain rotary embedding and a plain residual, a leading dense layer,
then a sigmoid router with the ``noaux_tc`` bias over experts plus one shared
expert — one training step in straightforward float32 ``jax.numpy``, matrix
products at precision ``highest``.

It imports nothing of the program's models or ops.  It walks a parameter tree
with the program's NAMES (``embed``, ``layerN/attn/q_a`` ...,
``layerN/ffn/gate`` ..., ``layerN/moe/experts/gate`` ..., ``projector/dense1``
...) holding the benchmark's own seeded values
(lib/weights_shortconv_trunk.py, whose rules cover every leaf of this tree),
sizes from the configuration file's plain keys (the catalog row's ``config``).
All norms are ``x / rms(x) * w``.  Block ``i``, input ``x``: ``h = x +
MLA(norm(x))``, ``y = h + F_i(norm(h))``; after the last block one more norm.

* **latent attention**: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` a head
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv <- norm(c_kv)``,
  ``[k_nope | v] = c_kv W_kvb`` a head; rotary on consecutive pairs ``(2i,
  2i+1)`` (``rope_interleave``) of ``q_rope`` and of the ONE ``k_r`` all heads
  share, plain frequencies ``theta^(-2i/d)`` (``rope_scaling`` null: no YaRN,
  no ``mscale``); scores ``[q_nope | rot q_rope] . [k_nope | rot k_r] /
  sqrt(d_nope + d_rope)``; THE PLAIN CAUSAL SOFTMAX over a query's whole row
  of keys; ``concat_heads(P v) W_o``.
* **dense layers** (``i < first_k_dense_replace``): SwiGLU of
  ``intermediate_size``.
* **experts** (``noaux_tc``, one group): ``lib/reference_decoder_trunk.
  expert_layer`` — ``s = sigmoid(x W_r)``, the top-k of ``s + b``, weights
  ``s_i / (sum_topk s + 1e-20)`` times ``routed_scaling_factor``, every held
  expert over every token times its weight or zero, plus the shared expert.
* representation, heads, loss, probe, learning rate, EMA schedule and LARS as
  ``lib/reference_decoder_trunk.py`` (by import).

The softmax runs over WHOLE ROWS, a block of ``QUERY_BLOCK`` queries at a time
(``lax.map`` over the blocks, each under ``jax.checkpoint``: one sequence's
``[32, 4096, 4096]`` float32 probabilities are 2 GB).

Departures from the published configuration (it states no training): no LM
head and no multi-token-prediction module (BYOL over token ids trains
neither); the sequence's representation is the mean over positions of the
final-norm hidden states; the selection bias is a fixed seeded buffer (the
published training moves it by the experts' loads, outside the gradient); one
chip's share of the experts and of the vocabulary (what the absent experts add
is left out, as in the program).

Memory: ONE SEQUENCE AT A TIME, each layer under ``jax.checkpoint``;
consecutive layers that are alike (the expert layers) run as ONE program
under ``lax.scan`` over their stacked weights.

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
                                                    _Z_CACHE, expert_layer,
                                                    gated_mlp,
                                                    lars_momentum_ema, mm,
                                                    rms_norm, rotary_angles,
                                                    rotate)

QUERY_BLOCK = 512        # queries a checkpointed block of whole rows


def sizes_of(conf: dict) -> dict:
    """What the trunk's equations need of a configuration file, hashable."""
    if conf.get("rope_scaling") is not None or conf.get("hc_mult", 1) != 1 \
            or conf.get("n_group", 1) != 1 or conf.get("topk_group", 1) != 1:
        raise ValueError("the equations are written for plain rotary "
                         "embedding, one residual stream and one group of "
                         "experts")
    index, of = (int(t) for t in conf["layer_share"].split(",")[0].split("/"))
    published = conf.get("published", {}).get("n_routed_experts",
                                              conf["n_routed_experts"])
    return dict(
        nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
        v=conf["v_head_dim"], kv_rank=conf["kv_lora_rank"],
        top_k=conf["num_experts_per_tok"],
        scaling=float(conf["routed_scaling_factor"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        first_expert=index * (published // of),
        eps=float(conf["rms_norm_eps"]), theta=float(conf["rope_theta"]),
        factor=1.0)


def latent_attention(p, h, z, precision, *, shared_key: bool = True):
    """``h``: ``(S, D)`` of one sequence.  ``shared_key=False`` leaves the
    rotary part out of the scores (what a test of the broken twin wants)."""
    s = h.shape[0]
    dn, dr, dv = z["nope"], z["rope"], z["v"]
    ein = lambda spec, a, b: q(jnp.einsum(
        spec, q(a, precision), q(b, precision), precision=HIGHEST), precision)
    c_q = rms_norm(mm(h, p["q_a"]["kernel"], precision),
                   p["q_norm"]["scale"], z["eps"])
    qh = mm(c_q, p["q_b"]["kernel"], precision).reshape(s, -1, dn + dr)
    heads = qh.shape[1]
    kv_a = mm(h, p["kv_a"]["kernel"], precision)
    c_kv = rms_norm(kv_a[:, :z["kv_rank"]], p["kv_norm"]["scale"], z["eps"])
    kv = mm(c_kv, p["kv_b"]["kernel"], precision).reshape(s, heads, dn + dv)
    angles = rotary_angles(z, s)
    query, key = qh[..., :dn], kv[..., :dn]
    if shared_key:
        k_rot = rotate(kv_a[:, z["kv_rank"]:], angles, 1.0)
        query = jnp.concatenate(
            [query, rotate(qh[..., dn:], angles, 1.0)], axis=-1)
        key = jnp.concatenate(
            [key, jnp.broadcast_to(k_rot[:, None, :], (s, heads, dr))],
            axis=-1)
    value, scale = kv[..., dn:], (dn + dr) ** -0.5

    @jax.checkpoint
    def rows(block):
        q_blk, first = block
        scores = ein("qhd,khd->hqk", q_blk, key) * scale
        causal = (first + jnp.arange(q_blk.shape[0]))[:, None] >= \
            jnp.arange(s)[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return ein("hqk,khd->qhd", weights, value)

    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (query.reshape((s // size, size)
                                           + query.shape[1:]),
                             jnp.arange(0, s, size)))
    return mm(out.reshape(s, heads * dv), p["o"]["kernel"], precision)


def trunk_layer(p, x, z, precision):
    """-> the layer's output and the rows each held expert was sent (none
    for a dense layer)."""
    x = x + latent_attention(
        p["attn"], rms_norm(x, p["attn_norm"]["scale"], z["eps"]), z,
        precision)
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
    layer = jax.checkpoint(
        lambda x, p: (trunk_layer(p, x, z, precision)[0], None))
    x = backbone["embed"]["embedding"][tokens]                # (S, D)
    for _, run in itertools.groupby(_layer_order(backbone),
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
