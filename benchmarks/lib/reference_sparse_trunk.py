"""The plain reference of a SPARSE-ATTENTION decoder trunk under BYOL —
grouped-query attention whose softmax runs over the keys a learned indexer
picks, every layer sparse with no shared expert — one training step in
straightforward float32 ``jax.numpy``, matrix products at precision
``highest``.

It imports nothing of the program's models or ops.  It walks a parameter
tree with the program's NAMES (``embed``, ``layerN/dsa/q`` ...,
``layerN/dsa/index_q`` ..., ``layerN/moe/experts/gate`` ...,
``projector/dense1`` ...) holding the benchmark's own seeded values
(lib/weights_sparse_trunk.py), sizes from the configuration file's plain
keys (the catalog row's ``config`` and its ``sa_config``).  All norms are
``x / rms(x) * w``.  Layer input ``x``, ``h = norm(x)``:

* **attention**: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v`` (Hkv
  heads); ``q, k`` normalised per head with a gain; rotate-half rotary over
  the WHOLE head at ``rope_theta`` (on text ids the three M-RoPE position
  streams coincide, so ``mrope_section`` is plain rotary); key/value heads
  repeated.
* **indexer**, on ``stop_gradient(h)``: ``qI = h W_qI`` (J heads of d_I),
  ``kI = h W_kI`` (ONE head), ``w = h W_w`` (J); the same rotary over their
  d_I dims; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(d_I J)``.
* **selection**: ``S_t`` = the ``min(t + 1, topk)`` keys ``s <= t`` of
  largest ``I[t, s]``, a tie to the lower index — BY A FULL STABLE SORT of
  every row (``-0.0`` taken as ``0.0``), the rank read back through the
  inverse permutation.
* **core**: THE PLAIN MASKED SOFTMAX over a query's whole row of keys,
  ``softmax_{s in S_t}(q[t, h] . k[s] / sqrt(d))``, then ``W_o``; no gate.
* **the indexer's loss**: ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t,
  s])``, ``p_t`` the mean over the H heads of the core's probabilities,
  under stop-gradient; summed over the layers (weight 1) and added to BYOL's
  loss and the probe's.
* **experts**: ``p = softmax(x W_r)`` over all published experts, top-k,
  ``p_j / sum_topk p``; A LOOP OVER THE HELD EXPERTS, each computing every
  token times its weight or zero (no sort, no ragged product); no shared
  expert.
* representation, heads, loss, probe, learning rate, EMA schedule and LARS
  as ``lib/reference_decoder_trunk.py`` (by import).

Index scores, softmax and selection run over WHOLE ROWS, a block of
``QUERY_BLOCK`` queries at a time (``lax.map`` over the blocks, each under
``jax.checkpoint``: one sequence's ``[32, 4096, 4096]`` float32
probabilities are 2 GB; a block's set is found once a pass, in front of its
checkpoint); no block of keys is skipped and nothing is selected by a
threshold.

Departures from the published configuration (it states no training): no LM
head and no vision tower (BYOL over token ids trains neither); the
sequence's representation is the mean over positions of the final-norm
hidden states; the indexer's loss and stop-gradients as the
DeepSeek-V3.2-Exp report's sparse-training stage; the top-k weights are
divided by ``sum + 1e-20``; one chip's share of the experts and of the
vocabulary (what the absent experts add is left out, as in the program).

Memory: ONE SEQUENCE AT A TIME, each layer under ``jax.checkpoint``
(``lax.scan`` over the layers' stacked weights: they are alike).

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
                                                    _Z_CACHE,
                                                    lars_momentum_ema, mm,
                                                    rms_norm)

QUERY_BLOCK = 512        # queries a checkpointed block of whole rows


def sizes_of(conf: dict) -> dict:
    """What the trunk's equations need of a configuration file, hashable."""
    index, of = (int(t) for t in conf["layer_share"].split(",")[0].split("/"))
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    sa = conf["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer's equations are written for ONE key "
                         "head")
    return dict(
        heads=int(conf["num_attention_heads"]),
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]), topk=int(sa["topk"]),
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        top_k=int(conf["num_experts_per_tok"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        first_expert=index * (published // of))


def rotary(x, theta):
    """Rotate-half rotary over the whole last axis of ``(S, H, D)``."""
    s, dim = x.shape[0], x.shape[-1]
    freqs = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.arange(s, dtype=np.float64)[:, None] * freqs[None, :]
    both = lambda t: jnp.asarray(np.concatenate([t, t], -1),
                                 jnp.float32)[:, None, :]
    turned = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * both(np.cos(angles)) + turned * both(np.sin(angles))


def selected_keys(index, causal, topk):
    """``(R, S)`` index scores and causal mask -> ``(R, S)`` bool: each
    row's ``topk`` largest causal keys (all of them where it has fewer), by
    a full stable sort."""
    keyed = jnp.where(causal, -jnp.where(index == 0.0, 0.0, index), jnp.inf)
    order = jnp.argsort(keyed, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)   # inverse permutation
    return causal & (rank < topk)


def sparse_attention(p, x, z, precision):
    """``x``: ``(S, D)`` of one sequence -> the layer's output and the sum
    over its queries of ``KL(p_t || softmax_{S_t} I[t, .])``."""
    s = x.shape[0]
    h, hkv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    j, di = z["index_heads"], z["index_dim"]
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
    seen = jax.lax.stop_gradient(x)
    q_i = rotary(mm(seen, p["index_q"]["kernel"], precision).reshape(
        s, j, di), z["theta"])
    k_i = rotary(mm(seen, p["index_k"]["kernel"], precision).reshape(
        s, 1, di), z["theta"])[:, 0]
    w_i = mm(seen, p["index_w"]["kernel"], precision)

    def index_rows(qi_blk, w_blk):
        return jnp.sum(
            w_blk.T[:, :, None] * jax.nn.relu(ein("qjd,kd->jqk", qi_blk, k_i)),
            axis=0) * (di * j) ** -0.5

    @jax.checkpoint
    def rows(q_blk, qi_blk, w_blk, keep):
        index = index_rows(qi_blk, w_blk)
        scores = ein("qhd,khd->hqk", q_blk, key) * dh ** -0.5
        weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        out = ein("hqk,khd->qhd", weights, value)
        target = jax.lax.stop_gradient(jnp.mean(weights, axis=0))
        log_q = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), -1)
        hit = keep & (target > 0.0)
        kl = jnp.sum(jnp.where(
            hit, target * (jnp.log(jnp.where(hit, target, 1.0))
                           - jnp.where(hit, log_q, 0.0)), 0.0))
        return out, kl

    def block_rows(block):
        q_blk, qi_blk, w_blk, first = block
        causal = (first + jnp.arange(q_blk.shape[0]))[:, None] >= \
            jnp.arange(s)[None, :]
        # the set once a pass, in front of the block's checkpoint: a sort
        # takes no gradient and is the slowest thing here to do again
        keep = selected_keys(jax.lax.stop_gradient(
            index_rows(qi_blk, w_blk)), causal, z["topk"])
        return rows(q_blk, qi_blk, w_blk, keep)

    size = min(QUERY_BLOCK, s)
    if s % size:
        raise ValueError(f"{s} queries do not come in blocks of {size}")
    blocks = lambda a: a.reshape((s // size, size) + a.shape[1:])
    # one block's program, run for each (``lax.map``): the equations are a
    # block's whatever the block, and eight copies of them compile for
    # minutes
    outs, kls = jax.lax.map(block_rows, (
        blocks(query), blocks(q_i), blocks(w_i), jnp.arange(0, s, size)))
    out = outs.reshape(s, h * dh)
    return mm(out, p["o"]["kernel"], precision), jnp.sum(kls)


def expert_layer(p, x, z, precision):
    """The held experts' part, one expert at a time over every token.  Also
    returns the held experts' loads."""
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
    return routed, jnp.sum(hit, axis=(0, 1))


def trunk_layer(p, x, z, precision):
    """-> the layer's output, its index loss's sum over the queries, and the
    rows each held expert was sent."""
    mixed, kl = sparse_attention(
        p["dsa"], rms_norm(x, p["attn_norm"]["scale"], z["eps"]), z,
        precision)
    x = x + mixed
    h = rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
    routed, rows = expert_layer(p["moe"], h, z, precision)
    return x + routed, kl, rows


def trunk(backbone, tokens, z, precision="float32"):
    """One sequence: ``(S,)`` ids -> ``(D,)`` its representation, and its
    layers' index loss (the mean over its queries, summed over layers).
    The layers are alike, so ONE layer's program runs for each (``lax.scan``
    over their stacked weights; four copies of it are 0.4 GB of executable
    and two minutes of compiling), under ``jax.checkpoint``: a layer's
    intermediates live for that layer's backward alone."""
    def layer(x, p):
        return trunk_layer(p, x, z, precision)[:2]
    x, kls = jax.lax.scan(
        jax.checkpoint(layer), backbone["embed"]["embedding"][tokens],
        jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[backbone[name] for name in _layer_order(backbone)]))
    hidden = rms_norm(x, backbone["final_norm"]["scale"], z["eps"])
    return jnp.mean(hidden, axis=0), jnp.sum(kls) / tokens.shape[0]


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
    """Loss (and the index loss in it) and the online gradient of one BYOL
    step: trunk sequence by sequence, heads and loss over all rows; the
    index loss is the mean over the sequences of theirs."""
    zkey = _frozen(z)
    rows = [jnp.asarray(r, jnp.int32) for r in np.concatenate(
        [np.asarray(view1), np.asarray(view2)], axis=0)]
    run = lambda p: [_features(p["backbone"], r, zkey=zkey,
                               precision=precision) for r in rows]
    target_proj = jax.jit(functools.partial(mlp_head, precision=precision))(
        target_params["projector"],
        jnp.stack([f for f, _ in run(target_params)]))
    online = run(params)
    index_loss = sum(kl for _, kl in online) / len(rows)
    heads = {k: params[k] for k in ("projector", "predictor", "probe")}
    tail = jax.jit(jax.value_and_grad(
        functools.partial(tail_loss, precision=precision), argnums=(0, 1)))
    loss, (g_heads, ct) = tail(heads, jnp.stack([f for f, _ in online]),
                               target_proj, jnp.asarray(labels))
    acc = jax.tree_util.tree_map(jnp.zeros_like, params["backbone"])
    share = jnp.asarray(1.0 / len(rows), jnp.float32)
    for i, r in enumerate(rows):
        acc = _accumulate(params["backbone"], acc, r, (ct[i], share),
                          zkey=zkey, precision=precision)
    return loss + index_loss, index_loss, dict(g_heads, backbone=acc)


def train_steps(params, batches, hp, *, conf, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from ``params`` (target = a
    copy, momentum zero, counters zero).  Returns per-step losses (and the
    index loss in each), the momentum after the FIRST step (host arrays)
    and the parameters after the last."""
    z = sizes_of(conf)
    params = jax.tree_util.tree_map(jnp.array, params)
    target = jax.tree_util.tree_map(jnp.array, params)
    trace = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, np.float32), params)
    losses, index_losses, first_trace = [], [], None
    for k, b in enumerate(batches):
        loss, index_loss, grads = loss_and_grads(
            params, target, b["view1"], b["view2"], b["label"], z=z,
            precision=precision)
        losses.append(float(loss))
        index_losses.append(float(index_loss))
        params, trace, target = lars_momentum_ema(
            params, grads, trace, target, learning_rate(k, hp),
            ema_decay(k, hp), wd=hp["weight_decay"])
        del grads
        if k == 0:
            first_trace = trace
    return {"losses": losses, "index_losses": index_losses,
            "first_trace": first_trace, "params": params}
