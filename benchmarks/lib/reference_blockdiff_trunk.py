"""The plain reference of a BLOCK-DIFFUSION decoder trunk under BYOL — the
training forward of BD3-LMs (arXiv 2503.09573, section 3 and its
"vectorized training" mask), which SDAR (arXiv 2510.06303) keeps, over the
Qwen3-30B-A3B block — one training step in straightforward float32
``jax.numpy``, matrix products at precision ``highest``.

It imports nothing of the program's models or ops.  It walks a parameter
tree with the program's NAMES (``embed``, ``layerN/blockdiff/q`` ...,
``layerN/moe/experts/gate`` ..., ``projector/dense1`` ...) holding the
benchmark's own seeded values (lib/weights_sparse_trunk.py), sizes from the
configuration file's plain keys (the catalog row's ``config`` and the
file's ``block_length``).  All norms are ``x / rms(x) * w``.

A view of a sample is ONE ROW of ``2 L`` ids, ``[x~ ; x0]``: ``x0`` the
``L`` clean ids, ``x~`` the same ids with some replaced by the mask id (the
traffic's business: lib of the driver).  Row ``n`` of either half stands at
POSITION ``n``; ``beta(p) = p // block_length`` is a position's block.
Layer input ``x (2L, D)``, ``h = norm(x)``:

* **attention**: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v`` (Hkv
  heads); ``q, k`` normalised per head with a gain; rotate-half rotary over
  the WHOLE head at ``rope_theta`` by the row's POSITION (the angles of ``0
  .. L-1`` twice); key/value heads repeated; ``softmax(q k^T / sqrt(d) +
  M) v`` then ``W_o``, no gate.  ``M`` is the WHOLE ``[2L, 2L]`` rule,
  written from its four lines (:func:`visible`) — a query SEES a key when
  - clean query p, clean key r:    ``beta(r) <= beta(p)``;
  - noised query p, clean key r:   ``beta(r) <  beta(p)``;
  - noised query p, noised key r:  ``beta(r) == beta(p)``;
  - clean query, noised key:       never
  — and applied to WHOLE ROWS of scores, ``QUERY_BLOCK`` queries at a time
  (``lax.map`` over the blocks, each under ``jax.checkpoint``: one
  sequence's ``[32, 8192, 8192]`` float32 probabilities are 8.6 GB); no
  tile of keys is skipped.
* **experts**: ``p = softmax(x W_r)`` over all published experts, top-k,
  ``p_j / sum_topk p``; A LOOP OVER THE HELD EXPERTS, each computing every
  row times its weight or zero (no sort, no ragged product); no shared
  expert.
* out: ``norm(x)``; the representation is the mean over the ``L`` NOISED
  rows.  Heads, loss, probe, learning rate, EMA schedule and LARS as
  ``lib/reference_decoder_trunk.py`` (by import).

Departures from the published forward (the config states no training and
the papers' loss is a masked-token cross-entropy): no LM head — BYOL reads a
pooled representation, so the rows the published loss reads, the noised
ones, are pooled by their mean instead; BYOL's two views are two
independent noisings of one ``x0``; one chip's share of the experts and of
the vocabulary (what the absent experts add is left out, as in the
program); the top-k weights are divided by ``sum + 1e-20``; block length 4
(the release's generation default) and one masking rate ``t ~ U(0, 1)`` a
block (BD3-LMs' linear schedule without its clipping) are ASSUMED: the
config has no key for either.

Memory: ONE ROW AT A TIME, each layer under ``jax.checkpoint`` (``lax.scan``
over the layers' stacked weights: they are alike), each expert's products
under one of their own.  A row of 8,192 positions is twice the other
trunks' and its backward wants 8.6 GB of the chip beside the parameters and
the gradient sum (PR 45's first chip call: ``RESOURCE_EXHAUSTED`` with the
target and a second copy of the seeded weights on the device too), so the
target's parameters and the momentum live on the HOST between their uses.

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
    return dict(
        heads=int(conf["num_attention_heads"]),
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]), theta=float(conf["rope_theta"]),
        eps=float(conf["rms_norm_eps"]),
        block_length=int(conf["block_length"]),
        top_k=int(conf["num_experts_per_tok"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        first_expert=index * (published // of))


def visible(query, key, length: int, block_length: int):
    """The block-diffusion training mask, its four lines: ``query``, ``key``
    are ROW numbers of ``[noised | clean]`` (``0 .. 2 length - 1``, any
    shapes that broadcast) -> bool, whether the query sees the key."""
    q_clean, k_clean = query >= length, key >= length
    q_block = (query % length) // block_length
    k_block = (key % length) // block_length
    return jnp.where(
        q_clean,
        k_clean & (k_block <= q_block),                  # clean -> clean
        jnp.where(k_clean, k_block < q_block,            # noised -> clean
                  k_block == q_block))                   # noised -> noised


def rotary(x, theta, positions):
    """Rotate-half rotary over the whole last axis of ``(S, H, D)``, row
    ``n`` by the angle of ``positions[n]``."""
    dim = x.shape[-1]
    freqs = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.asarray(positions, np.float64)[:, None] * freqs[None, :]
    both = lambda t: jnp.asarray(np.concatenate([t, t], -1),
                                 jnp.float32)[:, None, :]
    turned = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * both(np.cos(angles)) + turned * both(np.sin(angles))


def attention(p, x, z, precision):
    """``x``: ``(2L, D)`` of one row -> the layer's output."""
    s = x.shape[0]
    length = s // 2
    h, hkv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    ein = lambda spec, a, b: q(jnp.einsum(
        spec, q(a, precision), q(b, precision), precision=HIGHEST), precision)
    positions = np.concatenate([np.arange(length), np.arange(length)])
    query = mm(x, p["q"]["kernel"], precision).reshape(s, h, dh)
    key = mm(x, p["k"]["kernel"], precision).reshape(s, hkv, dh)
    value = mm(x, p["v"]["kernel"], precision).reshape(s, hkv, dh)
    query = rotary(rms_norm(query, p["q_norm"]["scale"], z["eps"]),
                   z["theta"], positions)
    key = rotary(rms_norm(key, p["k_norm"]["scale"], z["eps"]), z["theta"],
                 positions)
    key = jnp.repeat(key, h // hkv, axis=1)
    value = jnp.repeat(value, h // hkv, axis=1)

    @jax.checkpoint
    def rows(block):
        q_blk, first = block
        scores = ein("qhd,khd->hqk", q_blk, key) * dh ** -0.5
        seen = visible((first + jnp.arange(q_blk.shape[0]))[:, None],
                       jnp.arange(s)[None, :], length, z["block_length"])
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return ein("hqk,khd->qhd", weights, value)

    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (query.reshape((s // size, size)
                                           + query.shape[1:]),
                             jnp.arange(0, s, size)))
    return mm(out.reshape(s, h * dh), p["o"]["kernel"], precision)


def expert_layer(p, x, z, precision):
    """The held experts' part, one expert at a time over every position (no
    sort, no ragged product) — ``lib/reference_sparse_trunk.expert_layer``'s
    equations, each expert's products under ``jax.checkpoint``: at 8,192
    positions a row sixteen experts' activations would be 3.5 GB of the
    backward's memory.  Also returns the held experts' loads."""
    probs = jax.nn.softmax(mm(x, p["router"], precision), axis=-1)
    weight, chosen = jax.lax.top_k(probs, z["top_k"])
    if z["norm_topk"] and z["top_k"] > 1:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    held = p["experts"]["gate"].shape[0]
    ids = z["first_expert"] + jnp.arange(held)
    hit = chosen[:, :, None] == ids[None, None, :]            # (T, k, E)
    per_expert = jnp.sum(jnp.where(hit, weight[:, :, None], 0.0), axis=1)

    @jax.checkpoint
    def expert(w_gate, w_up, w_down, w_positions):
        act = jax.nn.silu(mm(x, w_gate, precision)) * mm(x, w_up, precision)
        return w_positions[:, None] * mm(act, w_down, precision)

    routed, _ = jax.lax.scan(
        lambda total, e: (total + expert(*e), None), jnp.zeros_like(x),
        (p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"],
         per_expert.T))
    return routed, jnp.sum(hit, axis=(0, 1))


def trunk_layer(p, x, z, precision):
    """-> the layer's output and the rows each held expert was sent."""
    x = x + attention(
        p["blockdiff"], rms_norm(x, p["attn_norm"]["scale"], z["eps"]), z,
        precision)
    h = rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
    routed, rows = expert_layer(p["moe"], h, z, precision)
    return x + routed, rows


def noised_hidden(backbone, tokens, z, precision="float32"):
    """One row: ``(2L,)`` ids, ``[noised | clean]`` -> ``(L, D)`` the final
    norm's output at the NOISED positions, the rows the loss reads.  The
    layers are alike, so ONE layer's program runs for each (``lax.scan``
    over their stacked weights), under ``jax.checkpoint``: a layer's
    intermediates live for that layer's backward alone."""
    layer = jax.checkpoint(
        lambda x, p: (trunk_layer(p, x, z, precision)[0], None))
    x, _ = jax.lax.scan(
        layer, backbone["embed"]["embedding"][tokens],
        jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[backbone[name] for name in _layer_order(backbone)]))
    hidden = rms_norm(x, backbone["final_norm"]["scale"], z["eps"])
    return hidden[:tokens.shape[0] // 2]


def trunk(backbone, tokens, z, precision="float32"):
    """One row -> ``(D,)`` its representation, the mean over the noised
    half."""
    return jnp.mean(noised_hidden(backbone, tokens, z, precision), axis=0)


# ---- one BYOL step --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("zkey", "precision"))
def _features(backbone, tokens, *, zkey, precision):
    return trunk(backbone, tokens, _Z_CACHE[zkey], precision)


@functools.partial(jax.jit, static_argnames=("zkey", "precision"))
def _noised_hidden(backbone, tokens, *, zkey, precision):
    return noised_hidden(backbone, tokens, _Z_CACHE[zkey], precision)


@functools.partial(jax.jit, static_argnames=("zkey", "precision"),
                   donate_argnums=(1,))
def _accumulate(backbone, acc, tokens, ct, *, zkey, precision):
    _, vjp = jax.vjp(
        lambda p: trunk(p, tokens, _Z_CACHE[zkey], precision), backbone)
    return jax.tree_util.tree_map(jnp.add, acc, vjp(ct)[0])


def loss_and_grads(params, target_params, view1, view2, labels, *, z,
                   precision="float32"):
    """Loss and the online gradient of one BYOL step: trunk row by row,
    heads and loss over all rows.  ``target_params`` arrive as HOST arrays
    and are on the device only while the target's features are made: a
    row's backward wants the room (below)."""
    zkey = _frozen(z)
    rows = [jnp.asarray(r, jnp.int32) for r in np.concatenate(
        [np.asarray(view1), np.asarray(view2)], axis=0)]
    feats = lambda p: jnp.stack([
        _features(p["backbone"], r, zkey=zkey, precision=precision)
        for r in rows])
    on_device = jax.device_put(target_params)
    target_proj = jax.jit(functools.partial(mlp_head, precision=precision))(
        on_device["projector"], feats(on_device))
    del on_device
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


def probe(params, rows, cotangent, *, conf, precision="float32"):
    """The trunk ALONE, in front of the heads and of the pooling: the final
    norm's output at every NOISED position of each of ``rows (N, 2L)``
    (``hidden (N, L, D)``: a mean over 4,096 positions hides what a mask
    does to a few of them), and the gradient of ``sum(representation *
    cotangent)`` in every leaf of the trunk, row by row as a step does it.
    The heads' BatchNorm never enters: the one cotangent is handed to the
    program's trunk and to this one alike (the driver's business)."""
    zkey = _frozen(sizes_of(conf))
    backbone = jax.tree_util.tree_map(jnp.array, params["backbone"])
    rows = [jnp.asarray(r, jnp.int32) for r in np.asarray(rows)]
    hidden = np.stack([np.asarray(_noised_hidden(
        backbone, r, zkey=zkey, precision=precision)) for r in rows])
    acc = jax.tree_util.tree_map(jnp.zeros_like, backbone)
    for r, ct in zip(rows, jnp.asarray(cotangent, jnp.float32)):
        acc = _accumulate(backbone, acc, r, ct, zkey=zkey,
                          precision=precision)
    return {"hidden": hidden, "grads": jax.device_get(acc)}


def train_steps(params, batches, hp, *, conf, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from ``params`` (target = a
    copy, momentum zero, counters zero).  Returns per-step losses, the
    momentum after the FIRST step (host arrays) and the parameters after
    the last."""
    z = sizes_of(conf)
    params = jax.tree_util.tree_map(jnp.array, params)
    target = jax.device_get(params)
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
        target = jax.device_get(target)
        del grads
        if k == 0:
            first_trace = trace
    return {"losses": losses, "first_trace": first_trace, "params": params}
