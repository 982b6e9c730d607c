"""The plain reference of a DECODER-HYBRID-DECODER trunk under BYOL — SambaY
(arXiv 2507.06607) as the public ``modeling_phi4flash.py`` writes it, with
differential attention (arXiv 2410.05258) and Mamba-1 (arXiv 2312.00752) —
one training step in straightforward float32 ``jax.numpy``, matrix products
at precision ``highest``.

It imports nothing of the program's models or ops.  It walks a parameter
tree with the program's NAMES (``embed``, ``layerN/ssm/in_proj`` ...,
``layerN/diff/qkv`` ..., ``layerN/gmu/in_proj`` ..., ``layerN/ffn/gate``
..., ``projector/dense1`` ...) holding the benchmark's own seeded values
(lib/weights_sambay_trunk.py), sizes from the configuration file's plain
keys (the catalog row's ``config``, the file's ``kept_layers`` and the
sizes it lists as assumed).  ``LN`` is LayerNorm with gain AND bias.  A
layer's ROLE follows from its PUBLISHED index ``i`` (:func:`role`): ``i``
even is of the Mamba kind, ``i`` odd of the attention kind; ``i < L/2`` the
self-decoder (Mamba; attention under the band), ``i = L/2`` the Mamba layer
whose scan output ``m`` is kept, ``i = L/2 + 1`` the ONE full attention
layer, whose ``k, v`` are kept, later layers the cross-decoder (a gated
memory unit on ``m``; cross attention on ``k, v``).  One row ``x (S, D)``:

* block: ``h = x + Mixer(LN1(x))``; ``y = h + W2(silu(g) * u)``, ``[g | u]
  = W1 LN2(h)``; one more ``LN`` after the last block; no position enters.
* **Mamba**: ``[a | z] = W_in x``; ``c = silu(conv4(a) + b_c)`` (depthwise,
  causal, zeros before the row); ``[dt | B | C] = W_x c``; ``delta =
  softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``; ``H_t = exp(delta_t (x)
  A) * H_{t-1} + (delta_t * c_t) (x) B_t``, ``H_0 = 0``; ``m_t = H_t C_t +
  D * c_t`` — A ``lax.scan`` OVER TIME, one step a position, under
  ``jax.checkpoint`` by chunk of ``SCAN_CHUNK`` steps so that one row of
  8,192 fits; the output is ``W_out (m * silu(z))`` and ``m`` is handed on
  BY NAME.
* **differential attention**: ``q`` as ``H / 2`` pairs ``(q1_i, q2_i)`` of
  consecutive heads, ``k`` as ``Hkv / 2`` pairs, ``v`` as ``Hkv / 2`` heads
  twice as wide; pair ``i`` reads key/value pair ``i // (H / Hkv)``; ``P^c_i
  = softmax(q^c_i k^c_j^T / sqrt(d) + M)`` over a query's WHOLE ROW of keys,
  ``QUERY_BLOCK`` queries at a time; ``M`` the whole ``[S, S]`` rule
  (:func:`visible`): causal, and under a window ``t - r < window``; ``o_i =
  P^1_i v_j - lambda P^2_i v_j``, ``lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_0``, ``lambda_0 = 0.8 - 0.6 exp(-0.3 i)``; ``o_i <- (1 -
  lambda_0) rmsnorm(o_i) w``; the pairs' outputs side by side through
  ``W_o``.  The full layer hands its ``k, v`` on BY NAME; a cross layer has
  ``W_q`` and ``W_o`` alone.
* **gated memory unit**: ``W_out (m * silu(W_in x))``.
* the representation is the mean over positions of the final ``LN``'s
  output.  Heads, loss, probe, learning rate and EMA schedule as
  ``lib/reference_decoder_trunk.py`` (by import); LARS as there with this
  trunk's rule for what is neither decayed nor adapted (:func:`adaptation`).

Departures from the published forward (the config states no training; its
loss is next-token cross-entropy): no LM head — BYOL reads a pooled
representation; the kept layers are published layers ``kept_layers`` of 32
(their roles and ``lambda_0`` by their published index); one chip's share of
the vocabulary.

Memory: ONE ROW AT A TIME, each layer under ``jax.checkpoint``; the target's
parameters and the momentum live on the HOST between their uses, as
``lib/reference_blockdiff_trunk.py`` keeps them.

``precision``: ``float32`` is the reference; ``bfloat16`` / ``fp8`` round
every matrix product's operands and result (the CONTROL, never a result);
the scan has no matrix product and stays float32 in every control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import (HIGHEST, ema_decay, learning_rate,
                                      mlp_head, q, tail_loss)
from benchmarks.lib.reference_decoder_trunk import (_frozen, _layer_order,
                                                    _update_leaf, _Z_CACHE,
                                                    gated_mlp, mm)

QUERY_BLOCK = 512        # queries a checkpointed block of whole rows
SCAN_CHUNK = 128         # steps of the scan a checkpoint
# leaves of two dimensions that LARS neither decays nor adapts (beside every
# leaf of one): the logarithm of a decay rate, a channel's few taps
UNADAPTED = ("A_log", "taps")


def role(index: int, layers: int, period: int) -> str:
    """What published layer ``index`` of ``layers`` is."""
    half = layers // 2
    if index % period == 0:
        return "ssm" if index <= half else "gmu"
    if index < half:
        return "band"
    return "full" if index < half + period else "cross"


def sizes_of(conf: dict) -> dict:
    """What the trunk's equations need of a configuration file, hashable."""
    published = conf.get("published", {}).get("num_hidden_layers",
                                              conf["num_hidden_layers"])
    first, last = conf["kept_layers"]
    if last - first + 1 != conf["num_hidden_layers"]:
        raise ValueError("kept_layers are not num_hidden_layers layers")
    return dict(
        heads=int(conf["num_attention_heads"]),
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]), window=int(conf["sliding_window"]),
        eps=float(conf["layer_norm_eps"]), state=int(conf["d_state"]),
        dt_rank=int(conf["dt_rank"]),
        kept=tuple(range(first, last + 1)),
        roles=tuple(role(i, published, int(conf["mb_per_layer"]))
                    for i in range(first, last + 1)))


def layer_norm(x, p, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps) * p["scale"] \
        + p["bias"]


def visible(query, key, window: int):
    """The whole ``[S, S]`` rule: ``query``, ``key`` are positions (any
    shapes that broadcast) -> bool; ``window`` 0 = every causal key."""
    seen = key <= query
    return seen & (query - key < window) if window else seen


def selective_scan(u, delta, a, b, c, d):
    """``(S, C)`` rows of ONE sequence, the recurrence a step a position."""
    s, channels = u.shape
    fill = -s % SCAN_CHUNK
    rows = [jnp.pad(x, ((0, fill), (0, 0))).reshape(
        (-1, SCAN_CHUNK) + x.shape[1:]) for x in (u, delta, b, c)]

    def step(h, row):
        u_t, d_t, b_t, c_t = row
        h = jnp.exp(d_t[:, None] * a) * h + (d_t * u_t)[:, None] * b_t[None]
        return h, h @ c_t

    chunk = jax.checkpoint(lambda h, rows: jax.lax.scan(step, h, rows))
    _, y = jax.lax.scan(chunk, jnp.zeros((channels, a.shape[1]),
                                         jnp.float32), tuple(rows))
    return y.reshape(-1, channels)[:s] + d * u


def mamba(p, x, z, precision):
    """-> the mixer's output and ``m``, the scan's output before its gate."""
    mixed = mm(x, p["in_proj"]["kernel"], precision)
    inner = mixed.shape[1] // 2
    a, gate = mixed[:, :inner], mixed[:, inner:]
    taps = p["taps"]
    k = taps.shape[0]
    padded = jnp.pad(a, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + a.shape[0]] * taps[j] for j in range(k))
    u = jax.nn.silu(conv + p["conv_bias"])
    low = mm(u, p["x_proj"]["kernel"], precision)
    r, n = z["dt_rank"], z["state"]
    delta = jax.nn.softplus(
        mm(low[:, :r], p["dt_proj"]["kernel"], precision) + p["dt_bias"])
    m = selective_scan(u, delta, -jnp.exp(p["A_log"]), low[:, r:r + n],
                       low[:, r + n:], p["D"])
    return mm(m * jax.nn.silu(gate), p["out_proj"]["kernel"], precision), m


def gated_memory(p, x, m, precision):
    gate = mm(x, p["in_proj"]["kernel"], precision)
    return mm(m * jax.nn.silu(gate), p["out_proj"]["kernel"], precision)


def lambda_of(p, index: int):
    """``lambda`` and ``lambda_0`` of published layer ``index``."""
    lambda_0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    return (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
            + lambda_0), lambda_0


def differential_attention(p, x, z, index, window, kv, precision):
    """``x``: ``(S, D)`` of one row; ``kv``: None or the ``(k, v)`` an
    earlier layer handed on -> the layer's output and its ``(k, v)``."""
    s = x.shape[0]
    h, hkv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    ein = lambda spec, a, b: q(jnp.einsum(
        spec, q(a, precision), q(b, precision), precision=HIGHEST), precision)
    if kv is None:
        qkv = mm(x, p["qkv"]["kernel"], precision)
        query = qkv[:, :h * dh]
        key = qkv[:, h * dh:(h + hkv) * dh].reshape(s, hkv // 2, 2, dh)
        value = qkv[:, (h + hkv) * dh:].reshape(s, hkv // 2, 2 * dh)
    else:
        query = mm(x, p["q"]["kernel"], precision)
        key, value = kv
    query = query.reshape(s, h // 2, 2, dh)
    each = h // hkv                       # query pairs a key/value pair
    keys, values = jnp.repeat(key, each, axis=1), jnp.repeat(value, each,
                                                             axis=1)
    lam, lambda_0 = lambda_of(p, index)

    @jax.checkpoint
    def rows(block):
        q_blk, first = block
        seen = visible((first + jnp.arange(q_blk.shape[0]))[:, None],
                       jnp.arange(s)[None, :], window)
        outs = []
        for c in (0, 1):
            scores = ein("qhd,khd->hqk", q_blk[:, :, c], keys[:, :, c]) \
                * dh ** -0.5
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            outs.append(ein("hqk,khd->qhd", weights, values))
        return outs[0] - lam * outs[1]

    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (query.reshape((s // size, size)
                                           + query.shape[1:]),
                             jnp.arange(0, s, size))).reshape(
                                 s, h // 2, 2 * dh)
    out = out * jax.lax.rsqrt(jnp.mean(out * out, -1, keepdims=True)
                              + z["eps"]) * p["subln"]["scale"]
    out = (1.0 - lambda_0) * out
    return mm(out.reshape(s, h * dh), p["o"]["kernel"], precision), \
        (key, value)


def trunk_layer(p, x, handed, kind, index, z, precision):
    """-> the layer's output and what it hands on, BY NAME (``m``; ``k``,
    ``v``); ``handed``: what earlier layers handed on."""
    h = layer_norm(x, p["attn_norm"], z["eps"])
    handed = dict(handed)
    if kind == "ssm":
        mixed, handed["m"] = mamba(p["ssm"], h, z, precision)
    elif kind == "gmu":
        mixed = gated_memory(p["gmu"], h, handed["m"], precision)
    else:
        mixed, kv = differential_attention(
            p["diff"], h, z, index, z["window"] if kind == "band" else 0,
            (handed["k"], handed["v"]) if kind == "cross" else None,
            precision)
        if kind == "full":
            handed["k"], handed["v"] = kv
    x = x + mixed
    return x + gated_mlp(p["ffn"], layer_norm(x, p["ffn_norm"], z["eps"]),
                         precision), handed


def hidden_states(backbone, tokens, z, precision="float32"):
    """One row: ``(S,)`` ids -> ``(S, D)`` the final norm's output.  Every
    layer under ``jax.checkpoint``: its intermediates live for that layer's
    backward alone."""
    x, handed = backbone["embed"]["embedding"][tokens], {}
    for name, kind, index in zip(_layer_order(backbone), z["roles"],
                                 z["kept"]):
        layer = jax.checkpoint(functools.partial(
            trunk_layer, kind=kind, index=index, z=z, precision=precision))
        x, handed = layer(backbone[name], x, handed)
    return layer_norm(x, backbone["final_norm"], z["eps"])


def trunk(backbone, tokens, z, precision="float32"):
    """One row -> ``(D,)`` its representation."""
    return jnp.mean(hidden_states(backbone, tokens, z, precision), axis=0)


# ---- one BYOL step --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("zkey", "precision"))
def _features(backbone, tokens, *, zkey, precision):
    return trunk(backbone, tokens, _Z_CACHE[zkey], precision)


@functools.partial(jax.jit, static_argnames=("zkey", "precision", "first"))
def _first_hidden(backbone, tokens, *, zkey, precision, first):
    return hidden_states(backbone, tokens, _Z_CACHE[zkey], precision)[:first]


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
    and are on the device only while the target's features are made."""
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


def probe(params, rows, cotangent, *, conf, first, precision="float32"):
    """The trunk ALONE, in front of the heads and of the pooling: the final
    norm's output at the first ``first`` positions of each of ``rows (N,
    S)`` (``hidden (N, first, D)``) and the gradient of ``sum(representation
    * cotangent)`` in every leaf of the trunk, row by row as a step does
    it."""
    zkey = _frozen(sizes_of(conf))
    backbone = jax.tree_util.tree_map(jnp.array, params["backbone"])
    rows = [jnp.asarray(r, jnp.int32) for r in np.asarray(rows)]
    hidden = np.stack([np.asarray(_first_hidden(
        backbone, r, zkey=zkey, precision=precision, first=first))
        for r in rows])
    acc = jax.tree_util.tree_map(jnp.zeros_like, backbone)
    for r, ct in zip(rows, jnp.asarray(cotangent, jnp.float32)):
        acc = _accumulate(backbone, acc, r, ct, zkey=zkey,
                          precision=precision)
    return {"hidden": hidden, "grads": jax.device_get(acc)}


def adaptation(names, p) -> str:
    """LARS's rule for a leaf: 'whole' (decayed, and adapted by one trust
    ratio) or 'none'."""
    return "none" if p.ndim <= 1 or names[-1] in UNADAPTED else "whole"


def lars_momentum_ema(params, grads, trace, target, lr, tau, *, wd,
                      trust=1e-3):
    """Leaf by leaf, ``lib/reference_decoder_trunk.py``'s update under this
    trunk's rule (``trace`` arrives and leaves as host arrays)."""
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for (path, p), g, m, t in zip(
            flat_p, jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(trace),
            jax.tree_util.tree_leaves(target)):
        names = [getattr(k, "key", str(k)) for k in path]
        p_new, m_new, t_new = _update_leaf(
            p, g, jnp.asarray(m), jnp.asarray(t), lr, tau,
            kind=adaptation(names, p), wd=wd, trust=trust)
        out.append((p_new, np.asarray(m_new), np.asarray(t_new)))
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
        del grads
        if k == 0:
            first_trace = trace
    return {"losses": losses, "first_trace": first_trace, "params": params}
