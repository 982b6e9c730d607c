"""The plain reference: one BYOL training step, and the served forward, in
straightforward float32 ``jax.numpy``.

It imports nothing of the program.  It walks a parameter tree with the
program's NAMES (``stem_conv``, ``stageI_blockJ/conv1`` ...,
``blockN/attn/qkv`` ..., ``projector/dense1`` ...) holding the benchmark's
own seeded values (lib/weights.py), and follows the published equations:

* ResNet (He et al., v1, torchvision layout): 7x7/2 stem, 3x3/2 max-pool,
  post-activation basic / bottleneck blocks (stride on the 3x3), BatchNorm
  with batch statistics in training and running statistics when served,
  global average pool.  Inputs of at most 64 px take the 3x3/1 stem without
  the pool, as the program does.
* ViT (Dosovitskiy et al., Table 1): patch embedding, class token, learned
  positions, pre-LN blocks with dense softmax attention and a tanh-GELU
  MLP, final LayerNorm, class-token read-out.
* BYOL (Grill et al. 2020, section 3): projector and predictor MLPs
  (linear, BatchNorm, ReLU, linear), the symmetrised normalised regression
  loss against the target network's projections, a linear probe on
  stop-gradient features trained beside it, LARS (weight decay folded into
  the gradient, trust ratio 1e-3 on kernels only) over momentum 0.9 with a
  warm-up/cosine learning rate, and the cosine-annealed EMA of the target.

Departures, each because the configuration states it: both views go
through the encoder as ONE batch (``fuse_views``: BatchNorm statistics span
the 2N rows); the probe's cross-entropy is part of the loss.

Memory.  At the timed batch a float32 backward of the whole network does
not fit a 16 GB chip, so the gradient is taken LAYER BY LAYER: the forward
keeps each segment's input (on the host once a device budget is spent),
and the backward walks the segments in reverse with ``jax.vjp`` of one
segment at a time.  Same functions, same numbers as ``jax.grad`` of their
composition (tests/test_reference.py).

``precision``: ``float32`` is the reference (matmul precision highest).
``bfloat16`` / ``fp8`` round every convolution's and matrix product's
operands and result to that type (straight-through in the backward) — the
CONTROL that check.py must fail, never a result.
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_QUANT = {"float32": None, "bfloat16": jnp.bfloat16,
          "fp8": jnp.float8_e4m3fn}


def q(x, precision: str):
    """Round to ``precision`` and back, gradient passed straight through."""
    dt = _QUANT[precision]
    if dt is None:
        return x
    if dt == jnp.float8_e4m3fn:
        x_q = jnp.clip(x, -448.0, 448.0).astype(dt).astype(jnp.float32)
    else:
        x_q = x.astype(dt).astype(jnp.float32)
    return x + jax.lax.stop_gradient(x_q - x)


# ---- layers ---------------------------------------------------------------

def conv(x, kernel, stride, padding, precision, bias=None):
    y = jax.lax.conv_general_dilated(
        q(x, precision), q(kernel, precision), (stride, stride),
        ((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    if bias is not None:
        y = y + bias
    return q(y, precision)


def dense(x, p, precision):
    y = jnp.matmul(q(x, precision), q(p["kernel"], precision),
                   precision=HIGHEST) + p["bias"]
    return q(y, precision)


def batch_norm(x, p, stats=None, eps=1e-5):
    """Batch statistics over every axis but the last (training), or the
    given running statistics (served)."""
    if stats is None:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axes)
        var = jnp.mean(jnp.square(x), axes) - jnp.square(mean)
    else:
        mean, var = stats["mean"], stats["var"]
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x), -1, keepdims=True) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


# ---- segments: (name, fn(params_of_segment, stats_of_segment, x)) ----------

def _bn(x, p, s, name):
    return batch_norm(x, p[name], None if s is None else s[name])


def resnet_stem(p, s, x, *, small, precision):
    if small:
        x = conv(x, p["stem_conv"]["kernel"], 1, 1, precision)
    else:
        x = conv(x, p["stem_conv"]["kernel"], 2, 3, precision)
    x = jax.nn.relu(_bn(x, p, s, "stem_bn"))
    return x if small else max_pool_3x3_s2(x)


def resnet_block(p, s, x, *, stride, precision):
    r = x
    if "conv3" in p:                                   # bottleneck
        y = conv(x, p["conv1"]["kernel"], 1, 0, precision)
        y = jax.nn.relu(_bn(y, p, s, "bn1"))
        y = conv(y, p["conv2"]["kernel"], stride, 1, precision)
        y = jax.nn.relu(_bn(y, p, s, "bn2"))
        y = conv(y, p["conv3"]["kernel"], 1, 0, precision)
        y = _bn(y, p, s, "bn3")
    else:                                              # basic
        y = conv(x, p["conv1"]["kernel"], stride, 1, precision)
        y = jax.nn.relu(_bn(y, p, s, "bn1"))
        y = conv(y, p["conv2"]["kernel"], 1, 1, precision)
        y = _bn(y, p, s, "bn2")
    if "downsample_conv" in p:
        r = conv(x, p["downsample_conv"]["kernel"], stride, 0, precision)
        r = _bn(r, p, s, "downsample_bn")
    return jax.nn.relu(y + r)


def vit_stem(p, s, x, *, precision):
    k = p["patch_embed"]["kernel"]
    x = conv(x, k, k.shape[0], 0, precision, bias=p["patch_embed"]["bias"])
    b = x.shape[0]
    x = x.reshape(b, -1, x.shape[-1])
    cls = jnp.broadcast_to(p["cls_token"], (b, 1, x.shape[-1]))
    return jnp.concatenate([cls, x], axis=1) + p["pos_embedding"]


def vit_block(p, s, x, *, heads, precision):
    b, n, d = x.shape
    y = layer_norm(x, p["ln1"])
    qkv = dense(y, p["attn"]["qkv"], precision)
    qkv = qkv.reshape(b, n, 3, heads, d // heads)
    qh, kh, vh = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = q(jnp.einsum("bhqd,bhkd->bhqk", q(qh, precision),
                          q(kh, precision), precision=HIGHEST), precision)
    w = jax.nn.softmax(scores * (d // heads) ** -0.5, axis=-1)
    out = q(jnp.einsum("bhqk,bhkd->bhqd", q(w, precision), q(vh, precision),
                       precision=HIGHEST), precision)
    out = out.transpose(0, 2, 1, 3).reshape(b, n, d)
    x = x + dense(out, p["attn"]["proj"], precision)
    y = layer_norm(x, p["ln2"])
    y = dense(gelu_tanh(dense(y, p["mlp"]["fc1"], precision)),
              p["mlp"]["fc2"], precision)
    return x + y


def vit_head(p, s, x, *, precision):
    return layer_norm(x, p["ln_final"])[:, 0]


def resnet_pool(p, s, x, *, precision):
    return jnp.mean(x, axis=(1, 2))


def _block_order(names):
    def key(n):
        return [int(t) for t in re.findall(r"\d+", n)]
    return sorted(names, key=key)


def backbone_segments(backbone_params, *, image_size, vit_heads=0,
                      precision="float32"):
    """The backbone as an ordered list of ``(keys, fn)``: ``keys`` are the
    top-level names whose parameters (and running statistics) ``fn`` takes,
    ``fn(params_subset, stats_subset_or_None, x) -> x``."""
    names = set(backbone_params)
    segs = []
    if "patch_embed" in names:
        segs.append((("patch_embed", "cls_token", "pos_embedding"),
                     functools.partial(vit_stem, precision=precision)))
        for n in _block_order(k for k in names if k.startswith("block")):
            segs.append(((n,), _unwrap(functools.partial(
                vit_block, heads=vit_heads, precision=precision), n)))
        segs.append((("ln_final",),
                     functools.partial(vit_head, precision=precision)))
        return segs
    segs.append((("stem_conv", "stem_bn"), functools.partial(
        resnet_stem, small=image_size <= 64, precision=precision)))
    for n in _block_order(k for k in names if k.startswith("stage")):
        stage, block = (int(t) for t in re.findall(r"\d+", n))
        stride = 2 if (stage > 1 and block == 1) else 1
        segs.append(((n,), _unwrap(functools.partial(
            resnet_block, stride=stride, precision=precision), n)))
    segs.append(((), functools.partial(resnet_pool, precision=precision)))
    return segs


def _unwrap(fn, name):
    def wrapped(p, s, x):
        return fn(p[name], None if s is None else s.get(name), x)
    return wrapped


def _subset(tree, keys):
    return {k: tree[k] for k in keys if k in tree}


def encode(backbone_params, backbone_stats, x, **kw):
    """Backbone forward; ``backbone_stats=None`` uses batch statistics."""
    for keys, fn in backbone_segments(backbone_params, **kw):
        s = None if backbone_stats is None else _subset(backbone_stats, keys)
        x = fn(_subset(backbone_params, keys), s, x)
    return x


def mlp_head(p, x, precision):
    y = dense(x, p["dense1"], precision)
    y = jax.nn.relu(batch_norm(y, p["bn"]))
    return dense(y, p["dense2"], precision)


def regression_loss(x, y):
    x = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
    y = y / (jnp.linalg.norm(y, axis=-1, keepdims=True) + 1e-12)
    return -2.0 * jnp.sum(x * y, axis=-1)


def tail_loss(head_params, features, target_proj, labels, precision):
    """Heads + loss from the backbone's features of the 2N fused rows."""
    n = features.shape[0] // 2
    proj = mlp_head(head_params["projector"], features, precision)
    pred = mlp_head(head_params["predictor"], proj, precision)
    byol = jnp.mean(regression_loss(pred[:n], target_proj[n:])
                    + regression_loss(pred[n:], target_proj[:n]))
    logits = dense(jax.lax.stop_gradient(features),
                   head_params["probe"]["classifier"], precision)
    lab = jnp.concatenate([labels, labels])
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.mean(jnp.take_along_axis(logp, lab[:, None], axis=1))
    return byol + ce


# ---- the layer-by-layer gradient -----------------------------------------

class _Stash:
    """Segment inputs kept for the backward: on the device while a byte
    budget lasts, on the host after that."""

    def __init__(self, device_budget_bytes):
        self.left = device_budget_bytes
        self.items = []

    def push(self, x):
        if x.nbytes <= self.left:
            self.left -= x.nbytes
            self.items.append(x)
        else:
            self.items.append(np.asarray(x))

    def pop(self):
        x = self.items.pop()
        if isinstance(x, np.ndarray):
            return jnp.asarray(x)
        self.left += x.nbytes
        return x


@functools.lru_cache(maxsize=None)
def _jit_fwd(fn):
    return jax.jit(lambda p, x: fn(p, None, x))


@functools.lru_cache(maxsize=None)
def _jit_bwd(fn):
    def bwd(p, x, ct):
        _, vjp = jax.vjp(lambda p_, x_: fn(p_, None, x_), p, x)
        return vjp(ct)
    return jax.jit(bwd)


_SEG_CACHE = {}


def _segments_cached(backbone_params, **kw):
    key = (tuple(sorted(backbone_params)), tuple(sorted(kw.items())))
    if key not in _SEG_CACHE:
        _SEG_CACHE[key] = backbone_segments(backbone_params, **kw)
    return _SEG_CACHE[key]


def loss_and_grads(params, target_params, view1, view2, labels, *,
                   image_size, vit_heads=0, precision="float32",
                   device_budget_bytes=3 << 30):
    """Loss and the online gradient of one BYOL step, layer by layer."""
    kw = dict(image_size=image_size, vit_heads=vit_heads,
              precision=precision)
    x = jnp.concatenate([jnp.asarray(view1, jnp.float32),
                         jnp.asarray(view2, jnp.float32)], axis=0)
    segs = _segments_cached(params["backbone"], **kw)

    h = x                                    # target branch: no gradient
    for keys, fn in segs:
        h = _jit_fwd(fn)(_subset(target_params["backbone"], keys), h)
    target_proj = jax.jit(functools.partial(mlp_head, precision=precision))(
        target_params["projector"], h)

    stash = _Stash(device_budget_bytes)
    h = x
    for keys, fn in segs:
        stash.push(h)
        h = _jit_fwd(fn)(_subset(params["backbone"], keys), h)
    heads = {k: params[k] for k in ("projector", "predictor", "probe")}
    tail = jax.jit(jax.value_and_grad(
        functools.partial(tail_loss, precision=precision), argnums=(0, 1)))
    loss, (g_heads, ct) = tail(heads, h, target_proj, jnp.asarray(labels))
    g_backbone = {}
    for keys, fn in reversed(segs):
        g_p, ct = _jit_bwd(fn)(_subset(params["backbone"], keys),
                               stash.pop(), ct)
        g_backbone.update(g_p)
    return loss, dict(g_heads, backbone=g_backbone)


# ---- the update: LARS over momentum, warm-up/cosine lr, EMA target --------

def learning_rate(count, hp):
    base = hp["lr"] * hp["global_batch"] / 256.0
    warm, total = hp["warmup_steps"], hp["total_steps"]
    t = float(count)
    if warm > 0 and t < warm:
        return base * t / warm
    return base * 0.5 * (1.0 + math.cos(
        math.pi * (t - warm) / max(total - warm, 1)))


def ema_decay(step, hp):
    return 1.0 - (1.0 - hp["base_decay"]) * (
        math.cos(math.pi * step / hp["total_steps"]) + 1.0) / 2.0


@functools.partial(jax.jit, static_argnames=("wd", "trust"))
def _lars_momentum(params, grads, trace, lr, tau, target, *, wd, trust):
    def leaf(p, g, m, t):
        if p.ndim > 1:                       # kernels: decayed and adapted
            g = g + wd * p
            pn, gn = jnp.linalg.norm(p), jnp.linalg.norm(g)
            g = g * jnp.where((pn > 0) & (gn > 0), trust * pn / gn, 1.0)
        m_new = g + 0.9 * m
        p_new = p - lr * m_new
        return p_new, m_new, tau * t + (1.0 - tau) * p_new
    out = jax.tree_util.tree_map(leaf, params, grads, trace, target)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def train_steps(params, batches, hp, *, image_size, vit_heads=0,
                precision="float32", device_budget_bytes=3 << 30):
    """Follow ``len(batches)`` optimizer steps from ``params`` (target =
    a copy, momentum zero, counters zero).  Returns per-step losses, the
    momentum trace after the FIRST step (the first gradient as the
    optimizer got it), and the parameters after the last."""
    params = jax.tree_util.tree_map(jnp.asarray, params)
    target = params
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_trace = [], None
    for k, b in enumerate(batches):
        loss, grads = loss_and_grads(
            params, target, b["view1"], b["view2"], b["label"],
            image_size=image_size, vit_heads=vit_heads, precision=precision,
            device_budget_bytes=device_budget_bytes)
        losses.append(float(loss))
        params, trace, target = _lars_momentum(
            params, grads, trace, learning_rate(k, hp), ema_decay(k, hp),
            target, wd=hp["weight_decay"], trust=1e-3)
        if k == 0:
            first_trace = trace
    return {"losses": losses, "first_trace": first_trace, "params": params}


def embed(params, batch_stats, images, *, image_size, vit_heads=0,
          precision="float32", rows_per_block=16):
    """The served forward: running statistics, float32 features, in blocks
    of rows (rows are independent when served)."""
    kw = dict(image_size=image_size, vit_heads=vit_heads,
              precision=precision)
    fn = jax.jit(lambda p, s, x: encode(p, s, x, **kw))
    images = np.asarray(images, np.float32)
    n = len(images)
    pad = -n % rows_per_block                  # one shape, one program
    if pad:
        images = np.concatenate([images, np.zeros(
            (pad,) + images.shape[1:], np.float32)])
    out = [np.asarray(fn(params["backbone"], batch_stats["backbone"],
                         images[i:i + rows_per_block]))
           for i in range(0, len(images), rows_per_block)]
    return np.concatenate(out, axis=0)[:n]
