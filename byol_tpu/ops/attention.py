"""Attention ops — the pluggable compute seam for the ViT path.

All implementations share one signature::

    fn(q, k, v) -> out      # (B, H, S, D) x3 -> (B, H, S, D)

so the model swaps between them by name without re-plumbing:
  ``dense``   — exact softmax attention over the whole sequence, written as
                two einsums.  XLA does NOT fuse them: at ViT-B/16's 197
                tokens the ``[B,H,S,S]`` scores and weights cross HBM and
                q, k, v and the output are relaid out by copies — half of
                the train step's bytes (compiler and trace, PERF.md §5,
                PR 28).  Where the shapes allow and the program lowers for
                a TPU, ``models/vit.SelfAttention`` therefore hands the
                packed ``qkv`` to ``ops/packed_attention.py`` instead
                (:func:`packed_kernel_applies`): same arithmetic, one
                kernel forward and one backward;
  ``blockwise`` (:func:`blockwise_causal_attention`, the decoder trunk's
                grouped-query layers, gated or plain) — the same arithmetic
                under a visibility rule known at trace time (causal; causal
                under a band; the block-diffusion training mask), as a list
                of tile pairs
                with a kind each (:class:`TilePairs`), over blocks of keys
                with a running max and sum, forward and backward, so that no
                ``[S, S]`` array exists at any length and no tile without a
                visible pair is formed.  Where the program lowers for a TPU and the
                shapes allow (``causal_attention.applies``: heads of 64, 128
                or 256; a value width of its own; a part of the key shared
                by all heads — latent attention's 128 + 64 against 128), the
                Pallas kernels of ops/causal_attention.py: a tile's scores
                and weights never leave VMEM; elsewhere plain
                ``jax.numpy`` — ``[B,Hkv,G,block,block]`` float32 tiles
                through HBM;
  ``selected`` (:func:`selected_attention`, the decoder trunk's
                sparse-attention layers) — the same again with each query's
                softmax over a SET of its causal keys
                (ops/key_selection.py finds it).  Where the program lowers
                for a TPU and the shapes allow, the same Pallas kernels
                with the set as one more operand (``causal_attention.
                applies(.., selected=True)``); elsewhere plain
                ``jax.numpy``, the block pairs walked by loops the
                compiler keeps rolled — ``[B,Hkv,G,block,block]`` float32
                tiles through HBM;
  ``ring``    — sequence-parallel blockwise attention over the mesh's
                ``sequence`` axis (parallel/ring_attention.py), for sequences
                sharded across chips.

The reference has no attention at all (ResNet path, main.py:190-193); this
module exists because long-context support is first-class in the rebuild.
"""
from __future__ import annotations

import functools
import itertools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from byol_tpu.ops import packed_attention
from byol_tpu.ops.common import MASKED
from byol_tpu.parallel.mesh import DATA_AXIS


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: Optional[float] = None, causal: bool = False
                    ) -> jnp.ndarray:
    """Standard softmax attention. (B, H, S, D) -> (B, H, S, Dv).

    Softmax statistics in fp32 regardless of compute dtype (bf16-safe),
    matmuls in the input dtype (MXU-friendly).  ``scale`` defaults to
    ``1/sqrt(D)``; ``causal`` masks key positions after the query's (the
    decoder trunk, models/decoder_trunk.py, whose value heads are also
    narrower than its query/key heads)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        visible = jnp.tril(jnp.ones(scores.shape[-2:], bool))
        scores = jnp.where(visible, scores, jnp.finfo(scores.dtype).min)
    weights = jnp.exp(
        scores.astype(jnp.float32)
        - jnp.max(scores, axis=-1, keepdims=True).astype(jnp.float32))
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


# ---- a visibility rule known at trace time: tile pairs with a kind each ----
#
# Both lowerings of the blockwise core walk ONE list: ``(query tile, key
# tile, kind)``, tiles of ``block`` rows, only the tiles that hold a visible
# pair in it, a query tile's pairs side by side.  A kind says which pairs of
# a tile are visible, from the rows' OFFSETS inside their tiles counted in
# blocks of ``span`` rows, ``beta(r) = r // span``:
FULL = 0           # every pair
NOT_AFTER = 1      # beta(key) <= beta(query); span 1: the causal diagonal
BEFORE = 2         # beta(key) <  beta(query)
SAME = 3           # beta(key) == beta(query)
WITHIN = 4         # lo <= beta(query) - beta(key) <= hi, the PAIR's own bounds
VISIBLE = {NOT_AFTER: lambda key, query: key <= query,
           BEFORE: lambda key, query: key < query,
           SAME: lambda key, query: key == query}
# ... and the same three as bounds ``lo <= beta(query) - beta(key) <= hi``
# (what a kernel that learns a pair's kind only when it runs compares with)
FAR = 1 << 30
BOUNDS = {NOT_AFTER: (0, FAR), BEFORE: (1, FAR), SAME: (0, 0)}


class TilePairs(NamedTuple):
    """The list, hashable (it is static wherever it goes).  ``bounds``: a
    ``(lo, hi)`` a pair where the list has ``WITHIN`` pairs (a band), ``()``
    otherwise."""

    q_of: Tuple[int, ...]
    k_of: Tuple[int, ...]
    kind: Tuple[int, ...]
    span: int = 1
    bounds: Tuple[Tuple[int, int], ...] = ()


def causal_pairs(blocks: int):
    """``(query block, key block)`` of every tile on or under the diagonal,
    as two int32 arrays (pair ``(i, j)`` is tile ``i (i + 1) / 2 + j``)."""
    q_of = np.repeat(np.arange(blocks), np.arange(1, blocks + 1))
    k_of = np.concatenate([np.arange(i + 1) for i in range(blocks)])
    return q_of.astype(np.int32), k_of.astype(np.int32)


@functools.lru_cache(maxsize=None)
def causal_tiles(blocks: int) -> TilePairs:
    """Causal attention: :func:`causal_pairs`, the diagonal ``NOT_AFTER``."""
    q_of, k_of = (x.tolist() for x in causal_pairs(blocks))
    return TilePairs(tuple(q_of), tuple(k_of), tuple(
        NOT_AFTER if j == i else FULL for i, j in zip(q_of, k_of)))


@functools.lru_cache(maxsize=None)
def block_diffusion_tiles(blocks: int, span: int) -> TilePairs:
    """The block-diffusion training forward (arXiv 2503.09573, its
    vectorized mask): a row is ``[noised | clean]``, each half ``blocks``
    tiles, row ``n`` of either half at position ``n``, ``beta`` over blocks
    of ``span`` positions (``span`` divides the tile and is shorter).  A
    clean query sees the clean keys with ``beta(key) <= beta(query)``; a
    noised query the clean keys with ``beta(key) < beta(query)`` and the
    noised keys of its own block; nothing else.  ``blocks^2 + 2 blocks``
    tiles of the ``(2 blocks)^2``; every row's last pair holds its own
    block, so every row sees a key.  ``span`` 1 with the clean half alone
    is :func:`causal_tiles`."""
    n, rows = blocks, []
    for i in range(n):                                   # noised queries
        rows += [(i, n + j, FULL) for j in range(i)]
        rows += [(i, n + i, BEFORE), (i, i, SAME)]
    for i in range(n):                                   # clean queries
        rows += [(n + i, n + j, FULL) for j in range(i)]
        rows += [(n + i, n + i, NOT_AFTER)]
    return TilePairs(*(tuple(column) for column in zip(*rows)), span=span)


@functools.lru_cache(maxsize=None)
def window_tiles(blocks: int, window: int, block: int) -> TilePairs:
    """Causal attention under a band: a query at position ``t`` sees the
    keys ``r`` with ``0 <= t - r < window``, over ``blocks`` tiles of
    ``block`` rows.  Query tile ``i`` forms the key tiles ``i - d`` with ``d
    block <= window + block - 2`` and no other: 2 of a row's 16 at a window
    of one tile.  A tile ``d`` back holds a visible pair at every offset
    where ``-d block <= offset(query) - offset(key) <= window - 1 - d
    block``: ``FULL`` where no offset of the tile fails that, ``WITHIN``
    with those bounds elsewhere (the diagonal among them: its upper bound
    binds only under a window shorter than a tile).  Every row's last pair
    is its own tile, so every row sees a key.  A window that covers the
    row is :func:`causal_tiles`."""
    if window < 1:
        raise ValueError(f"a window of {window} keys")
    rows = []
    for i in range(blocks):
        for j in range(max(0, i - (window + block - 2) // block), i + 1):
            lo, hi = (j - i) * block, window - 1 + (j - i) * block
            whole = lo <= 1 - block and hi >= block - 1
            rows.append((i, j, FULL if whole else WITHIN, (lo, hi)))
    q_of, k_of, kind, bounds = (tuple(column) for column in zip(*rows))
    return TilePairs(q_of, k_of, kind, bounds=bounds)


def _tile_rows(tiles: TilePairs):
    """``[(query tile, [(key tile, rule), ..])]``, query tiles in order; a
    pair's rule is its kind or, ``WITHIN``, its ``(lo, hi)``."""
    rules = [tiles.bounds[n] if kind == WITHIN else kind
             for n, kind in enumerate(tiles.kind)]
    rows = [(i, [(j, rule) for _, j, rule in group])
            for i, group in itertools.groupby(
                zip(tiles.q_of, tiles.k_of, rules), key=lambda t: t[0])]
    if [i for i, _ in rows] != list(range(len(rows))):
        raise ValueError("a query tile's pairs lie side by side, the query "
                         "tiles in order")
    return rows


def _tile_scores(q_blk, k_blk, scale, rule, span):
    """``(B, Hkv, G, bq, bk)`` float32 scores of one tile, what its rule (a
    kind, or a ``WITHIN`` pair's ``(lo, hi)``) hides masked."""
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                        preferred_element_type=jnp.float32) * scale
    if rule != FULL:
        query = (jnp.arange(q_blk.shape[-2]) // span)[:, None]
        key = (jnp.arange(k_blk.shape[-2]) // span)[None, :]
        if isinstance(rule, tuple):
            ahead = query - key
            visible = (ahead >= rule[0]) & (ahead <= rule[1])
        else:
            visible = VISIBLE[rule](key, query)
        scores = jnp.where(visible, scores, MASKED)
    return scores


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blockwise(q, k, v, scale, block, tiles):
    return _blockwise_fwd(q, k, v, scale, block, tiles)[0]


def _blockwise_fwd(q, k, v, scale, block, tiles):
    """``q``: ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``.  One
    query tile at a time over the key tiles ``tiles`` lists for it, with a
    running max and sum; no other tile is formed.  (A row that sees nothing
    in a tile weighs its keys 1 each until a visible key's max wipes them:
    ``exp(MASKED - max) = 0``.)"""
    rows = lambda x, i: x[..., i * block:(i + 1) * block, :]
    outs, lses = [], []
    for i, keys in _tile_rows(tiles):
        q_blk = rows(q, i)
        top = total = acc = None
        for j, kind in keys:
            scores = _tile_scores(q_blk, rows(k, j), scale, kind, tiles.span)
            here = jnp.max(scores, axis=-1)
            new_top = here if top is None else jnp.maximum(top, here)
            weights = jnp.exp(scores - new_top[..., None])
            part = jnp.einsum("bhgqk,bhkd->bhgqd", weights.astype(v.dtype),
                              rows(v, j),
                              preferred_element_type=jnp.float32)
            if top is None:
                total, acc = jnp.sum(weights, axis=-1), part
            else:
                keep = jnp.exp(top - new_top)
                total = total * keep + jnp.sum(weights, axis=-1)
                acc = acc * keep[..., None] + part
            top = new_top
        outs.append((acc / total[..., None]).astype(q.dtype))
        lses.append(top + jnp.log(total))
    out = jnp.concatenate(outs, axis=-2)
    return out, (q, k, v, out, jnp.concatenate(lses, axis=-1))


def _blockwise_bwd(scale, block, tiles, residuals, d_out):
    """The same tiles again: scores recomputed from ``q, k`` and the saved
    log-sum-exp, five products a tile."""
    q, k, v, out, lse = residuals
    rows = lambda x, i: x[..., i * block:(i + 1) * block, :]
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    blocks = -(-k.shape[-2] // block)
    d_k, d_v, d_q = [None] * blocks, [None] * blocks, []
    add = lambda old, new: new if old is None else old + new
    for i, keys in _tile_rows(tiles):
        q_blk, do_blk = rows(q, i), rows(d_out, i)
        stat = lambda x: x[..., i * block:(i + 1) * block, None]
        dq_blk = None
        for j, kind in keys:
            k_blk, v_blk = rows(k, j), rows(v, j)
            weights = jnp.exp(
                _tile_scores(q_blk, k_blk, scale, kind, tiles.span)
                - stat(lse))
            d_v[j] = add(d_v[j], jnp.einsum(
                "bhgqk,bhgqd->bhkd", weights.astype(v.dtype), do_blk,
                preferred_element_type=jnp.float32))
            d_weights = jnp.einsum("bhgqd,bhkd->bhgqk", do_blk, v_blk,
                                   preferred_element_type=jnp.float32)
            d_scores = (weights * (d_weights - stat(delta))
                        * scale).astype(q.dtype)
            dq_blk = add(dq_blk, jnp.einsum(
                "bhgqk,bhkd->bhgqd", d_scores, k_blk,
                preferred_element_type=jnp.float32))
            d_k[j] = add(d_k[j], jnp.einsum(
                "bhgqk,bhgqd->bhkd", d_scores, q_blk,
                preferred_element_type=jnp.float32))
        d_q.append(dq_blk.astype(q.dtype))
    together = lambda blocks, like: jnp.concatenate(
        blocks, axis=-2).astype(like.dtype)
    return (jnp.concatenate(d_q, axis=-2), together(d_k, k),
            together(d_v, v))


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


def blockwise_causal_attention(q: jnp.ndarray, k: jnp.ndarray,
                               v: jnp.ndarray, *,
                               scale: Optional[float] = None,
                               block: int = 512,
                               group: int = 0,
                               shared=None,
                               tiles: Optional[TilePairs] = None
                               ) -> jnp.ndarray:
    """Softmax attention under a visibility rule known at trace time —
    causal unless ``tiles`` says otherwise — whose memory is linear in S,
    forward and backward: ``(B, Hq, S, D)`` queries on ``(B, Hkv, S, D)``
    keys and ``(B, Hkv, S, Dv)`` values (``Dv`` need not be ``D``: latent
    attention's values are narrower than its keys), each key/value head
    shared by ``Hq / Hkv`` consecutive query heads and never repeated in
    memory; returns ``(B, Hq, S, Dv)``.  ``tiles``: the rule as a
    :class:`TilePairs` over tiles of ``block`` rows (None:
    :func:`causal_tiles`; :func:`block_diffusion_tiles`;
    :func:`window_tiles`); only the tiles it lists are formed.  ``shared = (q_s (B, Hq, S, r), k_s (B, S, r))``
    adds ``q_s . k_s`` to every score: a part of the head whose KEY is one
    vector for all heads (latent attention's rotary key), handed over once
    and never copied a head; ``scale`` defaults to ``(D + r)^-1/2``.
    Blockwise over the keys with a running max and sum; nothing larger than
    one ``(B, Hq, block, block)`` tile of scores is ever held, and the
    backward recomputes the tiles from ``q, k`` and the saved log-sum-exp
    (``jax.custom_vjp``).
    Statistics in float32, products in the input dtype.  Two lowerings of
    one arithmetic over one list, chosen from what the code can see
    (``ops/causal_attention.applies``): where the program lowers for a TPU,
    ``block`` is a multiple of 128, each of ``D``, ``Dv`` and ``r`` is 64 or
    a multiple of 128 and the working set fits VMEM, the Pallas kernels
    ``causal_attention_fwd`` / ``causal_attention_bwd`` of
    ops/causal_attention.py over the whole batch — a tile's scores, weights
    and their cotangents live and die in VMEM, the shared part a second
    product a tile; a program holds a key head's ``Hq / Hkv`` query heads
    and, where those are fewer than four, several key heads
    (``causal_attention.key_heads``: four of latent attention's, the shared
    key fetched once for them); everywhere else (the CPU, the tiny presets, odd shapes
    such as one 192-wide key) plain ``jax.numpy``, the tile pairs unrolled
    in Python, every ``(B, Hkv, G, block, block)`` float32 tile through HBM
    and the shared part joined to every head's ``q`` and ``k`` first —
    which is also the tests' oracle for the kernels.  ``group`` > 0 is the
    ``jax.numpy`` lowering's alone: that many sequences a pass (a
    ``lax.map``; where it divides ``B``), because the compiler keeps some
    twenty tiles alive at once."""
    from byol_tpu.ops import causal_attention as kernels     # imports this
    b, hq, s, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not share {hkv} key heads")
    if tiles is None:
        tiles = causal_tiles(-(-s // block))
    elif (max(tiles.q_of + tiles.k_of) + 1) * block != s \
            or block % tiles.span:
        raise ValueError(f"{s} rows are not the tiles of {block} the rule "
                         f"lists, or its span {tiles.span} does not divide "
                         "a tile")
    r = shared[0].shape[-1] if shared is not None else 0
    if scale is None:
        scale = (d + r) ** -0.5
    group_heads = lambda x: x.reshape((b, hkv, hq // hkv) + x.shape[2:])
    if kernels.applies(block, d, s, hq, hkv, q.dtype, vdim=dv, shared=r):
        out, _ = kernels.attend(
            group_heads(q), k, v, scale=scale, block=block, tiles=tiles,
            shared=None if shared is None else (group_heads(shared[0]),
                                                shared[1]))
        return out.reshape(b, hq, s, dv)
    if shared is not None:
        q = jnp.concatenate([q, shared[0]], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            shared[1][:, None], (b, hkv, s, r))], axis=-1)
    grouped = group_heads(q)
    body = lambda *qkv: _blockwise(*qkv, float(scale), int(block), tiles)
    if group and b > group and b % group == 0:
        split = lambda x: x.reshape((b // group, group) + x.shape[1:])
        out = jax.lax.map(lambda qkv: body(*qkv),
                          (split(grouped), split(k), split(v)))
        out = out.reshape((b,) + out.shape[2:])
    else:
        out = body(grouped, k, v)
    return out.reshape(b, hq, s, dv)


# ---- attention over a per-query set of keys --------------------------------
#
# The TILE layout.  Everything ``[S, S]``-shaped round this core is held as the
# block pairs on and under the diagonal and nothing else: ``(P, B, block,
# block)``, ``P = n (n + 1) / 2`` pairs of ``n = S / block`` blocks, query
# block by query block and key block by key block inside (:func:`causal_pairs`;
# pair ``(i, j)`` is tile ``i (i + 1) / 2 + j``).  No head axis.  Every loop
# over pairs is a ``lax`` loop whose body is traced ONCE: the program's size
# does not grow with ``S``.

def _slab(x, i, block: int, axis: int = -2):
    """Block ``i`` (traced) of ``x`` along ``axis``."""
    return jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=axis)


def _kept_scores(q_blk, k_blk, scale, keep):
    """``(B, Hkv, G, bq, bk)`` float32 scores of one tile; ``keep`` (``(B,
    bq, bk)`` bool, the same for every head) says which keys each query
    attends."""
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                        preferred_element_type=jnp.float32) * scale
    return jnp.where(keep[:, None, None], scores, MASKED)


def _tile(selected, i, j):
    return jax.lax.dynamic_index_in_dim(selected, i * (i + 1) // 2 + j,
                                        keepdims=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _selected(q, k, v, selected, scale, block):
    """``(out, log-sum-exp)``; the second takes no cotangent."""
    return _selected_fwd(q, k, v, selected, scale, block)[0]


def _selected_fwd(q, k, v, selected, scale, block):
    """``q``: ``(B, Hkv, G, S, D)``; ``k, v``: ``(B, Hkv, S, D)``;
    ``selected``: ``(P, B, block, block)`` bool.  A query block at a time
    over the key blocks it can see, with a running max and sum.  A row none
    of whose keys in a tile is kept carries ``MASKED`` as its max until a
    kept key comes, whose ``keep`` factor then zeroes what went before:
    every row keeps a key somewhere."""
    b, hkv, g, s, _ = q.shape
    rows = (b, hkv, g, block)

    def query_block(i):
        q_blk = _slab(q, i, block)

        def key_block(j, carry):
            top, total, acc = carry
            scores = _kept_scores(q_blk, _slab(k, j, block), scale,
                                  _tile(selected, i, j))
            new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
            weights = jnp.exp(scores - new_top[..., None])
            keep = jnp.exp(top - new_top)
            part = jnp.einsum("bhgqk,bhkd->bhgqd", weights.astype(v.dtype),
                              _slab(v, j, block),
                              preferred_element_type=jnp.float32)
            return (new_top, total * keep + jnp.sum(weights, axis=-1),
                    acc * keep[..., None] + part)

        top, total, acc = jax.lax.fori_loop(
            0, i + 1, key_block,
            (jnp.full(rows, MASKED, jnp.float32),
             jnp.zeros(rows, jnp.float32),
             jnp.zeros(rows + v.shape[-1:], jnp.float32)))
        return (acc / total[..., None]).astype(q.dtype), top + jnp.log(total)

    outs, lses = jax.lax.map(query_block, jnp.arange(s // block))
    out = jnp.moveaxis(outs, 0, 3).reshape(b, hkv, g, s, v.shape[-1])
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, hkv, g, s)
    return (out, lse), (q, k, v, selected, out, lse)


def _selected_bwd(scale, block, residuals, cotangents):
    """The same tiles again: scores recomputed from ``q, k`` and the saved
    log-sum-exp, five products a tile; ``d_k, d_v`` grow in place, block by
    block, in float32."""
    q, k, v, selected, out, lse = residuals
    d_out, _ = cotangents
    # sum_k w (dw) of the softmax's backward is rowsum(dO . O)
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def grown(total, j, part):
        return jax.lax.dynamic_update_slice_in_dim(
            total, _slab(total, j, block) + part, j * block, axis=-2)

    def query_block(carry, i):
        q_blk, do_blk = _slab(q, i, block), _slab(d_out, i, block)
        lse_blk = _slab(lse, i, block, -1)[..., None]
        delta_blk = _slab(delta, i, block, -1)[..., None]

        def key_block(j, carry):
            dq_blk, d_k, d_v = carry
            k_blk, v_blk = _slab(k, j, block), _slab(v, j, block)
            weights = jnp.exp(_kept_scores(q_blk, k_blk, scale,
                                           _tile(selected, i, j)) - lse_blk)
            d_v = grown(d_v, j, jnp.einsum(
                "bhgqk,bhgqd->bhkd", weights.astype(v.dtype), do_blk,
                preferred_element_type=jnp.float32))
            d_weights = jnp.einsum("bhgqd,bhkd->bhgqk", do_blk, v_blk,
                                   preferred_element_type=jnp.float32)
            d_scores = (weights * (d_weights - delta_blk)
                        * scale).astype(q.dtype)
            dq_blk = dq_blk + jnp.einsum(
                "bhgqk,bhkd->bhgqd", d_scores, k_blk,
                preferred_element_type=jnp.float32)
            d_k = grown(d_k, j, jnp.einsum(
                "bhgqk,bhgqd->bhkd", d_scores, q_blk,
                preferred_element_type=jnp.float32))
            return dq_blk, d_k, d_v

        dq_blk, d_k, d_v = jax.lax.fori_loop(
            0, i + 1, key_block,
            (jnp.zeros(q_blk.shape, jnp.float32),) + carry)
        return (d_k, d_v), dq_blk.astype(q.dtype)

    (d_k, d_v), d_q = jax.lax.scan(
        query_block, (jnp.zeros(k.shape, jnp.float32),
                      jnp.zeros(v.shape, jnp.float32)),
        jnp.arange(q.shape[-2] // block))
    d_q = jnp.moveaxis(d_q, 0, 3).reshape(q.shape)
    return d_q, d_k.astype(k.dtype), d_v.astype(v.dtype), None


_selected.defvjp(_selected_fwd, _selected_bwd)


def selected_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       selected: jnp.ndarray, *,
                       scale: Optional[float] = None, block: int = 512):
    """:func:`blockwise_causal_attention` with each query's softmax over a
    SET of its causal keys, one set for all heads: ``selected`` is the set
    in the tile layout above (``(P, B, block, block)`` bool;
    ops/key_selection.py finds it), ``S`` a multiple of ``block``.  Returns
    the output and each row's log-sum-exp over its keys (``(B, Hq, S)``
    float32, for :func:`kept_probabilities`).  Masked-dense: a tile none of
    whose keys is kept is still formed; a tile above the diagonal never.
    Two lowerings of one arithmetic, chosen by :func:`blockwise_causal_
    attention`'s rule (``ops/causal_attention.applies(.., selected=True)``):
    its Pallas kernels with the set as one more operand
    (``selected_attention_fwd`` / ``selected_attention_bwd``); everywhere
    else (the CPU, the tiny presets, odd shapes) plain ``jax.numpy`` under
    ``lax`` loops — a query block at a time (``lax.map``), its key blocks
    under a ``fori_loop`` — which is also the tests' oracle for the
    kernels.  Forward and backward on both (``jax.custom_vjp``, scores
    recomputed from the saved log-sum-exp)."""
    from byol_tpu.ops import causal_attention as kernels     # imports this
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hq % hkv or s % block:
        raise ValueError(f"{hq} query heads on {hkv} key heads, {s} tokens "
                         f"in blocks of {block}")
    if scale is None:
        scale = d ** -0.5
    grouped = q.reshape(b, hkv, hq // hkv, s, d)
    if kernels.applies(block, d, s, hq, hkv, q.dtype, vdim=v.shape[-1],
                       selected=True):
        out, lse = kernels.attend(grouped, k, v, scale=scale, block=block,
                                  selected=selected)
    else:
        out, lse = _selected(grouped, k, v, selected, float(scale),
                             int(block))
    return out.reshape(b, hq, s, v.shape[-1]), lse.reshape(b, hq, s)


def kept_probabilities(q: jnp.ndarray, k: jnp.ndarray, lse: jnp.ndarray,
                       selected: jnp.ndarray, *,
                       scale: Optional[float] = None, block: int = 512):
    """The MEAN over the query heads of the attention probabilities
    :func:`selected_attention` used, in the tile layout (``(P, B, block,
    block)`` float32, zero where a key is not kept): the scores once more
    from ``q, k`` and the rows' ``lse``, summed over the heads a tile at a
    time, so that no ``[B, H, S, S]`` array exists.  Not differentiated (the
    indexer's target: ops/key_selection.index_loss)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    q = q.reshape(b, hkv, hq // hkv, s, d)
    lse = lse.reshape(b, hkv, hq // hkv, s)

    def tile(pair):
        i, j, keep = pair
        weights = jnp.exp(
            _kept_scores(_slab(q, i, block), _slab(k, j, block),
                         float(scale), keep)
            - _slab(lse, i, block, -1)[..., None])
        return jnp.sum(weights, axis=(1, 2)) / hq

    return jax.lax.map(tile, causal_pairs(s // block) + (selected,))


def packed_kernel_applies(batch: int, seq_len: int, num_heads: int,
                          head_dim: int, *, causal: bool = False,
                          masked: bool = False, mesh=None,
                          backend: Optional[str] = None) -> bool:
    """Whether ``dense`` self-attention runs as the fused kernel over the
    packed ``qkv`` (ops/packed_attention.py) — decided from what the code
    can see, never by a flag: the program lowers for a TPU, nothing is
    masked, the padded sequence's ``[S,S]`` float32 tiles and row blocks fit
    VMEM, the head width tiles the 128 lanes, and the mesh in scope (if any)
    shards nothing but the batch."""
    backend = jax.default_backend() if backend is None else backend
    if backend != "tpu" or causal or masked:
        return False
    if mesh is not None and (
            batch % mesh.shape.get(DATA_AXIS, 1)
            or mesh.size != mesh.shape.get(DATA_AXIS, 1)):
        return False
    return packed_attention.supported(seq_len, num_heads, head_dim)


def get_attention_fn(impl: str) -> Callable:
    if impl == "dense":
        return dense_attention
    if impl == "ring":
        from byol_tpu.parallel.ring_attention import ring_attention
        return ring_attention
    raise ValueError(f"unknown attention impl {impl!r}; known: dense, ring")
