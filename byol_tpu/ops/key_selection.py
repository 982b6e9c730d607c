"""Which keys a query attends: a learned index score for every causal pair,
the exact ``k`` largest of each row, and the loss that teaches the scorer.

The parts of sparse attention round its core (ops/attention.py
``selected_attention``), as the DeepSeek-V3.2-Exp report describes them:

* :func:`index_scores` — ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  from a few small heads ``j`` that share ONE key head;
* :func:`select_top_keys` — ``S_t``: the ``min(t + 1, k)`` causal keys of
  largest ``I[t, s]``, a tie to the lower index;
* :func:`index_loss` — ``mean_t KL(p_t || softmax_{s in S_t} I[t, s])``, ``p_t``
  the core's head-mean probabilities (``ops/attention.kept_probabilities``).

Everything ``[S, S]``-shaped here is in the TILE layout of ops/attention.py:
the block pairs on and under the diagonal, ``(P, B, block, block)``, query
block by query block — 36/64 of the square at eight blocks — with no head
axis: the per-head scores exist one tile at a time, forward and
(``jax.checkpoint``) backward.  A ROW is the tiles of one query block side
by side, so what is taken over a row is taken over a tile's last axis and
then over those tiles (:func:`_over_rows`).  Entries above the diagonal of
a diagonal tile are there and mean nothing; :func:`causal_tiles` says
which.  ``S`` is a multiple of ``block`` (the layer pads).

Plain ``jax.numpy``; float32 scores and statistics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from byol_tpu.ops.attention import _MASKED, _slab, causal_pairs

_SIGN = np.uint32(0x80000000)      # numpy: nothing touches a backend at import


def _blocks(tiles: int) -> int:
    """``n`` of ``P = n (n + 1) / 2`` tiles."""
    return int((2 * tiles) ** 0.5)


def causal_tiles(blocks: int, block: int, first: int = 0) -> jnp.ndarray:
    """``(P', block, block)`` bool, from query block ``first`` on: the key
    is not after the query."""
    q_of, k_of = (x[first * (first + 1) // 2:] for x in causal_pairs(blocks))
    at = lambda of: of[:, None] * block + np.arange(block)
    return jnp.asarray(at(q_of))[:, :, None] >= jnp.asarray(at(k_of))[:, None]


def _row_bounds(blocks: int, first: int = 0):
    """``[lo, hi)`` of each query block's tiles among the tiles from query
    block ``first`` on."""
    ends = np.cumsum(np.arange(first + 1, blocks + 1))
    return [(int(hi - n), int(hi))
            for n, hi in zip(range(first + 1, blocks + 1), ends)]


def _over_rows(per_tile, bounds, reduce):
    """``(P', ...)``, a value a row of a tile -> ``(len(bounds), ...)``:
    ``reduce`` over the tiles of each query block."""
    return jnp.stack([reduce(per_tile[lo:hi], axis=0) for lo, hi in bounds])


def _to_tiles(per_row, bounds):
    """``(len(bounds), ...)`` -> ``(P', ..., 1)``: each query block's value at
    every one of its tiles, against the tile's last axis."""
    return jnp.repeat(per_row, np.asarray([hi - lo for lo, hi in bounds]),
                      axis=0, total_repeat_length=bounds[-1][1])[..., None]


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _index_tile(q_blk, k_blk, w_blk, scale):
    """One tile: ``(B, bq, J, d), (B, bk, d), (B, bq, J) -> (B, bq, bk)``.
    Under ``jax.checkpoint``: the backward forms the tile's per-head scores
    again instead of keeping every tile's."""
    scores = jnp.einsum("bqjd,bkd->bjqk", q_blk, k_blk,
                        preferred_element_type=jnp.float32)
    w = jnp.swapaxes(w_blk.astype(jnp.float32), 1, 2)[..., None]
    return jnp.sum(w * jax.nn.relu(scores), axis=1) * scale


def index_scores(q_i, k_i, w, *, scale: float, block: int = 512):
    """``q_i``: ``(B, S, J, d)``; ``k_i``: ``(B, S, d)``; ``w``: ``(B, S, J)``
    -> the tiles of ``I``, float32."""
    batch, seq_len, heads, dim = q_i.shape
    q_i = q_i.reshape(batch, seq_len, heads * dim)    # rows on the axis
                                                      # ``_slab`` cuts
    def tile(pair):
        i, j = pair
        return _index_tile(
            _slab(q_i, i, block).reshape(batch, block, heads, dim),
            _slab(k_i, j, block), _slab(w, i, block), float(scale))

    return jax.lax.map(tile, causal_pairs(seq_len // block))


def _ordered_bits(x):
    """float32 -> uint32 in the floats' order (``-0.0`` as ``0.0``); every
    finite float and both infinities map above 0."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0.0, 0.0, x).astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >= _SIGN, ~bits, bits | _SIGN)


def _top_of_rows(scores, causal, need, bounds):
    """``(P', B, bq, bk)`` scores and ``(P', bq, bk)`` causal of the query
    blocks whose tiles ``bounds`` delimits, ``(len(bounds), bq)`` how many
    to keep of each row -> ``(P', B, bq, bk)`` bool: exactly ``need`` causal
    keys a row, the largest, a tie to the lower index.  The ``need``-th
    largest value of a row is built bit by bit — 32 counts of ``row >=
    candidate`` — where a sort would move every row's 4,096 entries through
    a bitonic network; keys equal to it are then taken from the left."""
    bits = jnp.where(causal[:, None], _ordered_bits(scores), jnp.uint32(0))
    need = need.astype(jnp.int32)[:, None, :]
    tiles = lambda per_row: _to_tiles(per_row, bounds)
    count = lambda mask: _over_rows(
        jnp.sum(mask, axis=-1, dtype=jnp.int32), bounds, jnp.sum)

    def refine(i, least):
        candidate = least | (_SIGN >> i.astype(jnp.uint32))
        enough = count(bits >= tiles(candidate)) >= need
        return jnp.where(enough, candidate, least)

    least = tiles(jax.lax.fori_loop(
        0, 32, refine,
        jnp.zeros((len(bounds),) + bits.shape[1:3], jnp.uint32)))
    at_least = bits >= least

    def from_the_left():
        above, equal = bits > least, bits == least
        # the equal keys up to this one: in its tile, and in the row's
        # tiles before it
        inside = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
        whole = inside[..., -1]
        before = jnp.concatenate([
            jnp.cumsum(whole[lo:hi], axis=0) - whole[lo:hi]
            for lo, hi in bounds])
        return above | (equal & (inside + before[..., None]
                                 <= tiles(need - count(above))))

    # rows whose threshold is met by more keys than it has room for are rare
    # (an exact zero of the ReLU's): the running count runs only then
    return jax.lax.cond(jnp.any(count(at_least) > need), from_the_left,
                        lambda: at_least)


def select_top_keys(scores, topk: int, *, block: int = 512):
    """The tiles of ``I`` -> the tiles of the set, bool.  A query block
    none of whose queries has more than ``topk`` causal keys keeps them all
    and is not searched.  No gradient passes (a set has none)."""
    batch = scores.shape[1]
    blocks = _blocks(scores.shape[0])
    free = min(topk // block, blocks)      # query blocks that keep all
    kept = []
    if free:
        kept.append(jnp.broadcast_to(
            causal_tiles(free, block)[:, None],
            (free * (free + 1) // 2, batch, block, block)))
    if free < blocks:
        need = jnp.minimum(jnp.arange(free * block, blocks * block) + 1, topk)
        kept.append(_top_of_rows(
            jax.lax.stop_gradient(scores[free * (free + 1) // 2:]),
            causal_tiles(blocks, block, free),
            need.reshape(blocks - free, block), _row_bounds(blocks, free)))
    return jnp.concatenate(kept)


def _real_rows(tiles: int, block: int, seq_len: int):
    """``(P, 1, block, 1)`` bool: the tile's row is one of the sequence's
    ``seq_len`` (the last block's may be padding)."""
    row = causal_pairs(_blocks(tiles))[0][:, None] * block + np.arange(block)
    return jnp.asarray(row < seq_len)[:, None, :, None]


def pair_counts(selected, seq_len: int):
    """``[causal pairs, selected pairs]`` of one pass over ``seq_len``-token
    sequences, float32: the second COUNTED from the set."""
    tiles, batch, block = selected.shape[:3]
    causal = batch * seq_len * (seq_len + 1) // 2
    kept = jnp.sum(selected & _real_rows(tiles, block, seq_len),
                   dtype=jnp.float32)
    return jnp.stack([jnp.asarray(causal, jnp.float32), kept])


def index_loss(scores, probabilities, selected, seq_len: int):
    """``mean_t KL(p_t || softmax_{s in S_t} I[t, .])`` over every row of the
    batch's ``seq_len``-token sequences: ``scores`` the tiles of ``I`` (the
    gradient's way in), ``probabilities`` the tiles of ``p`` (no gradient),
    ``selected`` the set."""
    tiles, batch, block = scores.shape[:3]
    bounds = _row_bounds(_blocks(tiles))
    over = lambda per_tile, reduce: _to_tiles(
        _over_rows(per_tile, bounds, reduce), bounds)
    p = jax.lax.stop_gradient(jnp.where(selected, probabilities, 0.0))
    kept = jnp.where(selected, scores, _MASKED)
    top = jax.lax.stop_gradient(over(jnp.max(kept, axis=-1), jnp.max))
    log_q = kept - top - jnp.log(over(
        jnp.sum(jnp.exp(kept - top), axis=-1), jnp.sum))
    seen = (p > 0.0) & _real_rows(tiles, block, seq_len)
    return jnp.sum(jnp.where(
        seen, p * (jnp.log(jnp.where(seen, p, 1.0))
                   - jnp.where(seen, log_q, 0.0)), 0.0)) / (batch * seq_len)
