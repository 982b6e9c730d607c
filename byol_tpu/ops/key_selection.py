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

Plain ``jax.numpy``, float32 scores and statistics — but for the SEARCH of
:func:`select_top_keys` (the ``need``-th largest value of each row in 32
counting passes, then the keys that reach it), which has two lowerings of
one algorithm, chosen from what the code can see (:func:`applies`): where
the program lowers for a TPU the Pallas kernel ``top_keys_search``
(:func:`search_rows`), everywhere else :func:`_top_of_rows`, which is also
the tests' oracle.  The ``jax.numpy`` body reads the searched tiles from HBM
34 times a call and more where a row has ties (218 MB a time at 8 x 4,096
tokens: 11.8 ms, PERF.md section 5, PR 40); the kernel reads them twice and
counts over a row held in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.attention import _slab, causal_pairs
from byol_tpu.ops.common import LANES, MASKED, VMEM_LIMIT_BYTES

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


# ---- the search as a kernel ------------------------------------------------

_ROWS = 256        # keys a trip of the counting loop takes (32 sublane
                   # groups) where a tile's keys are whole such trips


def _vmem_bytes(block: int, blocks: int) -> int:
    """The longest row's tiles as ordered words, the arriving tile and the
    leaving int8 one twice each (double buffering), three values a query
    down the sublanes, and the live squares of a step: taking a tile in (the
    tile with its zeros made one, its word, the ordered word, that turned,
    that masked) or writing one (the ordered word, two masks, the bf16 ties
    and the triangle they meet, the float32 ranks)."""
    square = block * block
    return (blocks + 2 + 6) * 4 * square + 2 * square + 3 * 4 * block * LANES


def applies(block: int, blocks: int, *, backend: Optional[str] = None) -> bool:
    """Whether :func:`select_top_keys` searches as the kernel — decided from
    what the code can see, never by a flag: the program lowers for a TPU, a
    tile fills whole 128-lane tiles both ways (it is turned) and the longest
    row, ``blocks`` tiles, fits VMEM beside the kernel's working set.  At
    blocks of 512 the count is 17 MiB at 4,096 tokens and 25 MiB at 8,192
    (a row of 16 tiles): both fit ``VMEM_LIMIT_BYTES``, as does every
    sequence up to 19,456 tokens; a longer one runs the ``jax.numpy``
    body."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and block > 0 and block % LANES == 0
            and _vmem_bytes(block, blocks) <= VMEM_LIMIT_BYTES)


def _steps(blocks: int, first: int):
    """The kernel's steps a sequence, five int32 arrays: the step's ``(query
    block, key block)``; whether it WRITES its tile of the set (1) or takes
    its tile of the scores in (0); the tile of the scores it reads and the
    tile of the set its output block is.  A query block before ``first`` only
    writes; a later one takes its tiles in, searches on the last of them, and
    then writes them, left to right.  A step that reads nothing new names the
    tile the next reading step reads, and one that writes nothing the tile
    the next writing step writes: neither moves anything."""
    tile = lambda i, j: i * (i + 1) // 2 + j
    rows = []
    for i in range(blocks):
        if i >= first:
            rows += [(i, j, 0, tile(i, j), tile(i, 0)) for j in range(i + 1)]
        rows += [(i, j, 1, tile(max(i, first), j if i >= first else 0),
                  tile(i, j)) for j in range(i + 1)]
    return tuple(jnp.asarray(column, jnp.int32) for column in zip(*rows))


def _search_kernel(q_of_ref, k_of_ref, writes_ref, reads_ref, set_ref,
                   scores_ref, need_ref, kept_ref, bits_ref, floor_ref,
                   room_ref, before_ref, *, first: int):
    """One step a tile (:func:`_steps`).  Refs: ``scores (bq, bk)`` float32,
    the step's tile; ``need (1, bq)`` int32; ``kept (bq, bk)`` int8, its tile
    of the set; scratch ``bits (blocks * bk, bq)`` int32: the row's tiles so
    far, TURNED — keys down the sublanes, so a query's count is a sum of
    whole registers and its candidate a lane — as :func:`_ordered_bits` with
    the top bit flipped (the floats' order in SIGNED words, the compare the
    vector unit has; not causal: the least word); and, a query a sublane
    (the same value on all 128 lanes) for the writing steps: ``floor`` int32,
    the row's threshold as such a word; ``room`` float32, how many keys AT
    the threshold the row still takes; ``before`` float32, how many of them
    the tiles written so far held."""
    step = pl.program_id(1)
    i, j = q_of_ref[step], k_of_ref[step]
    bq, bk = scores_ref.shape
    lowest = jnp.iinfo(jnp.int32).min
    column = lambda row: jnp.broadcast_to(row, (LANES, bq)).T   # (bq, LANES)
    across = lambda ref: jnp.tile(ref[...], (1, bk // LANES))   # (bq, bk)

    def ordered():
        x = scores_ref[...]
        word = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x),
                                            jnp.int32)
        return jnp.where(word < 0, word ^ 0x7FFFFFFF, word)

    def search():
        """The 32 counting trips over the row the scratch holds, and a 33rd
        for the keys over the threshold."""
        need = need_ref[...]
        rows = _ROWS if bk % _ROWS == 0 else LANES
        trips = (i + 1) * (bk // rows)

        def counted(reaches, floor):
            """``(1, bq)``: how many of each query's words ``reaches`` its
            ``floor``."""
            against = jnp.broadcast_to(floor, (8, bq))

            def count(trip, counts):
                # two running sums, so that an add does not wait for the
                # one before it
                base = pl.multiple_of(trip * rows, rows)
                for group in range(rows // 8):
                    words = bits_ref[pl.ds(base + 8 * group, 8), :]
                    counts[group % 2] += jnp.where(reaches(words, against),
                                                   1, 0)
                return counts

            zero = jnp.zeros((8, bq), jnp.int32)
            even, odd = jax.lax.fori_loop(0, trips, count, [zero, zero])
            return jnp.sum(even + odd, axis=0, keepdims=True)

        def refine(bit, least):
            candidate = least | (jnp.int32(1) << (31 - bit))
            enough = counted(jnp.greater_equal, candidate ^ lowest) >= need
            return jnp.where(enough, candidate, least)

        floor = jax.lax.fori_loop(0, 32, refine,
                                  jnp.zeros((1, bq), jnp.int32)) ^ lowest
        floor_ref[...] = column(floor)
        room_ref[...] = column(
            (need - counted(jnp.greater, floor)).astype(jnp.float32))

    @pl.when(writes_ref[step] == 0)
    def _take_in():
        turned = ordered().T                                    # (bk, bq)
        here = pl.ds(pl.multiple_of(j * bk, bk), bk)

        @pl.when(j < i)
        def _under():
            bits_ref[here, :] = turned

        @pl.when(j == i)
        def _on():
            key = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            query = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            bits_ref[here, :] = jnp.where(key <= query, turned, lowest)
            search()

    @pl.when(writes_ref[step] == 1)
    def _write():
        query = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        key = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # under the diagonal every key is causal
        causal = key <= query + jnp.where(j < i, bk, 0)

        @pl.when(i < first)
        def _all():
            kept_ref[...] = causal.astype(jnp.int8)

        @pl.when(i >= first)
        def _largest():
            @pl.when(j == 0)
            def _start():
                before_ref[...] = jnp.zeros_like(before_ref)

            words, floor = ordered(), across(floor_ref)
            # keys at the threshold are taken from the left: a key's rank
            # among them is a product with a triangle of ones, exact in
            # float32, and the tiles before this one have counted theirs
            tie = causal & (words == floor)
            ties = jnp.where(tie, 1.0, 0.0).astype(jnp.bfloat16)
            earlier = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
            later = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
            rank = jnp.dot(
                ties, jnp.where(earlier <= later, 1.0, 0.0).astype(
                    jnp.bfloat16), preferred_element_type=jnp.float32)
            taken = tie & (rank + across(before_ref) <= across(room_ref))
            kept_ref[...] = ((causal & (words > floor)) | taken).astype(
                jnp.int8)
            before_ref[...] += jnp.dot(
                ties, jnp.ones((bk, LANES), jnp.bfloat16),
                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _search(scores, need, first, interpret):
    tiles, batch, block = scores.shape[:3]
    blocks = _blocks(tiles)
    steps = _steps(blocks, first)
    searched = (tiles - first * (first + 1) // 2) * batch * block * block
    a_query = lambda kind: pltpu.VMEM((block, LANES), kind)
    return pl.pallas_call(
        functools.partial(_search_kernel, first=first),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(steps),
            grid=(batch, len(steps[0])),
            in_specs=[
                pl.BlockSpec((None, None, block, block),
                             lambda n, s, qo, ko, wr, rd, st: (rd[s], n, 0, 0)),
                pl.BlockSpec((None, 1, block), lambda n, s, qo, *_: (
                    jnp.maximum(qo[s] - first, 0), 0, 0))],
            out_specs=pl.BlockSpec(
                (None, None, block, block),
                lambda n, s, qo, ko, wr, rd, st: (st[s], n, 0, 0)),
            scratch_shapes=[pltpu.VMEM((blocks * block, block), jnp.int32),
                            a_query(jnp.int32), a_query(jnp.float32),
                            a_query(jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(33 * 3 + 32 + 2 * (block + LANES)) * searched,
            transcendentals=0,
            bytes_accessed=2 * 4 * searched + scores.size),
        interpret=interpret,
        name="top_keys_search",
    )(*steps, scores, need.astype(jnp.int32)[:, None, :])


def search_rows(scores, need, first: int, *,
                interpret: Optional[bool] = None):
    """The kernel ``top_keys_search``: the tiles of ``I`` (``(P, B, block,
    block)`` float32) and ``(blocks - first, block)`` how many to keep of
    each row from query block ``first`` on -> the tiles of the set (``(P, B,
    block, block)`` bool; every causal key before ``first``), bit for bit
    :func:`_top_of_rows`'s: exactly ``need`` causal keys a row, the largest,
    a tie to the lower index.  One program step a tile, grid ``(B, steps)``:
    a row's tiles arrive one by one through the pipeline, are turned into
    ordered words in VMEM — the diagonal tile's mask from two iotas, no
    ``causal`` operand — and the counting trips run there on the row's last
    tile; then the tiles arrive once more, left to right, and leave as the
    set, the keys AT the threshold ranked on the matrix unit.  A searched
    tile is read from HBM TWICE, where the ``jax.numpy`` loop reads it 34
    times, and nothing of the tiles' size is written but the set."""
    return _search(scores, need, int(first),
                   ops_common.resolve_interpret(interpret)) != 0


def select_top_keys(scores, topk: int, *, block: int = 512):
    """The tiles of ``I`` -> the tiles of the set, bool.  A query block
    none of whose queries has more than ``topk`` causal keys keeps them all
    and is not searched.  No gradient passes (a set has none).  Two
    lowerings of one algorithm, chosen by :func:`applies`: the kernel
    (:func:`search_rows`) or the ``jax.numpy`` body below, the tests'
    oracle; the set is the same bits either way."""
    batch = scores.shape[1]
    blocks = _blocks(scores.shape[0])
    free = min(topk // block, blocks)      # query blocks that keep all
    how_many = lambda: jnp.minimum(
        jnp.arange(free * block, blocks * block) + 1, topk)
    if free < blocks and applies(block, blocks):
        return search_rows(jax.lax.stop_gradient(scores),
                           how_many().reshape(blocks - free, block), free)
    kept = []
    if free:
        kept.append(jnp.broadcast_to(
            causal_tiles(free, block)[:, None],
            (free * (free + 1) // 2, batch, block, block)))
    if free < blocks:
        need = how_many()
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
    kept = jnp.where(selected, scores, MASKED)
    top = jax.lax.stop_gradient(over(jnp.max(kept, axis=-1), jnp.max))
    log_q = kept - top - jnp.log(over(
        jnp.sum(jnp.exp(kept - top), axis=-1), jnp.sum))
    seen = (p > 0.0) & _real_rows(tiles, block, seq_len)
    return jnp.sum(jnp.where(
        seen, p * (jnp.log(jnp.where(seen, p, 1.0))
                   - jnp.where(seen, log_q, 0.0)), 0.0)) / (batch * seq_len)
