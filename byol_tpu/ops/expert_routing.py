"""The expert layer's routing tables without a sort.

``models/decoder_trunk.ExpertLayer`` routes every token to ``k`` of ``E``
experts and hands the rows of the ``held`` experts a chip owns to its ragged
products in EXPERT ORDER: a copy ``c = t * k + j`` (token ``t``, slot ``j``)
of held expert ``e`` lies at row ``start[e] + (copies of e before c)``.  As
first written that was ``jax.lax.top_k`` — on a TPU a stable sort of the
router's whole width, E values to keep k — and ``argsort(bucket)`` with its
inverse, two sorts of ``tokens x k`` integers that take ``held + 1`` values
(PERF.md section 5, PR 46).  Here the same tables come from counting:

- :func:`choose` — the k largest of a router row, values descending, ties
  from the left (what the stable sort returns, element for element).  The
  kernel ``route_choose`` takes ``ROWS`` router rows into VMEM once, TURNED
  ``[E, rows]`` (experts down the sublanes, so a row's maximum is a sum of
  whole registers and its result a lane), and runs k rounds of (maximum, its
  FIRST index, mask it).  With ``values`` it selects on ``select`` and
  reads ``values`` at the chosen columns by a one-hot select in the same
  pass (the sigmoid rule: ``scores + bias`` chooses, ``scores`` weigh).  Its
  backward is written by hand: the k cotangents of a row go back to ``[tokens,
  E]`` as ONE dense pass of k compare-selects (the chosen columns are
  distinct: each element takes at most one term, the sum is exact) — no
  scatter.
- :func:`tables` — ``place``, ``token_of``, ``weight_of``, ``group_sizes``.
  The kernel ``route_tables`` holds everything in VMEM (one program, no
  grid).  Bucket by bucket it forms the membership of the tokens ``[tokens /
  128, 128]`` (a token has at most one copy an expert), their exclusive
  prefix — within a row of 128 a product with a strict triangle of ones on
  the matrix unit, across rows one more, exact in float32 — and the carried
  start: that is ``place``.  The sorted order itself is the COMPRESSION of
  the ``held x tokens`` membership, bucket-major: an element ``d`` zeros
  from its row moves left by ``d``, bit by bit of ``d`` from the lowest
  (``log2(held x tokens)`` passes of shift-and-select over VMEM; two
  elements never meet: between two members lie fewer zeros than
  positions).  Its payload is the copy's weight; a copy's token is its final
  position plus ``d``, modulo ``tokens``.  The backward of ``weight_of``
  runs the same passes the other way over the cotangent (an EXPANSION by the
  kept ``d``) and reads it at the chosen experts: no sort, no gather, no
  scatter either way.

WHAT IS DEFINED.  ``chosen``, ``weight`` and ``group_sizes`` everywhere.
``place[t, j]`` for a copy a held expert owns; for the others it is 0 (the
``jax.numpy`` body: a row past ``rows_held``) — every use of it is under
``here &`` or clipped into the window.  ``token_of[r]`` and ``weight_of[r]``
for rows ``r < rows_held``; past them ``token_of`` is only kept in bounds (0
here, the unheld copies' tokens in the ``jax.numpy`` body) and ``weight_of``
is 0: the expert layer masks those rows on the way in and out.

Which lowering runs is read from what the code sees (:func:`applies`): the
program lowers for a TPU and the shapes are ones the kernels take.  Elsewhere
the ``jax.numpy`` bodies (``top_k``, ``argsort``) are the second lowering of
the one algorithm, and the tests' oracle.  ``interpret=True`` (default
off-TPU) runs the kernels under the Pallas interpreter.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.common import LANES, NN, VMEM_LIMIT_BYTES

ROWS = 512          # router rows a program of ``route_choose``
CHUNK = 64          # rows of 128 elements a trip of a moving pass


class Tables(NamedTuple):
    """The dispatch tables of one routing (module docstring: what is
    defined where)."""

    place: jax.Array        # (tokens, k) int32: a copy's sorted row
    token_of: jax.Array     # (tokens * k,) int32: a sorted row's token
    weight_of: jax.Array    # (tokens * k,) float32: its routing weight
    group_sizes: jax.Array  # (held,) int32: an expert's rows


# -- the jax.numpy lowering ---------------------------------------------------

def _choose_by_sorting(select, values, k: int):
    if values is None:
        # the k largest ARE the weights: no gather of them
        return jax.lax.top_k(select, k)
    _, chosen = jax.lax.top_k(select, k)
    return jnp.take_along_axis(values, chosen, axis=-1), chosen


def _tables_by_sorting(chosen, weight, lo: int, held: int) -> Tables:
    tokens, k = chosen.shape
    local = chosen.reshape(-1) - lo
    here = (local >= 0) & (local < held)
    bucket = jnp.where(here, local, held)               # the rest sort last
    order = jnp.argsort(bucket)                         # stable
    place = jnp.argsort(order).reshape(tokens, k)       # a copy's row
    weight_of = jnp.where(here, weight.reshape(-1), 0.0)[order]
    group_sizes = jnp.sum(
        bucket[:, None] == jnp.arange(held, dtype=bucket.dtype),
        axis=0, dtype=jnp.int32)
    return Tables(place.astype(jnp.int32), (order // k).astype(jnp.int32),
                  weight_of, group_sizes)


# -- which lowering -----------------------------------------------------------

def _chunk(rows: int) -> int:
    """Rows a trip, of the ``rows`` of 128 tokens a bucket has: the largest
    power of two up to ``CHUNK`` that divides them."""
    chunk = CHUNK
    while rows % chunk:
        chunk //= 2
    return chunk


def _vmem_bytes(tokens: int, experts: int, k: int, held: int) -> int:
    """The larger of the two kernels' counts.  ``route_choose``: its blocks
    twice and what a round holds of a strip.  ``route_tables``: every
    operand and result once (one program: no second buffer) — the slots'
    experts, weights, rows, and the sorted rows' tokens and weights, the
    compressed offsets — the two moving arrays with their margins, the
    triangles."""
    rows = tokens // LANES
    choose = 4 * (2 * 2 * ROWS * experts + 2 * 2 * 16 * ROWS
                  + 6 * experts * LANES)
    moving = held * rows + 2 * (CHUNK + 8)
    tables = (4 * LANES * (5 * k * rows + held * rows + 2 * moving)
              + 2 * (rows * rows + 2 * LANES * LANES))
    return max(choose, tables)


def supported(tokens: int, experts: int, k: int, held: int) -> bool:
    """Shapes the kernels take: whole sublane tiles of 128 tokens (so whole
    blocks of ``ROWS`` router rows), a router 64 to 512 wide in steps of 64,
    and a working set that fits."""
    return (tokens > 0 and tokens % (8 * LANES) == 0
            and 64 <= experts <= 512 and experts % 64 == 0
            and 0 < k <= experts and 0 < held <= experts
            and _vmem_bytes(tokens, experts, k, held) <= VMEM_LIMIT_BYTES)


def applies(tokens: int, experts: int, k: int, held: int, *,
            backend: Optional[str] = None) -> bool:
    """Whether routing runs as the two kernels — decided from what the code
    can see, never by a flag: the program lowers for a TPU and the shapes
    are ones the kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return backend == "tpu" and supported(tokens, experts, k, held)


# -- the k choices ------------------------------------------------------------

def _choose_kernel(*refs, k: int, with_values: bool):
    """Refs: ``select (E, rows)`` float32 — experts down the sublanes, a
    router row a lane — and, with values, ``values`` alike; out ``weight (k,
    rows)`` float32, ``chosen (k, rows)`` int32.  A strip of 128 router rows
    at a time: ``E / 8`` registers, a round's maximum their elementwise
    maximum and one reduction of 8 sublanes."""
    select_ref, value_ref = refs[0], refs[1] if with_values else None
    weight_ref, chosen_ref = refs[-2:]
    experts, rows = select_ref.shape
    expert = jax.lax.broadcasted_iota(jnp.int32, (experts, LANES), 0)
    for strip in range(rows // LANES):
        cols = pl.ds(strip * LANES, LANES)
        x = select_ref[:, cols]
        for j in range(k):
            top = jnp.max(x, axis=0, keepdims=True)
            first = jnp.minimum(jnp.min(
                jnp.where(x == top, expert, experts), axis=0, keepdims=True),
                experts - 1)
            hit = expert == first
            weight_ref[j:j + 1, cols] = top if value_ref is None else jnp.sum(
                jnp.where(hit, value_ref[:, cols], 0.0), axis=0,
                keepdims=True)
            chosen_ref[j:j + 1, cols] = first
            x = jnp.where(hit, -jnp.inf, x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _choose_call(select, values, k, interpret):
    """``select`` and ``values`` (or None) ``(E, tokens)`` -> ``weight,
    chosen (k, tokens)``."""
    experts, tokens = select.shape
    with_values = values is not None
    block = pl.BlockSpec((experts, ROWS), lambda i: (0, i))
    out = pl.BlockSpec((k, ROWS), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_choose_kernel, k=k, with_values=with_values),
        grid=(tokens // ROWS,),
        in_specs=[block] * (1 + with_values),
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((k, tokens), jnp.float32),
                   jax.ShapeDtypeStruct((k, tokens), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(6 + 2 * with_values) * k * tokens * experts,
            transcendentals=0,
            bytes_accessed=4 * tokens * ((1 + with_values) * experts + 2 * k)),
        interpret=interpret,
        name="route_choose",
    )(select, *([values] if with_values else []))


# The router's rows arrive and leave TURNED, ``(E, tokens)``: the layout the
# TPU compiler gives the router product's result by itself (it made a copy
# of it for ``top_k``), so the transposes below move nothing there.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _choose(values, select, k, interpret):
    """``values`` at the k largest of ``select`` (of ``values`` itself where
    ``select`` is None): the gradient's one way in is ``values``."""
    operands = (values.T, None) if select is None else (select.T, values.T)
    weight, chosen = _choose_call(*operands, k, interpret)
    return weight.T, chosen.T


def _choose_fwd(values, select, k, interpret):
    weight, chosen = _choose(values, select, k, interpret)
    return (weight, chosen), (chosen, jnp.arange(values.shape[1],
                                                 dtype=chosen.dtype))


def _choose_bwd(k, interpret, res, cotangents):
    chosen, expert = res
    g = cotangents[0]
    return sum(jnp.where(expert[:, None] == chosen[:, j], g[:, j], 0.0)
               for j in range(k)).T, None


_choose.defvjp(_choose_fwd, _choose_bwd)


def choose(select, values, k: int, *, kernel: bool,
           interpret: Optional[bool] = None):
    """``select (tokens, E)`` float32 -> ``weight, chosen``, both ``(tokens,
    k)``: the columns of a row's k largest entries, values descending, a tie
    to the lower index, and beside them those entries (``values`` None) or
    ``values`` at those columns.  Gradient: to ``values`` if given, else to
    ``select``, at the chosen columns.  ``kernel``: what :func:`applies`
    said of the routing's shapes."""
    if not kernel:
        return _choose_by_sorting(select, values, k)
    read, by = (select, None) if values is None else (values, select)
    return _choose(read, by, k, ops_common.resolve_interpret(interpret))


# -- the dispatch tables ------------------------------------------------------

def _move(dist_ref, load_ref, data_rows: int, chunk: int, back: bool):
    """The passes of a compression (``back``: of the expansion that undoes
    it) over ``dist`` and its payload ``load``, in place.  Both hold
    ``data_rows`` rows of 128 between margins of ``chunk + 8`` empty rows; an
    element is ``dist >= 0``, how far LEFT of its expanded position its
    compressed one lies.  Pass ``s`` moves the elements with that bit of
    ``dist`` set by ``s`` positions; a trip reads its own chunk and the one
    it takes from before it writes, and chunks are walked AGAINST the
    movement, so a pass reads only what the pass before left."""
    margin, trips = chunk + 8, data_rows // chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)
    bits = (data_rows * LANES - 1).bit_length()
    for bit in (reversed(range(bits)) if back else range(bits)):
        s = 1 << bit

        def trip(c, _, s=s):
            r0 = pl.multiple_of(
                margin + (trips - 1 - c if back else c) * chunk, 8)

            def pair(ref):
                own = ref[pl.ds(r0, chunk), :]
                if s < LANES:           # within a row, the rest from the next
                    other = ref[pl.ds(r0 + (-1 if back else 1), chunk), :]
                    shift = s if back else LANES - s
                    mine = lane >= s if back else lane < LANES - s
                    return own, jnp.where(
                        mine, pltpu.roll(own, shift, 1),
                        pltpu.roll(other, shift, 1))
                rows = s // LANES       # whole rows; past the data: a margin
                at = (jnp.maximum(r0 - rows, margin - chunk) if back
                      else jnp.minimum(r0 + rows, margin + data_rows))
                if rows % 8 == 0:
                    at = pl.multiple_of(at, 8)
                return own, ref[pl.ds(at, chunk), :]

            d_own, d_from = pair(dist_ref)
            w_own, w_from = pair(load_ref)
            take = (d_from >= 0) & ((d_from & s) != 0)
            stay = (d_own >= 0) & ((d_own & s) == 0)
            dist_ref[pl.ds(r0, chunk), :] = jnp.where(
                take, d_from, jnp.where(stay, d_own, -1))
            load_ref[pl.ds(r0, chunk), :] = jnp.where(take, w_from, w_own)
            return 0

        jax.lax.fori_loop(0, trips, trip, 0)


def _clear_margins(dist_ref, load_ref, data_rows: int, margin: int):
    for at in (0, margin + data_rows):
        dist_ref[pl.ds(at, margin), :] = jnp.full((margin, LANES), -1,
                                                  jnp.int32)
        load_ref[pl.ds(at, margin), :] = jnp.zeros((margin, LANES),
                                                   jnp.float32)


def _tables_kernel(chosen_ref, weight_ref, place_ref, token_ref, sorted_ref,
                   moved_ref, sizes_ref, dist_ref, load_ref, *, lo: int,
                   held: int):
    """Refs: ``chosen, weight (k, R, 128)``, a slot's tokens as ``R`` rows of
    128; out ``place`` alike, ``token, sorted (k * R, 128)`` the sorted rows'
    tokens and weights, ``moved (held * R, 128)`` the compressed ``dist``
    (the backward's residual), ``sizes (1, held)`` in SMEM; scratch ``dist,
    load``: ``held * R`` rows between their margins."""
    k, rows, _ = chosen_ref.shape
    tokens, data_rows, chunk = rows * LANES, held * rows, _chunk(rows)
    margin = chunk + 8
    _clear_margins(dist_ref, load_ref, data_rows, margin)
    square = lambda n, dim: jax.lax.broadcasted_iota(jnp.int32, (n, n), dim)
    ones = lambda mask: jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)
    before = ones(square(LANES, 0) < square(LANES, 1))     # [l', l]: l' < l
    above = ones(square(rows, 1) < square(rows, 0))        # [r, r']: r' < r
    every_lane = jnp.ones((LANES, LANES), jnp.bfloat16)
    within_bucket = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
                     * LANES
                     + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    for j in range(k):
        place_ref[j] = jnp.zeros((rows, LANES), jnp.int32)

    def bucket(e, start):
        member, weight = None, jnp.zeros((rows, LANES), jnp.float32)
        for j in range(k):
            one = chosen_ref[j] == lo + e
            member = one if member is None else member | one
            weight = jnp.where(one, weight_ref[j], weight)
        hot = ones(member)
        # members before a token: in its row, and in the rows above (a row's
        # count, at most 128, on every lane: exact in bfloat16)
        in_row = ops_common.dot(hot, before, NN)
        row_count = ops_common.dot(hot, every_lane, NN)
        rank = start + (in_row + ops_common.dot(
            above, row_count.astype(jnp.bfloat16), NN)).astype(jnp.int32)
        for j in range(k):
            place_ref[j] = jnp.where(chosen_ref[j] == lo + e, rank,
                                     place_ref[j])
        here = pl.ds(pl.multiple_of(margin + e * rows, 8), rows)
        dist_ref[here, :] = jnp.where(
            member, e * tokens + within_bucket - rank, -1)
        load_ref[here, :] = weight
        count = jnp.sum(jnp.where(member, 1.0, 0.0)).astype(jnp.int32)
        sizes_ref[0, e] = count
        return start + count

    jax.lax.fori_loop(0, held, bucket, jnp.int32(0))
    _move(dist_ref, load_ref, data_rows, chunk, back=False)

    position = (jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1))

    def write(c, _):
        at = pl.multiple_of(c * chunk, 8)
        dist = dist_ref[pl.ds(margin + at, chunk), :]
        moved_ref[pl.ds(at, chunk), :] = dist

        @pl.when(c < min(k, held) * rows // chunk)
        def _sorted_rows():
            token = jax.lax.rem(at * LANES + position + dist, tokens)
            token_ref[pl.ds(at, chunk), :] = jnp.where(dist >= 0, token, 0)
            sorted_ref[pl.ds(at, chunk), :] = jnp.where(
                dist >= 0, load_ref[pl.ds(margin + at, chunk), :], 0.0)
        return 0

    jax.lax.fori_loop(0, data_rows // chunk, write, 0)
    if k > held:            # more copies than a chip can hold of a token
        rest = pl.ds(data_rows, (k - held) * rows)
        token_ref[rest, :] = jnp.zeros(((k - held) * rows, LANES), jnp.int32)
        sorted_ref[rest, :] = jnp.zeros(((k - held) * rows, LANES),
                                        jnp.float32)


def _expand_kernel(chosen_ref, moved_ref, g_ref, out_ref, dist_ref, load_ref,
                   *, lo: int, held: int):
    """``weight_of``'s backward.  Refs: ``chosen (k, R, 128)``, ``moved
    (held * R, 128)`` the forward's compressed ``dist``, ``g (k * R, 128)``
    the sorted rows' cotangent; out ``(k, R, 128)`` the copies'."""
    k, rows, _ = chosen_ref.shape
    data_rows, chunk = held * rows, _chunk(rows)
    margin = chunk + 8
    _clear_margins(dist_ref, load_ref, data_rows, margin)

    def read(c, _):
        at = pl.multiple_of(c * chunk, 8)
        dist_ref[pl.ds(margin + at, chunk), :] = moved_ref[pl.ds(at, chunk), :]

        @pl.when(c < min(k, held) * rows // chunk)
        def _sorted_rows():
            load_ref[pl.ds(margin + at, chunk), :] = g_ref[pl.ds(at, chunk), :]
        return 0

    jax.lax.fori_loop(0, data_rows // chunk, read, 0)
    _move(dist_ref, load_ref, data_rows, chunk, back=True)
    for j in range(k):
        out_ref[j] = jnp.zeros((rows, LANES), jnp.float32)

    def bucket(e, _):
        g = load_ref[pl.ds(pl.multiple_of(margin + e * rows, 8), rows), :]
        for j in range(k):
            out_ref[j] = jnp.where(chosen_ref[j] == lo + e, g, out_ref[j])
        return 0

    jax.lax.fori_loop(0, held, bucket, 0)


def _moving(held: int, rows: int):
    rows = held * rows + 2 * (_chunk(rows) + 8)
    return [pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.float32)]


_WHOLE = pl.BlockSpec(memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _tables_call(chosen, weight, lo, held, interpret):
    """``chosen, weight (k, tokens)`` -> ``place (k, tokens)``, ``token_of,
    weight_of (k * tokens,)``, ``group_sizes (held,)``, ``moved``."""
    k, tokens = chosen.shape
    rows = tokens // LANES
    shaped = lambda n, kind: jax.ShapeDtypeStruct((n, LANES), kind)
    passes = (held * tokens - 1).bit_length()
    place, token, ordered, moved, sizes = pl.pallas_call(
        functools.partial(_tables_kernel, lo=lo, held=held),
        in_specs=[_WHOLE, _WHOLE],
        out_specs=[_WHOLE] * 4 + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((k, rows, LANES), jnp.int32),
                   shaped(k * rows, jnp.int32), shaped(k * rows, jnp.float32),
                   shaped(held * rows, jnp.int32),
                   jax.ShapeDtypeStruct((1, held), jnp.int32)],
        scratch_shapes=_moving(held, rows),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=held * tokens * (6 * k + 8 * passes
                                   + 2 * (2 * LANES + rows)),
            transcendentals=0,
            bytes_accessed=4 * tokens * (5 * k + held)),
        interpret=interpret,
        name="route_tables",
    )(chosen.reshape(k, rows, LANES), weight.reshape(k, rows, LANES))
    return (place.reshape(k, tokens), token.reshape(-1), ordered.reshape(-1),
            sizes.reshape(held), moved)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _expand_call(chosen, moved, g, lo, held, interpret):
    k, tokens = chosen.shape
    rows = tokens // LANES
    return pl.pallas_call(
        functools.partial(_expand_kernel, lo=lo, held=held),
        in_specs=[_WHOLE] * 3,
        out_specs=_WHOLE,
        out_shape=jax.ShapeDtypeStruct((k, rows, LANES), jnp.float32),
        scratch_shapes=_moving(held, rows),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=held * tokens * (2 * k + 8 * (
                held * tokens - 1).bit_length()),
            transcendentals=0,
            bytes_accessed=4 * tokens * (3 * k + held)),
        interpret=interpret,
        name="route_tables_bwd",
    )(chosen.reshape(k, rows, LANES), moved,
      g.reshape(k * rows, LANES)).reshape(k, tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _tables(chosen, weight, lo, held, interpret):
    return _tables_call(chosen, weight, lo, held, interpret)[:4]


def _tables_fwd(chosen, weight, lo, held, interpret):
    *out, moved = _tables_call(chosen, weight, lo, held, interpret)
    return tuple(out), (chosen, moved)


def _tables_bwd(lo, held, interpret, res, cotangents):
    chosen, moved = res
    return None, _expand_call(chosen, moved, cotangents[2], lo, held,
                              interpret)


_tables.defvjp(_tables_fwd, _tables_bwd)


def tables(chosen, weight, lo: int, held: int, *, kernel: bool,
           interpret: Optional[bool] = None) -> Tables:
    """``chosen (tokens, k)`` int32, the experts of a token's copies, and
    ``weight (tokens, k)`` float32 -> the dispatch :class:`Tables` of the
    experts ``[lo, lo + held)``.  Gradient: from ``weight_of`` to
    ``weight``, read at the copies' rows.  ``kernel``: as :func:`choose`."""
    if not kernel:
        return _tables_by_sorting(chosen, weight, lo, held)
    place, token_of, weight_of, group_sizes = _tables(
        chosen.T, weight.T, lo, held, ops_common.resolve_interpret(interpret))
    return Tables(place.T, token_of, weight_of, group_sizes)
