"""The chunked gated delta rule as two kernel pairs: WITHIN a chunk
(``delta_wy_fwd`` / ``delta_wy_bwd``) and BETWEEN chunks (``delta_scan_fwd``
/ ``delta_scan_bwd``).

``within_chunk(q, k, v, g, beta, chunk=, dtype=)`` is everything
``models/gated_delta._chunked_rule`` does between its chunked inputs and its
``lax.scan``: per chunk of ``C`` tokens and per value head, from ``k, q``
``(C, d_k)``, ``v`` ``(C, d_v)`` and the gates ``g, beta`` ``(C,)``::

    gamma = cumsum(g)                         D_ij = exp(gamma_i - gamma_j), i >= j
    A = strict_tril((k beta) k^T . D)         T = (I + A)^-1
    [u | w] = T [beta v | beta k e^gamma]     within = (q k^T) . D
    q_in = q e^gamma                          k_out = k e^(gamma_C - gamma)

It exists because of what the compiler does with the ``jax.numpy`` form
(PERF.md section 5, PR 31): every ``C x C`` intermediate of every chunk was a
whole float32 ``[B,H,N,C,C]`` array in HBM — 37 GB a layer and pass where
the rule's inputs and outputs are 1.1 — and the inverse's diagonal blocks
went through a ``triangular_solve`` custom call that substitutes row by row
over all of them.  Here a chunk's matrices live and die in VMEM.

Design (see /opt/skills/guides/pallas_guide.md; times are chip runs, PR 32,
a call of 4,096 chunks of 128 with heads of 128 in bf16):
- every chunk is independent: the grid is ``(B, N / tile, H)``, all axes
  parallel; a program holds ``tile`` chunks of one value head;
- a chunk's products wait for each other — twelve in a row through the
  inverse — and the kernel's instructions are scheduled near the order
  they were traced in: one chunk after the other the forward took 10.4 ms,
  latency-bound.  So a chunk's body is a GENERATOR that yields after each
  product the next one waits for, and :func:`_in_step` traces the tile's
  chunks breadth first: 6.7 ms (a tile of 8; 6.9 at 4, 7.4 at 2, 12.0 at 1);
- the kernels read ``q, k, v`` where the layer holds them — 128-lane column
  blocks of ``[B, S, H * d]``, key head ``h // r`` for value head ``h``, so
  the keys are never repeated in memory — and write what the scan reads
  in the scan's own layout ``[N, B, H, C, d]``;
- the gates arrive as rows ``[B, H, N, 1, C]`` (chunk on the lanes).  A
  row becomes a column by transposing its ``(C, C)`` broadcast, which IS the
  ``gamma_i`` matrix the decay needs; ``cumsum`` and its transpose are
  products with a triangular matrix of ones;
- ``(I + A)^-1`` EXACTLY, in float32: forward substitution on the
  ``SUBSTITUTED``-sized diagonal blocks (at side 2 it is one row: ``T = I -
  A`` on the pairs), then block elimination bottom up, ``T <- T - T (A . M)
  T`` with ``M`` the lower-left quarters of the next side — no power series
  of ``A``, nothing cancels where keys repeat.  Only the lower half of each
  block changes: from side 8 up those rows are whole sublane tiles and
  only they go through the matrix unit (11.8 -> 10.4 ms before the chunks
  were traced in step).  The six rounds are 3/5 of the forward kernel;
- float32 products (the inverse, ``[u | w]``, the sums over a chunk) run at
  ``Precision.HIGHEST``; the products ``_chunked_rule`` makes in ``dtype``
  (``K K^T``, ``Q K^T``) take ``dtype`` operands with float32 accumulation,
  here too, and their backward rounds its cotangent operand the same way;
- the backward is written out by hand: ``dT = dSol R^T``, ``dA = -T^T dT
  T^T`` below the diagonal, ``dgamma`` from the row and column sums of ``dD
  . D``, ``dg`` its reversed cumulative sum.  It rebuilds ``D``, ``K K^T``,
  ``Q K^T`` in VMEM from the residuals ``q, k, v, g, beta`` and READS ``T``,
  which the forward that precedes a backward writes once more (float32
  ``[N, B, H, C, C]``, 268 MB a group of 4 sequences, between the two
  kernels only): rebuilding it took the backward from 6.7 to 15.7 ms.

``between_chunks(u, w, within, q_in, k_out, decay)`` is that ``lax.scan``:
``gated_delta._chunk_step`` from a zero state down a sequence's chunks, on
``within_chunk``'s results in the layout they have, per value head::

    held = dtype(S)                           delta = u - w held
    o = q_in held + within dtype(delta)       S <- S decay + k_out^T dtype(delta)

As a ``lax.scan`` a trip was three to five XLA ops with a dynamic slice in
front of each operand, read AND wrote the ``[B, H, d_k, d_v]`` float32 state
in HBM, and its autodiff kept ``held``, ``delta`` and the state of every
trip: 2.31 ms a forward and 9.97 ms a forward with its backward (chip run,
PR 47: a group of 4 sequences x 4,096 tokens x 32 heads, 32 chunks of 128,
heads of 128, bf16 — the sizes of every time below).  Design of the pair:
- the grid is ``(B, H / heads, N)``, the chunk axis last and ``arbitrary``:
  a float32 VMEM scratch ``(heads, d_k, d_v)`` carries the states down the
  column of chunks, zeroed where the chunk index is 0; they never reach
  HBM but where a backward follows, which reads each chunk's INCOMING
  states (float32 ``[N, B, H, d_k, d_v]``, 268 MB a group of 4 sequences,
  between the two kernels only — less than the scan's autodiff kept);
- a trip is ``_chunk_step`` to the letter — operands in ``dtype``, float32
  accumulation, ``held`` rounded once a chunk, the state float32 — so ``o``
  is the scan's to the bit on the CPU;
- a head's two dependent products a chunk wait for each other, the heads
  do not: a program holds ``heads`` value heads (:func:`_heads`: the most
  that divide ``H`` and fit, 8 at the published sizes) and traces them
  breadth first with :func:`_in_step`, as the within-chunk kernels trace
  their chunks.  At 4 | 8 | 16 heads the forward ran 1.373 | 1.322 | 1.305
  ms: with even 4 chains in step the kernel is bound by HBM, not by the
  chain;
- ``o`` is written as 128-lane column blocks of ``[B, S, H d_v]``, the
  array the gated norm reads: nothing turns the scan's ``[N, B, H, C,
  d_v]`` round any more, and ``d_o`` is read the same way;
- the backward runs the chunks last to first on the cotangent of the
  handed-on states, float32 in the scratch, and forms ``delta`` again:
  nine products a chunk and head.  A cotangent that is a product's operand
  is rounded to ``dtype`` as the scan's transpose rounds it; SUMS of
  products stay float32 (the scan's transpose rounds each part to
  ``dtype`` first: a rounding of the gradient's norm apart);
- VMEM (:func:`_scan_vmem_bytes`): a head's blocks are 224 KB forward (+ 64
  kept), 480 backward, twice for double buffering, plus the carried
  states and the temporaries of all heads, which are traced in step: 8
  heads are 5.0 | 6.0 | 10.0 MiB of the 16 MiB scope; what the chip's
  compiler took and refused at 16 and 32 heads set the temporaries' count;
- times: ``delta_scan_fwd`` 1.32 ms a call for 0.94 GB (711 GB/s), 1.78
  where it keeps the states (1.21 GB, 678 GB/s), ``delta_scan_bwd`` 2.97
  for 2.01 GB (678 GB/s) — the rate of a plain pass over HBM on a v5e;
  inside the train step, beside the compiler's asynchronous copies, 1.55,
  2.0 and 2.99.  ``o`` is the scan's to the bit on the chip too.

``interpret=True`` (default off-TPU) runs the same kernels under the Pallas
interpreter so CPU tests exercise identical code paths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.common import LANES, NN, NT, TN

HIGHEST = jax.lax.Precision.HIGHEST
# Side of the diagonal blocks that forward substitution inverts; the matrix
# unit takes over above (block elimination).  At 2 a block's substitution
# is one row, ``T = I - A``: free.  A larger side substitutes on the vector
# unit, a row at a time over every block, to spare rounds whose cost is
# mostly waiting: with the round from 2 to 4 done there instead (two
# shifts, by a row and by a lane) the forward kernel took 6.5 ms for 6.7
# (chip run, PR 32) — not worth its code.
SUBSTITUTED = 2
# What a program may take of VMEM: the compiler's default scope, 16 MiB of
# the 128 a v5e holds; no ``vmem_limit_bytes`` is asked for (a kernel that
# asks makes XLA write an explicit scope on every fusion of the module, for
# nothing: with 32 MiB and tiles of 8 in both kernels the step was no
# faster).  The double-buffered blocks of a tile of chunks plus the float32
# temporaries of all its chunks, which are traced in step, have to fit:
# counted from what Mosaic reported at chunk 128, heads of 128, bf16, a
# tile of 8 — 13.5 MB forward, 17.9 backward (9.3 at 4).
VMEM_BYTES = 16 * 2 ** 20
# float32 values a chunk holds at once, (forward, backward): (C, C) ones
# and (C, d_k + d_v) ones
_LIVE_SQUARES = (8, 12)
_LIVE_WIDE = (3, 5)
MAX_TILE = 8                     # chunks a program holds

SUBLANES = 8                     # rows of a float32 tile


def _vmem_bytes(chunk: int, dk: int, dv: int, tile: int, itemsize: int,
                forward: bool):
    """A kernel's blocks twice (double buffering) — ``q, k, v``, the scan's
    operands, the kept inverse; the backward's cotangents of all of them —
    and the chunks' temporaries."""
    inputs = itemsize * (2 * dk + dv)
    results = 4 * dv + itemsize * (3 * dk + chunk) + 4 * chunk
    per_token = inputs + results + (0 if forward else inputs)
    live = 4 * chunk * (_LIVE_SQUARES[not forward] * chunk
                        + _LIVE_WIDE[not forward] * (dk + dv))
    return tile * (2 * chunk * per_token + live)


def _most(n: int, limit: int, vmem_bytes) -> int:
    """The largest divisor of ``n`` up to ``limit`` whose count fits."""
    for size in range(min(limit, n), 1, -1):
        if n % size == 0 and vmem_bytes(size) <= VMEM_BYTES:
            return size
    return 1


def _tile(n: int, chunk: int, dk: int, dv: int, itemsize: int,
          forward: bool) -> int:
    """Chunks a program holds: the most that divide ``n`` and fit."""
    return _most(n, MAX_TILE, lambda tile: _vmem_bytes(
        chunk, dk, dv, tile, itemsize, forward))


def supported(chunk: int, dk: int, dv: int, itemsize: int = 2) -> bool:
    """Shapes the kernels take: a chunk's tokens and a head's width fill
    whole 128-lane tiles, and one chunk's matrices — within it, and one
    head's between chunks — fit VMEM."""
    return (chunk > 0 and chunk % LANES == 0 and dk > 0 and dk % LANES == 0
            and dv > 0 and dv % LANES == 0
            and _vmem_bytes(chunk, dk, dv, 1, itemsize, False) <= VMEM_BYTES
            and _scan_vmem_bytes(chunk, dk, dv, 1, itemsize,
                                 "backward") <= VMEM_BYTES)


def applies(chunk: int, dk: int, dv: int, dtype=jnp.bfloat16, *,
            backend: Optional[str] = None) -> bool:
    """Whether the within-chunk stage runs as the kernels — decided from
    what the code can see, never by a flag: the program lowers for a TPU
    and the shapes are ones the kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return backend == "tpu" and supported(chunk, dk, dv,
                                          jnp.dtype(dtype).itemsize)


# ---- one chunk, on values in VMEM ----------------------------------------

def _dot(a, b, dims, exact=True):
    """``exact``: float32 operands at full precision; else the operands as
    they are (``dtype``), float32 accumulation."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _iotas(c):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _levels(c):
    """``(C, C)`` int32: for ``i > j`` the side ``s`` at whose round of the
    elimination entry ``(i, j)`` is a lower-left quarter (``i, j`` in one
    block of ``2s``, ``i`` in its lower half, ``j`` in its upper); 0 on and
    above the diagonal."""
    rows, cols = _iotas(c)
    level = jnp.zeros((c, c), jnp.int32)
    s = 1
    while s < c:
        quarter = ((rows // (2 * s) == cols // (2 * s))
                   & (rows % (2 * s) >= s) & (cols % (2 * s) < s))
        level = jnp.where(quarter, s, level)
        s *= 2
    return level


def _inverse(lower, level):
    """``(I + L)^-1`` for ``L = lower`` where ``level > 0``: see the module
    docstring.  A generator: it yields after every product that the next
    one waits for (see :func:`_in_step`) and returns the inverse."""
    c = lower.shape[0]
    rows, cols = _iotas(c)
    # the blocks of side SUBSTITUTED = 2 by forward substitution: one row
    inverse = (rows == cols).astype(jnp.float32) - jnp.where(
        level == 1, lower, 0.0)
    s = SUBSTITUTED
    while s < c:
        quarter = jnp.where(level == s, lower, 0.0)
        whole = s % SUBLANES or c % (2 * s)
        if whole:
            low = inverse
        else:
            # only the lower half of every block of 2s changes, and those
            # rows are whole sublane tiles: half the rows through the unit
            halves = inverse.reshape(c // (2 * s), 2, s, c)
            low = halves[:, 1].reshape(c // 2, c)
        first = _dot(low, quarter, NN)
        yield
        low = low - _dot(first, inverse, NN)
        yield
        inverse = low if whole else jnp.concatenate(
            [halves[:, :1], low.reshape(c // (2 * s), 1, s, c)],
            axis=1).reshape(c, c)
        s *= 2
    return inverse


def _in_step(bodies):
    """Trace the generators BREADTH first, each up to its next ``yield``:
    a chunk's products wait for each other (twelve in a row through the
    inverse), the chunks of a tile do not, and the kernel's instructions
    are scheduled near the order they were traced in."""
    bodies, done = list(bodies), object()
    while bodies:
        bodies = [body for body in bodies if next(body, done) is not done]


def _row(column, c):
    """``(C, 1)`` -> ``(1, C)``."""
    return jnp.broadcast_to(column, (c, c)).T[:1]


def _cumsum(row, c, reverse=False):
    """Along the lanes, as a product with a triangular matrix of ones (the
    matrix unit's rows are 8 at least)."""
    rows, cols = _iotas(c)
    ones = (rows >= cols if reverse else rows <= cols).astype(jnp.float32)
    return _dot(jnp.broadcast_to(row, (8, c)), ones, NN)[:1]


def _gates(g_row, beta_row, c):
    """``gamma`` as a row, ``gamma_i - gamma_j`` masked above the diagonal
    BEFORE the exp (there it is positive and may overflow), and the columns
    ``beta``, ``gamma``, ``gamma_C - gamma``."""
    rows, cols = _iotas(c)
    gamma_row = _cumsum(g_row, c)
    by_col = jnp.broadcast_to(gamma_row, (c, c))         # gamma_j
    by_row = by_col.T                                    # gamma_i
    decay = jnp.exp(jnp.where(rows >= cols, by_row - by_col, -jnp.inf))
    gamma = by_row[:, :1]
    total = by_col[:, c - 1:]                            # gamma_C, (C, 1)
    beta = jnp.broadcast_to(beta_row, (c, c)).T[:, :1]
    return gamma_row, decay, beta, gamma, total - gamma


def _system(k, v, beta, gamma, exact):
    """``k beta`` (float32), the right-hand side ``[beta v | beta k
    e^gamma]`` and ``(k beta) k^T``."""
    k_beta = k.astype(jnp.float32) * beta
    rhs = jnp.concatenate([v.astype(jnp.float32) * beta,
                           k_beta * jnp.exp(gamma)], axis=1)
    return k_beta, rhs, _dot(k_beta.astype(k.dtype), k, NT, exact)


def _forward_chunk(q, k, v, g_row, beta_row, level):
    """A generator (see :func:`_in_step`); returns the chunk's results."""
    c, dv = k.shape[0], v.shape[1]
    exact = k.dtype == jnp.float32
    gamma_row, decay, beta, gamma, left = _gates(g_row, beta_row, c)
    _, rhs, gram = _system(k, v, beta, gamma, exact)
    yield
    inverse = yield from _inverse(gram * decay, level)
    solved = _dot(inverse, rhs, NN)
    within = _dot(q, k, NT, exact) * decay
    q_in = q.astype(jnp.float32) * jnp.exp(gamma)
    k_out = k.astype(jnp.float32) * jnp.exp(left)
    return (solved[:, :dv], solved[:, dv:], within, q_in, k_out, gamma_row,
            inverse)


def _backward_chunk(q, k, v, g_row, beta_row, inverse, d_u, d_w, d_within,
                    d_q_in, d_k_out, d_gamma_row):
    """A generator (see :func:`_in_step`); returns ``dq, dk, dv, dg,
    dbeta``."""
    c, dv = k.shape[0], v.shape[1]
    dt = k.dtype
    exact = dt == jnp.float32
    f32 = lambda x: x.astype(jnp.float32)
    lanes = lambda x: jnp.sum(x, axis=1, keepdims=True)        # (C, 1)
    _, decay, beta, gamma, left = _gates(g_row, beta_row, c)
    k_beta, rhs, gram = _system(k, v, beta, gamma, exact)
    q32, k32, v32 = f32(q), f32(k), f32(v)
    grow, fade = jnp.exp(gamma), jnp.exp(left)

    # [u | w] = T rhs
    d_solved = jnp.concatenate([d_u, f32(d_w)], axis=1)
    turned = inverse.T
    d_rhs = _dot(turned, d_solved, NN)
    d_inverse = _dot(d_solved, rhs, NT)
    yield
    # T = (I + A)^-1, A = strict_tril(gram . decay)
    d_lower = _dot(turned, d_inverse, NN)
    yield
    rows, cols = _iotas(c)
    d_lower = jnp.where(rows > cols, -_dot(d_lower, turned, NN), 0.0)
    yield
    # within = (q k^T) . decay
    d_scores = f32(d_within)
    d_decay = d_lower * gram + d_scores * _dot(q, k, NT, exact)
    d_gram = (d_lower * decay).astype(dt)
    d_scores = (d_scores * decay).astype(dt)
    # decay = exp(gamma_i - gamma_j): zero above the diagonal, so is this
    d_exponent = d_decay * decay
    d_gamma = lanes(d_exponent)
    d_gamma_row = d_gamma_row - jnp.sum(d_exponent, axis=0, keepdims=True)

    d_k_grow = d_rhs[:, dv:]                       # of k beta e^gamma
    d_k_beta = _dot(d_gram, k, NN, exact) + d_k_grow * grow
    d_v_beta = d_rhs[:, :dv]
    d_k = (_dot(d_gram, k_beta.astype(dt), TN, exact)
           + _dot(d_scores, q, TN, exact)
           + d_k_beta * beta + f32(d_k_out) * fade)
    d_q = _dot(d_scores, k, NN, exact) + f32(d_q_in) * grow
    d_v = d_v_beta * beta
    d_beta = lanes(d_k_beta * k32) + lanes(d_v_beta * v32)
    # e^gamma scales k beta (in rhs) and q; e^(gamma_C - gamma) scales k
    d_fade = lanes(f32(d_k_out) * k32) * fade
    d_gamma = d_gamma + (lanes(d_k_grow * k_beta)
                         + lanes(f32(d_q_in) * q32)) * grow - d_fade
    last = (jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1)
    d_gamma_row = (d_gamma_row + _row(d_gamma, c)
                   + jnp.where(last, jnp.sum(d_fade, axis=0, keepdims=True),
                               0.0))
    return (d_q, d_k, d_v, _cumsum(d_gamma_row, c, reverse=True),
            _row(d_beta, c))


# ---- the kernels ---------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *out_refs):
    """``out_refs``: u, w, within, q e^gamma, k e^(gamma_C - gamma), gamma
    and, where the backward follows, the inverse."""
    tile, c = out_refs[0].shape[0], out_refs[0].shape[1]
    level = _levels(c)

    def chunk(t):
        tokens = pl.ds(t * c, c)
        outs = yield from _forward_chunk(
            q_ref[tokens, :], k_ref[tokens, :], v_ref[tokens, :], g_ref[t],
            beta_ref[t], level)
        for ref, out in zip(out_refs, outs):
            ref[t] = out.astype(ref.dtype)

    _in_step(chunk(t) for t in range(tile))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref, du_ref,
                dw_ref, dwithin_ref, dq_in_ref, dk_out_ref, dgamma_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    tile, c = du_ref.shape[0], du_ref.shape[1]

    def chunk(t):
        tokens = pl.ds(t * c, c)
        d_q, d_k, d_v, d_g, d_beta = yield from _backward_chunk(
            q_ref[tokens, :], k_ref[tokens, :], v_ref[tokens, :], g_ref[t],
            beta_ref[t], inverse_ref[t], du_ref[t], dw_ref[t],
            dwithin_ref[t], dq_in_ref[t], dk_out_ref[t], dgamma_ref[t])
        dq_ref[tokens, :] = d_q.astype(dq_ref.dtype)
        dk_ref[tokens, :] = d_k.astype(dk_ref.dtype)
        dv_ref[tokens, :] = d_v.astype(dv_ref.dtype)
        dg_ref[t] = d_g
        dbeta_ref[t] = d_beta

    _in_step(chunk(t) for t in range(tile))


def _passes(c, forward):
    """128-deep products a chunk, for the cost estimate: six a float32
    product, one a product in ``dtype``."""
    return 12 * max(c.bit_length() - 2, 0) + 14 if forward else 44


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _call(mode, hk, interpret, q, k, v, g, beta, *cotangents):
    """One ``pallas_call`` over ``(batch, tile of chunks, value head)``.
    ``q, k``: ``(B, S, Hk * dk)``; ``v``: ``(B, S, H * dv)``; ``g, beta``:
    ``(B, H, N, 1, C)`` float32; cotangents in the layouts of the forward's
    results.  Jitted so that a model's layers share one trace and lowering
    of each kernel."""
    b, s, _ = q.shape
    _, h, n, _, c = g.shape
    dt = v.dtype
    dk, dv, r = q.shape[2] // hk, v.shape[2] // h, h // hk
    forward = mode != "backward"
    tile = _tile(n, c, dk, dv, dt.itemsize, forward)
    wide = lambda d, per: pl.BlockSpec(
        (None, tile * c, d), lambda i, j, l: (i, j, l // per))
    gate = pl.BlockSpec((None, None, tile, 1, c),
                        lambda i, j, l: (i, l, j, 0, 0))
    # what the scan reads, chunk-major: (N, B, H, rows, d)
    scanned = lambda rows, d, kind: (
        pl.BlockSpec((tile, None, None, rows, d),
                     lambda i, j, l: (j, i, l, 0, 0)),
        jax.ShapeDtypeStruct((n, b, h, rows, d), kind))
    results = [scanned(c, dv, jnp.float32),       # u
               scanned(c, dk, dt),                # w
               scanned(c, c, dt),                 # within
               scanned(c, dk, dt),                # q e^gamma
               scanned(c, dk, dt),                # k e^(gamma_C - gamma)
               scanned(1, c, jnp.float32)]        # gamma
    inverse = scanned(c, c, jnp.float32)
    in_specs = [wide(dk, r), wide(dk, r), wide(dv, 1), gate, gate]
    if forward:
        kernel, name, outs = _fwd_kernel, "delta_wy_fwd", results
        if mode == "keep":
            outs = outs + [inverse]
    else:
        in_specs += [inverse[0]] + [spec for spec, _ in results]
        per_head = lambda d: jax.ShapeDtypeStruct((b, s, h * d), dt)
        kernel, name = _bwd_kernel, "delta_wy_bwd"
        outs = [(wide(dk, 1), per_head(dk)), (wide(dk, 1), per_head(dk)),
                (wide(dv, 1), per_head(dv)),
                (gate, jax.ShapeDtypeStruct(g.shape, jnp.float32)),
                (gate, jax.ShapeDtypeStruct(g.shape, jnp.float32))]
    arrays = (q, k, v, g, beta) + cotangents
    moved = sum(a.size * a.dtype.itemsize for a in arrays) + sum(
        out.size * out.dtype.itemsize for _, out in outs)
    return pl.pallas_call(
        kernel,
        grid=(b, n // tile, h),
        in_specs=in_specs,
        out_specs=[spec for spec, _ in outs],
        out_shape=[out for _, out in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * h * _passes(c, forward) * c * c * LANES,
            transcendentals=b * n * h * c * (c + 3),
            bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _stage(q, k, v, g, beta, hk, interpret):
    return tuple(_call("forward", hk, interpret, q, k, v, g, beta))


def _stage_fwd(q, k, v, g, beta, hk, interpret):
    *outs, inverse = _call("keep", hk, interpret, q, k, v, g, beta)
    return tuple(outs), (q, k, v, g, beta, inverse)


def _stage_bwd(hk, interpret, residuals, cotangents):
    q, g = residuals[0], residuals[3]
    (b, s, _), h = q.shape, g.shape[1]
    d_q, d_k, d_v, d_g, d_beta = _call("backward", hk, interpret,
                                       *residuals, *cotangents)
    # a key head's cotangent: the sum over the value heads it serves
    shared = lambda x: jnp.sum(
        x.reshape(b, s, hk, h // hk, -1).astype(jnp.float32),
        axis=3).reshape(q.shape).astype(q.dtype)
    if h != hk:
        d_q, d_k = shared(d_q), shared(d_k)
    return d_q, d_k, d_v, d_g, d_beta


_stage.defvjp(_stage_fwd, _stage_bwd)


def within_chunk(q, k, v, g, beta, *, chunk: int, dtype=jnp.float32,
                 interpret: Optional[bool] = None):
    """The within-chunk stage of the chunked gated delta rule.

    ``q, k``: ``(B, S, Hk, d_k)``; ``v``: ``(B, S, H, d_v)``, ``Hk``
    dividing ``H`` (value head ``h`` reads key head ``h // (H / Hk)``);
    ``g, beta``: ``(B, S, H)``; ``chunk`` divides ``S``.  Returns what the
    scan between chunks reads, chunk-major — ``u (N, B, H, C, d_v)``
    float32; ``w``, ``q e^gamma``, ``k e^(gamma_C - gamma)`` ``(N, B, H, C,
    d_k)`` and ``within (N, B, H, C, C)`` in ``dtype``; ``gamma (N, B, H, 1,
    C)`` float32 — differentiable w.r.t. all five inputs."""
    b, s, hk, _ = q.shape
    h, n = v.shape[2], s // chunk
    flat = lambda x: x.astype(dtype).reshape(b, s, -1)
    rows = lambda x: jnp.moveaxis(
        x.astype(jnp.float32).reshape(b, n, chunk, h), 3, 1)[:, :, :, None]
    return _stage(flat(q), flat(k), flat(v), rows(g), rows(beta), hk,
                  ops_common.resolve_interpret(interpret))


# ---- the scan between chunks ---------------------------------------------

MAX_HEADS = 8                    # value heads a program of the scan holds
# float32 values a head holds at once, (forward, backward): (C, d_v) ones
# and (d_k, d_v) ones — the heads are traced in step, so all of them; from
# what the chip's compiler took and refused at heads of 128 and 256 wide
_SCAN_LIVE = ((1, 1), (2, 2))


def _scan_vmem_bytes(chunk: int, dk: int, dv: int, heads: int, itemsize: int,
                     mode: str):
    """A scan kernel's blocks twice — the five operands and ``o``; the kept
    states; the backward's cotangents of all of them — the carried states
    and the heads' temporaries."""
    forward = mode != "backward"
    operands = chunk * (4 * dv + itemsize * (3 * dk + chunk))
    tokens = chunk * dv * itemsize                      # o, or its cotangent
    state = 4 * dk * dv
    blocks = operands + tokens + (0 if mode == "forward" else state) + (
        0 if forward else operands)
    wide, square = _SCAN_LIVE[not forward]
    return heads * (2 * blocks + state
                    + 4 * dv * (wide * chunk + square * dk))


def _heads(h: int, chunk: int, dk: int, dv: int, itemsize: int,
           mode: str) -> int:
    """Value heads a program of the scan holds: the most that divide ``h``
    and fit."""
    return _most(h, MAX_HEADS, lambda heads: _scan_vmem_bytes(
        chunk, dk, dv, heads, itemsize, mode))


def _scan_fwd_kernel(u_ref, w_ref, within_ref, q_in_ref, k_out_ref,
                     decay_ref, o_ref, *rest):
    """One chunk of ``heads`` value heads: ``_chunk_step`` to the letter on
    the states the scratch carries down the column of chunks.  ``rest``:
    where the backward follows, each chunk's INCOMING states; the scratch."""
    *kept, state_ref = rest
    heads, dv, dt = u_ref.shape[0], u_ref.shape[2], w_ref.dtype
    exact = dt == jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def head(h):
        state = state_ref[h]
        for ref in kept:
            ref[h] = state
        held = state.astype(dt)
        delta = u_ref[h] - _dot(w_ref[h], held, NN, exact)
        read = _dot(q_in_ref[h], held, NN, exact)
        yield
        delta = delta.astype(dt)
        out = read + _dot(within_ref[h], delta, NN, exact)
        o_ref[:, h * dv:(h + 1) * dv] = out.astype(o_ref.dtype)
        state_ref[h] = state * decay_ref[h] + _dot(k_out_ref[h], delta, TN,
                                                   exact)

    _in_step(head(h) for h in range(heads))


def _scan_bwd_kernel(u_ref, w_ref, within_ref, q_in_ref, k_out_ref,
                     decay_ref, states_ref, do_ref, du_ref, dw_ref,
                     dwithin_ref, dq_in_ref, dk_out_ref, ddecay_ref,
                     carried_ref):
    """The chunks last to first; ``carried_ref``: the cotangent of the
    states a chunk hands on.  A cotangent that is a product's operand is
    rounded to ``dtype``, as the transpose of ``_chunk_step`` rounds it;
    sums of products stay float32."""
    heads, dv, dt = u_ref.shape[0], u_ref.shape[2], w_ref.dtype
    exact = dt == jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        carried_ref[...] = jnp.zeros_like(carried_ref)

    def head(h):
        state, d_next = states_ref[h], carried_ref[h]
        held, d_handed = state.astype(dt), d_next.astype(dt)
        d_out = do_ref[:, h * dv:(h + 1) * dv]
        delta = u_ref[h] - _dot(w_ref[h], held, NN, exact)
        d_delta = (_dot(within_ref[h], d_out, TN, exact)
                   + _dot(k_out_ref[h], d_handed, NN, exact))
        dq_in_ref[h] = _dot(d_out, held, NT, exact).astype(dt)
        d_state = d_next * decay_ref[h] + _dot(q_in_ref[h], d_out, TN, exact)
        ddecay_ref[h] = jnp.sum(
            jnp.sum(d_next * state, axis=1, keepdims=True), axis=0,
            keepdims=True)
        yield
        du_ref[h] = d_delta
        delta, d_delta = delta.astype(dt), d_delta.astype(dt)
        dw_ref[h] = (-_dot(d_delta, held, NT, exact)).astype(dt)
        dwithin_ref[h] = _dot(d_out, delta, NT, exact).astype(dt)
        dk_out_ref[h] = _dot(delta, d_handed, NT, exact).astype(dt)
        carried_ref[h] = d_state - _dot(w_ref[h], d_delta, TN, exact)

    _in_step(head(h) for h in range(heads))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _scan_program(mode, heads, interpret, u, w, within, q_in, k_out, decay,
                  *rest):
    """One ``pallas_call`` over ``(batch, H / heads, chunk)``, the chunks in
    turn (backward: last to first) on the states a float32 VMEM scratch
    carries.  Operands chunk-major as :func:`_call` writes them; ``rest``:
    the kept states and ``o``'s cotangent."""
    n, b, h, c, dv = u.shape
    dk, dt = w.shape[-1], w.dtype
    forward = mode != "backward"
    at = (lambda l: l) if forward else (lambda l: n - 1 - l)
    per_chunk = lambda rows, d, kind: (
        pl.BlockSpec((None, None, heads, rows, d),
                     lambda i, j, l: (at(l), i, j, 0, 0)),
        jax.ShapeDtypeStruct((n, b, h, rows, d), kind))
    # o: 128-lane column blocks of (B, S, H d_v), as the gated norm reads it
    tokens = (pl.BlockSpec((None, c, heads * dv),
                           lambda i, j, l: (i, at(l), j)),
              jax.ShapeDtypeStruct((b, n * c, h * dv), dt))
    operands = [per_chunk(c, dv, jnp.float32),    # u
                per_chunk(c, dk, dt),             # w
                per_chunk(c, c, dt),              # within
                per_chunk(c, dk, dt),             # q e^gamma
                per_chunk(c, dk, dt),             # k e^(gamma_C - gamma)
                per_chunk(1, 1, jnp.float32)]     # e^gamma_C
    states = per_chunk(dk, dv, jnp.float32)
    if forward:
        kernel, name, ins = _scan_fwd_kernel, "delta_scan_fwd", operands
        outs = [tokens, states] if mode == "keep" else [tokens]
    else:
        kernel, name = _scan_bwd_kernel, "delta_scan_bwd"
        ins, outs = operands + [states, tokens], operands
    arrays = (u, w, within, q_in, k_out, decay) + rest
    moved = sum(a.size * a.dtype.itemsize for a in arrays) + sum(
        out.size * out.dtype.itemsize for _, out in outs)
    return pl.pallas_call(
        kernel,
        grid=(b, h // heads, n),
        in_specs=[spec for spec, _ in ins],
        out_specs=[spec for spec, _ in outs],
        out_shape=[out for _, out in outs],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * h * c * dv * (
                3 * dk + c if forward else 7 * dk + 2 * c),
            transcendentals=0, bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(*arrays)


def _scan_call(mode, interpret, u, w, *rest):
    """:func:`_scan_program` at the most heads that fit."""
    (_, _, h, c, dv), dk = u.shape, w.shape[-1]
    return _scan_program(mode, _heads(h, c, dk, dv, w.dtype.itemsize, mode),
                         interpret, u, w, *rest)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(u, w, within, q_in, k_out, decay, interpret):
    return _scan_call("forward", interpret, u, w, within, q_in, k_out,
                      decay)[0]


def _scan_fwd(u, w, within, q_in, k_out, decay, interpret):
    operands = (u, w, within, q_in, k_out, decay)
    o, states = _scan_call("keep", interpret, *operands)
    return o, operands + (states,)


def _scan_bwd(interpret, residuals, d_o):
    return tuple(_scan_call("backward", interpret, *residuals, d_o))


_scan.defvjp(_scan_fwd, _scan_bwd)


def between_chunks(u, w, within, q_in, k_out, decay, *,
                   interpret: Optional[bool] = None):
    """The recurrence between chunks, ``lax.scan(gated_delta._chunk_step)``
    from a zero state, on what :func:`within_chunk` returns: ``u (N, B, H,
    C, d_v)`` float32; ``w``, ``q_in``, ``k_out`` ``(N, B, H, C, d_k)`` and
    ``within (N, B, H, C, C)`` in ``dtype``; ``decay = exp(gamma[..., -1:])``
    ``(N, B, H, 1, 1)`` float32.  Returns ``o (B, N C, H d_v)`` in
    ``dtype``, a head's columns side by side — differentiable w.r.t. all
    six."""
    return _scan(u, w, within, q_in, k_out, decay.astype(jnp.float32),
                 ops_common.resolve_interpret(interpret))
