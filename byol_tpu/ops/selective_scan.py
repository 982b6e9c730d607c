"""Mamba's selective scan — a forward and a backward kernel that keep the
state on the chip.

``selective_scan(u, delta, a, b, c, d)`` is the recurrence of a Mamba-1
layer (arXiv 2312.00752, section 3) over ``[B, S, C]`` rows with a state of
``N`` per channel::

    H_t = exp(delta_t (x) A) * H_{t-1} + (delta_t * u_t) (x) B_t     H_0 = 0
    y_t = H_t C_t + D * u_t                       H: [C, N], A: [C, N] < 0

The decay differs by channel AND state index, so no chunk of it is a matrix
product: it is elementwise work, one ``exp`` and five multiply-adds a state
element and step.  Written out in XLA the states are ``[B, S, C, N]``
float32 — 10.7 GB a layer at 32,768 positions of 5,120 channels and 16
states — and the program cannot be built.  Here ``H`` lives in VMEM down a
row and only the states at the chunks' borders reach HBM (``S / CHUNK``
of them: 21 MB a row of 8,192), for the backward to start each chunk from.

Design (see /opt/skills/guides/pallas_guide.md):
- grid ``(B, S / CHUNK, C / lanes)``, a row's chunks in order (backward:
  last to first) and a chunk's column blocks of ``lanes`` channels
  innermost: the state of the whole row, ``[C / lanes, N, lanes]`` float32
  (320 KB), is a VMEM scratch from chunk to chunk, a program takes its
  column block of it — the channels on the LANES, the state index on the
  SUBLANES, 128 channels' state two registers at ``N = 16``.  The column
  blocks innermost, because ``B_t`` and ``C_t`` are one block for all of
  them (fetched once a chunk) and their cotangents one resident block the
  column blocks add into;
- inside a chunk a ``lax.fori_loop`` walks the time ``ROWS`` steps a trip:
  one aligned ``(16, lanes)`` load of ``u`` and ``delta`` (a bfloat16
  sublane tile), the steps of a trip unrolled, a step's row a static
  sublane slice broadcast down the state's sublanes; the trip's sixteen
  output rows are gathered under a sublane iota and stored as one aligned
  tile;
- ``B_t`` and ``C_t`` weigh the state's ROWS: they come as ``[B, S, N,
  128]``, broadcast along the lanes outside (a ``[.., N, 1]`` array is as
  wide in HBM's tiled layout), so a step reads them as whole ``(N, 128)``
  tiles and the kernel makes no lane broadcast, no transposition and no
  cross-lane reduction at all: the only reductions are over the SUBLANES
  (``y_t``, and backward the two sums over the state index);
- backward: a chunk recomputes its states from its border state into a VMEM
  scratch ``[CHUNK, N, lanes]`` (the state BEFORE every step), then walks
  the steps last to first with ``G``, the cotangent of the state handed
  back, in a scratch of its own from chunk to chunk.  ``d B_t`` and ``d
  C_t`` are sums over ALL channels: a program adds its ``lanes / 128``
  lane groups into the chunk's resident ``(CHUNK, N, 128)`` block, which
  the column blocks share, and one small XLA sum over the lanes finishes
  them; ``d A`` accumulates over a row's chunks in the row's resident
  output block and is summed over the rows outside;
- ``D * u`` and its cotangents are one fused pass outside the kernels;
- ``delta``, ``A``, the state and every sum are float32 (the bfloat16
  policy keeps them so); ``u``, ``B``, ``C`` and ``y`` cross HBM in
  ``u``'s own dtype — bfloat16 under that policy, as the layer makes them —
  and are widened in registers: as float32 arrays the kernels' operands
  were 4.9 GiB of a step's memory at 32,768 positions of 5,120 channels.

Two lowerings of one arithmetic, chosen from what the code can see
(:func:`applies`): the kernels where the program lowers for a TPU and the
shapes fit; everywhere else (the CPU, odd shapes) :func:`chunked_scan`, a
``lax.scan`` over chunks under ``jax.checkpoint`` with a ``lax.scan`` over
a chunk's steps inside — plain ``jax.numpy``, the memory of one chunk's
states.  ``interpret=True`` (default off-TPU) runs the kernels under the
Pallas interpreter so CPU tests exercise identical code paths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byol_tpu.ops.common import LANES, VMEM_LIMIT_BYTES, resolve_interpret

CHUNK = 128         # steps between two border states
ROWS = 16           # steps a trip of a kernel's loop: one bfloat16 tile
MAX_LANES = 512     # channels a program holds: 8 registers of state at N 16
SUBLANES = 8        # rows of a float32 register


def _lanes(channels: int) -> int:
    """Channels a program holds: the most lane tiles up to ``MAX_LANES``
    that divide ``channels``; 0 if none."""
    for lanes in range(MAX_LANES, 0, -LANES):
        if channels % lanes == 0:
            return lanes
    return 0


def _vmem_bytes(chunk: int, channels: int, lanes: int, state: int) -> int:
    """The backward's blocks twice (double buffering) and its scratch, every
    element counted as float32: five blocks of rows, four of ``(N, 128)``
    tiles a step, ``A`` and a border state, the row's ``d A``; a chunk's
    states and what is handed back for the whole row."""
    rows, tiles = 5 * chunk * lanes, 4 * chunk * state * LANES
    small = 2 * state * lanes + state * channels
    return 4 * (2 * (rows + tiles + small)
                + chunk * state * lanes + state * channels)


def supported(channels: int, state: int, chunk: int = CHUNK) -> bool:
    """Shapes the kernels take: channels in whole lane tiles, a state index
    that fills whole float32 sublane tiles, whole trips a chunk."""
    lanes = _lanes(channels)
    return (lanes > 0 and state > 0 and state % SUBLANES == 0
            and chunk > 0 and chunk % ROWS == 0
            and _vmem_bytes(chunk, channels, lanes, state)
            <= VMEM_LIMIT_BYTES)


def applies(channels: int, state: int, *, chunk: int = CHUNK,
            backend: Optional[str] = None) -> bool:
    """Whether :func:`selective_scan` runs as the kernels — decided from
    what the code can see, never by a flag: the program lowers for a TPU
    and the shapes are ones the kernels take."""
    backend = jax.default_backend() if backend is None else backend
    return backend == "tpu" and supported(channels, state, chunk)


# ---- plain jax.numpy ---------------------------------------------------------

def _padded(arrays, chunk: int):
    """Rows filled to whole chunks with ZERO steps: ``delta = 0`` keeps the
    state, ``u = 0`` adds nothing to it, ``C = 0`` reads nothing."""
    s = arrays[0].shape[1]
    fill = -s % chunk
    if not fill:
        return arrays
    return tuple(jnp.pad(x, [(0, 0), (0, fill)] + [(0, 0)] * (x.ndim - 2))
                 for x in arrays)


def chunked_scan(u, delta, a, b, c, *, chunk: int = CHUNK):
    """``y_t = H_t C_t`` of the recurrence above, ``(B, S, C)`` float32:
    a ``lax.scan`` over chunks of ``chunk`` steps, each under
    ``jax.checkpoint`` (its backward holds one chunk's states), a
    ``lax.scan`` over the steps inside.  ``u``, ``delta``: ``(B, S, C)``;
    ``a``: ``(C, N)``; ``b``, ``c``: ``(B, S, N)``."""
    batch, s, channels = u.shape
    u, delta, b, c = _padded((u, delta, b, c), chunk)
    by_chunk = lambda x: jnp.moveaxis(
        x.reshape((batch, -1, chunk) + x.shape[2:]), (1, 2), (0, 1))

    def step(h, row):
        u_t, d_t, b_t, c_t = row
        h = jnp.exp(d_t[..., None] * a) * h + (
            d_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def one(h, rows):
        return jax.lax.scan(step, h, rows)

    _, y = jax.lax.scan(
        one, jnp.zeros((batch, channels, a.shape[1]), jnp.float32),
        tuple(by_chunk(x.astype(jnp.float32)) for x in (u, delta, b, c)))
    y = jnp.moveaxis(y, 2, 0).reshape(batch, -1, channels)
    return y[:, :s]


# ---- the kernels ---------------------------------------------------------------

def _groups(lanes: int):
    return [slice(g * LANES, (g + 1) * LANES) for g in range(lanes // LANES)]


def _f32(x):
    return x.astype(jnp.float32)


def _placed(rows):
    """``ROWS`` rows ``(1, 128)`` as one ``(ROWS, 128)`` tile: each
    broadcast down the sublanes and kept where the sublane's number is its
    own."""
    like = (len(rows), LANES)
    at = jax.lax.broadcasted_iota(jnp.int32, like, 0)
    tile = jnp.zeros(like, jnp.float32)
    for k, row in enumerate(rows):
        tile = jnp.where(at == k, row, tile)
    return tile


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *rest):
    """One chunk of one column block.  ``rest``: where the backward
    follows, the chunk's INCOMING state; the scratch that carries it."""
    *kept, state_ref = rest
    chunk, lanes = u_ref.shape
    groups = _groups(lanes)
    column = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[column] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    for ref in kept:
        ref[...] = state_ref[column]
    decay_of = [a_ref[:, g] for g in groups]

    def trip(i, states):
        t0 = pl.multiple_of(i * ROWS, ROWS)
        us, ds = _f32(u_ref[pl.ds(t0, ROWS), :]), dt_ref[pl.ds(t0, ROWS), :]
        bs, cs = _f32(b_ref[pl.ds(t0, ROWS)]), _f32(c_ref[pl.ds(t0, ROWS)])
        states, outs = list(states), [[] for _ in groups]
        for k in range(ROWS):
            for n, g in enumerate(groups):
                d = ds[k:k + 1, g]
                h = jnp.exp(d * decay_of[n]) * states[n] + (
                    d * us[k:k + 1, g]) * bs[k]
                states[n] = h
                outs[n].append(jnp.sum(h * cs[k], axis=0, keepdims=True))
        for n, g in enumerate(groups):
            y_ref[pl.ds(t0, ROWS), g] = _placed(outs[n]).astype(y_ref.dtype)
        return tuple(states)

    states = jax.lax.fori_loop(0, chunk // ROWS, trip,
                               tuple(state_ref[column, :, g] for g in groups))
    for n, g in enumerate(groups):
        state_ref[column, :, g] = states[n]


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, border_ref, dy_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref, before_ref,
                handed_ref):
    """The chunks of a column block last to first.  ``before_ref``: the
    state BEFORE every step of the chunk, made again from ``border_ref``;
    ``handed_ref``: ``exp(delta_{t+1} A) G_{t+1}``, the cotangent a step
    hands the one before it, from chunk to chunk; ``da_ref``: the whole
    row's, resident over its chunks; ``db_ref``, ``dc_ref``: the chunk's,
    resident over its column blocks."""
    chunk, lanes = u_ref.shape
    groups = _groups(lanes)
    decay_of = [a_ref[:, g] for g in groups]
    column = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        handed_ref[column] = jnp.zeros(handed_ref.shape[1:], jnp.float32)
        da_ref[column] = jnp.zeros(da_ref.shape[1:], jnp.float32)

    @pl.when(column == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    def again(i, states):
        t0 = pl.multiple_of(i * ROWS, ROWS)
        us, ds = _f32(u_ref[pl.ds(t0, ROWS), :]), dt_ref[pl.ds(t0, ROWS), :]
        bs = _f32(b_ref[pl.ds(t0, ROWS)])
        states = list(states)
        for k in range(ROWS):
            for n, g in enumerate(groups):
                before_ref[t0 + k, :, g] = states[n]
                d = ds[k:k + 1, g]
                states[n] = jnp.exp(d * decay_of[n]) * states[n] + (
                    d * us[k:k + 1, g]) * bs[k]
        return tuple(states)

    jax.lax.fori_loop(0, chunk // ROWS, again,
                      tuple(border_ref[:, g] for g in groups))

    def trip(i, carry):
        t0 = pl.multiple_of((chunk // ROWS - 1 - i) * ROWS, ROWS)
        us, ds = _f32(u_ref[pl.ds(t0, ROWS), :]), dt_ref[pl.ds(t0, ROWS), :]
        dys = _f32(dy_ref[pl.ds(t0, ROWS), :])
        bs, cs = _f32(b_ref[pl.ds(t0, ROWS)]), _f32(c_ref[pl.ds(t0, ROWS)])
        handed, d_decay = (list(x) for x in carry)
        d_u, d_dt = ([[None] * ROWS for _ in groups] for _ in range(2))
        for k in reversed(range(ROWS)):
            d_b = d_c = None
            for n, g in enumerate(groups):
                d, u, dy = (x[k:k + 1, g] for x in (ds, us, dys))
                before = before_ref[t0 + k, :, g]
                decay = jnp.exp(d * decay_of[n])
                x = d * u
                state = decay * before + x * bs[k]
                grad = handed[n] + dy * cs[k]          # of H_t, whole
                through = grad * before * decay        # d decay * decay
                to_x = jnp.sum(grad * bs[k], axis=0, keepdims=True)
                d_u[n][k] = to_x * d
                d_dt[n][k] = to_x * u + jnp.sum(
                    through * decay_of[n], axis=0, keepdims=True)
                d_decay[n] = d_decay[n] + through * d
                handed[n] = decay * grad
                part_b, part_c = grad * x, dy * state
                d_b = part_b if d_b is None else d_b + part_b
                d_c = part_c if d_c is None else d_c + part_c
            db_ref[t0 + k] += d_b
            dc_ref[t0 + k] += d_c
        for n, g in enumerate(groups):
            du_ref[pl.ds(t0, ROWS), g] = _placed(d_u[n]).astype(du_ref.dtype)
            ddt_ref[pl.ds(t0, ROWS), g] = _placed(d_dt[n])
        return tuple(handed), tuple(d_decay)

    nothing = jnp.zeros((a_ref.shape[0], LANES), jnp.float32)
    handed, d_decay = jax.lax.fori_loop(
        0, chunk // ROWS, trip,
        (tuple(handed_ref[column, :, g] for g in groups),
         (nothing,) * len(groups)))
    for n, g in enumerate(groups):
        handed_ref[column, :, g] = handed[n]
        da_ref[column, :, g] += d_decay[n]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _call(mode, chunk, interpret, u, delta, a_t, b_wide, c_wide, *rest):
    """One ``pallas_call`` over ``(batch, chunk, C / lanes)``.  ``u``: ``(B,
    S, C)``, ``S`` whole chunks, in the dtype ``y`` and both their
    cotangents take; ``delta``: ``(B, S, C)`` float32; ``a_t``: ``(N, C)``
    float32; ``b_wide``, ``c_wide``: ``(B, S, N, 128)``, their cotangents
    float32; ``rest``, backward: the border states ``(B, S / chunk, N, C)``
    and ``y``'s cotangent."""
    batch, s, channels = u.shape
    state, lanes, n = a_t.shape[0], _lanes(channels), s // chunk
    columns = channels // lanes
    forward = mode != "backward"
    at = (lambda l: l) if forward else (lambda l: n - 1 - l)
    rows = lambda kind: (
        pl.BlockSpec((None, chunk, lanes), lambda i, l, j: (i, at(l), j)),
        jax.ShapeDtypeStruct((batch, s, channels), kind))
    thin, wide = rows(u.dtype), rows(jnp.float32)
    # one block for all the column blocks of a chunk
    tiles = (pl.BlockSpec((None, chunk, state, LANES),
                          lambda i, l, j: (i, at(l), 0, 0)),
             jax.ShapeDtypeStruct((batch, s, state, LANES), jnp.float32))
    decay = pl.BlockSpec((state, lanes), lambda i, l, j: (0, j))
    border = (pl.BlockSpec((None, None, state, lanes),
                           lambda i, l, j: (i, at(l), 0, j)),
              jax.ShapeDtypeStruct((batch, n, state, channels), jnp.float32))
    held = pltpu.VMEM((columns, state, lanes), jnp.float32)
    ins = [thin[0], wide[0], decay, tiles[0], tiles[0]]
    if forward:
        kernel, name = _fwd_kernel, "selective_scan_fwd"
        outs = [thin, border] if mode == "keep" else [thin]
        scratch = [held]
    else:
        kernel, name = _bwd_kernel, "selective_scan_bwd"
        ins += [border[0], thin[0]]
        outs = [thin, wide,
                (pl.BlockSpec((None, columns, state, lanes),
                              lambda i, l, j: (i, 0, 0, 0)),
                 jax.ShapeDtypeStruct((batch, columns, state, lanes),
                                      jnp.float32)),
                tiles, tiles]
        scratch = [pltpu.VMEM((chunk, state, lanes), jnp.float32), held]
    arrays = (u, delta, a_t, b_wide, c_wide) + rest
    elements = batch * s * channels * state
    moved = sum(x.size * x.dtype.itemsize for x in arrays) + sum(
        out.size * out.dtype.itemsize for _, out in outs)
    return pl.pallas_call(
        kernel,
        grid=(batch, n, columns),
        in_specs=ins,
        out_specs=[spec for spec, _ in outs],
        out_shape=[out for _, out in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(6 if forward else 22) * elements,
            transcendentals=(1 if forward else 2) * elements,
            bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(*arrays)


def _wide(x):
    """``(B, S, N) -> (B, S, N, 128)``: a step's weights of the state's
    rows, one value a sublane."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(u, delta, a, b, c, chunk, interpret):
    return _call("forward", chunk, interpret, u, delta, a.T, _wide(b),
                 _wide(c))[0]


def _scan_fwd(u, delta, a, b, c, chunk, interpret):
    y, borders = _call("keep", chunk, interpret, u, delta, a.T, _wide(b),
                       _wide(c))
    return y, (u, delta, a, b, c, borders)


def _scan_bwd(chunk, interpret, residuals, d_y):
    u, delta, a, b, c, borders = residuals
    d_u, d_delta, d_decay, d_b, d_c = _call(
        "backward", chunk, interpret, u, delta, a.T, _wide(b), _wide(c),
        borders, d_y.astype(u.dtype))
    # (B, C / lanes, N, lanes) over the rows -> (C, N)
    d_a = jnp.moveaxis(jnp.sum(d_decay, axis=0), 1, 0).reshape(
        a.shape[1], a.shape[0]).T
    narrow = lambda wide, like: jnp.sum(wide, axis=-1).astype(like.dtype)
    return d_u, d_delta, d_a, narrow(d_b, b), narrow(d_c, c)


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_kernels(u, delta, a, b, c, *, chunk: int = CHUNK,
                 interpret: Optional[bool] = None):
    """:func:`chunked_scan` as the kernels ``selective_scan_fwd`` /
    ``selective_scan_bwd`` (shapes :func:`supported` takes), the result in
    ``u``'s dtype; differentiable w.r.t. all five."""
    s = u.shape[1]
    u, delta, b, c = _padded(
        (u, _f32(delta), b.astype(u.dtype), c.astype(u.dtype)), chunk)
    return _scan(u, delta, _f32(a), b, c, int(chunk),
                 resolve_interpret(interpret))[:, :s]


def selective_scan(u, delta, a, b, c, d, *, chunk: int = CHUNK):
    """``y_t = H_t C_t + D u_t`` of the recurrence in the module docstring,
    ``(B, S, C)`` float32.  ``u``: ``(B, S, C)``; ``delta``: ``(B, S, C)``
    float32, after its softplus; ``a``: ``(C, N)`` float32, negative;
    ``b``, ``c``: ``(B, S, N)``; ``d``: ``(C,)``.  ``S`` need not be whole
    chunks.  The kernels where :func:`applies`, :func:`chunked_scan`
    elsewhere; differentiable w.r.t. all six."""
    channels, state = a.shape
    body = scan_kernels if applies(channels, state, chunk=chunk) \
        else chunked_scan
    return _f32(body(u, delta, a, b, c, chunk=chunk)) + d * _f32(u)
