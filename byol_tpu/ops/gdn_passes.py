"""Gated DeltaNet's two ELEMENTWISE stages, one pass over HBM each.

Round the delta rule ``models/gated_delta.GatedDeltaNet`` has two stages
that do no product: ``silu(causal_conv(mixed, taps))`` in front of it and
``rmsnorm(out) * gain * silu(gate)`` behind it.  As ``jax.numpy`` the TPU
compiler made every tap, every cast and every reduction a pass of its own
over a half-gigabyte array — the four taps four shifted slices of a
``jnp.pad`` copy, the norm's float32 ``[B, S, H, d]`` arrays written and read
back: 92 GB a step where the work is 26, 183 ms of a 1,364 ms step in
``qwen3next_train_b4_s4096`` (PERF.md section 5, PR 43).  Here each stage
reads its operands once and writes its result once, forward and backward,
with the float32 arithmetic on a few rows in registers:

- :func:`conv_silu` — kernels ``conv_silu_fwd`` / ``conv_silu_bwd``;
- :func:`gated_norm` — kernels ``gated_norm_fwd`` / ``gated_norm_bwd``;
- :func:`applies` — whether a layer takes them: decided from what the code
  can see, never by a flag.

Design (see /opt/skills/guides/pallas_guide.md):
- a block is a COLUMN block of a sequence, ``(S, lanes)`` of ``[B, S, C]``
  with ``lanes`` a multiple of 128: the whole sequence is in VMEM, so the
  convolution needs no padded copy and no halo, and a value head (``d``
  lanes) has its mean of squares inside the block.  Grid ``(B, C / lanes)``,
  every program independent;
- inside a program a ``lax.fori_loop`` walks the sequence ``CONV_ROWS`` /
  ``NORM_ROWS`` rows a trip (and one shorter trip where they do not divide
  it): Mosaic keeps
  what was traced as one array op one pass over VMEM, so the arithmetic is
  written for a chunk that fits the registers and the chain from the bf16
  load to the bf16 store never leaves them;
- a tap is a shift of the SUBLANES: a chunk is read with the ``HALO`` rows
  before it (one bf16 sublane tile; zeros before the sequence's start), and
  ``x[t - k]`` is ``pltpu.roll`` by ``k`` with the halo's rows dropped.  The
  backward's four anti-causal shifts of ``g silu'(pre)`` walk the sequence
  from its END and carry the 8 rows after the chunk from trip to trip;
- sums over rows — the taps' gradient, the gain's — stay ``(8, lanes)``
  float32 accumulators across a program (adds of whole registers), are
  folded once at its end, and leave as per-program partial sums that one
  small XLA sum finishes;
- operands are read WHERE THEY LIE: ``conv_silu`` convolves the first ``C``
  columns of a wider ``(B, S, W)`` array and ``gated_norm`` takes its gate
  as ``H d`` columns of one from ``column`` on (a block's index is the
  column's), so that ``GatedDeltaNet`` hands both the ONE product ``[q | k |
  v | z]`` and XLA never cuts ``mixed`` or ``gate`` out of it — per key
  head, as ``[B, S, 16, 768]``, that was a relayout of every activation
  (≈ 61 GB a step: PERF.md section 5, PR 43); the backward's cotangent of
  the wide array is the kernel's ``dx`` / ``d_gate`` padded with zeros,
  which XLA adds as one pass;
- float32 arithmetic throughout, rounded ONCE to the operands' dtype on the
  way out.  The convolution's sum, which ``causal_conv`` makes in the compute
  dtype from taps rounded to it, is float32 over float32 taps here;
- ``jax.custom_vjp``: the residuals are the operands (``x, taps``; ``out,
  gate, gain``), the backward forms the pre-activation / the row's ``rsqrt``
  again in registers.

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
from byol_tpu.ops.common import LANES

# Rows a trip of a kernel's loop (chip runs, PR 43, ms a call at the cell's
# size, forward | backward): the convolution at 16 / 32 / 64 / 128 rows 2.81 |
# 4.00, 1.96 | 3.19, 1.73 | 3.18, 1.79 | 3.43 (256 lanes: past 64 rows a
# chunk's float32 values outgrow the registers); the norm at 16 / 32 / 64 /
# 128 / 256 / 512 rows 3.55 | 12.51, 1.99 | 6.23, 1.35 | 3.60, 1.32 | 2.35,
# 1.30 | 2.14, 1.27 | 2.03 (a trip is one chain from the load through two
# lane reductions to the store, and trips do not overlap: the more rows,
# the more of it runs side by side, spills and all).
CONV_ROWS = 64
NORM_ROWS = 512
TAPS = 4            # the convolution's taps: what every published config has
HALO = 16           # rows read before a chunk: one bf16 sublane tile
SUBLANES = 8        # rows of a float32 register; the backward's carried rows
# What a program may take of VMEM: the compiler's default scope, 16 MiB of
# the 128 a v5e holds, as ops/delta_rule.py (no ``vmem_limit_bytes`` is
# asked for).  A kernel's blocks twice (double buffering) have to fit with
# room for its partial sums and Mosaic's own scratch.
VMEM_BYTES = 16 * 2 ** 20
_SPARE_BYTES = 2 * 2 ** 20
# Arrays of a block's size a kernel holds: (forward, backward)
_CONV_ARRAYS = (2, 3)       # x, y | g, x, dx
_NORM_ARRAYS = (3, 5)       # out, gate, y | dy, out, gate, d_out, d_gate


def _lanes(seq: int, channels: int, unit: int, arrays: int,
           itemsize: int) -> int:
    """Lanes of a column block: two ``unit`` or one, the wider that divides
    ``channels`` and whose ``arrays`` blocks fit VMEM twice; 0 if none."""
    for lanes in (2 * unit, unit):
        if channels % lanes == 0 and (
                2 * arrays * seq * lanes * itemsize
                <= VMEM_BYTES - _SPARE_BYTES):
            return lanes
    return 0


def applies(seq: int, channels: int, head: int, dtype=jnp.bfloat16, *,
            taps: int = TAPS, backend: Optional[str] = None) -> bool:
    """Whether a Gated DeltaNet layer of ``seq`` tokens, ``channels``
    convolved channels and value heads ``head`` wide runs its two elementwise
    stages as the kernels — decided from what the code can see, never by a
    flag: the program lowers for a TPU (``backend``: the tests' way to ask
    for another), the dtype is one the kernels round to, the taps are the
    ``TAPS`` every published config has, the sequence is whole bf16 sublane
    tiles, channels and a head fill whole 128-lane tiles, and the backward's
    blocks fit VMEM."""
    backend = jax.default_backend() if backend is None else backend
    kind = jnp.dtype(dtype)
    return (backend == "tpu"
            and kind in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and taps == TAPS
            and seq > 0 and seq % HALO == 0 and head > 0 and head % LANES == 0
            and channels > 0 and channels % LANES == 0
            and _lanes(seq, channels, LANES, _CONV_ARRAYS[1],
                       kind.itemsize) > 0
            and _lanes(seq, head, head, _NORM_ARRAYS[1], kind.itemsize) > 0)


# ---- a program's walk over its sequence ------------------------------------

def _over_chunks(seq: int, rows: int, body, carry=None, *,
                 reverse: bool = False):
    """``carry = body(r0, n, carry)`` over the rows ``[r0, r0 + n)`` of the
    sequence, ``rows`` at a time in one traced loop and, where ``rows`` does
    not divide it, one shorter trip at its end; ``reverse``: from the end
    backwards."""
    full, rest = divmod(seq, rows)

    def trip(i, carry):
        i = full - 1 - i if reverse else i
        return body(pl.multiple_of(i * rows, rows), rows, carry)

    if rest and reverse:
        carry = body(full * rows, rest, carry)
    if full:
        carry = jax.lax.fori_loop(0, full, trip, carry)
    if rest and not reverse:
        carry = body(full * rows, rest, carry)
    return carry


def _fold(x):
    """``(rows, lanes) -> (8, lanes)``: the rows added register by register
    (what is left of the sum over rows is one fold at the program's end)."""
    return jnp.sum(x.reshape(-1, SUBLANES, x.shape[-1]), axis=0)


def _silu_slope(x, s):
    """``silu'(x)`` from ``s = sigmoid(x)``."""
    return s * (1.0 + x * (1.0 - s))


# ---- the causal convolution and its SiLU ------------------------------------

def _shifted(x_ref, r0, rows: int, taps: int):
    """``[x[t - k] for k in range(taps)]`` over the rows ``[r0, r0 + rows)``,
    float32: the chunk read with the ``HALO`` rows before it, zeros before
    the sequence's start."""
    start = pl.multiple_of(jnp.maximum(r0 - HALO, 0), HALO)
    before = jnp.where(r0 > 0, x_ref[pl.ds(start, HALO), :], 0)
    whole = jnp.concatenate(
        [before, x_ref[pl.ds(r0, rows), :]], axis=0).astype(jnp.float32)
    return [whole[HALO:]] + [pltpu.roll(whole, k, 0)[HALO:]
                             for k in range(1, taps)]


def _weighted(weights, shifted):
    """``sum_j taps[j] x[t - (K-1) + j]``: tap ``K-1`` meets the current
    token."""
    k = len(weights)
    return sum(weights[j] * shifted[k - 1 - j] for j in range(k))


def _conv_fwd_kernel(x_ref, taps_ref, y_ref):
    seq, k = x_ref.shape[0], taps_ref.shape[0]
    weights = [taps_ref[j:j + 1, :] for j in range(k)]

    def body(r0, rows, carry):
        pre = _weighted(weights, _shifted(x_ref, r0, rows, k))
        y_ref[pl.ds(r0, rows), :] = (
            pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return carry

    _over_chunks(seq, CONV_ROWS, body)


def _conv_bwd_kernel(g_ref, x_ref, taps_ref, dx_ref, dtaps_ref):
    (seq, lanes), k = x_ref.shape, taps_ref.shape[0]
    weights = [taps_ref[j:j + 1, :] for j in range(k)]
    nothing = jnp.zeros((SUBLANES, lanes), jnp.float32)

    def body(r0, rows, carry):
        after, sums = carry     # g silu'(pre) on the 8 rows after the chunk
        shifted = _shifted(x_ref, r0, rows, k)
        pre = _weighted(weights, shifted)
        d_pre = g_ref[pl.ds(r0, rows), :].astype(jnp.float32) * _silu_slope(
            pre, jax.nn.sigmoid(pre))
        # dx[t] = sum_j taps[j] d_pre[t + (K-1) - j]: the shifts the other way
        whole = jnp.concatenate([d_pre, after], axis=0)
        ahead = lambda n: (pltpu.roll(whole, rows + SUBLANES - n, 0)[:rows]
                           if n else d_pre)
        dx_ref[pl.ds(r0, rows), :] = sum(
            weights[j] * ahead(k - 1 - j) for j in range(k)
        ).astype(dx_ref.dtype)
        sums = tuple(sums[j] + _fold(d_pre * shifted[k - 1 - j])
                     for j in range(k))
        return d_pre[:SUBLANES], sums

    _, sums = _over_chunks(seq, CONV_ROWS, body,
                           (nothing, (nothing,) * k), reverse=True)
    dtaps_ref[...] = jnp.concatenate(
        [jnp.sum(part, axis=0, keepdims=True) for part in sums], axis=0)


def _conv_call(forward: bool, interpret: bool, x, taps, g=None):
    (b, s, _), (k, c) = x.shape, taps.shape
    lanes = _lanes(s, c, LANES, _CONV_ARRAYS[not forward], x.dtype.itemsize)
    block = pl.BlockSpec((None, s, lanes), lambda i, j: (i, 0, j))
    weights = pl.BlockSpec((k, lanes), lambda i, j: (0, j))
    like_x = jax.ShapeDtypeStruct((b, s, c), x.dtype)
    if forward:
        kernel, name, arrays = _conv_fwd_kernel, "conv_silu_fwd", (x, taps)
        in_specs, out_specs, out_shape = [block, weights], block, like_x
    else:
        kernel, name, arrays = _conv_bwd_kernel, "conv_silu_bwd", (g, x, taps)
        in_specs = [block, block, weights]
        out_specs = [block, pl.BlockSpec((None, k, lanes),
                                         lambda i, j: (i, 0, j))]
        out_shape = [like_x, jax.ShapeDtypeStruct((b, k, c), jnp.float32)]
    passes = 2 if forward else 3
    return pl.pallas_call(
        kernel,
        grid=(b, c // lanes),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(2 if forward else 6) * (2 * k + 4) * b * s * c,
            transcendentals=b * s * c,
            bytes_accessed=passes * b * s * c * x.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_silu(x, taps, interpret):
    return _conv_call(True, interpret, x, taps)


def _conv_silu_fwd(x, taps, interpret):
    return _conv_call(True, interpret, x, taps), (x, taps)


def _conv_silu_bwd(interpret, residuals, g):
    x, taps = residuals
    dx, parts = _conv_call(False, interpret, x, taps, g)
    beside = x.shape[-1] - dx.shape[-1]     # columns of x the taps do not meet
    if beside:
        dx = jnp.pad(dx, ((0, 0), (0, 0), (0, beside)))
    return dx, jnp.sum(parts, axis=0)       # over the programs of a column


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x, taps, *, interpret: Optional[bool] = None):
    """``silu(y)`` with ``y[t] = sum_j taps[j] x[t - (K-1) + j]``, nothing
    before the sequence's start: ``models/gated_delta.causal_conv`` and its
    activation as one pass.  ``x``: ``(B, S, W)``, of which the FIRST ``C``
    columns are convolved and read where they lie (``W - C`` a multiple of
    128); ``taps``: ``(TAPS, C)`` float32, used as they are; the result ``(B,
    S, C)`` in ``x``'s dtype, the sum and the activation float32.
    Differentiable w.r.t. both (``d taps`` float32; ``dx`` zero beside the
    convolved columns)."""
    if taps.shape[0] != TAPS:       # what the halo and the carried rows are for
        raise ValueError(f"conv_silu: {taps.shape[0]} taps, not {TAPS}")
    return _conv_silu(x, taps.astype(jnp.float32),
                      ops_common.resolve_interpret(interpret))


# ---- the gated RMS norm -----------------------------------------------------

def _heads(ref, head: int):
    return [slice(h * head, (h + 1) * head)
            for h in range(ref.shape[1] // head)]


def _unit_rows(out, eps: float):
    """A head's rows over their root mean square, and its inverse."""
    inverse = jax.lax.rsqrt(
        jnp.mean(jnp.square(out), axis=-1, keepdims=True) + eps)
    return out * inverse, inverse


def _norm_fwd_kernel(out_ref, gate_ref, gain_ref, y_ref, *, eps: float):
    gain = gain_ref[...]

    def body(r0, rows, carry):
        at = pl.ds(r0, rows)
        for cols in _heads(out_ref, gain.shape[1]):
            unit, _ = _unit_rows(out_ref[at, cols].astype(jnp.float32), eps)
            gate = gate_ref[at, cols].astype(jnp.float32)
            y_ref[at, cols] = (
                unit * gain * (gate * jax.nn.sigmoid(gate))
            ).astype(y_ref.dtype)
        return carry

    _over_chunks(out_ref.shape[0], NORM_ROWS, body)


def _norm_bwd_kernel(dy_ref, out_ref, gate_ref, gain_ref, d_out_ref,
                     d_gate_ref, d_gain_ref, *, eps: float):
    gain = gain_ref[...]
    heads = _heads(out_ref, gain.shape[1])
    nothing = jnp.zeros((SUBLANES, gain.shape[1]), jnp.float32)

    def body(r0, rows, sums):
        at, grown = pl.ds(r0, rows), []
        for cols, held in zip(heads, sums):
            unit, inverse = _unit_rows(
                out_ref[at, cols].astype(jnp.float32), eps)
            gate = gate_ref[at, cols].astype(jnp.float32)
            s = jax.nn.sigmoid(gate)
            act = gate * s                          # silu(gate)
            dy = dy_ref[at, cols].astype(jnp.float32)
            d_unit = dy * gain * act
            d_out_ref[at, cols] = (inverse * (d_unit - unit * jnp.mean(
                d_unit * unit, axis=-1, keepdims=True))
            ).astype(d_out_ref.dtype)
            through = dy * unit                     # what the gate scales
            d_gate_ref[at, cols] = (
                through * gain * _silu_slope(gate, s)
            ).astype(d_gate_ref.dtype)
            grown.append(held + _fold(through * act))
        return tuple(grown)

    sums = _over_chunks(out_ref.shape[0], NORM_ROWS, body,
                        (nothing,) * len(heads))
    d_gain_ref[...] = jnp.concatenate(
        [jnp.sum(part, axis=0, keepdims=True) for part in sums], axis=1)


def _norm_call(forward: bool, eps: float, column: int, interpret: bool, out,
               gate, gain, dy=None):
    """``gate``: ``(B, S, W)``, the heads' ``H d`` columns from ``column``
    on, read where they lie."""
    b, s, h, d = out.shape
    lanes = _lanes(s, h * d, d, _NORM_ARRAYS[not forward], out.dtype.itemsize)
    while column % lanes:       # the gate's blocks start on a block's edge
        lanes //= 2
    flat = lambda x: x.reshape(b, s, h * d)
    block = pl.BlockSpec((None, s, lanes), lambda i, j: (i, 0, j))
    beside = pl.BlockSpec((None, s, lanes),
                          lambda i, j: (i, 0, j + column // lanes))
    scale = pl.BlockSpec((1, d), lambda i, j: (0, 0))
    like_out = jax.ShapeDtypeStruct((b, s, h * d), out.dtype)
    if forward:
        kernel, name = _norm_fwd_kernel, "gated_norm_fwd"
        arrays = (flat(out), gate, gain[None])
        in_specs, out_specs, out_shape = [block, beside, scale], block, like_out
    else:
        kernel, name = _norm_bwd_kernel, "gated_norm_bwd"
        arrays = (flat(dy), flat(out), gate, gain[None])
        in_specs = [block, block, beside, scale]
        out_specs = [block, block, pl.BlockSpec((None, 1, lanes),
                                                lambda i, j: (i, 0, j))]
        out_shape = [like_out, like_out,
                     jax.ShapeDtypeStruct((b, 1, h * d), jnp.float32)]
    passes = 3 if forward else 5
    return pl.pallas_call(
        functools.partial(kernel, eps=eps),
        grid=(b, h * d // lanes),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(12 if forward else 30) * out.size,
            transcendentals=out.size * (d + 1) // d,
            bytes_accessed=passes * out.size * out.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated_norm(out, gate, gain, eps, column, interpret):
    return _norm_call(True, eps, column, interpret, out, gate,
                      gain).reshape(out.shape)


def _gated_norm_fwd(out, gate, gain, eps, column, interpret):
    y = _norm_call(True, eps, column, interpret, out, gate, gain)
    return y.reshape(out.shape), (out, gate, gain)


def _gated_norm_bwd(eps, column, interpret, residuals, dy):
    out, gate, gain = residuals
    d_out, d_gate, parts = _norm_call(False, eps, column, interpret, out,
                                      gate, gain, dy)
    after = gate.shape[-1] - column - d_gate.shape[-1]
    if column or after:         # zero beside the heads' columns
        d_gate = jnp.pad(d_gate, ((0, 0), (0, 0), (column, after)))
    # over the programs and the heads
    d_gain = jnp.sum(parts.reshape(-1, gain.shape[0]), axis=0)
    return d_out.reshape(out.shape), d_gate, d_gain


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def gated_norm(out, gate, gain, eps: float, *, column: int = 0,
               interpret: Optional[bool] = None):
    """``rmsnorm(out) * gain * silu(gate)`` per head as one pass
    (``models/gated_delta.gated_rms_norm``).  ``out``: ``(B, S, H, d)``;
    ``gate``: ``(B, S, W)`` with the heads' ``H d`` columns from ``column``
    (whole heads) on, read where they lie; ``gain``: ``(d,)`` float32; the
    result in ``out``'s dtype, the statistics and the products float32.
    Differentiable w.r.t. all three (``d gain`` float32; ``d gate`` zero
    beside the heads' columns)."""
    _, _, h, d = out.shape
    if column % d or column + h * d > gate.shape[-1]:
        raise ValueError(f"gated_norm: {h * d} columns from {column} of "
                         f"{gate.shape}")
    return _gated_norm(out, gate.astype(out.dtype), gain.astype(jnp.float32),
                       float(eps), column,
                       ops_common.resolve_interpret(interpret))
