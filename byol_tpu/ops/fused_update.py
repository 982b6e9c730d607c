"""Pallas fused LARS+EMA weight update over a flat segmented buffer.

BYOL's optimizer step ends in three full-parameter elementwise sweeps, each
a separate HBM round trip over every parameter *and* its optimizer state:
the LARS trust-ratio scaling, the optax momentum/weight-decay update, and
the EMA target tick — exactly the weight-update tax that *Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training* (arXiv
2004.13336) identifies as the non-compute cost of data-parallel training,
and (with the EMA momentum config-derived per arXiv 2307.13813) a chain
whose math is settled enough to fuse.  This module performs the whole
update in ~one pass over a FLAT parameter buffer:

1. every leaf is raveled into one contiguous fp32 buffer viewed as
   ``(rows, 128)`` — 128 = the TPU lane width — with each leaf's segment
   zero-padded to whole rows (:class:`SegmentMap`: leaf -> [start, end)
   offsets, <= 127 pad elements per leaf; the padding maps through the
   entire update chain as zeros and contributes nothing to any norm, the
   same invariance parallel/zero1.py relies on);
2. a **segment-norm pass** (:func:`_segment_norms_kernel`): one grid walk
   computing per-row partial sums of ``|p|^2`` and ``|g + wd*p|^2`` (the
   POST-weight-decay gradient — the norm LARS actually takes,
   optim/lars.py step 1); the tiny per-row partials are segment-summed
   (and, under ZeRO-1, psum'd across shards) into per-layer norms feeding
   :func:`~byol_tpu.optim.lars.trust_ratio_from_norms` — the ONE
   trust-ratio formula shared with the optax transform, so the kernel can
   never apply a different ratio than the chain would;
3. a **fused apply pass** (:func:`_fused_apply_kernel`): per tile, fold
   weight decay into the gradient, scale by the row's segment trust
   ratio, tick the LARS momentum (``m = mu*m + u``), write the new params
   (``p - lr*m``), and tick the EMA target (``tau*t + (1-tau)*p``) — one
   read of (p, g, m, t) and one aliased in-place write of (p, m, t)
   replacing the ~3 full-tree sweeps of the unfused chain.

Grid tiling is DECOUPLED from the segment layout: segments align to rows,
and the grid walks ``(block_rows, 128)`` tiles with per-row ``(R, 1)``
scalar columns (weight decay, trust scale), so tile height is a free
knob.  Off-TPU it defaults to a handful of fat tiles — the Pallas
interpreter's cost scales with GRID STEPS (each step re-stages its
operands), so CPU tier-1 stays fast — while on TPU it defaults to
VMEM-sized tiles (256 rows = 128 KiB per fp32 operand).

Layouts: :func:`fused_lars_ema_update` takes the SHAPED replicated trees
(``--zero1 off``); :func:`fused_lars_ema_update_zero1` takes the flat
leaf-partitioned trees of parallel/zero1.py and runs the kernel
shard-local inside ``shard_map`` — each chip walks only its 1/N of the
buffer, partial segment norms are psum'd over the data axis (identical to
the replicated norms: the flat layout's zero padding is norm-inert), and
the fresh flat params come back still sharded for the step's existing
just-in-time all-gather.

``interpret=`` (default: on iff no TPU backend) runs the same kernels
under the Pallas interpreter so CPU tier-1 exercises the real kernel code
path — the flash_attention.py pattern, enforced tree-wide by graphlint
GL109.

Pack/unpack cost: with ``--flat-resident off`` (the transient layout),
:func:`pack_flat` / :func:`unpack_flat` run per step — a concatenate
feeding an opaque custom call (plus slices of its outputs) materializes
as real copies XLA cannot elide, traffic the unfused chain does not pay
(RESULTS.md carries the matching caveat on the CPU-interpreter rows).
``--flat-resident on`` (parallel/flat_state.py) removes that cost
structurally: the momentum, the EMA target, and (under ZeRO-1) the param
shadow LIVE as resident flat buffers across steps, packed once at setup,
so :func:`fused_lars_ema_update_resident` /
:func:`fused_lars_ema_update_resident_zero1` pack only the fresh
GRADIENTS per step (one concatenate, unavoidable: autodiff emits shaped
leaves) and unpack nothing — state outputs stay buffers, aliased onto
their inputs step over step by the jit donation.  The off/on A/B on
silicon is ``bench.py --resident-ab`` (the TPU capture row ROADMAP.md
tracks); both layouts share :func:`_fused_update_buffers`, so the
resident path can never drift numerically from the transient one
(parity pinned by tests/test_flat_state.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from byol_tpu.optim import lars as lars_lib
from byol_tpu.ops import common as ops_common
# Shared kernel plumbing (ops/common.py): interpret resolution + grid
# sizing are one implementation for every in-tree kernel.  The names are
# re-exported here because this module shipped them first (tests and the
# bench microbenchmark import them from here).
from byol_tpu.ops.common import (LANES as _LANES, TPU_BLOCK_ROWS,
                                 resolve_block_rows)
from byol_tpu.parallel.mesh import DATA_AXIS


# shared shard_map wrapper (ops/common.py)
_shard_map = ops_common.shard_map_unchecked


# ---------------------------------------------------------------------------
# segment map: leaf -> [start, end) offsets in the flat buffer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SegmentMap:
    """Static layout of per-leaf segments inside the flat buffer.

    ``sizes[i]`` real elements of leaf i live at ``[starts[i],
    starts[i] + sizes[i])``; the tail up to ``starts[i] + padded[i]`` is
    zero padding (row alignment, < _LANES elements per leaf), inert under
    every norm and every elementwise update step (``(0, 0) -> 0``).
    Segments tile the buffer exactly: ``starts[i+1] == starts[i] +
    padded[i]`` and ``sum(padded) == total`` (pinned by the
    tests/test_fused_update.py property test).  ``adapted[i]`` is the
    bias/BN exclusion mask slot: False segments get trust ratio 1 and
    weight decay 0 (optim/lars.py ``default_exclusion_mask`` semantics).
    """

    sizes: Tuple[int, ...]
    padded: Tuple[int, ...]
    starts: Tuple[int, ...]
    adapted: Tuple[bool, ...]

    @property
    def total(self) -> int:
        return self.starts[-1] + self.padded[-1] if self.sizes else 0

    @property
    def num_rows(self) -> int:
        return self.total // _LANES

    @property
    def num_segments(self) -> int:
        return len(self.sizes)

    def row_segment_ids(self) -> np.ndarray:
        """(num_rows,) int32: which segment each 128-lane row belongs to —
        well-defined because every segment is row-aligned."""
        return np.repeat(np.arange(self.num_segments, dtype=np.int32),
                         [p // _LANES for p in self.padded])


def build_segment_map(sizes: Sequence[int],
                      adapted: Sequence[bool]) -> SegmentMap:
    """Lay out one flat segment per leaf, each padded to whole rows."""
    if len(sizes) != len(adapted):
        raise ValueError(f"{len(sizes)} sizes vs {len(adapted)} mask slots")
    if any(s <= 0 for s in sizes):
        raise ValueError(f"empty segment in {sizes}")
    padded = tuple(-(-s // _LANES) * _LANES for s in sizes)
    starts = tuple(int(x) for x in np.cumsum((0,) + padded[:-1]))
    return SegmentMap(sizes=tuple(int(s) for s in sizes), padded=padded,
                      starts=starts,
                      adapted=tuple(bool(a) for a in adapted))


def pack_flat(leaves: Sequence[jnp.ndarray], seg: SegmentMap,
              grid_rows: Optional[int] = None) -> jnp.ndarray:
    """Ravel + zero-pad each leaf into its segment; returns the buffer
    viewed as (rows, 128) fp32.  ``grid_rows`` additionally zero-pads the
    buffer tail to a whole number of grid tiles (tail rows belong to no
    segment's real data — zeros, inert like all padding)."""
    parts = []
    for leaf, size, padded in zip(leaves, seg.sizes, seg.padded):
        flat = jnp.ravel(leaf).astype(jnp.float32)
        if flat.size != size:
            raise ValueError(f"leaf has {flat.size} elements, segment map "
                             f"expects {size}")
        if padded != size:
            flat = jnp.pad(flat, (0, padded - size))
        parts.append(flat)
    rows = seg.num_rows if grid_rows is None else grid_rows
    buf = jnp.concatenate(parts)
    tail = rows * _LANES - buf.size
    if tail:
        buf = jnp.pad(buf, (0, tail))
    return buf.reshape(rows, _LANES)


def unpack_flat(buf: jnp.ndarray, seg: SegmentMap,
                templates: Sequence[Any]) -> List[jnp.ndarray]:
    """Slice each segment's real elements back out to its template's
    shape/dtype (the inverse of :func:`pack_flat`; padding is dropped)."""
    flat = buf.reshape(-1)
    outs = []
    for start, size, tmpl in zip(seg.starts, seg.sizes, templates):
        piece = flat[start:start + size]
        outs.append(piece.reshape(tuple(tmpl.shape)).astype(tmpl.dtype))
    return outs


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _segment_norms_kernel(p_ref, g_ref, wd_ref, o_ref):
    """Per-row partial sums of |p|^2 and |g + wd*p|^2 (fp32).

    ``wd`` arrives per row — the row's segment weight decay, 0 for
    excluded bias/BN segments — so the gradient norm is taken AFTER the
    fold-in, the exact tensor the LARS transform norms (optim/lars.py
    steps 1-2).  Output: an (R, 2) column pair per tile; the host
    segment-sums the rows into per-layer norms.
    """
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    gp = g + wd_ref[...] * p                        # wd: (R, 1), broadcast
    o_ref[...] = jnp.concatenate(
        [jnp.sum(p * p, axis=1, keepdims=True),
         jnp.sum(gp * gp, axis=1, keepdims=True)], axis=1)


def _fused_apply_kernel(p_ref, g_ref, m_ref, t_ref, wd_ref, sc_ref, hp_ref,
                        po_ref, mo_ref, to_ref, *, mu: float,
                        ema_pre: bool):
    """One tile of the whole weight update:

    ``u = (g + wd*p) * scale``  (wd fold-in + trust-ratio scaling)
    ``m' = mu*m + u``           (LARS momentum tick, optax.trace)
    ``p' = p - lr*m'``          (inner sgd + apply_updates)
    ``t' = tau*t + (1-tau)*src``(EMA target tick; src = p' or, under
                                 ema_update_mode='reference_pre', p)

    ``wd``/``sc`` are (R, 1) per-row columns (the row's segment weight
    decay and applied trust ratio), ``hp`` the global (1, 2) = (lr, tau)
    pair; ``mu``/``ema_pre`` are trace-time constants.
    """
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    lr = hp_ref[0, 0]
    tau = hp_ref[0, 1]
    u = (g + wd_ref[...] * p) * sc_ref[...]
    m_new = mu * m + u
    p_new = p - lr * m_new
    src = p if ema_pre else p_new
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    to_ref[...] = (t * tau + (1.0 - tau) * src).astype(to_ref.dtype)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return ops_common.resolve_interpret(interpret)


def _fused_update_lists(p_list, g_list, m_list, t_list, lr, tau, *,
                        seg: SegmentMap, weight_decay: float,
                        momentum_decay: float, trust_coefficient: float,
                        eps: float, ema_pre: bool,
                        axis_name: Optional[str],
                        block_rows: Optional[int], interpret: bool):
    """Fused update on lists of (local) leaves: the TRANSIENT layout —
    pack all four trees, run :func:`_fused_update_buffers`, return the
    buffers for the caller to unpack.  ``axis_name`` set means the lists
    are shard-local (inside shard_map) and the segment norms need a psum
    to be global.  Returns (p', m', t', trust_vector) with trust_vector =
    the applied ratios of the ADAPTED segments in tree order (the
    optim/lars.py ``trust_ratio_vector`` contract).
    """
    br = resolve_block_rows(seg.num_rows, interpret, block_rows)
    grid_rows = -(-seg.num_rows // br) * br
    return _fused_update_buffers(
        pack_flat(p_list, seg, grid_rows),
        pack_flat(g_list, seg, grid_rows),
        pack_flat(m_list, seg, grid_rows),
        pack_flat(t_list, seg, grid_rows),
        lr, tau, seg=seg, weight_decay=weight_decay,
        momentum_decay=momentum_decay,
        trust_coefficient=trust_coefficient, eps=eps, ema_pre=ema_pre,
        axis_name=axis_name, block_rows=br, interpret=interpret)


def _fused_update_buffers(p_buf, g_buf, m_buf, t_buf, lr, tau, *,
                          seg: SegmentMap, weight_decay: float,
                          momentum_decay: float, trust_coefficient: float,
                          eps: float, ema_pre: bool,
                          axis_name: Optional[str], block_rows: int,
                          interpret: bool):
    """The kernel core on PACKED ``(grid_rows, 128)`` fp32 buffers.

    Shared verbatim by the transient path (packed per step above) and the
    resident path (buffers live across steps, parallel/flat_state.py) —
    one implementation, so the two layouts cannot drift numerically.
    ``block_rows`` here is the RESOLVED tile height and must divide the
    buffers' row count (the resident layout bakes it in at build time).
    """
    br = block_rows
    grid_rows = p_buf.shape[0]
    if grid_rows % br:
        raise ValueError(
            f"buffer rows {grid_rows} not a multiple of block_rows {br}")
    nblocks = grid_rows // br

    # per-row statics: segment id (grid-tail rows fold into the last
    # segment — their data is zeros, inert everywhere) and weight decay
    # (wd on adapted segments, 0 on excluded — the lars_weight_decay mask)
    row_ids = seg.row_segment_ids()
    if grid_rows != seg.num_rows:
        row_ids = np.concatenate(
            [row_ids, np.full(grid_rows - seg.num_rows,
                              seg.num_segments - 1, np.int32)])
    adapted_np = np.asarray(seg.adapted, bool)
    wd_rows = jnp.asarray(
        np.where(adapted_np[row_ids], np.float32(weight_decay),
                 np.float32(0.0))[:, None])

    tile = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))

    # ---- pass 1: per-row partial norms -> per-segment norms ------------
    row_sums = pl.pallas_call(
        _segment_norms_kernel,
        grid=(nblocks,),
        in_specs=[tile, tile, col],
        out_specs=pl.BlockSpec((br, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid_rows, 2), jnp.float32),
        interpret=interpret,
        name="fused_lars_ema_update_norms",
    )(p_buf, g_buf, wd_rows)
    seg_sums = jax.ops.segment_sum(
        row_sums, jnp.asarray(row_ids),
        num_segments=seg.num_segments, indices_are_sorted=True)
    if axis_name is not None:
        # shard-local partials -> global norms (ZeRO-1: each shard holds
        # 1/N of every segment; zero padding contributes nothing)
        seg_sums = jax.lax.psum(seg_sums, axis_name)
    param_norm = jnp.sqrt(seg_sums[:, 0])
    grad_norm = jnp.sqrt(seg_sums[:, 1])
    ratios = lars_lib.trust_ratio_from_norms(
        param_norm, grad_norm, trust_coefficient, eps)
    scale_seg = jnp.where(jnp.asarray(adapted_np), ratios,
                          jnp.float32(1.0))

    # ---- pass 2: fused apply -------------------------------------------
    sc_rows = scale_seg[jnp.asarray(row_ids)][:, None]
    hp = jnp.stack([jnp.asarray(lr, jnp.float32),
                    jnp.asarray(tau, jnp.float32)]).reshape(1, 2)
    out_struct = jax.ShapeDtypeStruct((grid_rows, _LANES), jnp.float32)
    kernel = functools.partial(_fused_apply_kernel,
                               mu=float(momentum_decay),
                               ema_pre=bool(ema_pre))
    p_out, m_out, t_out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[tile, tile, tile, tile, col, col,
                  pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[tile, tile, tile],
        out_shape=[out_struct, out_struct, out_struct],
        # in-place: the fresh params/momentum/target overwrite the old
        # buffers' HBM — the fused sweep's memory story, not just its
        # bandwidth story
        input_output_aliases={0: 0, 2: 1, 3: 2},
        interpret=interpret,
        name="fused_lars_ema_update_apply",
    )(p_buf, g_buf, m_buf, t_buf, wd_rows, sc_rows, hp)
    trust = ratios[jnp.asarray(np.nonzero(adapted_np)[0])] \
        if adapted_np.any() else jnp.ones((1,), jnp.float32)
    return p_out, m_out, t_out, trust


def _adapted_flags(template_leaves: Sequence[Any]) -> List[bool]:
    """bias/BN exclusion per leaf from the CANONICAL shapes (ndim > 1 —
    ``default_exclusion_mask`` semantics; under ZeRO-1 every live leaf is
    1-D, so the flags must come from the shaped templates)."""
    return [len(tuple(t.shape)) > 1 for t in template_leaves]


def fused_lars_ema_update(params: Any, grads: Any, momentum: Any,
                          target: Any, *, lr, tau, weight_decay: float,
                          momentum_decay: float,
                          trust_coefficient: float = lars_lib.TRUST_COEFFICIENT_DEFAULT,
                          eps: float = lars_lib.LARS_EPS_DEFAULT,
                          ema_pre: bool = False, mesh=None,
                          block_rows: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Fused update on SHAPED replicated trees (``--zero1 off``).

    Returns ``(new_params, new_momentum, new_target, trust_vector)`` with
    the trees in the input layout.  When ``mesh`` spans several devices
    the kernel runs inside a replicated ``shard_map`` (every chip computes
    the identical full update, exactly like the replicated optax chain
    under GSPMD) — pallas_call itself cannot be partitioned by GSPMD.
    """
    interpret = _resolve_interpret(interpret)
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    m_leaves = treedef.flatten_up_to(momentum)
    t_leaves = treedef.flatten_up_to(target)
    seg = build_segment_map(
        [math.prod(l.shape) if l.shape else 1 for l in p_leaves],
        _adapted_flags(p_leaves))

    def run(p_l, g_l, m_l, t_l, lr_, tau_):
        p_buf, m_buf, t_buf, trust = _fused_update_lists(
            p_l, g_l, m_l, t_l, lr_, tau_, seg=seg,
            weight_decay=weight_decay, momentum_decay=momentum_decay,
            trust_coefficient=trust_coefficient, eps=eps,
            ema_pre=ema_pre, axis_name=None, block_rows=block_rows,
            interpret=interpret)
        return (unpack_flat(p_buf, seg, p_l),
                unpack_flat(m_buf, seg, m_l),
                unpack_flat(t_buf, seg, t_l), trust)

    if mesh is not None and math.prod(mesh.shape.values()) > 1:
        rep = P()
        run = _shard_map(run, mesh,
                         in_specs=(rep, rep, rep, rep, rep, rep),
                         out_specs=(rep, rep, rep, rep))
    new_p, new_m, new_t, trust = run(p_leaves, g_leaves, m_leaves,
                                     t_leaves, lr, tau)
    unflatten = jax.tree_util.tree_unflatten
    return (unflatten(treedef, new_p), unflatten(treedef, new_m),
            unflatten(treedef, new_t), trust)


def fused_lars_ema_update_zero1(flat_params: Any, flat_grads: Any,
                                flat_momentum: Any, flat_target: Any, *,
                                param_template: Any, mesh, num_shards: int,
                                lr, tau, weight_decay: float,
                                momentum_decay: float,
                                trust_coefficient: float = lars_lib.TRUST_COEFFICIENT_DEFAULT,
                                eps: float = lars_lib.LARS_EPS_DEFAULT,
                                ema_pre: bool = False,
                                block_rows: Optional[int] = None,
                                interpret: Optional[bool] = None):
    """Fused update on the FLAT leaf-partitioned ZeRO-1 trees.

    Inputs are trees of global flat-padded 1-D leaves sharded
    ``P(data)`` (parallel/zero1.py layout: params/grads through
    ``Zero1Context.shard``, momentum/target straight off the state).
    Inside ``shard_map`` each chip packs its LOCAL slices — every flat
    leaf's shard is ``padded_size/num_shards`` contiguous elements — into
    a shard-local buffer, psums the segment-norm partials over the data
    axis (global trust ratios, identical to the replicated step's: zero
    padding is inert under the norms), and applies the update to its 1/N
    only.  Outputs stay sharded for the step's existing just-in-time
    all-gather; the trust vector is replicated (it is a pure function of
    the psum'd norms).
    """
    from byol_tpu.parallel import zero1 as zero1_lib
    interpret = _resolve_interpret(interpret)
    tmpl_leaves, treedef = jax.tree_util.tree_flatten(param_template)
    seg = build_segment_map(
        [zero1_lib.local_flat_size(t, num_shards) for t in tmpl_leaves],
        _adapted_flags(tmpl_leaves))

    def local(p_l, g_l, m_l, t_l, lr_, tau_):
        p_buf, m_buf, t_buf, trust = _fused_update_lists(
            p_l, g_l, m_l, t_l, lr_, tau_, seg=seg,
            weight_decay=weight_decay, momentum_decay=momentum_decay,
            trust_coefficient=trust_coefficient, eps=eps,
            ema_pre=ema_pre, axis_name=DATA_AXIS, block_rows=block_rows,
            interpret=interpret)
        return (unpack_flat(p_buf, seg, p_l),
                unpack_flat(m_buf, seg, m_l),
                unpack_flat(t_buf, seg, t_l), trust)

    sharded, rep = P(DATA_AXIS), P()
    run = _shard_map(local, mesh,
                     in_specs=(sharded, sharded, sharded, sharded, rep,
                               rep),
                     out_specs=(sharded, sharded, sharded, rep))
    p_leaves = treedef.flatten_up_to(flat_params)
    g_leaves = treedef.flatten_up_to(flat_grads)
    m_leaves = treedef.flatten_up_to(flat_momentum)
    t_leaves = treedef.flatten_up_to(flat_target)
    new_p, new_m, new_t, trust = run(p_leaves, g_leaves, m_leaves,
                                     t_leaves, lr, tau)
    unflatten = jax.tree_util.tree_unflatten
    return (unflatten(treedef, new_p), unflatten(treedef, new_m),
            unflatten(treedef, new_t), trust)


def fused_lars_ema_update_resident(params: Any, grads: Any,
                                   m_buf: jnp.ndarray, t_buf: jnp.ndarray,
                                   *, layout: Any, lr, tau,
                                   weight_decay: float,
                                   momentum_decay: float,
                                   trust_coefficient: float = lars_lib.TRUST_COEFFICIENT_DEFAULT,
                                   eps: float = lars_lib.LARS_EPS_DEFAULT,
                                   ema_pre: bool = False, mesh=None,
                                   interpret: Optional[bool] = None):
    """Fused update with RESIDENT momentum/target buffers, replicated
    layout (``--flat-resident on --zero1 off``).

    ``params``/``grads`` are shaped trees — params stay shaped for the
    forward, and gradients are fresh autodiff outputs, so both are packed
    here per step — while ``m_buf``/``t_buf`` are the resident
    ``(layout.global_size,)`` fp32 buffers (parallel/flat_state.py,
    ``num_shards == 1``) consumed and produced IN PLACE: same shape, same
    sharding, so the jit-level state donation aliases them step over
    step and the momentum/target pack+unpack copies of the transient
    path never happen.  Returns ``(new_params, new_p_buf, new_m_buf,
    new_t_buf, trust_vector)`` — ``new_p_buf`` is the kernel's own packed
    view of the fresh params (no extra compute: it IS the kernel output
    the shaped params are carved from), handed back so telemetry can norm
    the buffer directly.
    """
    interpret = _resolve_interpret(interpret)
    seg, gr, br = layout.seg, layout.grid_rows, layout.block_rows
    p_leaves = layout.treedef.flatten_up_to(params)
    g_leaves = layout.treedef.flatten_up_to(grads)

    def run(p_l, g_l, m_b, t_b, lr_, tau_):
        p_out, m_out, t_out, trust = _fused_update_buffers(
            pack_flat(p_l, seg, gr), pack_flat(g_l, seg, gr),
            m_b.reshape(gr, _LANES), t_b.reshape(gr, _LANES), lr_, tau_,
            seg=seg, weight_decay=weight_decay,
            momentum_decay=momentum_decay,
            trust_coefficient=trust_coefficient, eps=eps, ema_pre=ema_pre,
            axis_name=None, block_rows=br, interpret=interpret)
        return (unpack_flat(p_out, seg, p_l), p_out.reshape(-1),
                m_out.reshape(-1), t_out.reshape(-1), trust)

    if mesh is not None and math.prod(mesh.shape.values()) > 1:
        rep = P()
        run = _shard_map(run, mesh,
                         in_specs=(rep, rep, rep, rep, rep, rep),
                         out_specs=(rep, rep, rep, rep, rep))
    new_p, p_out, m_out, t_out, trust = run(p_leaves, g_leaves, m_buf,
                                            t_buf, lr, tau)
    return (jax.tree_util.tree_unflatten(layout.treedef, new_p), p_out,
            m_out, t_out, trust)


def fused_lars_ema_update_resident_zero1(p_buf: jnp.ndarray,
                                         flat_grads: Any,
                                         m_buf: jnp.ndarray,
                                         t_buf: jnp.ndarray, *,
                                         layout: Any, mesh, lr, tau,
                                         weight_decay: float,
                                         momentum_decay: float,
                                         trust_coefficient: float = lars_lib.TRUST_COEFFICIENT_DEFAULT,
                                         eps: float = lars_lib.LARS_EPS_DEFAULT,
                                         ema_pre: bool = False,
                                         interpret: Optional[bool] = None):
    """Fused update on fully RESIDENT ZeRO-1 buffers (``--flat-resident
    on --zero1 on``).

    ``p_buf`` (the param shadow), ``m_buf``, and ``t_buf`` are resident
    ``(layout.global_size,)`` fp32 buffers sharded ``P(data)`` — each
    device's contiguous chunk is exactly the shard-local packed buffer
    the transient path built per step, so inside ``shard_map`` every chip
    reshapes its chunk to ``(grid_rows, 128)`` (a bitcast, not a copy)
    and runs the identical kernel core.  Only the GRADIENTS are packed
    per step: ``flat_grads`` is the global flat-padded tree from
    ``Zero1Context.shard`` (fresh autodiff leaves — the one unavoidable
    pack).  Segment-norm partials psum over the data axis as in
    :func:`fused_lars_ema_update_zero1`.  Returns ``(new_p_buf,
    new_m_buf, new_t_buf, trust_vector)``, the buffers still sharded and
    shape-identical to their inputs (the step-over-step donation alias).
    """
    interpret = _resolve_interpret(interpret)
    seg, gr, br = layout.seg, layout.grid_rows, layout.block_rows
    g_leaves = layout.treedef.flatten_up_to(flat_grads)

    def local(p_b, g_l, m_b, t_b, lr_, tau_):
        p_out, m_out, t_out, trust = _fused_update_buffers(
            p_b.reshape(gr, _LANES), pack_flat(g_l, seg, gr),
            m_b.reshape(gr, _LANES), t_b.reshape(gr, _LANES), lr_, tau_,
            seg=seg, weight_decay=weight_decay,
            momentum_decay=momentum_decay,
            trust_coefficient=trust_coefficient, eps=eps, ema_pre=ema_pre,
            axis_name=DATA_AXIS, block_rows=br, interpret=interpret)
        return (p_out.reshape(-1), m_out.reshape(-1), t_out.reshape(-1),
                trust)

    sharded, rep = P(DATA_AXIS), P()
    run = _shard_map(local, mesh,
                     in_specs=(sharded, sharded, sharded, sharded, rep,
                               rep),
                     out_specs=(sharded, sharded, sharded, rep))
    return run(p_buf, g_leaves, m_buf, t_buf, lr, tau)
