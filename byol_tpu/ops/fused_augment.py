"""Pallas fused uint8→two-view augmentation — one VMEM round trip per image.

BYOL lives on its two-view augmentation (arXiv 2006.07733), and since the
step-fused input path landed (``--augment-placement step``) that
augmentation runs inside the jitted train step as a chain of ~7 XLA ops
per view — crop resample, flip, color jitter, grayscale, blur — each
sweeping the microbatch's float32 views through HBM.  The extreme-
throughput ImageNet recipes (arXiv 1709.05011) show the input path is what
caps img/s once the model itself is fast; this module collapses the chain
so the step's input tax stops scaling with its length:

1. **All randomness is drawn OUTSIDE the kernel** from the existing
   per-microbatch ``augment_keys`` stream via
   :func:`~byol_tpu.data.device_augment.view_params` — the SAME draw
   functions the unfused path uses, so the two paths share every line that
   could drift.  Host-RNG primitives do not exist inside a Pallas kernel
   body (graphlint GL111); the kernel is a deterministic function of its
   operands.
2. **The crop window math is realized as per-row sampling weights** built
   on the host side of the ``pallas_call`` (:func:`crop_weight_mats`):
   the exact (H, size)/(W, size) separable weight matrices
   ``jax.image.scale_and_translate`` builds internally for
   ``device_augment.apply_crop`` (triangle kernel, antialiased — faithful
   to jax's ``compute_weight_mat``), with the horizontal flip FOLDED into
   the column order of the width matrix (a column permutation — exact).
   The kernel's crop is then two matmuls per view (rows, then columns —
   the order scale_and_translate contracts in), which is both
   bitwise-reproducible against the unfused path on the CPU backend and
   MXU-shaped.
3. **One kernel invocation per image produces BOTH views**
   (:func:`_two_view_kernel`): the raw uint8 image is read once,
   converted to float32/255 in VMEM, and each view's crop-resample, color
   jitter (via the shared ``jitter_planes`` arithmetic), and grayscale
   run per tile without ever materializing an intermediate full-size
   float image in HBM.  The kernel works on channel PLANES laid side by
   side along the lanes (:func:`planar_rows`), never on ``(H, W, 3)``
   blocks: the chip's compiler refused those (PR 22: no uint8 -> float32
   cast, no vector layout for the reshapes around a minor dimension of
   3).  The transposes into and out of that layout are XLA ops on either
   side of the call.
4. **The separable gaussian blur stays an MXU depthwise conv applied to
   the kernel's output** — it is the one op that genuinely wants the MXU
   conv path (and XLA fuses the final clip into its epilogue), so fusing
   it into the VPU kernel would trade a matmul unit for vector ALUs.
   ImageNet input standardization likewise stays where the step applies
   it (``steps.normalize_images``, after the compute-dtype cast): moving
   it into the kernel would reorder it against the bf16 cast and change
   rounding under ``--half``.

Layout/meshes: on a multi-device mesh the ``pallas_call`` runs inside a
``shard_map`` over the data axis (GSPMD cannot partition a pallas_call);
every chip augments only its batch shard, and
the per-image parameter/weight construction before it and the blur after
it are ordinary GSPMD ops.

``interpret=`` (default: on iff no TPU backend) runs the same kernel under
the Pallas interpreter so CPU tier-1 pins fused-vs-unfused equivalence on
the REAL kernel code (GL109).  NB the interpreter dispatches one XLA op
per kernel instruction: CPU timings document mechanism, not speed — the
``bench.py --augment-ab`` TPU row is the perf claim.

Known costs not yet measured on the chip: the per-image weight matrices
are an HBM transient the unfused path does not pay (2 views x (H+W) x
size x 4 B per image ≈ 1.6 MiB at 224px — ~100 MiB per 256-image
microbatch, vs the ~1.2 MiB of float32 views the kernel avoids holding
per chain stage), and the planar layout adds one uint8 transpose before
the call and one float32 transpose per view after it.  If the weight
transient eats the win, the fallback is the 2-tap index/weight form
(exact only for the upsampling crops where ``ch <= size``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from byol_tpu.data import device_augment
from byol_tpu.ops import common as ops_common
from byol_tpu.parallel.mesh import DATA_AXIS

# Per-view scalar-parameter vector layout (the kernel's SMEM table): gates
# ride as 0/1 float32 and are compared > 0.5 in-kernel; the hue angle
# rides as (cos, sin) — a kernel body has no scalar transcendentals.
_JITTER, _FB, _FC, _FS, _COS, _SIN, _GRAY = range(7)
_NPARAM = 7

# jax.image's degenerate-weight threshold (1000 * fp32 eps), hoisted to a
# host-time constant so the traced weight builder touches no numpy.
_WEIGHT_EPS = 1000.0 * float(np.finfo(np.float32).eps)


# ---------------------------------------------------------------------------
# crop window -> separable sampling-weight matrices (host side of the call)
# ---------------------------------------------------------------------------

def _weight_mat(in_size: int, out_size: int, scale, translation):
    """One dimension's (in_size, out_size) resampling weights — faithful
    to ``jax._src.image.scale.compute_weight_mat`` with the triangle
    (bilinear) kernel and antialias=True, which is exactly what
    ``scale_and_translate(..., method='bilinear')`` builds internally.
    Reimplemented (not imported) so the in-tree contract does not hang off
    a private jax symbol; the decomposition test pins equality against
    ``apply_crop`` itself, so drift in a future jax shows up as a test
    failure, not silent skew."""
    dtype = jnp.float32
    inv_scale = 1.0 / scale
    # antialias: widen the kernel when downsampling (scale < 1) so the
    # resample low-pass filters; pure interpolation when upsampling.
    kernel_scale = jnp.maximum(inv_scale, 1.0)
    sample_f = ((jnp.arange(out_size, dtype=dtype) + 0.5) * inv_scale
                - translation * inv_scale - 0.5)
    x = jnp.abs(sample_f[jnp.newaxis, :]
                - jnp.arange(in_size, dtype=dtype)[:, jnp.newaxis]) \
        / kernel_scale
    weights = jnp.maximum(0, 1 - jnp.abs(x))          # triangle kernel
    total = jnp.sum(weights, axis=0, keepdims=True)
    weights = jnp.where(
        jnp.abs(total) > _WEIGHT_EPS,
        jnp.divide(weights, jnp.where(total != 0, total, 1)), 0)
    # zero out samples that fall completely outside the input extent
    return jnp.where(
        jnp.logical_and(sample_f >= -0.5,
                        sample_f <= in_size - 0.5)[jnp.newaxis, :],
        weights, 0)


def crop_weight_mats(p: device_augment.ViewParams, h: int, w: int,
                     size: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Realize one view's crop window as per-row/per-column sampling
    weights: ``(wy, wx)`` of shapes (h, size)/(w, size), with the
    horizontal flip folded into ``wx``'s column order (exact — a column
    permutation commutes with the row contraction and the clip)."""
    sy, sx = size / p.ch, size / p.cw
    wy = _weight_mat(h, size, sy, -p.y0 * sy)
    wx = _weight_mat(w, size, sx, -p.x0 * sx)
    wx = jnp.where(p.flip, wx[:, ::-1], wx)
    return wy, wx


def view_kernel_inputs(keys, h: int, w: int, size: int, strength: float):
    """Per-image kernel operands for ONE view stream: vmap
    :func:`~byol_tpu.data.device_augment.view_params` over the key batch
    and pack what the kernel consumes — ``(wy, wx, prm)`` — plus the blur
    gate/sigma the post-kernel conv consumes."""
    def one(key):
        p = device_augment.view_params(key, h, w, strength)
        wy, wx = crop_weight_mats(p, h, w, size)
        prm = jnp.stack([p.jitter.astype(jnp.float32), p.fb, p.fc, p.fs,
                         jnp.cos(p.theta), jnp.sin(p.theta),
                         p.gray.astype(jnp.float32)])
        return wy, wx, prm, p.blur, p.sigma
    return jax.vmap(one)(keys)


# ---------------------------------------------------------------------------
# lane-dense layout (host side of the call)
# ---------------------------------------------------------------------------
#
# The chip's compiler refuses the natural ``(H, W, 3)`` block: a minor
# dimension of 3 sits on 128 lanes, and Mosaic has no layout for the
# reshapes the crop contraction needs around it.  The kernel therefore
# works on channel PLANES laid side by side along the lanes: an image is
# ``(H, 3*Wp)`` with ``Wp`` = W rounded up to the lane width, channel c in
# columns ``[c*Wp, c*Wp + W)`` and zeros after (inert under the
# contraction: the matching rows of ``wx`` are zero too).

def _lane_pad(w: int) -> int:
    return -(-w // ops_common.LANES) * ops_common.LANES


def planar_rows(images: jnp.ndarray) -> jnp.ndarray:
    """``(..., H, W, C)`` -> ``(..., H, C*Wp)`` channel planes side by side
    along the last axis (dtype kept: the uint8 contract stays uint8)."""
    w = images.shape[-2]
    x = jnp.swapaxes(images, -1, -2)                       # (..., H, C, W)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, _lane_pad(w) - w)])
    return x.reshape(x.shape[:-2] + (-1,))


def _pad_wx(wx: jnp.ndarray) -> jnp.ndarray:
    """Zero rows for the lane padding of :func:`planar_rows`."""
    w = wx.shape[-2]
    return jnp.pad(wx, [(0, 0)] * (wx.ndim - 2)
                   + [(0, _lane_pad(w) - w), (0, 0)])


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _view_planes(x, wyt, wx, prm, *, hue: bool):
    """One view's op chain on a loaded ``(h, 3*wp)`` float32 planar image:
    crop-resample as two matmuls + clip, then the gated jitter/grayscale
    arithmetic — pure jnp, shared by the kernel body and (through
    :func:`_view_pipeline`) the decomposition tests.  ``wyt``: (size, h);
    ``wx``: (wp, size); ``prm``: the seven scalars of the layout above.
    Returns the three (size, size) planes."""
    size, wp = wyt.shape[0], wx.shape[0]
    hi = jax.lax.Precision.HIGHEST
    # scale_and_translate contracts rows first, all channels at once, then
    # columns; the same order here (channels side by side on the lanes for
    # the row contraction, stacked on the sublanes for the column one)
    # reproduces device_augment.apply_crop bit-for-bit on the CPU backend
    rows = jnp.dot(wyt, x, precision=hi)                   # (size, 3*wp)
    stacked = jnp.concatenate(
        [rows[:, c * wp:(c + 1) * wp] for c in range(3)], axis=0)
    crop = jnp.clip(jnp.dot(stacked, wx, precision=hi), 0.0, 1.0)
    r, g, b = (crop[c * size:(c + 1) * size] for c in range(3))
    jittered = device_augment.jitter_planes(
        r, g, b, prm[_FB], prm[_FC], prm[_FS], prm[_COS], prm[_SIN],
        hue=hue)
    jitter_on = prm[_JITTER] > 0.5
    r, g, b = (jnp.where(jitter_on, j, c)
               for j, c in zip(jittered, (r, g, b)))
    lum = device_augment.luminance(r, g, b)
    gray_on = prm[_GRAY] > 0.5
    return tuple(jnp.where(gray_on, lum, c) for c in (r, g, b))


def _view_pipeline(img, wy, wx, prm, *, hue: bool):
    """:func:`_view_planes` on an ``(h, w, c)`` float32 image with
    ``view_kernel_inputs``-shaped operands — the decomposition tests call
    this with forced gates so an equivalence failure names the op."""
    planes = _view_planes(planar_rows(img), wy.T, _pad_wx(wx), prm,
                          hue=hue)
    return jnp.stack(planes, axis=-1)


def _two_view_kernel(prm_ref, img_ref, wyt_ref, wx_ref, o1_ref, o2_ref, *,
                     uint8_in: bool, hue: bool):
    """One image -> both pre-blur views.

    The uint8 source is read ONCE and converted to float32/255 in VMEM
    (widened through int32: Mosaic has no uint8 -> float32 cast); each
    view then runs :func:`_view_planes` on it.  Scalars come from the
    prefetched SMEM table.  No randomness in here (GL111): every
    stochastic choice arrived as an operand.
    """
    i = pl.program_id(0)
    x = img_ref[0]
    if uint8_in:
        x = x.astype(jnp.int32).astype(jnp.float32) / 255.0
    size = wyt_ref.shape[2]
    for view, out_ref in ((0, o1_ref), (1, o2_ref)):
        base = (i * 2 + view) * _NPARAM
        prm = [prm_ref[base + k] for k in range(_NPARAM)]
        planes = _view_planes(x, wyt_ref[0, view], wx_ref[0, view], prm,
                              hue=hue)
        for c, plane in enumerate(planes):
            out_ref[0, c * size:(c + 1) * size, :] = plane


def _call_kernel(prm, x, wyt, wx, *, uint8_in: bool, hue: bool,
                 interpret: bool):
    """Grid over the (local) batch: one image, both views, per step.
    ``prm`` is the flat ``(n*2*_NPARAM,)`` scalar table (SMEM, scalar
    prefetch); outputs are ``(n, 3*size, size)`` stacked planes."""
    n, h, lanes = x.shape
    size, wp = wyt.shape[2], wx.shape[2]
    out_struct = jax.ShapeDtypeStruct((n, 3 * size, size), jnp.float32)
    kernel = functools.partial(_two_view_kernel, uint8_in=uint8_in,
                               hue=hue)
    out_spec = pl.BlockSpec((1, 3 * size, size), lambda i, prm: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, h, lanes), lambda i, prm: (i, 0, 0)),
                pl.BlockSpec((1, 2, size, h),
                             lambda i, prm: (i, 0, 0, 0)),
                pl.BlockSpec((1, 2, wp, size),
                             lambda i, prm: (i, 0, 0, 0)),
            ],
            out_specs=[out_spec, out_spec]),
        out_shape=[out_struct, out_struct],
        interpret=interpret,
        name="fused_two_view",
    )(prm, x, wyt, wx)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

def fused_two_view(key, images: jnp.ndarray, size: int, *,
                   strength: float = 1.0, mesh=None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in fused replacement for
    :func:`~byol_tpu.data.device_augment.two_view`: same key stream, same
    augmentation distribution, views matching the unfused program to fp32
    tolerance (crop/flip exact; pinned by tests/test_fused_augment.py).

    ``images``: (B, H, W, C) uint8 (the step-placement raw contract) or
    float32 [0,1].  ``mesh`` spanning >1 device wraps the kernel in a
    ``shard_map`` over the data axis — required under the jitted step's
    GSPMD partitioning, where the batch arrives sharded.
    """
    interpret = ops_common.resolve_interpret(interpret)
    b, h, w, _ = images.shape
    uint8_in = images.dtype == jnp.uint8
    hue = 0.2 * strength > 0
    k1, k2 = jax.random.split(key)
    per_view = [view_kernel_inputs(jax.random.split(k, b), h, w, size,
                                   strength) for k in (k1, k2)]
    # (B, 2, ...) stacks: one kernel operand per tensor, both views —
    # in the kernel's lane-dense layout (rows pre-transposed, columns
    # lane-padded, scalars as one flat SMEM table)
    wyt = jnp.swapaxes(
        jnp.stack([per_view[0][0], per_view[1][0]], axis=1), -1, -2)
    wx = _pad_wx(jnp.stack([per_view[0][1], per_view[1][1]], axis=1))
    prm = jnp.stack([per_view[0][2], per_view[1][2]], axis=1).reshape(-1)

    call = functools.partial(_call_kernel, uint8_in=uint8_in, hue=hue,
                             interpret=interpret)
    if mesh is not None and math.prod(mesh.shape.values()) > 1:
        # GSPMD cannot partition a pallas_call: run it shard-local over
        # the data axis (augmentation is per-image — no cross-shard data)
        sh = P(DATA_AXIS)
        call = ops_common.shard_map_unchecked(call, mesh,
                                              in_specs=(sh, sh, sh, sh),
                                              out_specs=(sh, sh))

    def nhwc(planes):   # (B, 3*size, size) stacked planes -> (B, s, s, 3)
        return jnp.transpose(planes.reshape(b, 3, size, size),
                             (0, 2, 3, 1))

    v1_pre, v2_pre = map(nhwc, call(prm, planar_rows(images), wyt, wx))

    # blur stays an MXU depthwise conv on the kernel's output; the final
    # clip fuses into its epilogue under XLA
    kblur = int(0.1 * size)

    def tail(v_pre, blur_gate, sigma):
        blurred = jax.vmap(
            lambda im, s: device_augment.apply_gaussian_blur(s, im, kblur)
        )(v_pre, sigma)
        v = jnp.where(blur_gate[:, None, None, None], blurred, v_pre)
        return jnp.clip(v, 0.0, 1.0)

    v1 = tail(v1_pre, per_view[0][3], per_view[0][4])
    v2 = tail(v2_pre, per_view[1][3], per_view[1][4])
    return v1, v2
