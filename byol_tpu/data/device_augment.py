"""On-device (TPU) batched two-view augmentation — the DALI equivalent.

The reference offloads decode+augment to GPUs via NVIDIA DALI when host CPU
can't keep up (``dali_multi_augment_image_folder``,
/root/reference/main.py:356-382; README.md:90-93).  The TPU-native analog:
the host ships raw resized uint8 batches; crop/flip/jitter/grayscale/blur all
run ON CHIP inside one jitted, vmapped program — elementwise work fuses into
the surrounding step, the blur is a depthwise conv on the MXU, and every op
has static shapes (crop windows are realized with
``jax.image.scale_and_translate`` instead of dynamic slicing).

Unlike the reference's DALI path, which silently changes augmentation
hyperparameters (HFlip .2 vs .5, saturation .2s vs .8s, no blur — Quirk Q4,
accuracy caveat README.md:93), this path uses the SAME canonical parameters
as the host pipeline (data/augment.py).

Since ISSUE 14 every stochastic DRAW is factored away from its APPLY
(:func:`crop_window` / :func:`jitter_params` / :func:`blur_sigma` /
:func:`view_params` vs :func:`apply_crop` / :func:`apply_color_jitter` /
:func:`apply_gaussian_blur` / :func:`apply_view`): the fused Pallas
augmentation kernel (ops/fused_augment.py) draws its per-image parameters
from the SAME functions outside the ``pallas_call`` (host-RNG primitives do
not exist in-kernel — graphlint GL111) and applies the same arithmetic
in-kernel, so the two paths share every line that could drift.  The
factoring preserves the key streams and op order exactly (``augment_one``
splits the same key the same way as before), with ONE deliberate
numerical exception: the hue rotation is rewritten scalar-unrolled (a
kernel body cannot capture the constant YIQ matrices), replacing three
einsums with the equivalent per-channel arithmetic — identical math,
fp-rounding-level differences only.  The color arithmetic is written on
three channel PLANES (:func:`jitter_planes`, :func:`luminance`), because
the chip's compiler refuses the kernel on ``(H, W, 3)`` blocks: the
unfused path stacks the planes back into an ``(..., 3)`` image.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


def _uniform(key, lo=0.0, hi=1.0, shape=()):
    return jax.random.uniform(key, shape, minval=lo, maxval=hi)


def crop_window(key, h: int, w: int, scale=(0.08, 1.0),
                ratio=(3 / 4, 4 / 3)):
    """Draw one RandomResizedCrop window: ``(y0, x0, ch, cw)`` fractional
    offsets/extents in source pixels (area in ``scale``·A, log-uniform
    aspect in ``ratio``, clamped to the image — the torchvision
    fallback-to-whole-image analog)."""
    k_area, k_ratio, k_y, k_x = jax.random.split(key, 4)
    area = _uniform(k_area, scale[0], scale[1]) * (h * w)
    log_r = _uniform(k_ratio, jnp.log(ratio[0]), jnp.log(ratio[1]))
    r = jnp.exp(log_r)
    cw = jnp.sqrt(area * r)
    ch = jnp.sqrt(area / r)
    cw = jnp.minimum(cw, w * 1.0)
    ch = jnp.minimum(ch, h * 1.0)
    y0 = _uniform(k_y, 0.0, h - ch)
    x0 = _uniform(k_x, 0.0, w - cw)
    return y0, x0, ch, cw


def apply_crop(image: jnp.ndarray, y0, x0, ch, cw, size: int) -> jnp.ndarray:
    """Map the (fractional) crop window to (size, size) by
    scale_and_translate — no dynamic shapes, so XLA tiles it cleanly."""
    sy, sx = size / ch, size / cw
    out = jax.image.scale_and_translate(
        image, (size, size, image.shape[2]), (0, 1),
        scale=jnp.stack([sy, sx]),
        translation=jnp.stack([-y0 * sy, -x0 * sx]),
        method="bilinear")
    return jnp.clip(out, 0.0, 1.0)


def random_resized_crop(key, image: jnp.ndarray, size: int,
                        scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)
                        ) -> jnp.ndarray:
    """torchvision RandomResizedCrop with static output shape."""
    y0, x0, ch, cw = crop_window(key, image.shape[0], image.shape[1],
                                 scale, ratio)
    return apply_crop(image, y0, x0, ch, cw, size)


def luminance(r, g, b):
    """Luma of three channel planes (the torchvision grayscale weights)."""
    return 0.2989 * r + 0.587 * g + 0.114 * b


def _planes(image):
    return image[..., 0], image[..., 1], image[..., 2]


def apply_grayscale(image: jnp.ndarray) -> jnp.ndarray:
    """Three-channel grayscale (the torchvision RandomGrayscale branch)."""
    lum = luminance(*_planes(image))
    return jnp.stack([lum, lum, lum], axis=-1)


def jitter_params(key, strength: float):
    """Draw the color-jitter factors: multiplicative brightness, blend
    contrast/saturation factors, and the hue angle (inert when
    ``0.2 * strength == 0``)."""
    b = c = s = 0.8 * strength
    hs = 0.2 * strength
    kb, kc, ks, kh = jax.random.split(key, 4)
    fb = _uniform(kb, max(0., 1 - b), 1 + b)
    fc = _uniform(kc, max(0., 1 - c), 1 + c)
    fs = _uniform(ks, max(0., 1 - s), 1 + s)
    theta = _uniform(kh, -hs, hs) * 2.0 * jnp.pi
    return fb, fc, fs, theta


def jitter_planes(r, g, b, fb, fc, fs, cos, sin, *, hue: bool):
    """The color-jitter arithmetic on three channel PLANES with pre-drawn
    factors and the hue angle given as ``(cos, sin)``: brightness/
    contrast/saturation (.8s) + hue (.2s), torch semantics (multiplicative
    brightness; blend-based contrast/saturation).  Planes, not an
    ``(..., 3)`` image, because this function IS the fused augmentation
    kernel's jitter stage (ops/fused_augment.py): on the TPU a minor
    dimension of 3 wastes 125 of 128 lanes and Mosaic refuses the reshapes
    around it, and a kernel body has no scalar ``cos``/``sin``."""
    r, g, b = (jnp.clip(c * fb, 0., 1.) for c in (r, g, b))
    mean = jnp.mean(luminance(r, g, b))
    r, g, b = (jnp.clip(fc * c + (1 - fc) * mean, 0., 1.)
               for c in (r, g, b))
    lum = luminance(r, g, b)
    r, g, b = (jnp.clip(fs * c + (1 - fs) * lum, 0., 1.)
               for c in (r, g, b))
    if hue:
        # hue rotation in YIQ space (equivalent to HSV hue shift, cheaper
        # and branch-free on TPU), scalar-unrolled: a Pallas kernel body
        # cannot capture array constants, scalar coefficients inline fine
        # and the channel mixes stay pure VPU arithmetic
        y = 0.299 * r + 0.587 * g + 0.114 * b
        i = 0.596 * r - 0.274 * g - 0.322 * b
        q = 0.211 * r - 0.523 * g + 0.312 * b
        i, q = cos * i + sin * q, -sin * i + cos * q
        r, g, b = (jnp.clip(c, 0.0, 1.0)
                   for c in (y + 0.956 * i + 0.621 * q,
                             y - 0.272 * i - 0.647 * q,
                             y - 1.106 * i + 1.703 * q))
    return r, g, b


def apply_color_jitter(image: jnp.ndarray, fb, fc, fs, theta, *,
                       hue: bool) -> jnp.ndarray:
    """:func:`jitter_planes` on an ``(..., 3)`` image — the unfused path's
    spelling of the arithmetic the fused kernel shares."""
    return jnp.stack(
        jitter_planes(*_planes(image), fb, fc, fs, jnp.cos(theta),
                      jnp.sin(theta), hue=hue), axis=-1)


def color_jitter(key, image: jnp.ndarray, strength: float) -> jnp.ndarray:
    fb, fc, fs, theta = jitter_params(key, strength)
    return apply_color_jitter(image, fb, fc, fs, theta,
                              hue=0.2 * strength > 0)


def blur_sigma(key, sigma_range=(0.1, 2.0)):
    return _uniform(key, *sigma_range)


def apply_gaussian_blur(sigma, image: jnp.ndarray,
                        kernel_size: int) -> jnp.ndarray:
    """Separable depthwise gaussian blur with a pre-drawn sigma."""
    k = max(int(kernel_size) | 1, 3)
    x = jnp.arange(-(k // 2), k // 2 + 1, dtype=image.dtype)
    g = jnp.exp(-(x ** 2) / (2.0 * sigma ** 2))
    g = g / jnp.sum(g)
    ch = image.shape[-1]
    r = k // 2
    # reflect-101 borders — keeps all three blur backends (tf host, C++
    # host, on-device) border-consistent; zero padding would dim border
    # pixels (see data/augment.py:gaussian_blur).
    img = jnp.pad(image, ((r, r), (r, r), (0, 0)), mode="reflect")[None]
    kx = jnp.tile(g.reshape(1, k, 1, 1), (1, 1, 1, ch))  # HWIO, grouped
    ky = jnp.tile(g.reshape(k, 1, 1, 1), (1, 1, 1, ch))
    dn = jax.lax.conv_dimension_numbers(img.shape, kx.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    img = jax.lax.conv_general_dilated(img, kx, (1, 1), "VALID",
                                       dimension_numbers=dn,
                                       feature_group_count=ch)
    img = jax.lax.conv_general_dilated(img, ky, (1, 1), "VALID",
                                       dimension_numbers=dn,
                                       feature_group_count=ch)
    return img[0]


def gaussian_blur(key, image: jnp.ndarray, kernel_size: int,
                  sigma_range=(0.1, 2.0)) -> jnp.ndarray:
    """Separable depthwise gaussian blur; per-image sigma."""
    return apply_gaussian_blur(blur_sigma(key, sigma_range), image,
                               kernel_size)


class ViewParams(NamedTuple):
    """Every stochastic parameter one view draws, in augment_one's key
    order — the contract the fused kernel path (ops/fused_augment.py)
    consumes OUTSIDE its ``pallas_call``.  A pytree of scalars: vmap over
    a key batch for per-image parameter arrays."""

    y0: jnp.ndarray          # crop window (crop_window)
    x0: jnp.ndarray
    ch: jnp.ndarray
    cw: jnp.ndarray
    flip: jnp.ndarray        # bool gates
    jitter: jnp.ndarray
    fb: jnp.ndarray          # jitter factors (jitter_params)
    fc: jnp.ndarray
    fs: jnp.ndarray
    theta: jnp.ndarray
    gray: jnp.ndarray
    blur: jnp.ndarray
    sigma: jnp.ndarray       # blur sigma (blur_sigma)


def view_params(key, h: int, w: int,
                strength: float = 1.0) -> ViewParams:
    """Draw every parameter of one view from ``key`` — the exact split
    structure augment_one has always used (7-way split; crop/jitter
    subkeys split further inside their draw functions).  Gate and sigma
    draw from independent keys (seed reuse would pin sigma to a
    deterministic function of the gate draw)."""
    ks = jax.random.split(key, 7)
    y0, x0, ch, cw = crop_window(ks[0], h, w)
    fb, fc, fs, theta = jitter_params(ks[3], strength)
    return ViewParams(
        y0=y0, x0=x0, ch=ch, cw=cw,
        flip=_uniform(ks[1]) < 0.5,
        jitter=_uniform(ks[2]) < 0.8,
        fb=fb, fc=fc, fs=fs, theta=theta,
        gray=_uniform(ks[4]) < 0.2,
        blur=_uniform(ks[5]) < 0.5,
        sigma=blur_sigma(ks[6]))


def apply_view(p: ViewParams, image: jnp.ndarray, size: int, *,
               strength: float = 1.0) -> jnp.ndarray:
    """Apply one view's pre-drawn parameters (HWC float32 [0,1] in,
    (size, size, C) float32 out); pure arithmetic — no RNG."""
    v = apply_crop(image, p.y0, p.x0, p.ch, p.cw, size)
    v = jnp.where(p.flip, v[:, ::-1, :], v)
    v = jnp.where(p.jitter,
                  apply_color_jitter(v, p.fb, p.fc, p.fs, p.theta,
                                     hue=0.2 * strength > 0), v)
    v = jnp.where(p.gray, apply_grayscale(v), v)
    v = jnp.where(p.blur, apply_gaussian_blur(p.sigma, v, int(0.1 * size)),
                  v)
    return jnp.clip(v, 0.0, 1.0)


def augment_one(key, image: jnp.ndarray, size: int,
                color_jitter_strength: float = 1.0) -> jnp.ndarray:
    """One view for one image (HWC float32 [0,1]); vmap over the batch."""
    p = view_params(key, image.shape[0], image.shape[1],
                    color_jitter_strength)
    return apply_view(p, image, size, strength=color_jitter_strength)


def two_view(key, images: jnp.ndarray, size: int, *,
             strength: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traceable batched two-view program — the ONE augmentation function
    behind both placements (core/config.py ``augment_placement``): the
    loader path jit-dispatches it standalone (:func:`two_view_batch`) and
    the step-fused path traces it per microbatch inside the train step
    (training/steps.py), so identical keys provably yield identical views.

    images: (B, H, W, C) uint8 or float32 [0,1] -> two (B, size, size, C)
    float32 views.
    """
    if images.dtype == jnp.uint8:
        images = images.astype(jnp.float32) / 255.0
    b = images.shape[0]
    k1, k2 = jax.random.split(key)
    aug = jax.vmap(lambda k, im: augment_one(k, im, size, strength))
    v1 = aug(jax.random.split(k1, b), images)
    v2 = aug(jax.random.split(k2, b), images)
    return v1, v2


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=("strength",))
def two_view_batch(key, images: jnp.ndarray, size: int, *,
                   strength: float = 1.0
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Standalone jitted dispatch of :func:`two_view` — the loader-placement
    backend (``--data-backend device``).  uint8 in, so the host→HBM transfer
    is 4x smaller than shipping floats (the DALI-style bandwidth win)."""
    return two_view(key, images, size, strength=strength)
