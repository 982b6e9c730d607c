"""Two-view SimCLR/BYOL augmentation pipeline (tf.data host path).

Reproduces the reference's torchvision transform stack exactly
(/root/reference/main.py:386-398):

  train: RandomResizedCrop(size)                       (scale .08-1, ratio 3/4-4/3)
         RandomHorizontalFlip(p=.5)
         ColorJitter(.8s, .8s, .8s, .2s) applied with p=.8
         RandomGrayscale(p=.2)
         GaussianBlur(kernel_size=int(.1*size), p=.5)  (datasets.utils contract,
                                                        main.py:384,396; sigma
                                                        ~ U(.1, 2) per SimCLR)
  test:  Resize(size) only — NO center crop and NO mean/std normalization
         (main.py:398; Quirk Q3), pixels stay in [0, 1] (contract enforced at
         main.py:486-490 and re-asserted by the loader here).

Deviation (documented): torchvision's ColorJitter applies its four sub-ops in
random order; here the order is fixed brightness→contrast→saturation→hue.
All randomness is stateless (seeded per-sample from (seed, epoch, index)) so
epoch reshuffling is deterministic — the ``set_all_epochs`` analog
(main.py:760) is just a different fold-in.

``aug_spec="paper"`` selects the BYOL paper's ASYMMETRIC recipe instead
(arXiv 2006.07733 App. B — the spec behind the 74.3% headline, which the
reference never implemented): jitter strengths (.4s, .4s, .2s, .1s); view 1
blurs with p=1.0 and never solarizes; view 2 blurs with p=0.1 and solarizes
(threshold 0.5) with p=0.2.  ``"reference"`` (default) keeps the symmetric
reference stack above.
"""
from __future__ import annotations

import functools
from typing import Tuple

from byol_tpu.data.tf_host import tf


def _uniform(seed, shape=(), lo=0.0, hi=1.0):
    return tf.random.stateless_uniform(shape, seed=seed, minval=lo, maxval=hi)


def _split(seed, n):
    """Derive n statistically-independent seeds from one (2,) int seed."""
    return tf.unstack(
        tf.random.stateless_uniform((n, 2), seed=seed, minval=None,
                                    maxval=None, dtype=tf.int32), axis=0)


def random_resized_crop(image: tf.Tensor, size: int, seed,
                        scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)) -> tf.Tensor:
    """torchvision RandomResizedCrop semantics via
    ``stateless_sample_distorted_bounding_box`` (area + aspect-ratio sampling
    with whole-image fallback), bilinear resize to (size, size)."""
    bbox = tf.zeros((1, 1, 4), tf.float32)
    begin, sz, _ = tf.image.stateless_sample_distorted_bounding_box(
        tf.shape(image), bounding_boxes=bbox, seed=seed,
        min_object_covered=0.0, aspect_ratio_range=ratio, area_range=scale,
        max_attempts=10, use_image_if_no_bounding_boxes=True)
    crop = tf.slice(image, begin, sz)
    return tf.image.resize(crop, (size, size), method="bilinear")


def _blend(a: tf.Tensor, b: tf.Tensor, factor: tf.Tensor) -> tf.Tensor:
    return tf.clip_by_value(factor * a + (1.0 - factor) * b, 0.0, 1.0)


# torchvision ColorJitter(.8s,.8s,.8s,.2s) — the reference stack
# (main.py:391); single source for every default below.
REFERENCE_JITTER = (0.8, 0.8, 0.8, 0.2)


def color_jitter(image: tf.Tensor, strength: float, seed,
                 factors=REFERENCE_JITTER) -> tf.Tensor:
    """torchvision ColorJitter(brightness, contrast, saturation, hue) =
    ``factors`` x ``strength``, with multiplicative brightness (torch
    semantics, not tf's additive one)."""
    b = factors[0] * strength
    c = factors[1] * strength
    s = factors[2] * strength
    h = factors[3] * strength
    seeds = _split(seed, 4)
    # brightness: img * U(max(0, 1-b), 1+b)
    image = tf.clip_by_value(
        image * _uniform(seeds[0], (), max(0.0, 1.0 - b), 1.0 + b), 0., 1.)
    # contrast: blend with mean of grayscale image
    gray = tf.image.rgb_to_grayscale(image)
    image = _blend(image, tf.reduce_mean(gray),
                   _uniform(seeds[1], (), max(0.0, 1.0 - c), 1.0 + c))
    # saturation: blend with grayscale
    image = _blend(image, tf.image.rgb_to_grayscale(image),
                   _uniform(seeds[2], (), max(0.0, 1.0 - s), 1.0 + s))
    # hue: rotate hue channel in HSV
    if h > 0:
        image = tf.image.stateless_random_hue(image, h, seeds[3])
        image = tf.clip_by_value(image, 0.0, 1.0)
    return image


def random_grayscale(image: tf.Tensor, seed, p: float = 0.2) -> tf.Tensor:
    gray = tf.tile(tf.image.rgb_to_grayscale(image), [1, 1, 3])
    return tf.where(_uniform(seed) < p, gray, image)


def solarize(image: tf.Tensor, threshold: float = 0.5) -> tf.Tensor:
    """Invert pixels above ``threshold`` (paper spec, view 2 only)."""
    return tf.where(image < threshold, image, 1.0 - image)


# Per-(spec, view) parameters.  The reference spec is symmetric
# (main.py:386-397); the paper spec is asymmetric (arXiv 2006.07733 App B).
_VIEW_PARAMS = {
    ("reference", 0): dict(jitter=REFERENCE_JITTER, blur_p=0.5,
                           solarize_p=0.0),
    ("reference", 1): dict(jitter=REFERENCE_JITTER, blur_p=0.5,
                           solarize_p=0.0),
    ("paper", 0): dict(jitter=(0.4, 0.4, 0.2, 0.1), blur_p=1.0,
                       solarize_p=0.0),
    ("paper", 1): dict(jitter=(0.4, 0.4, 0.2, 0.1), blur_p=0.1,
                       solarize_p=0.2),
}


def view_params(spec: str, view: int) -> dict:
    try:
        return _VIEW_PARAMS[(spec, view)]
    except KeyError:
        raise ValueError(f"unknown aug spec/view {(spec, view)!r}; specs: "
                         f"'reference' | 'paper', views: 0 | 1") from None


def gaussian_blur(image: tf.Tensor, kernel_size: int, seed,
                  sigma_range=(0.1, 2.0)) -> tf.Tensor:
    """Depthwise separable gaussian blur; kernel_size = int(.1 * image_size)
    per the reference's GaussianBlur(kernel_size, p=.5) (main.py:384,396)."""
    k = max(int(kernel_size) | 1, 3)  # odd, >= 3
    r = k // 2
    sigma = _uniform(seed, (), *sigma_range)
    x = tf.range(-r, r + 1, dtype=tf.float32)
    g = tf.exp(-(x ** 2) / (2.0 * sigma ** 2))
    g = g / tf.reduce_sum(g)
    ch = image.shape[-1] or 3
    kx = tf.tile(tf.reshape(g, (1, k, 1, 1)), [1, 1, ch, 1])
    ky = tf.tile(tf.reshape(g, (k, 1, 1, 1)), [1, 1, ch, 1])
    # reflect-101 borders (the cv2 GaussianBlur convention, matched by the
    # native C++ backend): zero padding would dim border pixels because the
    # kernel weights falling outside the image contribute nothing.
    img = tf.pad(image[tf.newaxis], [[0, 0], [r, r], [r, r], [0, 0]],
                 mode="REFLECT")
    img = tf.nn.depthwise_conv2d(img, kx, [1, 1, 1, 1], "VALID")
    img = tf.nn.depthwise_conv2d(img, ky, [1, 1, 1, 1], "VALID")
    return img[0]


def post_crop_augment(image: tf.Tensor, size: int, seed,
                      color_jitter_strength: float = 1.0, *,
                      jitter=REFERENCE_JITTER, blur_p: float = 0.5,
                      solarize_p: float = 0.0) -> tf.Tensor:
    """Everything after the crop: flip, jitter(p=.8), grayscale(p=.2),
    blur(p=blur_p), solarize(p=solarize_p), [0,1] clip.  Single source of
    truth shared by the host-array pipeline and the ImageFolder pipeline
    (whose crop is fused with JPEG decode).  The blur gate and blur sigma
    get INDEPENDENT seeds — reusing one seed would make sigma a
    deterministic function of the gate draw."""
    seeds = _split(seed, 7)
    image = tf.image.stateless_random_flip_left_right(image, seeds[0])
    image = tf.where(_uniform(seeds[1]) < 0.8,
                     color_jitter(image, color_jitter_strength, seeds[2],
                                  factors=jitter),
                     image)
    image = random_grayscale(image, seeds[3], p=0.2)
    image = tf.where(_uniform(seeds[4]) < blur_p,
                     gaussian_blur(image, int(0.1 * size), seeds[5]),
                     image)
    if solarize_p > 0.0:
        image = tf.where(_uniform(seeds[6]) < solarize_p,
                         solarize(image), image)
    image = tf.reshape(image, (size, size, 3))
    return tf.clip_by_value(image, 0.0, 1.0)


def train_augment(image: tf.Tensor, size: int, seed,
                  color_jitter_strength: float = 1.0, *,
                  spec: str = "reference", view: int = 0) -> tf.Tensor:
    """One augmented view: image float32 [0,1] HWC -> (size, size, 3)."""
    s_crop, s_rest = _split(seed, 2)
    image = random_resized_crop(image, size, s_crop)
    return post_crop_augment(image, size, s_rest, color_jitter_strength,
                             **view_params(spec, view))


def test_resize(image: tf.Tensor, size: int) -> tf.Tensor:
    """Resize only — no crop, no normalization (main.py:398, Quirk Q3)."""
    image = tf.image.resize(image, (size, size), method="bilinear")
    return tf.clip_by_value(tf.reshape(image, (size, size, 3)), 0.0, 1.0)


def two_views(image: tf.Tensor, size: int, seed,
              color_jitter_strength: float = 1.0,
              spec: str = "reference") -> Tuple[tf.Tensor, tf.Tensor]:
    """Two independently-augmented views of one image — the
    ``multi_augment_image_folder`` contract (main.py:475,579).  Views are
    asymmetric under ``spec='paper'`` (module docstring)."""
    s1, s2 = _split(seed, 2)
    return (train_augment(image, size, s1, color_jitter_strength,
                          spec=spec, view=0),
            train_augment(image, size, s2, color_jitter_strength,
                          spec=spec, view=1))
