"""Array dataset readers: CIFAR-10/100, MNIST, FashionMNIST, digits, fake.

The reference delegates simple datasets (with ``--download``) to its
``datasets`` submodule (/root/reference/main.py:44-45; SURVEY.md §2.3).  Here
they are read from the standard on-disk binary formats into numpy arrays once
and streamed through tf.data; ``download=True`` fetches the archives when the
environment has egress and fails with a clear message when it does not.

The ``fake`` backend (no reference analog — SURVEY.md §4 test strategy) is a
deterministic synthetic dataset for tests and benchmarks.
"""
from __future__ import annotations

import gzip
import os
import pickle
import tarfile
import urllib.request
from typing import Tuple

import numpy as np

Arrays = Tuple[np.ndarray, np.ndarray]  # images uint8 NHWC, labels int64


_URLS = {
    "cifar10": "https://www.cs.toronto.edu/~kriz/cifar-10-python.tar.gz",
    "cifar100": "https://www.cs.toronto.edu/~kriz/cifar-100-python.tar.gz",
    "mnist": "https://storage.googleapis.com/cvdf-datasets/mnist/",
    "fashion_mnist":
        "http://fashion-mnist.s3-website.eu-central-1.amazonaws.com/",
}


def _download(url: str, dest: str) -> None:
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    try:
        urllib.request.urlretrieve(url, dest)  # noqa: S310
    except Exception as e:
        raise RuntimeError(
            f"could not download {url} (no egress?): {e}; place the archive "
            f"at {dest} manually") from e


def load_cifar10(data_dir: str, train: bool, download: bool = False) -> Arrays:
    root = os.path.join(data_dir, "cifar-10-batches-py")
    if not os.path.isdir(root):
        tgz = os.path.join(data_dir, "cifar-10-python.tar.gz")
        if not os.path.exists(tgz):
            if not download:
                raise FileNotFoundError(
                    f"{root} not found; pass download=True (--download)")
            _download(_URLS["cifar10"], tgz)
        with tarfile.open(tgz) as tar:
            tar.extractall(data_dir)  # noqa: S202
    names = ([f"data_batch_{i}" for i in range(1, 6)] if train
             else ["test_batch"])
    imgs, labels = [], []
    for n in names:
        with open(os.path.join(root, n), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        imgs.append(d[b"data"])
        labels.extend(d[b"labels"])
    x = np.concatenate(imgs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    return np.ascontiguousarray(x), np.asarray(labels, np.int64)


def load_cifar100(data_dir: str, train: bool,
                  download: bool = False) -> Arrays:
    root = os.path.join(data_dir, "cifar-100-python")
    if not os.path.isdir(root):
        tgz = os.path.join(data_dir, "cifar-100-python.tar.gz")
        if not os.path.exists(tgz):
            if not download:
                raise FileNotFoundError(
                    f"{root} not found; pass download=True (--download)")
            _download(_URLS["cifar100"], tgz)
        with tarfile.open(tgz) as tar:
            tar.extractall(data_dir)  # noqa: S202
    with open(os.path.join(root, "train" if train else "test"), "rb") as f:
        d = pickle.load(f, encoding="bytes")
    x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    return np.ascontiguousarray(x), np.asarray(d[b"fine_labels"], np.int64)


def _load_idx(path: str) -> np.ndarray:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    magic = int.from_bytes(data[2:3], "big")
    ndim = data[3]
    dims = [int.from_bytes(data[4 + 4 * i:8 + 4 * i], "big")
            for i in range(ndim)]
    del magic
    return np.frombuffer(data, np.uint8, offset=4 + 4 * ndim).reshape(dims)


def _load_mnist_like(name: str, data_dir: str, train: bool,
                     download: bool) -> Arrays:
    root = os.path.join(data_dir, name)
    prefix = "train" if train else "t10k"
    files = [f"{prefix}-images-idx3-ubyte", f"{prefix}-labels-idx1-ubyte"]
    paths = []
    for f in files:
        for cand in (os.path.join(root, f), os.path.join(root, f + ".gz")):
            if os.path.exists(cand):
                paths.append(cand)
                break
        else:
            if not download:
                raise FileNotFoundError(
                    f"{os.path.join(root, f)}[.gz] not found; pass "
                    f"download=True (--download)")
            dest = os.path.join(root, f + ".gz")
            _download(_URLS[name] + f + ".gz", dest)
            paths.append(dest)
    images = _load_idx(paths[0])[..., np.newaxis]          # N,28,28,1
    images = np.tile(images, (1, 1, 1, 3))                 # grayscale -> RGB
    return images, _load_idx(paths[1]).astype(np.int64)


def load_mnist(data_dir: str, train: bool, download: bool = False) -> Arrays:
    return _load_mnist_like("mnist", data_dir, train, download)


def load_fashion_mnist(data_dir: str, train: bool,
                       download: bool = False) -> Arrays:
    return _load_mnist_like("fashion_mnist", data_dir, train, download)


def load_fake(num_samples: int = 512, image_size: int = 32,
              num_classes: int = 10, seed: int = 0) -> Arrays:
    rng = np.random.RandomState(seed)
    x = rng.randint(0, 256, size=(num_samples, image_size, image_size, 3),
                    dtype=np.uint8)
    y = rng.randint(0, num_classes, size=(num_samples,)).astype(np.int64)
    return x, y


def load_synth(num_samples: int = 10_000, image_size: int = 32,
               num_classes: int = 10, seed: int = 0, train: bool = True
               ) -> Arrays:
    """Procedural LEARNABLE dataset for offline learning-dynamics evidence.

    ``fake`` is pure noise (nothing to learn); ``synth`` gives each class a
    fixed smooth color template (4x4 noise upsampled bilinearly to full
    resolution) and renders samples as template + per-sample brightness +
    pixel noise.  Smooth templates keep local crops correlated with class
    identity, so BYOL's crop-invariance objective has real signal and the
    concurrent linear probe must beat chance by a wide margin if (and only
    if) representation learning works.  Templates depend only on
    (num_classes, image_size), never on ``train``, so train/test share
    classes but not samples.
    """
    tmpl_rng = np.random.RandomState(123)           # class identity, fixed
    rng = np.random.RandomState(seed + (0 if train else 10_007))
    # smooth per-class color fields in [0.2, 0.8]
    coarse = tmpl_rng.rand(num_classes, 4, 4, 3)
    xs = np.linspace(0, 3, image_size)
    i0 = np.clip(np.floor(xs).astype(int), 0, 2)
    frac = xs - i0                                  # (S,)
    def _up(t):                                     # bilinear 4x4 -> S x S
        t = (t[i0] * (1 - frac)[:, None, None]
             + t[i0 + 1] * frac[:, None, None])                 # rows
        t = (t[:, i0] * (1 - frac)[None, :, None]
             + t[:, i0 + 1] * frac[None, :, None])              # cols
        return t
    templates = np.stack([0.2 + 0.6 * _up(c) for c in coarse])  # (C,S,S,3)

    y = rng.randint(0, num_classes, size=(num_samples,))
    gain = rng.uniform(0.6, 1.0, size=(num_samples, 1, 1, 1))
    bias = rng.uniform(-0.1, 0.1, size=(num_samples, 1, 1, 1))
    noise = rng.normal(0.0, 0.06, size=(num_samples, image_size,
                                        image_size, 3))
    x = np.clip(templates[y] * gain + bias + noise, 0.0, 1.0)
    return (x * 255).astype(np.uint8), y.astype(np.int64)


def load_synth_tokens(num_samples: int, seq_len: int, vocab: int,
                      num_classes: int = 10, seed: int = 0,
                      train: bool = True) -> Arrays:
    """Procedural LEARNABLE id sequences: ``(N, S) int32`` below
    ``vocab - 1`` (the last id is reserved for masking) and labels.

    Each class owns a fixed random quarter of the usable ids; a sample
    draws three positions in four from its class's ids and the rest from
    all of them.  Which ids a sequence is made of says its class whatever
    is masked, so a masked-view BYOL objective has signal and the probe
    must beat chance.  The class sets depend on ``(num_classes, vocab)``
    only: train and test share classes, not samples."""
    usable = vocab - 1
    if usable < 4 * 2:
        raise ValueError(f"vocabulary of {vocab} ids is too small")
    set_rng = np.random.RandomState(123)
    own = np.stack([set_rng.choice(usable, size=usable // 4, replace=False)
                    for _ in range(num_classes)])
    rng = np.random.RandomState(seed + (0 if train else 10_007))
    y = rng.randint(0, num_classes, size=(num_samples,))
    from_own = own[y[:, None], rng.randint(0, own.shape[1],
                                           size=(num_samples, seq_len))]
    anywhere = rng.randint(0, usable, size=(num_samples, seq_len))
    x = np.where(rng.rand(num_samples, seq_len) < 0.75, from_own, anywhere)
    return x.astype(np.int32), y.astype(np.int64)


def mask_tokens(ids: np.ndarray, rng: np.random.RandomState, mask_id: int,
                rate: float = 0.15) -> np.ndarray:
    """One view of ``ids``: ``rate`` of the positions, drawn independently,
    replaced by ``mask_id``."""
    return np.where(rng.rand(*ids.shape) < rate, np.int32(mask_id),
                    ids).astype(np.int32)


def noise_blocks(ids: np.ndarray, rng: np.random.RandomState, mask_id: int,
                 block: int) -> np.ndarray:
    """One block-diffusion view of ``ids (N, L)``: ``[noised | clean]``, ``(N,
    2 L)`` — the ids once with every position of block ``p // block``
    replaced by ``mask_id`` with probability ``t``, one ``t ~ U(0, 1)`` a
    block and sample (arXiv 2503.09573, the linear schedule without its
    clipping), and once as they are."""
    n, length = ids.shape
    blocks = -(-length // block)
    draws = rng.rand(n, blocks + length)       # a rate a block | a position's
    rate = np.repeat(draws[:, :blocks], block, axis=1)[:, :length]
    noised = np.where(draws[:, blocks:] < rate, np.int32(mask_id), ids)
    return np.concatenate([noised, ids], axis=1).astype(np.int32)


def load_digits_img(data_dir: str = "", train: bool = True,
                    download: bool = False) -> Arrays:
    """Real handwritten-digit images (sklearn's bundled UCI digits), no
    network needed: the one REAL image dataset available in an egress-free
    environment.  1,797 8x8 grayscale digits -> nearest-upsampled to 32x32
    RGB uint8 so the standard augmentation stack (random resized crop at
    32px, color ops) applies unchanged.  Fills the simple-dataset role the
    reference delegates to its datasets submodule (main.py:44-45) when the
    canonical archives (CIFAR/MNIST) cannot be fetched.

    The split is a fixed seeded permutation (1,500 train / 297 test) —
    sklearn defines no canonical split; pinning one keeps runs comparable.
    ``data_dir``/``download`` are accepted for ARRAY_LOADERS signature
    compatibility and ignored (the data ships inside sklearn).
    """
    del data_dir, download
    try:
        from sklearn.datasets import load_digits as _sk_load
    except ImportError as e:
        raise RuntimeError(
            "--task digits needs scikit-learn (bundles the UCI digits "
            "images); it is not installed") from e
    d = _sk_load()
    x = (d.images / 16.0 * 255.0).astype(np.uint8)      # (1797, 8, 8)
    x = x.repeat(4, axis=1).repeat(4, axis=2)           # 8x8 -> 32x32
    x = np.tile(x[..., np.newaxis], (1, 1, 1, 3))       # grayscale -> RGB
    y = d.target.astype(np.int64)
    perm = np.random.RandomState(42).permutation(len(x))
    split = 1500
    idx = perm[:split] if train else perm[split:]
    return np.ascontiguousarray(x[idx]), y[idx]


ARRAY_LOADERS = {
    "cifar10": (load_cifar10, 10),
    "cifar100": (load_cifar100, 100),
    "mnist": (load_mnist, 10),
    "fashion_mnist": (load_fashion_mnist, 10),
    "digits": (load_digits_img, 10),
}
