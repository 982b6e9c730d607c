"""Dataset loader bundle — the ``datasets.loader.get_loader`` contract.

Reconstructed API surface (SURVEY.md §2.3; call sites
/root/reference/main.py:24,413-423,430,475,579,760):

  bundle = get_loader(cfg)          # dispatch on cfg.task.task
  bundle.train_loader               # iterable of {'view1','view2','label'}
  bundle.test_loader                # ditto (two resized views, Quirk Q9 note)
  bundle.input_shape                # (H, W, C)
  bundle.num_train_samples          # GLOBAL counts (resolve() divides per
  bundle.num_test_samples           #  replica, core/config.py)
  bundle.output_size                # number of classes
  bundle.set_all_epochs(epoch)      # epoch reseed (DistributedSampler analog)

TPU-native differences:
- batches are dicts of numpy arrays sized for THIS HOST
  (global_batch / process_count); the trainer shards them onto the mesh's
  ``data`` axis (parallel/mesh.py), which is the per-replica split the
  reference does by mutating args.batch_size (main.py:725);
- the train set is sharded per host by ``jax.process_index()`` (the
  DistributedSampler analog); test is NOT sharded, matching the reference
  (main.py:422, Quirk Q9), unless ``shard_eval=True``;
- iteration uses drop-remainder batching, matching steps_per_train_epoch
  (main.py:424).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

from byol_tpu.core.config import Config
from byol_tpu.data import readers

Batch = Dict[str, np.ndarray]


@dataclasses.dataclass
class LoaderBundle:
    """Loader bundle; iterables re-seed from the epoch set via
    ``set_all_epochs`` (reference main.py:760)."""

    make_train_iter: Callable[[int], Iterator[Batch]]  # epoch -> iterator
    make_test_iter: Callable[[int], Iterator[Batch]]
    input_shape: Tuple[int, ...]         # (H, W, C), or (S,) for token ids
    num_train_samples: int
    num_test_samples: int
    output_size: int
    epoch: int = 0
    # TRAIN split under the EVAL transform (resize-only, unshuffled) — what
    # the offline linear-eval protocol trains its probe on (training/
    # linear_eval.py).  Optional: None for hand-built test bundles.
    make_train_eval_iter: Optional[Callable[[int], Iterator[Batch]]] = None
    # Whether the TEST split was sharded per host at build time (get_loader's
    # shard_eval).  Consumers (multi-host linear eval) key de-duplication off
    # this rather than re-reading the config, so a caller-built loader can't
    # silently disagree with the flag it was built under.
    eval_sharded: bool = False
    # Validation split (reference main.py:421-423: the datasets submodule
    # exposed num_valid_samples next to train/test; sharded per host like
    # train).  Built when cfg.task.valid_fraction > 0 or, for image_folder,
    # when a valid/ root exists on disk.  Eval transform (resize-only).
    make_valid_iter: Optional[Callable[[int], Iterator[Batch]]] = None
    num_valid_samples: int = 0

    def set_all_epochs(self, epoch: int) -> None:
        self.epoch = epoch

    @property
    def train_loader(self) -> Iterator[Batch]:
        return self.make_train_iter(self.epoch)

    @property
    def test_loader(self) -> Iterator[Batch]:
        return self.make_test_iter(self.epoch)

    @property
    def train_eval_loader(self) -> Iterator[Batch]:
        if self.make_train_eval_iter is None:
            raise ValueError("this LoaderBundle provides no train-eval "
                             "(resize-only train split) iterator")
        return self.make_train_eval_iter(self.epoch)

    @property
    def valid_loader(self) -> Iterator[Batch]:
        if self.make_valid_iter is None:
            raise ValueError(
                "this LoaderBundle has no validation split: set "
                "--valid-fraction > 0 (or provide a valid/ root for "
                "image_folder)")
        return self.make_valid_iter(self.epoch)


def pad_batch(batch: Batch, target: int) -> Batch:
    """Pad a (possibly short) batch up to ``target`` rows and attach a
    validity ``mask`` (1.0 = real row).  Every eval batch then has ONE
    static shape — a single XLA compile — and a final batch that isn't
    divisible by the mesh's data axis still shards cleanly.  Consumers
    (trainer eval step, linear-eval extraction) mask pad rows out of every
    metric."""
    n = len(next(iter(batch.values())))
    if n > target:
        raise ValueError(
            f"pad_batch: batch has {n} rows > target {target}; the caller's "
            "host batch derivation disagrees with the loader's batch size")
    mask = np.zeros((target,), np.float32)
    mask[:n] = 1.0
    out = {}
    for k, v in batch.items():
        v = np.asarray(v)
        if n < target:
            pad = np.zeros((target - n,) + v.shape[1:], v.dtype)
            v = np.concatenate([v, pad], axis=0)
        out[k] = v
    out["mask"] = mask
    return out


def carve_valid_split(n: int, fraction: float, seed: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """-> (valid_indices, train_indices): the seeded permutation's head is
    held out (reference main.py:421-423 num_valid_samples contract).  ONE
    implementation shared by the array and image_folder paths so both tasks
    split identically and every host agrees."""
    if not 0.0 <= fraction < 1.0:
        raise ValueError(f"valid_fraction must be in [0, 1), got {fraction}")
    n_valid = int(n * fraction)
    perm = np.random.RandomState(seed ^ 0x5eed).permutation(n)
    return perm[:n_valid], perm[n_valid:]


def _process_info() -> Tuple[int, int]:
    import jax
    return jax.process_index(), jax.process_count()


def _shard_arrays(x: np.ndarray, y: np.ndarray, index: int, count: int):
    """Contiguous per-host shard (DistributedSampler analog)."""
    if count == 1:
        return x, y
    per = len(x) // count
    lo = index * per
    return x[lo:lo + per], y[lo:lo + per]


def _array_pipeline(images: np.ndarray, labels: np.ndarray, *,
                    batch_size: int, image_size: int, train: bool,
                    color_jitter_strength: float, seed: int,
                    shuffle: bool, aug_spec: str = "reference"
                    ) -> Callable[[int], Iterator[Batch]]:
    """tf.data pipeline over in-memory arrays -> numpy batch iterator.

    Train: two independently-augmented views; test: one resize applied to
    both view slots so eval code paths stay identical (the reference's eval
    also runs the full two-view forward, main.py:589-606)."""
    from byol_tpu.data.tf_host import tf

    from byol_tpu.data import augment

    def make(epoch: int) -> Iterator[Batch]:
        ds = tf.data.Dataset.from_tensor_slices(
            {"image": images, "label": labels.astype(np.int32),
             "index": np.arange(len(labels), dtype=np.int64)})
        if shuffle:
            ds = ds.shuffle(min(len(labels), 50_000), seed=seed + epoch,
                            reshuffle_each_iteration=False)

        def _map(ex):
            img = tf.image.convert_image_dtype(ex["image"], tf.float32)
            if train:
                s = tf.stack([tf.cast(ex["index"], tf.int32),
                              tf.constant(seed, tf.int32) + epoch])
                v1, v2 = augment.two_views(
                    img, image_size, s, color_jitter_strength,
                    spec=aug_spec)
            else:
                v1 = augment.test_resize(img, image_size)
                v2 = v1
            return {"view1": v1, "view2": v2, "label": ex["label"]}

        ds = ds.map(_map, num_parallel_calls=tf.data.AUTOTUNE)
        ds = ds.batch(batch_size, drop_remainder=train)
        ds = ds.prefetch(tf.data.AUTOTUNE)
        return ds.as_numpy_iterator()

    return make


def _native_pipeline(images: np.ndarray, labels: np.ndarray, *,
                     batch_size: int, image_size: int, train: bool,
                     color_jitter_strength: float, seed: int, shuffle: bool,
                     num_threads: int) -> Callable[[int], Iterator[Batch]]:
    """C++ host pipeline (data/native_aug.py) — the DALI-equivalent backend.

    Same iterator contract as the tf.data path: per-epoch reshuffle from
    (seed, epoch), two augmented views in train, resize-only eval,
    drop-remainder train batching."""
    from byol_tpu.data import native_aug

    labels = labels.astype(np.int32)

    def make(epoch: int) -> Iterator[Batch]:
        idx = np.arange(len(labels))
        if shuffle:
            np.random.RandomState(seed + epoch).shuffle(idx)
        n = len(idx)
        end = n - (n % batch_size) if train else n
        for lo in range(0, end, batch_size):
            take = idx[lo:lo + batch_size]
            imgs = images[take]
            if train:
                v1, v2 = native_aug.augment_two_views(
                    imgs, image_size,
                    color_jitter_strength=color_jitter_strength,
                    # epoch folded into the stream seed = set_all_epochs
                    seed=seed + 1_000_003 * epoch, index_base=int(lo),
                    num_threads=num_threads)
            else:
                v1 = native_aug.resize_batch(imgs, image_size,
                                             num_threads=num_threads)
                v2 = v1
            yield {"view1": v1, "view2": v2, "label": labels[take]}

    return make


def _device_pipeline(images: np.ndarray, labels: np.ndarray, *,
                     batch_size: int, image_size: int, train: bool,
                     color_jitter_strength: float, seed: int, shuffle: bool
                     ) -> Callable[[int], Iterator[Batch]]:
    """On-device (TPU) two-view augmentation backend — the DALI analog that
    actually uses the accelerator (data/device_augment.py).

    The host ships raw uint8 batches (4x less H2D bandwidth than float32
    views); crop/flip/jitter/grayscale/blur run on chip in one jitted vmapped
    program.  Train only — ``get_loader`` routes eval through the host
    resize path, where augmentation throughput is irrelevant."""
    from byol_tpu.core import rng as rng_lib
    from byol_tpu.data import device_augment

    labels = labels.astype(np.int32)

    def make(epoch: int) -> Iterator[Batch]:
        idx = np.arange(len(labels))
        if shuffle:
            np.random.RandomState(seed + epoch).shuffle(idx)
        n = len(idx)
        end = n - (n % batch_size) if train else n
        # per-epoch key stream: the set_all_epochs reseed (main.py:760)
        epoch_key = rng_lib.for_step(rng_lib.root_key(seed), epoch)
        for i, lo in enumerate(range(0, end, batch_size)):
            take = idx[lo:lo + batch_size]
            v1, v2 = device_augment.two_view_batch(
                rng_lib.for_step(epoch_key, i), images[take], image_size,
                strength=color_jitter_strength)
            yield {"view1": v1, "view2": v2, "label": labels[take]}

    return make


def _raw_pipeline(images: np.ndarray, labels: np.ndarray, *,
                  batch_size: int, seed: int, shuffle: bool
                  ) -> Callable[[int], Iterator[Batch]]:
    """Step-placement train pipeline: raw uint8 batches, no host-side
    augmentation at all (``augment_placement='step'``).

    Yields ``{'images': (B,H,W,C) uint8, 'label': (B,) int32}`` — the train
    step derives per-microbatch keys from its step counter and augments
    inside the accumulation scan (training/steps.py).  ~8x fewer H2D bytes
    than two float32 views, and the host's per-batch work collapses to an
    index gather."""
    labels = labels.astype(np.int32)
    if images.dtype != np.uint8:
        raise ValueError(
            f"augment_placement='step' ships raw uint8 pixels; this dataset "
            f"holds {images.dtype} arrays")

    def make(epoch: int) -> Iterator[Batch]:
        idx = np.arange(len(labels))
        if shuffle:
            np.random.RandomState(seed + epoch).shuffle(idx)
        n = len(idx)
        end = n - (n % batch_size)
        for lo in range(0, end, batch_size):
            take = idx[lo:lo + batch_size]
            yield {"images": images[take], "label": labels[take]}

    return make


def _token_pipeline(ids: np.ndarray, labels: np.ndarray, *, batch_size: int,
                    mask_id: int, seed: int, shuffle: bool,
                    diffusion_block: int = 0
                    ) -> Callable[[int], Iterator[Batch]]:
    """epoch -> batches ``{'view1', 'view2': int32 (B, S), 'label'}``: two
    views of the same sequences under independent 15% masking — or, for a
    block-diffusion trunk (``diffusion_block`` > 0), two independent
    noisings ``[noised | clean]``, ``(B, 2 S)``, a rate a block
    (``readers.noise_blocks``) — reseeded per epoch (drop-remainder, as
    every pipeline here).  Each batch is made under the host span
    ``TOKEN_FEED_SPAN``."""
    from byol_tpu.observability import spans as spans_lib
    if diffusion_block:
        view = lambda rows, rng: readers.noise_blocks(
            rows, rng, mask_id, diffusion_block)
    else:
        view = lambda rows, rng: readers.mask_tokens(rows, rng, mask_id)

    def make(epoch: int) -> Iterator[Batch]:
        rng = np.random.RandomState((seed * 7919 + epoch) % (2 ** 31 - 1))
        order = rng.permutation(len(ids)) if shuffle else np.arange(len(ids))
        for i in range(0, len(order) - batch_size + 1, batch_size):
            with spans_lib.span(spans_lib.TOKEN_FEED_SPAN):
                rows = order[i:i + batch_size]
                batch = {"view1": view(ids[rows], rng),
                         "view2": view(ids[rows], rng),
                         "label": labels[rows].astype(np.int32)}
            yield batch
    return make


def _token_loader(cfg: Config, *, host_batch: int, num_samples: int,
                  index: int, count: int, shard_eval: bool) -> LoaderBundle:
    """``--task synth_tokens``: seeded id sequences for a backbone that
    takes tokens (models/registry.py ``input_kind``)."""
    from byol_tpu.models.registry import get_spec, held_vocab_rows
    if cfg.task.seq_len < 1:
        raise ValueError("--task synth_tokens needs --seq-len")
    if cfg.task.augment_placement != "loader" or \
            cfg.task.valid_fraction > 0:
        raise ValueError("--task synth_tokens masks its views in the "
                         "loader and carves no validation split")
    vocab = held_vocab_rows(cfg.model.arch, cfg.model.layer_share)
    seed, n_classes = cfg.device.seed, 10
    x_tr, y_tr = readers.load_synth_tokens(
        num_samples, cfg.task.seq_len, vocab, n_classes, seed, train=True)
    x_te, y_te = readers.load_synth_tokens(
        max(num_samples // 10, host_batch), cfg.task.seq_len, vocab,
        n_classes, seed, train=False)
    n_train, n_test = len(x_tr), len(x_te)
    x_trs, y_trs = _shard_arrays(x_tr, y_tr, index, count)
    if shard_eval:
        x_te, y_te = _shard_arrays(x_te, y_te, index, count)
    # a block-diffusion trunk reads [noised | clean]: twice --seq-len ids
    diffusion_block = get_spec(cfg.model.arch).diffusion_block
    pipe = lambda x, y, shuffle: _token_pipeline(
        x, y, batch_size=host_batch, mask_id=vocab - 1, seed=seed,
        shuffle=shuffle, diffusion_block=diffusion_block)
    return LoaderBundle(
        make_train_iter=pipe(x_trs, y_trs, True),
        make_test_iter=pipe(x_te, y_te, False),
        make_train_eval_iter=pipe(x_trs, y_trs, False),
        input_shape=(cfg.task.seq_len * (2 if diffusion_block else 1),),
        num_train_samples=n_train,
        num_test_samples=n_test, output_size=n_classes,
        eval_sharded=shard_eval and count > 1)


def get_loader(cfg: Config, *, num_fake_samples: int = 512,
               num_synth_samples: Optional[int] = None,
               shard_eval: bool = False) -> LoaderBundle:
    """Dispatch on ``cfg.task.task``; see module docstring for the contract.

    Tasks: 'fake', 'synth', 'synth_tokens' (id sequences for a token
    backbone), 'digits', 'cifar10', 'cifar100', 'mnist',
    'fashion_mnist', 'image_folder' (the reference's
    multi_augment_image_folder default, main.py:38-39).
    """
    task = cfg.task.task
    if num_synth_samples is None:   # explicit kwarg wins over the config
        num_synth_samples = cfg.task.num_synth_samples or 20_000
    # Reference task-name aliases (main.py:38-39; README.md:93): the DALI
    # variant maps to the native C++ backend for array tasks and to the
    # fused-decode tf.data path for image trees — ONE canonical augmentation
    # spec either way (Quirk Q4 deliberately not reproduced).
    if task == "multi_augment_image_folder":
        task = "image_folder"
    elif task == "dali_multi_augment_image_folder":
        task = "image_folder"
    index, count = _process_info()
    if cfg.task.batch_size % count != 0:
        raise ValueError(f"global batch {cfg.task.batch_size} not divisible "
                         f"by process count {count}")
    host_batch = cfg.task.batch_size // count
    if task == "synth_tokens":
        return _token_loader(cfg, host_batch=host_batch,
                             num_samples=num_synth_samples, index=index,
                             count=count, shard_eval=shard_eval)

    # Resolve the effective backend and validate the aug spec BEFORE any
    # dataset download/load, so a bad combination fails fast.
    backend = cfg.task.data_backend
    if backend not in ("tf", "native", "device"):
        raise ValueError(f"unknown data_backend {backend!r} "
                         f"('tf'|'native'|'device')")
    if backend == "native":
        from byol_tpu.data import native_aug
        if not native_aug.available():
            # documented graceful degradation: no toolchain/binary -> tf.data
            print("byol_tpu: native data backend unavailable "
                  "(no g++/.so); falling back to tf.data")
            backend = "tf"
        elif task == "image_folder" and not native_aug.has_jpeg():
            print("byol_tpu: native backend built without libjpeg; "
                  "image_folder falls back to tf.data fused decode")
            backend = "tf"
    if cfg.regularizer.aug_spec != "reference" and backend != "tf":
        raise ValueError(
            f"aug_spec={cfg.regularizer.aug_spec!r} is implemented on the "
            f"tf data backend only (got data_backend={backend!r})")
    placement = cfg.task.augment_placement
    if placement not in ("loader", "step"):
        raise ValueError(f"unknown augment_placement {placement!r} "
                         f"('loader'|'step')")
    if placement == "step":
        if task == "image_folder":
            raise ValueError(
                "augment_placement='step' does not serve image_folder: "
                "decode is host-side and yields variable-size images; use "
                "the loader placement")
        if cfg.regularizer.aug_spec != "reference":
            raise ValueError(
                f"augment_placement='step' runs the canonical 'reference' "
                f"augmentation spec on device (got "
                f"aug_spec={cfg.regularizer.aug_spec!r})")
        if backend == "device":
            raise ValueError(
                "data_backend='device' (loader-dispatched on-chip augment) "
                "and augment_placement='step' (step-fused augment) are "
                "mutually exclusive; pick one")

    if task == "image_folder":
        if backend == "device":
            raise ValueError(
                "data_backend='device' does not serve image_folder (decode "
                "is inherently host-side); use 'tf' or 'native'")
        from byol_tpu.data.imagefolder import image_folder_loader
        return image_folder_loader(cfg, host_batch=host_batch,
                                   shard_eval=shard_eval, backend=backend)

    if task == "fake":
        size = cfg.task.image_size_override or 32
        x_tr, y_tr = readers.load_fake(num_fake_samples, size,
                                       seed=cfg.device.seed)
        x_te, y_te = readers.load_fake(max(num_fake_samples // 4, host_batch),
                                       size, seed=cfg.device.seed + 1)
        n_classes = 10
    elif task == "synth":
        # learnable procedural dataset (readers.load_synth) — the offline
        # stand-in for CIFAR-scale learning-dynamics evidence
        size = cfg.task.image_size_override or 32
        x_tr, y_tr = readers.load_synth(num_synth_samples, size,
                                        seed=cfg.device.seed, train=True)
        x_te, y_te = readers.load_synth(
            max(num_synth_samples // 10, host_batch), size,
            seed=cfg.device.seed, train=False)
        n_classes = 10
    elif task in readers.ARRAY_LOADERS:
        fn, n_classes = readers.ARRAY_LOADERS[task]
        x_tr, y_tr = fn(cfg.task.data_dir, train=True,
                        download=cfg.task.download)
        x_te, y_te = fn(cfg.task.data_dir, train=False,
                        download=cfg.task.download)
        size = cfg.task.image_size_override or x_tr.shape[1]
    else:
        raise ValueError(f"unknown task {task!r}")

    # Validation carve-out (reference main.py:421-423 contract): held out
    # BEFORE host sharding so every host agrees on the split; valid is then
    # sharded per host like train.
    x_va = y_va = None
    n_valid = 0
    if cfg.task.valid_fraction > 0:
        va_idx, tr_idx = carve_valid_split(
            len(x_tr), cfg.task.valid_fraction, cfg.device.seed)
        n_valid = len(va_idx)
        x_va, y_va = x_tr[va_idx], y_tr[va_idx]
        x_tr, y_tr = x_tr[tr_idx], y_tr[tr_idx]

    n_train, n_test = len(x_tr), len(x_te)
    x_trs, y_trs = _shard_arrays(x_tr, y_tr, index, count)
    if n_valid:
        x_va, y_va = _shard_arrays(x_va, y_va, index, count)
    if shard_eval:
        x_te, y_te = _shard_arrays(x_te, y_te, index, count)

    cj = cfg.regularizer.color_jitter_strength
    import functools
    if backend == "native":
        pipeline = functools.partial(
            _native_pipeline,
            num_threads=max(cfg.device.workers_per_replica, 1))
        test_pipeline = pipeline
    elif backend == "tf":
        pipeline = test_pipeline = functools.partial(
            _array_pipeline, aug_spec=cfg.regularizer.aug_spec)
    else:  # device
        # on-chip train augmentation; eval resize stays on host (its
        # throughput never gates the MXU)
        pipeline, test_pipeline = _device_pipeline, _array_pipeline
    if placement == "step":
        # raw uint8 train stream (the step augments); eval keeps the host
        # resize path of whatever backend resolved above
        make_train = _raw_pipeline(x_trs, y_trs, batch_size=host_batch,
                                   seed=cfg.device.seed, shuffle=True)
    else:
        make_train = pipeline(
            x_trs, y_trs, batch_size=host_batch, image_size=size, train=True,
            color_jitter_strength=cj, seed=cfg.device.seed, shuffle=True)
    return LoaderBundle(
        make_train_iter=make_train,
        make_test_iter=test_pipeline(
            x_te, y_te, batch_size=host_batch, image_size=size, train=False,
            color_jitter_strength=cj, seed=cfg.device.seed, shuffle=False),
        make_train_eval_iter=test_pipeline(
            x_trs, y_trs, batch_size=host_batch, image_size=size,
            train=False, color_jitter_strength=cj, seed=cfg.device.seed,
            shuffle=False),
        input_shape=(size, size, 3),
        num_train_samples=n_train,
        num_test_samples=n_test,
        output_size=n_classes,
        eval_sharded=shard_eval and count > 1,
        make_valid_iter=(test_pipeline(
            x_va, y_va, batch_size=host_batch, image_size=size, train=False,
            color_jitter_strength=cj, seed=cfg.device.seed, shuffle=False)
            if n_valid else None),
        num_valid_samples=n_valid,
    )
