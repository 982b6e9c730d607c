"""ImageFolder pipeline: class-per-subdirectory image trees (ImageNet layout).

The reference's default task ``multi_augment_image_folder`` expects ``train/``
and ``test/`` ImageFolder roots (/root/reference/README.md:82) and leans on
NVIDIA DALI when host CPU decode becomes the bottleneck (main.py:356-382).
TPU-native replacements here (SURVEY.md §2.4 DALI row):

- fused ``decode_and_crop_jpeg``: the RandomResizedCrop window is sampled
  FIRST and only that window is decoded — the single biggest host-CPU win
  for JPEG trees;
- per-host file sharding by ``jax.process_index()`` (DistributedSampler
  analog);
- parallel interleaved reads + AUTOTUNE-parallel augmentation + prefetch;
  device transfer/double-buffering happens in the trainer
  (data/prefetch.py).
"""
from __future__ import annotations

import os
from typing import Callable, Iterator, List, Tuple

import numpy as np

from byol_tpu.core.config import Config

IMG_EXTS = (".jpg", ".jpeg", ".png", ".bmp")


def scan_image_folder(root: str) -> Tuple[List[str], List[int], List[str]]:
    """-> (paths, labels, class_names); classes sorted for determinism."""
    classes = sorted(d for d in os.listdir(root)
                     if os.path.isdir(os.path.join(root, d)))
    if not classes:
        raise FileNotFoundError(f"no class subdirectories under {root}")
    paths, labels = [], []
    for li, cls in enumerate(classes):
        cdir = os.path.join(root, cls)
        for fname in sorted(os.listdir(cdir)):
            if fname.lower().endswith(IMG_EXTS):
                paths.append(os.path.join(cdir, fname))
                labels.append(li)
    return paths, labels, classes


def _decode_full(data, channels=3):
    from byol_tpu.data.tf_host import tf
    img = tf.io.decode_image(data, channels=channels, expand_animations=False)
    img.set_shape([None, None, channels])
    return tf.image.convert_image_dtype(img, tf.float32)


def _fused_decode_random_crop(data, seed, size: int,
                              scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """Sample the crop window from the JPEG header, decode ONLY the window
    (tf.image.decode_and_crop_jpeg), then resize — DALI's fused
    decode+crop equivalent on the host."""
    from byol_tpu.data.tf_host import tf
    shape = tf.image.extract_jpeg_shape(data)
    bbox = tf.zeros((1, 1, 4), tf.float32)
    begin, sz, _ = tf.image.stateless_sample_distorted_bounding_box(
        shape, bounding_boxes=bbox, seed=seed, min_object_covered=0.0,
        aspect_ratio_range=ratio, area_range=scale, max_attempts=10,
        use_image_if_no_bounding_boxes=True)
    oy, ox, _ = tf.unstack(begin)
    th, tw, _ = tf.unstack(sz)
    img = tf.image.decode_and_crop_jpeg(data, [oy, ox, th, tw], channels=3)
    img = tf.image.convert_image_dtype(img, tf.float32)
    return tf.image.resize(img, (size, size), method="bilinear")


def _is_jpeg(path):
    from byol_tpu.data.tf_host import tf
    lower = tf.strings.lower(path)
    return tf.strings.regex_full_match(lower, r".*\.(jpg|jpeg)")


def image_folder_loader(cfg: Config, *, host_batch: int,
                        shard_eval: bool = False, backend: str = "tf"):
    """Build a LoaderBundle over train/ and test/ ImageFolder roots.

    ``backend='tf'``: tf.data with fused ``decode_and_crop_jpeg``.
    ``backend='native'``: the first-party C++ pipeline (data/native/) with
    libjpeg fused decode+crop — the DALI-equivalent that owns the whole
    decode→augment hot path without TF dispatch (reference main.py:356-382).
    """
    import jax
    from byol_tpu.data.tf_host import tf

    from byol_tpu.data import augment
    from byol_tpu.data.loader import LoaderBundle

    size = cfg.task.image_size_override or 224
    cj = cfg.regularizer.color_jitter_strength
    seed = cfg.device.seed
    index, count = jax.process_index(), jax.process_count()

    roots = {}
    for split in ("train", "test"):
        root = os.path.join(cfg.task.data_dir, split)
        if not os.path.isdir(root):
            raise FileNotFoundError(
                f"image_folder task expects {root}/<class>/<img> "
                f"(reference README.md:82)")
        roots[split] = scan_image_folder(root)
    tr_paths, tr_labels, classes = roots["train"]
    te_paths, te_labels, te_classes = roots["test"]
    if te_classes != classes:
        raise ValueError("train/ and test/ class sets differ")

    # Validation split (reference main.py:421-423): an on-disk valid/ root
    # wins; otherwise valid_fraction carves a seeded held-out head from the
    # train list BEFORE host sharding, so every host agrees on the split.
    va_paths, va_labels = [], []
    valid_root = os.path.join(cfg.task.data_dir, "valid")
    if os.path.isdir(valid_root):
        va_paths, va_labels, va_classes = scan_image_folder(valid_root)
        if va_classes != classes:
            raise ValueError("train/ and valid/ class sets differ")
    elif cfg.task.valid_fraction > 0:
        from byol_tpu.data.loader import carve_valid_split
        va_idx, tr_idx = carve_valid_split(
            len(tr_paths), cfg.task.valid_fraction, seed)
        va_paths = [tr_paths[i] for i in va_idx]
        va_labels = [tr_labels[i] for i in va_idx]
        tr_paths = [tr_paths[i] for i in tr_idx]
        tr_labels = [tr_labels[i] for i in tr_idx]
    n_train, n_test, n_valid = len(tr_paths), len(te_paths), len(va_paths)

    def shard(paths, labels):
        return paths[index::count], labels[index::count]

    tr_sh = shard(tr_paths, tr_labels)
    va_sh = shard(va_paths, va_labels)
    te_sh = shard(te_paths, te_labels) if shard_eval else (te_paths, te_labels)

    def make_native_iter(paths, labels, train: bool
                         ) -> Callable[[int], Iterator[dict]]:
        """C++ fused-JPEG pipeline iterator: threaded file reads, one
        native call per batch (decode window + augment in C++ threads), a
        depth-2 background prefetcher so host augment overlaps the train
        step.  Same contract as the tf.data path: per-epoch reshuffle from
        (seed, epoch), drop-remainder train batching, resize-only eval."""
        import concurrent.futures
        import queue as queue_lib
        import threading

        from byol_tpu.data import native_aug

        paths_t = np.asarray(paths)
        labels_t = np.asarray(labels, np.int32)
        workers = max(cfg.device.workers_per_replica, 1)

        def produce(epoch: int):
            idx = np.arange(len(labels_t))
            if train:
                np.random.RandomState(seed + epoch).shuffle(idx)
            n = len(idx)
            end = n - (n % host_batch) if train else n
            with concurrent.futures.ThreadPoolExecutor(workers) as pool:
                for lo in range(0, end, host_batch):
                    take = idx[lo:lo + host_batch]
                    blobs = list(pool.map(
                        lambda p: open(p, "rb").read(), paths_t[take]))
                    if train:
                        # process_index mixed into the seed: index_base is
                        # shard-LOCAL, so without it every host at the same
                        # epoch position would draw identical crop/jitter
                        # parameters for different images (ADVICE r4).  The
                        # C++ side multiplies seed by the splitmix64
                        # constant, so distinct seeds are disjoint stream
                        # families; single-host runs (index 0) keep the
                        # committed evidence streams unchanged.
                        v1, v2 = native_aug.jpeg_augment_two_views(
                            blobs, size, color_jitter_strength=cj,
                            seed=(seed + 1_000_003 * epoch
                                  + 7_919 * index),
                            index_base=int(lo), num_threads=workers)
                    else:
                        v1 = native_aug.jpeg_resize_batch(
                            blobs, size, num_threads=workers)
                        v2 = v1
                    yield {"view1": v1, "view2": v2,
                           "label": labels_t[take]}

        def make(epoch: int) -> Iterator[dict]:
            q: queue_lib.Queue = queue_lib.Queue(maxsize=2)
            DONE = object()
            stop = threading.Event()   # consumer abandoned the iterator

            def _put(item) -> bool:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        return True
                    except queue_lib.Full:
                        continue
                return False

            def worker():
                gen = produce(epoch)
                try:
                    for item in gen:
                        if not _put(item):
                            return       # abandoned: stop producing
                    _put(DONE)
                except BaseException as e:   # surface errors, don't hang
                    _put(e)
                finally:
                    gen.close()          # closes the read thread pool

            threading.Thread(target=worker, daemon=True).start()
            try:
                while True:
                    item = q.get()
                    if item is DONE:
                        return
                    if isinstance(item, BaseException):
                        raise item
                    yield item
            finally:
                # break early / GeneratorExit: release the producer thread
                # and its thread pool instead of leaking them blocked on a
                # full queue (each leak pins workers + two buffered batches)
                stop.set()

        return make

    def make_iter(paths, labels, train: bool
                  ) -> Callable[[int], Iterator[dict]]:
        if backend == "native":
            return make_native_iter(paths, labels, train)
        paths_t = np.asarray(paths)
        labels_t = np.asarray(labels, np.int32)

        def make(epoch: int):
            ds = tf.data.Dataset.from_tensor_slices(
                {"path": paths_t, "label": labels_t,
                 "index": np.arange(len(labels_t), dtype=np.int64)})
            if train:
                ds = ds.shuffle(min(len(labels_t), 100_000),
                                seed=seed + epoch,
                                reshuffle_each_iteration=False)

            def _load(ex):
                data = tf.io.read_file(ex["path"])
                if train:
                    # 100_003 * process_index: same cross-host
                    # decorrelation as the native path (ex["index"] is
                    # shard-local); epochs stay well below 100_003, so
                    # (epoch, host) seed pairs never collide
                    s0 = tf.stack([tf.cast(ex["index"], tf.int32),
                                   tf.constant(seed, tf.int32) + epoch
                                   + 100_003 * index])
                    # Proper seed splitting (not additive offsets, which
                    # collide across samples: i's view2 == (i+k)'s view1).
                    view_seeds = augment._split(s0, 2)
                    views = []
                    for vi, sv in enumerate(view_seeds):
                        s_crop, s_rest = augment._split(sv, 2)
                        crop = tf.cond(
                            _is_jpeg(ex["path"]),
                            lambda s=s_crop: _fused_decode_random_crop(
                                data, s, size),
                            lambda s=s_crop: augment.random_resized_crop(
                                _decode_full(data), size, s))
                        views.append(augment.post_crop_augment(
                            crop, size, s_rest, cj,
                            **augment.view_params(
                                cfg.regularizer.aug_spec, vi)))
                    return {"view1": views[0], "view2": views[1],
                            "label": ex["label"]}
                img = augment.test_resize(_decode_full(data), size)
                return {"view1": img, "view2": img, "label": ex["label"]}

            ds = ds.map(_load, num_parallel_calls=tf.data.AUTOTUNE)
            ds = ds.batch(host_batch, drop_remainder=train)
            ds = ds.prefetch(tf.data.AUTOTUNE)
            return ds.as_numpy_iterator()

        return make

    return LoaderBundle(
        make_train_iter=make_iter(*tr_sh, train=True),
        make_test_iter=make_iter(*te_sh, train=False),
        input_shape=(size, size, 3),
        num_train_samples=n_train,
        num_test_samples=n_test,
        output_size=len(classes),
        make_train_eval_iter=make_iter(*tr_sh, train=False),
        eval_sharded=shard_eval and count > 1,
        make_valid_iter=(make_iter(*va_sh, train=False) if n_valid
                         else None),
        num_valid_samples=n_valid,
    )
