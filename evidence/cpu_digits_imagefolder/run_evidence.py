"""FLAGSHIP-TASK evidence run: the full ``image_folder`` path end-to-end.

The reference's default task is an on-disk ImageFolder tree
(``multi_augment_image_folder``, main.py:38-39, README.md:82).  Until this
run the repo's flagship task had only a 12-image unit test (round-3 review);
here the REAL digits images (sklearn's bundled UCI set — the same data as
evidence/cpu_digits*, giving a direct A/B) are rendered to an on-disk JPEG
ImageFolder tree and trained through the production path:

  JPEG tree -> tf.data fused ``decode_and_crop_jpeg`` (only the sampled
  RandomResizedCrop window is decoded) -> two-view augment -> SPMD train
  on the 8-virtual-device CPU mesh -> offline linear eval (features
  re-extracted through the same fused-decode eval pipeline).

Hyperparameters mirror evidence/cpu_digits exactly (resnet18, 16px
pipeline, bs64, 8 epochs, lr .4, seed 11), so the delta vs that run
isolates the JPEG round-trip + ImageFolder pipeline: cpu_digits measured
86.9% offline top-1 from in-memory arrays.
"""
import sys, os; sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from byol_tpu.core.preflight import place_compile_cache
place_compile_cache()

import numpy as np

TREE = "/tmp/digits_imagefolder"


def render_tree():
    """digits arrays -> JPEG ImageFolder tree (train/ and test/ roots,
    reference README.md:82 layout), deterministic."""
    from PIL import Image

    from byol_tpu.data.readers import load_digits_img
    if os.path.isdir(TREE):
        import shutil
        shutil.rmtree(TREE)
    for split, train in (("train", True), ("test", False)):
        x, y = load_digits_img(train=train)
        for cls in range(10):
            os.makedirs(os.path.join(TREE, split, f"{cls}"))
        counters = {}
        for img, label in zip(x, y):
            i = counters.get(int(label), 0)
            counters[int(label)] = i + 1
            Image.fromarray(img).save(
                os.path.join(TREE, split, f"{label}", f"{i:04d}.jpg"),
                quality=95)
    n_tr = sum(len(files) for _, _, files in os.walk(f"{TREE}/train"))
    n_te = sum(len(files) for _, _, files in os.walk(f"{TREE}/test"))
    print(f"rendered {n_tr} train / {n_te} test JPEGs under {TREE}")


render_tree()

from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                  OptimConfig, TaskConfig)
from byol_tpu.data.loader import get_loader
from byol_tpu.training.trainer import fit
from byol_tpu.training.linear_eval import run_linear_eval_from_cfg

cfg = Config(
    task=TaskConfig(task="image_folder", data_dir=TREE, batch_size=64,
                    epochs=8, image_size_override=16,
                    log_dir="/tmp/evd_runs", uid="cpu_digits_imagefolder",
                    grapher="both"),
    model=ModelConfig(arch="resnet18", head_latent_size=64,
                      projection_size=32, fuse_views=True,
                      model_dir="/tmp/evd_models"),
    optim=OptimConfig(lr=0.4, warmup=1, optimizer="lars_momentum"),
    device=DeviceConfig(num_replicas=8, half=False, seed=11),
)
loader = get_loader(cfg)
assert loader.num_train_samples == 1500 and loader.num_test_samples == 297
result = fit(cfg, loader=loader)
le = run_linear_eval_from_cfg(cfg, result.state, loader=loader, seed=11)
print(f"linear_eval: top1={le.top1:.1f} top5={le.top5:.1f} "
      f"train_acc={le.train_acc:.1f} n={le.num_train}/{le.num_test}")
