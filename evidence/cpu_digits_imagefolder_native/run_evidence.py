"""Native-backend A/B for the flagship image_folder run: the SAME JPEG
tree, trained with ``--data-backend native`` — the first-party C++ libjpeg
fused decode+crop pipeline (data/native/image_pipeline.cpp) — instead of
tf.data.  3 epochs: enough to compare the BYOL trajectory epoch-for-epoch
against evidence/cpu_digits_imagefolder (tf fused decode; -0.756, -2.216,
-2.306) and prove the native DALI-analog path trains end-to-end through
train.py, not only through unit tests and the host bench.
"""
import sys, os; sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from byol_tpu.core.preflight import place_compile_cache
place_compile_cache()

TREE = "/tmp/digits_imagefolder"

if not os.path.isdir(TREE):
    # identical tree to the sibling tf-backend run (same renderer logic:
    # digits arrays -> 32x32 q95 JPEGs, class-per-subdirectory)
    from PIL import Image

    from byol_tpu.data.readers import load_digits_img
    for split, train in (("train", True), ("test", False)):
        x, y = load_digits_img(train=train)
        for cls in range(10):
            os.makedirs(os.path.join(TREE, split, f"{cls}"), exist_ok=True)
        counters = {}
        for img, label in zip(x, y):
            i = counters.get(int(label), 0)
            counters[int(label)] = i + 1
            Image.fromarray(img).save(
                os.path.join(TREE, split, f"{label}", f"{i:04d}.jpg"),
                quality=95)
    print(f"rendered JPEG tree under {TREE}")

from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                  OptimConfig, TaskConfig)
from byol_tpu.data.loader import get_loader
from byol_tpu.training.trainer import fit
from byol_tpu.training.linear_eval import run_linear_eval_from_cfg

cfg = Config(
    task=TaskConfig(task="image_folder", data_dir=TREE, batch_size=64,
                    epochs=3, image_size_override=16,
                    log_dir="/tmp/evd_runs",
                    uid="cpu_digits_imagefolder_native",
                    grapher="both", data_backend="native"),
    model=ModelConfig(arch="resnet18", head_latent_size=64,
                      projection_size=32, fuse_views=True,
                      model_dir="/tmp/evd_models"),
    optim=OptimConfig(lr=0.4, warmup=1, optimizer="lars_momentum"),
    device=DeviceConfig(num_replicas=8, half=False, seed=11,
                        workers_per_replica=2),
)
loader = get_loader(cfg)
assert loader.num_train_samples == 1500 and loader.num_test_samples == 297
result = fit(cfg, loader=loader)
le = run_linear_eval_from_cfg(cfg, result.state, loader=loader, seed=11)
print(f"linear_eval: top1={le.top1:.1f} top5={le.top5:.1f} "
      f"train_acc={le.train_acc:.1f} n={le.num_train}/{le.num_test}")
