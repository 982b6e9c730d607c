"""REAL-image learning-evidence run: sklearn's bundled UCI digits task.

Same harness as the synth evidence runs (evidence/cpu_synth*/): 8-virtual-
device CPU mesh, resnet18, fuse_views, offline linear eval — but on real
handwritten-digit photographs-of-ink rather than procedural templates
(data/readers.py:load_digits_img).  16px pipeline size keeps the 1-core
box's epoch under ~10 min.
"""
import sys, os; sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from byol_tpu.core.preflight import place_compile_cache
place_compile_cache()

from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                  OptimConfig, TaskConfig)
from byol_tpu.data.loader import get_loader
from byol_tpu.training.trainer import fit
from byol_tpu.training.linear_eval import run_linear_eval_from_cfg

cfg = Config(
    task=TaskConfig(task="digits", batch_size=64, epochs=8,
                    image_size_override=16, log_dir="/tmp/evr_runs",
                    uid="cpu_digits_resume", grapher="both"),
    model=ModelConfig(arch="resnet18", head_latent_size=64,
                      projection_size=32, fuse_views=True,
                      model_dir="/tmp/evr_models"),
    optim=OptimConfig(lr=0.4, warmup=1, optimizer="lars_momentum"),
    device=DeviceConfig(num_replicas=8, half=False, seed=11),
)
loader = get_loader(cfg)
result = fit(cfg, loader=loader)
le = run_linear_eval_from_cfg(cfg, result.state, loader=loader, seed=11)
print(f"linear_eval: top1={le.top1:.1f} top5={le.top5:.1f} "
      f"train_acc={le.train_acc:.1f} n={le.num_train}/{le.num_test}")
