"""ViT BYOL learning-evidence run on REAL images (digits, 12 epochs).

The committed synth/digits evidence runs all use resnet18; this run
evidences the SECOND model family end-to-end: a tiny ViT backbone
(width 64, depth 2, patch 4 -> 16 tokens at 16px, gap pooling, BN-free
LARS-exclusion path) learning BYOL representations from the same pinned
1500/297 digits split, scored by the offline linear protocol.  adam
replaces LARS (the ViT-typical choice; the reference's optimizer
registry carries both, main.py:311-318).
"""
import sys, os; sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import jax
import jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from byol_tpu.core.preflight import place_compile_cache
place_compile_cache()

from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig, RegularizerConfig,
                                  OptimConfig, TaskConfig)
from byol_tpu.data.loader import get_loader
from byol_tpu.models import registry
from byol_tpu.models import vit as vit_lib
from byol_tpu.training.trainer import fit
from byol_tpu.training.linear_eval import run_linear_eval_from_cfg

registry.register("vit_tiny_ev", registry.BackboneSpec(
    factory=lambda dtype=jnp.float32, small_inputs=False, **kw:
        vit_lib.ViT(width=64, depth=2, num_heads=4, patch_size=4,
                    dtype=dtype, **kw),
    feature_dim=64, has_batchnorm=False))

cfg = Config(
    task=TaskConfig(task="digits", batch_size=64, epochs=96,
                    image_size_override=16, log_dir="/tmp/evp_runs",
                    uid="cpu_digits_vit_paperaug", grapher="both"),
    model=ModelConfig(arch="vit_tiny_ev", head_latent_size=64,
                      projection_size=32, fuse_views=True, pooling="gap",
                      model_dir="/tmp/evp_models"),
    optim=OptimConfig(lr=1e-3, warmup=1, optimizer="adam"),
    regularizer=RegularizerConfig(aug_spec="paper"),
    device=DeviceConfig(num_replicas=8, half=False, seed=11),
)
loader = get_loader(cfg)
result = fit(cfg, loader=loader)
le = run_linear_eval_from_cfg(cfg, result.state, loader=loader, seed=11)
print(f"linear_eval: top1={le.top1:.1f} top5={le.top5:.1f} "
      f"train_acc={le.train_acc:.1f} n={le.num_train}/{le.num_test}")
