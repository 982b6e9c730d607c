"""Seed replicates of the cpu_digits array run: measure the noise band.

The round-4 three-path image_folder A/B (arrays 86.9 / tf-jpeg 87.5 /
native-jpeg 85.9 top-1 at n=297) calls its ~1.6 pt spread "inside the
augmentation-stream noise band" — but that band was asserted, not
measured.  This run measures it: the exact `evidence/cpu_digits`
configuration (resnet18, 16px, bs 64 over data=8, fuse_views, fp32,
lars_momentum lr .4 warmup 1, 8 epochs) at two additional seeds (12, 13;
seed 11 is the committed 86.9 run), so the arrays path contributes a
3-point seed distribution and the cross-path spread can be read against
within-path seed noise.

A third, shorter run exercises the round-4 ``--valid-fraction`` surface at
evidence scale (reference main.py:421-423 num_valid_samples contract):
seed 11 with valid_fraction=0.15, 3 epochs — per-epoch valid-split eval
(pad+mask lockstep, resize-only transform) through the real trainer loop,
not just the unit tests.
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


def run_one(seed: int, *, epochs: int = 8, valid_fraction: float = 0.0,
            tag: str = "") -> None:
    uid = f"cpu_digits_s{seed}{tag}"
    cfg = Config(
        task=TaskConfig(task="digits", batch_size=64, epochs=epochs,
                        image_size_override=16, log_dir="/tmp/evd_runs",
                        uid=uid, grapher="both",
                        valid_fraction=valid_fraction),
        model=ModelConfig(arch="resnet18", head_latent_size=64,
                          projection_size=32, fuse_views=True,
                          model_dir="/tmp/evd_models"),
        optim=OptimConfig(lr=0.4, warmup=1, optimizer="lars_momentum"),
        device=DeviceConfig(num_replicas=8, half=False, seed=seed),
    )
    print(f"=== run {uid}: seed={seed} epochs={epochs} "
          f"valid_fraction={valid_fraction} ===", flush=True)
    loader = get_loader(cfg)
    result = fit(cfg, loader=loader)
    le = run_linear_eval_from_cfg(cfg, result.state, loader=loader,
                                  seed=seed)
    print(f"linear_eval[{uid}]: top1={le.top1:.1f} top5={le.top5:.1f} "
          f"train_acc={le.train_acc:.1f} n={le.num_train}/{le.num_test}",
          flush=True)


if __name__ == "__main__":
    run_one(12)
    run_one(13)
    run_one(11, epochs=3, valid_fraction=0.15, tag="_valid")
    print("all seed-replicate runs complete", flush=True)
