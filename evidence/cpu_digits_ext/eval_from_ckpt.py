"""Direct-restore offline linear eval of the digits_ext run: build the
training-shaped state, restore the LAST (mid-epoch-9 SIGTERM) checkpoint
from the run's own directory, run the offline protocol."""
import sys
sys.path.insert(0, "/root/repo")
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from byol_tpu.core.preflight import place_compile_cache
place_compile_cache()

from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                  OptimConfig, TaskConfig, resolve)
from byol_tpu.checkpoint import ModelSaver
from byol_tpu.data.loader import get_loader
from byol_tpu.parallel.mesh import MeshSpec, build_mesh
from byol_tpu.training.build import setup_training
from byol_tpu.training.linear_eval import run_linear_eval_from_cfg

cfg = Config(
    task=TaskConfig(task="digits", batch_size=64, epochs=16,
                    image_size_override=16, uid="digits_ext"),
    model=ModelConfig(arch="resnet18", head_latent_size=64,
                      projection_size=32, fuse_views=True),
    optim=OptimConfig(lr=0.4, warmup=1, optimizer="lars_momentum"),
    device=DeviceConfig(num_replicas=8, half=False, seed=11),
)
loader = get_loader(cfg)
rcfg = resolve(cfg, num_train_samples=loader.num_train_samples,
               num_test_samples=loader.num_test_samples,
               output_size=loader.output_size,
               input_shape=loader.input_shape)
mesh = build_mesh(MeshSpec(data=8))
_, state, _, _, _ = setup_training(rcfg, mesh, jax.random.PRNGKey(11))
saver = ModelSaver("/tmp/digits_ext_models/digits_ext_resnet18_b64_5913e8dd")
state, next_epoch = saver.restore(state, best=False)
print(f"restored checkpoint; next_epoch={next_epoch}, step={int(state.step)}")
le = run_linear_eval_from_cfg(cfg, state, loader=loader, seed=11)
print(f"linear_eval: top1={le.top1:.1f} top5={le.top5:.1f} "
      f"train_acc={le.train_acc:.1f} n={le.num_train}/{le.num_test}")
