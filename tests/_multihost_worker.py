"""Worker process for the two-process multi-host integration test.

Runs the REAL stack end-to-end under explicit rendezvous: CPU backend, two
processes x two devices, per-host data sharding, multi-host batch assembly
(jax.make_array_from_process_local_data path of shard_batch_to_mesh), one
jitted SPMD train step with cross-process collectives (Gloo), and prints the
loss for the parent to compare across ranks.
"""
import sys

import jax

from byol_tpu.core import preflight

preflight.force_cpu_devices(2)
preflight.place_compile_cache()


def main() -> int:
    rank = int(sys.argv[1])
    port = sys.argv[2]
    from byol_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                        initialize_distributed,
                                        shard_batch_to_mesh)
    initialize_distributed(f"localhost:{port}", num_processes=2,
                           process_id=rank)
    assert jax.process_count() == 2
    assert jax.device_count() == 4 and len(jax.local_devices()) == 2

    from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                      TaskConfig, resolve)
    from byol_tpu.data.loader import get_loader
    from byol_tpu.training.build import setup_training

    cfg = Config(
        task=TaskConfig(task="fake", batch_size=8, epochs=1,
                        image_size_override=16),
        model=ModelConfig(arch="resnet18", head_latent_size=32,
                          projection_size=16),
        device=DeviceConfig(num_replicas=4, half=False, seed=3),
    )
    # per-host shard: each process sees 8 of 16 samples, host batch 4
    loader = get_loader(cfg, num_fake_samples=16)
    batch = next(loader.train_loader)
    assert len(batch["label"]) == 4, batch["label"].shape

    rcfg = resolve(cfg, num_train_samples=loader.num_train_samples,
                   num_test_samples=loader.num_test_samples,
                   output_size=loader.output_size,
                   input_shape=loader.input_shape)
    mesh = build_mesh(MeshSpec(data=4))
    net, state, train_step, eval_step, _ = setup_training(
        rcfg, mesh, jax.random.PRNGKey(0))

    dev_batch = shard_batch_to_mesh(batch, mesh)
    assert dev_batch["label"].shape[0] == 8      # assembled GLOBAL batch
    state, metrics = train_step(state, dev_batch)
    loss = float(metrics["loss_mean"])           # forces cross-host psum
    print(f"RANK{rank} OK loss={loss:.6f} step={int(state.step)}")

    # Offline linear eval ACROSS processes (round-3 review gap: the paper metric
    # must be computable on the pod config): SPMD feature extraction over
    # per-host loader shards, probe fit host-locally on the gathered global
    # features — both ranks must report the identical top-1.
    from byol_tpu.training.linear_eval import run_linear_eval_from_cfg
    le = run_linear_eval_from_cfg(cfg, state, loader=loader, mesh=mesh,
                                  epochs=2, seed=0)
    print(f"RANK{rank} LE top1={le.top1:.6f} ntrain={le.num_train} "
          f"ntest={le.num_test}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
