"""Worker for the two-process UNEVEN-SHARD image_folder integration test.

The hard multi-host case the round-4 machinery exists for: an ImageFolder
tree whose interleaved per-host shards differ in size, so naive per-host
iteration would give hosts different train/eval batch counts and deadlock
the SPMD collectives.  Covers, across two real OS processes (Gloo):

- train: ``epoch_batches`` pins every host to steps_per_train_epoch
  (wrap/truncate) — the epoch completes with the step counters equal;
- eval: ``lockstep_iter`` pad-feeds the short host;
- offline linear eval: SPMD extraction + lockstep drain + Quirk-Q9
  round-robin de-dup — both ranks must report identical results.

argv: rank port tree_dir
"""
import sys

import jax

from byol_tpu.core import preflight

preflight.force_cpu_devices(2)
preflight.place_compile_cache()


def main() -> int:
    rank, port, tree = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    from byol_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                        initialize_distributed)
    initialize_distributed(f"localhost:{port}", num_processes=2,
                           process_id=rank)
    assert jax.process_count() == 2

    from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                      OptimConfig, TaskConfig)
    from byol_tpu.data.loader import get_loader
    from byol_tpu.training.linear_eval import run_linear_eval_from_cfg
    from byol_tpu.training.trainer import fit

    cfg = Config(
        # 11 train files -> interleaved shards of 6 and 5; host batch 2 ->
        # hosts would naively run 3 vs 2 train batches.  7 test files ->
        # eval remainder batches of different counts under shard_eval.
        task=TaskConfig(task="image_folder", data_dir=tree, batch_size=4,
                        epochs=1, image_size_override=16, grapher="null",
                        log_dir="/tmp/mh_if_runs"),
        model=ModelConfig(arch="resnet18", head_latent_size=32,
                          projection_size=16, fuse_views=True,
                          model_dir=f"/tmp/mh_if_models_{port}"),
        optim=OptimConfig(lr=0.1, warmup=1),
        device=DeviceConfig(num_replicas=4, half=False, seed=3,
                            shard_eval=True, save_on_signal=False),
    )
    loader = get_loader(cfg, shard_eval=True)
    assert loader.num_train_samples == 11 and loader.num_test_samples == 7
    result = fit(cfg, loader=loader, verbose=False)
    # steps_per_train_epoch = (11 // 4) // (4 // 4) = 2 on EVERY host
    assert int(result.state.step) == 2, int(result.state.step)
    print(f"RANK{rank} FIT ok step={int(result.state.step)} "
          f"test_loss={result.test_metrics['loss_mean']:.6f}")

    le = run_linear_eval_from_cfg(cfg, result.state, loader=loader,
                                  mesh=result.mesh, epochs=2, seed=0)
    print(f"RANK{rank} LE top1={le.top1:.6f} ntrain={le.num_train} "
          f"ntest={le.num_test}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
