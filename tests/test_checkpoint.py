"""Checkpoint round-trip + ModelSaver early-stop semantics.

The reference could not test its resume path at all (SURVEY.md §4); these
cover the ModelSaver contract (main.py:750-769) plus the Quirk Q6 fix:
``ema_step`` must survive a save/restore cycle so the cosine tau schedule
continues instead of restarting.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from byol_tpu.checkpoint import CheckpointStore, ModelSaver, abstract_like
from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                  TaskConfig, resolve)
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh
from byol_tpu.training.build import setup_training


def _tiny_setup(mesh, tmp_path, seed=0):
    cfg = Config(
        task=TaskConfig(task="fake", batch_size=16, epochs=4,
                        image_size_override=16),
        model=ModelConfig(arch="resnet18", head_latent_size=32,
                          projection_size=16),
        device=DeviceConfig(num_replicas=8, half=False, seed=seed),
    )
    rcfg = resolve(cfg, num_train_samples=64, num_test_samples=16,
                   output_size=10, input_shape=(16, 16, 3))
    return rcfg, setup_training(rcfg, mesh, jax.random.PRNGKey(seed))


def _batch(mesh, b=16, size=16, seed=0):
    rng = np.random.RandomState(seed)
    batch = {
        "view1": rng.rand(b, size, size, 3).astype(np.float32),
        "view2": rng.rand(b, size, size, 3).astype(np.float32),
        "label": rng.randint(0, 10, size=(b,)).astype(np.int32),
    }
    return shard_batch_to_mesh(batch, mesh)


@pytest.mark.slow
def test_roundtrip_preserves_full_state(mesh8, tmp_path):
    _, (net, state, train_step, _, _) = _tiny_setup(mesh8, tmp_path)
    batch = _batch(mesh8)
    for _ in range(3):
        state, _ = train_step(state, batch)

    store = CheckpointStore(str(tmp_path / "ckpt"))
    store.save(0, state)
    restored, epoch = store.restore(abstract_like(state))
    assert epoch == 0

    # Every leaf identical — params, target EMA tree, opt state, counters.
    flat_a = jax.tree_util.tree_leaves_with_path(state)
    flat_b = {jax.tree_util.keystr(k): v
              for k, v in jax.tree_util.tree_leaves_with_path(restored)}
    assert len(flat_a) == len(flat_b)
    for k, v in flat_a:
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(flat_b[jax.tree_util.keystr(k)]),
                                      err_msg=jax.tree_util.keystr(k))
    # Quirk Q6 fix: the tau-schedule counter is part of the checkpoint.
    assert int(restored.ema_step) == 3
    store.close()


@pytest.mark.slow
def test_resume_continues_training(mesh8, tmp_path):
    """Restored state must be usable by the jitted step and keep counting."""
    _, (net, state, train_step, _, _) = _tiny_setup(mesh8, tmp_path)
    batch = _batch(mesh8)
    state, _ = train_step(state, batch)
    store = CheckpointStore(str(tmp_path / "ckpt"))
    store.save(0, state)
    restored, _ = store.restore(abstract_like(state))
    restored, metrics = train_step(restored, batch)
    assert np.isfinite(float(metrics["loss_mean"]))
    assert int(restored.step) == 2 and int(restored.ema_step) == 2
    store.close()


def test_model_saver_burn_in_and_best(mesh8, tmp_path):
    _, (net, state, train_step, _, _) = _tiny_setup(mesh8, tmp_path)
    saver = ModelSaver(str(tmp_path / "ms"), early_stop=False,
                       burn_in_interval=2, keep=2)
    # epochs 0,1 are burn-in: saved for preemption-resume, but never "best".
    assert not saver(1.0, 0, state)
    assert not saver(0.9, 1, state)
    assert saver.has_checkpoint()
    assert "best_epoch" not in saver.store.read_meta()
    # epoch 2 improves -> becomes best.
    assert not saver(0.5, 2, state)
    assert saver.has_checkpoint()
    assert saver.store.read_meta()["best_epoch"] == 2
    # worse epoch still saved as "last" but best pointer stays.
    assert not saver(0.7, 3, state)
    meta = saver.store.read_meta()
    assert meta["best_epoch"] == 2 and meta["last_epoch"] == 3
    restored, next_epoch = saver.restore(state, best=True)
    assert next_epoch == 3
    saver.close()


def test_model_saver_early_stop_patience(tmp_path):
    state = {"w": jnp.arange(4.0)}
    saver = ModelSaver(str(tmp_path / "es"), early_stop=True,
                       burn_in_interval=0, max_early_stop_steps=3)
    assert not saver(1.0, 0, state)
    assert not saver(0.5, 1, state)     # improvement resets patience
    assert not saver(0.6, 2, state)     # stall 1
    assert not saver(0.6, 3, state)     # stall 2
    assert saver(0.7, 4, state)         # stall 3 -> stop
    saver.close()


def test_burn_in_does_not_hold_best(tmp_path):
    """A good burn-in metric must not shadow post-burn-in saves: the first
    epoch after burn-in is always saved as best."""
    state = {"w": jnp.ones((2,))}
    saver = ModelSaver(str(tmp_path / "bi"), early_stop=True,
                       burn_in_interval=2, max_early_stop_steps=5)
    assert not saver(0.1, 0, state)   # burn-in, better than anything later
    assert not saver(0.2, 1, state)   # burn-in
    assert not saver(1.0, 2, state)   # first real epoch -> must become best
    meta = saver.store.read_meta()
    assert meta["best_epoch"] == 2 and saver.best_metric == 1.0
    assert saver.stall_count == 0
    saver.close()


def test_model_saver_larger_is_better(tmp_path):
    state = {"w": jnp.ones((2,))}
    saver = ModelSaver(str(tmp_path / "acc"), early_stop=True,
                       larger_is_better=True, max_early_stop_steps=2)
    assert not saver(0.1, 0, state)
    assert not saver(0.3, 1, state)
    assert not saver(0.2, 2, state)
    assert saver(0.2, 3, state)
    assert saver.store.read_meta()["best_epoch"] == 1
    saver.close()


def test_early_stop_marker_is_durable(tmp_path):
    """Once a run early-stops, a relaunched ModelSaver must report it so
    fit() can short-circuit instead of re-burning patience epochs."""
    state = {"w": jnp.ones((2,))}
    saver = ModelSaver(str(tmp_path / "es2"), early_stop=True,
                       max_early_stop_steps=2)
    saver(0.5, 0, state)
    saver(0.9, 1, state)
    assert saver(0.9, 2, state)  # stop fires
    saver.close()
    relaunched = ModelSaver(str(tmp_path / "es2"), early_stop=True,
                            max_early_stop_steps=2)
    assert relaunched.stopped_early
    # and the best checkpoint is still restorable
    restored, next_epoch = relaunched.restore(state, best=True)
    assert next_epoch == 1
    relaunched.close()


def test_plain_resume_uses_last_not_best(tmp_path):
    """A plain relaunch must continue from the LAST checkpoint — restoring
    best would discard post-best training on every restart (round-1 advisor
    finding; reference contract main.py:753-754 resumes, best-restore is the
    early-stop terminal path main.py:767-769)."""
    saver = ModelSaver(str(tmp_path / "pl"), early_stop=False, keep=3)
    saver(0.5, 0, {"w": jnp.zeros((2,))})       # best
    saver(0.9, 1, {"w": jnp.ones((2,))})        # worse, last
    saver.close()
    relaunched = ModelSaver(str(tmp_path / "pl"), early_stop=False)
    restored, next_epoch = relaunched.restore({"w": jnp.zeros((2,))},
                                              best=False)
    assert next_epoch == 2                       # continues after epoch 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones((2,)))
    # stall count must NOT be reset by a plain (last) resume
    assert relaunched.stall_count == 1
    restored_best, next_best = relaunched.restore({"w": jnp.zeros((2,))},
                                                  best=True)
    assert next_best == 1
    np.testing.assert_array_equal(np.asarray(restored_best["w"]),
                                  np.zeros((2,)))
    relaunched.close()


def test_restore_falls_back_when_meta_points_at_missing_ckpt(tmp_path):
    """Crash between async-save schedule and commit: meta.json names a
    ckpt dir that never hit disk.  restore() must fall back to the newest
    on-disk checkpoint instead of raising (round-1 advisor finding)."""
    import shutil
    store = CheckpointStore(str(tmp_path / "crash"))
    store.save(0, {"w": jnp.zeros((2,))})
    store.save(1, {"w": jnp.ones((2,))}, metric=0.1, is_best=True)
    store._ckptr.wait_until_finished()
    # Simulate the crash: ckpt-1 committed in meta but gone from disk.
    shutil.rmtree(str(tmp_path / "crash" / "ckpt-1"))
    assert store.read_meta()["last_epoch"] == 1
    restored, epoch = store.restore(abstract_like({"w": jnp.zeros((2,))}))
    assert epoch == 0
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.zeros((2,)))
    # best also points at the vanished ckpt -> same fallback
    restored, epoch = store.restore(abstract_like({"w": jnp.zeros((2,))}),
                                    best=True)
    assert epoch == 0
    store.close()


def test_best_fallback_picks_best_surviving_metric(tmp_path):
    """When the best ckpt dir is lost pre-commit, restore(best=True) must
    pick the best-metric SURVIVING checkpoint, not simply the newest (which
    after an early-stop stall is typically the worst)."""
    import shutil
    store = CheckpointStore(str(tmp_path / "bf"))
    vals = {0: 0.5, 1: 0.2, 2: 0.9, 3: 0.1}
    for e, m in vals.items():
        store.save(e, {"w": jnp.full((2,), float(e))}, metric=m,
                    is_best=(m == min(list(vals.values())[:e + 1])),
                    keep=10)
    store._ckptr.wait_until_finished()
    shutil.rmtree(str(tmp_path / "bf" / "ckpt-3"))   # lose the best
    restored, epoch = store.restore(abstract_like({"w": jnp.zeros((2,))}),
                                    best=True)
    assert epoch == 1                                # 0.2 beats 0.5 and 0.9
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.full((2,), 1.0))
    store.close()


def test_explicit_epoch_restore_never_substitutes(tmp_path):
    """An explicitly requested epoch must raise if missing — silent
    substitution is only for meta-derived epochs."""
    store = CheckpointStore(str(tmp_path / "ex"))
    store.save(0, {"w": jnp.zeros((2,))})
    store._ckptr.wait_until_finished()
    with pytest.raises(Exception):
        store.restore(abstract_like({"w": jnp.zeros((2,))}), epoch=7)
    store.close()


def test_burn_in_preemption_resume(tmp_path):
    """Preemption during burn-in must be resumable: burn-in epochs are saved
    (as last) even though best/patience tracking is suppressed."""
    saver = ModelSaver(str(tmp_path / "bires"), early_stop=True,
                       burn_in_interval=10, max_early_stop_steps=3)
    saver(1.0, 0, {"w": jnp.zeros((2,))})
    saver(0.9, 1, {"w": jnp.ones((2,))})
    saver.close()
    relaunched = ModelSaver(str(tmp_path / "bires"), early_stop=True,
                            burn_in_interval=10, max_early_stop_steps=3)
    assert relaunched.has_checkpoint()
    restored, next_epoch = relaunched.restore({"w": jnp.zeros((2,))},
                                              best=False)
    assert next_epoch == 2
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones((2,)))
    assert relaunched.best_metric is None and relaunched.stall_count == 0
    relaunched.close()


def _zero1_setup(mesh, *, data=8, accum=1):
    """ZeRO-1 training on ``mesh``; reuses test_zero1's config (identical
    jit cache keys -> the tier-1 run compiles this program once)."""
    from byol_tpu.parallel.compile_plan import build_plan
    from tests.test_zero1 import _rcfg
    import dataclasses as _dc
    rcfg = _rcfg(zero1="on", accum=accum)
    if data != 8:
        rcfg = resolve(
            rcfg.cfg.replace(device=_dc.replace(rcfg.cfg.device,
                                                num_replicas=data)),
            num_train_samples=64, num_test_samples=16, output_size=10,
            input_shape=(16, 16, 3), representation_size=512)
    plan = build_plan(mesh, zero1=True)
    return plan, setup_training(rcfg, mesh, jax.random.PRNGKey(0),
                                plan=plan)


def _canon_equal(a, b):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = {jax.tree_util.keystr(k): v
          for k, v in jax.tree_util.tree_leaves_with_path(b)}
    assert len(fa) == len(fb)
    for k, v in fa:
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(fb[jax.tree_util.keystr(k)]),
            err_msg=jax.tree_util.keystr(k))


def test_zero1_roundtrip_on_multidevice_mesh(mesh8, tmp_path):
    """ISSUE 7 checkpoint satellite (1/2): ZeRO-1 flat-sharded state
    save/restores on the 8-virtual-device CPU mesh.  Checkpoints store the
    CANONICAL (unflattened, replicated) layout via the compile plan's
    codec — the round trip through to_canonical -> disk ->
    canonical_template -> from_canonical must be exact and the restored
    state must be steppable."""
    from tests.test_zero1 import _batch as z1_batch
    plan, (net, state, train_step, _, _) = _zero1_setup(mesh8)
    batch = shard_batch_to_mesh(z1_batch(seed=0), mesh8)
    state, _ = train_step(state, batch)

    store = CheckpointStore(str(tmp_path / "z1"))
    canon = plan.to_canonical(state)
    # the canonical view really is mesh-portable: no flat leaves, no
    # data-axis shards left anywhere
    for leaf in jax.tree_util.tree_leaves(
            (canon.opt_state, canon.target_params)):
        assert "data" not in str(leaf.sharding.spec)
    store.save(0, canon)
    restored, epoch = store.restore(plan.canonical_template(state))
    assert epoch == 0
    _canon_equal(canon, restored)

    # back to plan layout: flat-sharded again, and usable by the step
    live = plan.from_canonical(restored)
    from byol_tpu.parallel.mesh import DATA_AXIS
    assert any(DATA_AXIS in str(leaf.sharding.spec) for leaf in
               jax.tree_util.tree_leaves(live.opt_state)
               if getattr(leaf, "ndim", 0) == 1)
    _canon_equal(canon, plan.to_canonical(live))
    live, metrics = train_step(live, batch)
    assert np.isfinite(float(metrics["loss_mean"]))
    assert int(live.step) == 2 and int(live.ema_step) == 2
    store.close()


def test_zero1_reshard_on_restore_different_device_count(mesh8, tmp_path):
    """ISSUE 7 checkpoint satellite (2/2): a checkpoint written under an
    8-way ZeRO-1 plan restores cleanly into a 4-way plan (different shard
    count, different zero padding) — reshard-on-restore, exact because
    the canonical layout never depends on the mesh size."""
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    from tests.test_zero1 import _batch as z1_batch
    plan8, (_, state8, step8, _, _) = _zero1_setup(mesh8)
    batch8 = shard_batch_to_mesh(z1_batch(seed=0), mesh8)
    state8, _ = step8(state8, batch8)
    store = CheckpointStore(str(tmp_path / "z18"))
    canon8 = plan8.to_canonical(state8)
    store.save(0, canon8)
    store._ckptr.wait_until_finished()

    mesh4 = build_mesh(MeshSpec(data=4), jax.devices()[:4])
    plan4, (_, state4, step4, _, _) = _zero1_setup(mesh4, data=4)
    restored, _ = store.restore(plan4.canonical_template(state4))
    live4 = plan4.from_canonical(restored)
    # the 4-way flat layout differs from the 8-way one (padding to 4, not
    # 8) but the canonical content must be exactly what the 8-way run saved
    _canon_equal(canon8, plan4.to_canonical(live4))
    # and training continues on the smaller mesh
    batch4 = shard_batch_to_mesh(z1_batch(seed=1), mesh4)
    live4, metrics = step4(live4, batch4)
    assert np.isfinite(float(metrics["loss_mean"]))
    assert int(live4.step) == 2
    store.close()


def test_saver_state_survives_restart(tmp_path):
    """Patience/best metric persist across ModelSaver re-construction
    (the reference forgets both on restart)."""
    state = {"w": jnp.ones((2,))}
    saver = ModelSaver(str(tmp_path / "rs"), early_stop=True,
                       max_early_stop_steps=3)
    saver(0.5, 0, state)
    saver(0.9, 1, state)   # stall 1
    saver.close()
    saver2 = ModelSaver(str(tmp_path / "rs"), early_stop=True,
                        max_early_stop_steps=3)
    assert saver2.best_metric == 0.5
    assert saver2.stall_count == 1
    assert not saver2(0.9, 2, state)  # stall 2
    assert saver2(0.9, 3, state)      # stall 3 -> stop
    saver2.close()


def test_meta_nonfinite_metric_roundtrips_strict_json(tmp_path):
    """GL110 (ISSUE 13 satellite): a NaN eval metric must neither crash
    the meta.json write (allow_nan=False would raise on a bare float)
    nor land as a bare NaN token — it writes as the events.py string
    convention and reads back as the float it was."""
    import json
    import math

    store = CheckpointStore(str(tmp_path / "nan"))
    store.write_meta({"last_epoch": 3,
                      "history": [{"epoch": 3, "metric": float("nan")}],
                      "best_metric": float("-inf"),
                      # sanitize is not injective: a user STRING that
                      # merely spells the sentinel must survive the
                      # round trip verbatim (restore is scoped to the
                      # numeric keys this module writes)
                      "note": "NaN"})
    raw = open(str(tmp_path / "nan" / "meta.json")).read()
    # strict parse: parse_constant fires only on bare non-finite tokens
    parsed = json.loads(raw, parse_constant=lambda tok: (_ for _ in ())
                        .throw(AssertionError(f"bare {tok} token")))
    assert parsed["history"][0]["metric"] == "NaN"
    meta = store.read_meta()
    assert math.isnan(meta["history"][0]["metric"])
    assert meta["best_metric"] == float("-inf")
    assert meta["last_epoch"] == 3
    assert meta["note"] == "NaN"          # still a string
    store.close()
