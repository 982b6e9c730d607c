"""Fused uint8→two-view augmentation kernel (ISSUE 14 tentpole).

The contracts under test:

- **View equivalence** (acceptance): ``fused_two_view`` matches
  ``device_augment.two_view`` under identical keys — crop and flip EXACT
  (the kernel contracts the very weight matrices scale_and_translate
  builds, with the flip folded as a column permutation), the jitter/
  grayscale/blur arithmetic within fp32 tolerance (1e-5) — under the
  ``step_guard`` transfer guard on uint8 AND float32 inputs.
- **Per-op decomposition** (satellite): crop / flip / jitter / grayscale
  each pinned in isolation through the shared ``_view_pipeline`` with
  FORCED gates, so an equivalence failure names the op, not just "views
  differ".
- **Train-step parity** (acceptance): ``--fused-augment on`` reaches the
  same loss metrics and post-step params as the unfused step-placement
  path at accum 1 AND 2 on the 8-device mesh, under ``guard_steps``.
- **Off-identity** (acceptance): ``--fused-augment off`` lowers
  byte-identical HLO to a step built with no fused-augment plumbing at
  all; ``on`` really traces a different program.
- **Key stream** (satellite): ``augment_keys`` never collides across
  (step, microbatch-index) pairs within a run's step range.
- **Gating**: resolve() and make_train_step reject the combinations the
  kernel does not serve, with actionable errors.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.core import config as config_lib
from byol_tpu.data import device_augment
from byol_tpu.ops import fused_augment
from byol_tpu.parallel.mesh import shard_batch_to_mesh
from byol_tpu.training.build import setup_training
from byol_tpu.training.steps import augment_keys
from tests.conftest import guard_steps, tree_maxdiff

SIZE = 24      # augment target (= model input)
RAW = 28       # stored raw image size (crops come from here)


def make_rcfg(fused, accum_steps=1, batch=16):
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, batch_size=batch, epochs=2,
                                 augment_placement="step",
                                 fused_augment=fused,
                                 image_size_override=SIZE),
        model=dataclasses.replace(c.model, arch="resnet18",
                                  head_latent_size=64, projection_size=32),
        optim=dataclasses.replace(c.optim, warmup=1, lr=0.1,
                                  accum_steps=accum_steps),
        device=dataclasses.replace(c.device, num_replicas=8, half=False,
                                   seed=11),
    )
    return config_lib.resolve(c, num_train_samples=128, num_test_samples=32,
                              output_size=10, input_shape=(SIZE, SIZE, 3))


def _uint8_batch(n=6, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, 256, (n, RAW, RAW, 3),
                                   dtype=np.uint8))


# ---------------------------------------------------------------------------
# view equivalence: fused kernel == the unfused two-view program
# ---------------------------------------------------------------------------

class TestViewEquivalence:
    def test_fused_matches_two_view_uint8(self, step_guard):
        """ACCEPTANCE: identical keys -> matching views (crop/flip exact,
        arithmetic <= 1e-5) on the raw uint8 step-placement contract,
        under the transfer guard (no hidden host syncs in the fused
        path)."""
        imgs = _uint8_batch()
        key = jax.random.PRNGKey(5)
        ref = jax.jit(lambda k, im: device_augment.two_view(k, im, SIZE))
        fus = jax.jit(lambda k, im: fused_augment.fused_two_view(
            k, im, SIZE))
        v1a, v2a = step_guard(ref)(key, imgs)
        v1b, v2b = step_guard(fus)(key, imgs)
        assert float(jnp.max(jnp.abs(v1a - v1b))) < 1e-5
        assert float(jnp.max(jnp.abs(v2a - v2b))) < 1e-5
        assert v1b.dtype == jnp.float32
        assert v1b.shape == (imgs.shape[0], SIZE, SIZE, 3)

    def test_fused_matches_two_view_float32(self):
        """two_view also accepts float32 [0,1] images; the kernel's uint8
        convert is statically gated off on that dtype."""
        imgs = _uint8_batch().astype(jnp.float32) / 255.0
        key = jax.random.PRNGKey(9)
        v1a, v2a = device_augment.two_view(key, imgs, SIZE)
        v1b, v2b = fused_augment.fused_two_view(key, imgs, SIZE)
        assert float(jnp.max(jnp.abs(v1a - v1b))) < 1e-5
        assert float(jnp.max(jnp.abs(v2a - v2b))) < 1e-5

    def test_strength_zero_skips_hue_statically(self):
        """strength=0 degenerates every jitter factor to 1/theta to 0 and
        statically removes the hue branch in BOTH paths — they must still
        agree (the hue=0.2*strength>0 static gate is shared)."""
        imgs = _uint8_batch(4, seed=3)
        key = jax.random.PRNGKey(2)
        v1a, _ = device_augment.two_view(key, imgs, SIZE, strength=0.0)
        v1b, _ = fused_augment.fused_two_view(key, imgs, SIZE, strength=0.0)
        assert float(jnp.max(jnp.abs(v1a - v1b))) < 1e-5


# ---------------------------------------------------------------------------
# per-op decomposition: a failure names the op (satellite)
# ---------------------------------------------------------------------------

class TestDecomposition:
    """Each stage pinned in isolation: the crop weights against
    scale_and_translate itself, the flip fold, and the shared jitter/
    grayscale arithmetic through ``_view_pipeline`` with forced gates."""

    def _img_and_params(self, seed=0):
        rng = np.random.RandomState(seed)
        img = jnp.asarray(rng.rand(RAW, RAW, 3).astype(np.float32))
        p = device_augment.view_params(jax.random.PRNGKey(seed), RAW, RAW,
                                       1.0)
        return img, p

    def _prm(self, p, *, jitter, gray):
        return jnp.stack([jnp.float32(jitter), p.fb, p.fc, p.fs,
                          jnp.cos(p.theta), jnp.sin(p.theta),
                          jnp.float32(gray)])

    def test_crop_indices_exact(self):
        """The host-side weight matrices applied by the kernel's einsum
        reproduce device_augment.apply_crop (= scale_and_translate)
        BITWISE — the crop window math is the same, only realized as
        explicit per-row sampling weights."""
        for seed in range(8):
            img, p = self._img_and_params(seed)
            ref = device_augment.apply_crop(img, p.y0, p.x0, p.ch, p.cw,
                                            SIZE)
            wy, wx = fused_augment.crop_weight_mats(
                p._replace(flip=jnp.asarray(False)), RAW, RAW, SIZE)
            got = fused_augment._view_pipeline(
                img, wy, wx, self._prm(p, jitter=0.0, gray=0.0), hue=True)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=f"crop seed={seed}")

    def test_flip_exact(self):
        """Flip folded into wx's column order == flipping the cropped
        view, bitwise (a column permutation commutes with the row
        contraction and the clip)."""
        img, p = self._img_and_params(1)
        ref = device_augment.apply_crop(img, p.y0, p.x0, p.ch, p.cw,
                                        SIZE)[:, ::-1, :]
        wy, wx = fused_augment.crop_weight_mats(
            p._replace(flip=jnp.asarray(True)), RAW, RAW, SIZE)
        got = fused_augment._view_pipeline(
            img, wy, wx, self._prm(p, jitter=0.0, gray=0.0), hue=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_jitter_fp32_tolerance(self):
        """Forced jitter gate: the kernel stage == apply_color_jitter on
        the same crop (shared arithmetic; fusion-order noise only)."""
        img, p = self._img_and_params(2)
        crop = device_augment.apply_crop(img, p.y0, p.x0, p.ch, p.cw, SIZE)
        ref = device_augment.apply_color_jitter(crop, p.fb, p.fc, p.fs,
                                                p.theta, hue=True)
        wy, wx = fused_augment.crop_weight_mats(
            p._replace(flip=jnp.asarray(False)), RAW, RAW, SIZE)
        got = fused_augment._view_pipeline(
            img, wy, wx, self._prm(p, jitter=1.0, gray=0.0), hue=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6)

    def test_grayscale_exact(self):
        img, p = self._img_and_params(4)
        crop = device_augment.apply_crop(img, p.y0, p.x0, p.ch, p.cw, SIZE)
        ref = device_augment.apply_grayscale(crop)
        wy, wx = fused_augment.crop_weight_mats(
            p._replace(flip=jnp.asarray(False)), RAW, RAW, SIZE)
        got = fused_augment._view_pipeline(
            img, wy, wx, self._prm(p, jitter=0.0, gray=1.0), hue=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_weight_mat_matches_scale_and_translate_downscale(self):
        """The antialiased (kernel-widened) downsampling arm: a crop
        window LARGER than the output (ch > size) must still match —
        the 2-tap bilinear shortcut would not."""
        img = jnp.asarray(np.random.RandomState(7).rand(RAW, RAW, 3)
                          .astype(np.float32))
        y0 = jnp.float32(0.5)
        x0 = jnp.float32(1.0)
        ch = jnp.float32(RAW - 1.0)      # > SIZE: genuine downscale
        cw = jnp.float32(RAW - 2.0)
        ref = device_augment.apply_crop(img, y0, x0, ch, cw, SIZE)
        sy, sx = SIZE / ch, SIZE / cw
        wy = fused_augment._weight_mat(RAW, SIZE, sy, -y0 * sy)
        wx = fused_augment._weight_mat(RAW, SIZE, sx, -x0 * sx)
        got = jnp.clip(
            jnp.einsum(img, [0, 1, 2], wy, [0, 3], wx, [1, 4], [3, 4, 2],
                       precision=jax.lax.Precision.HIGHEST), 0.0, 1.0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# augment_keys collision property (satellite)
# ---------------------------------------------------------------------------

class TestAugmentKeyStream:
    def test_no_collisions_over_run_step_range(self):
        """Property: across a run-sized (step, microbatch-index) range the
        derived keys are pairwise distinct — fold_in on the step counter
        and again on the microbatch index never lands two pairs on the
        same key (key reuse would correlate the two views' randomness
        across steps, the GL103 hazard at runtime)."""
        seed, k, steps = 7, 8, 64
        seen = set()
        for step in range(steps):
            keys = np.asarray(augment_keys(seed, jnp.asarray(step,
                                                             jnp.int32), k))
            assert keys.shape[0] == k
            seen.update(tuple(map(int, kk)) for kk in keys)
        assert len(seen) == steps * k

    def test_distinct_seeds_decorrelate(self):
        a = np.asarray(augment_keys(1, jnp.asarray(0, jnp.int32), 4))
        b = np.asarray(augment_keys(2, jnp.asarray(0, jnp.int32), 4))
        assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# train-step parity + HLO identity (acceptance)
# ---------------------------------------------------------------------------

class TestTrainStepParity:
    @pytest.mark.parametrize("accum", [1, 2])
    def test_fused_matches_unfused_step(self, mesh8, step_guard, accum):
        """ACCEPTANCE: the fused-augment train step == the unfused
        step-placement step on the same raw uint8 stream — matching loss
        metrics AND post-step params at accum 1 and 2, under the transfer
        guard on the 8-device mesh."""
        states, metrics = {}, {}
        rng = np.random.RandomState(3)
        batch = {
            "images": rng.randint(0, 256, (16, RAW, RAW, 3),
                                  dtype=np.uint8),
            "label": rng.randint(0, 10, size=(16,)).astype(np.int32),
        }
        for fused in ("off", "on"):
            rcfg = make_rcfg(fused, accum_steps=accum)
            _, state, step, _, _ = setup_training(rcfg, mesh8,
                                                  jax.random.PRNGKey(0))
            sb = shard_batch_to_mesh(dict(batch), mesh8)
            state, m = step_guard(step)(state, sb)
            states[fused], metrics[fused] = state, m
        for k in metrics["off"]:
            np.testing.assert_allclose(
                float(metrics["on"][k]), float(metrics["off"][k]),
                rtol=2e-4, atol=2e-4, err_msg=f"metric {k} @ accum={accum}")
        assert tree_maxdiff(states["off"].params,
                            states["on"].params) < 5e-4
        assert tree_maxdiff(states["off"].batch_stats,
                            states["on"].batch_stats) < 1e-4
        assert int(states["on"].step) == int(states["off"].step)

    def test_fused_off_lowers_identical_hlo(self, mesh8):
        """The off arm's program must be byte-identical to a step built
        with NO fused-augment plumbing at all — make_train_step invoked
        exactly as the pre-fused-augment code invoked it."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from byol_tpu.core.precision import get_policy
        from byol_tpu.parallel.compile_plan import build_plan
        from byol_tpu.parallel.mesh import DATA_AXIS
        from byol_tpu.parallel.partitioning import state_shardings
        from byol_tpu.training.build import build_net, build_tx, step_config
        from byol_tpu.training.steps import make_train_step

        rcfg = make_rcfg("off")
        plan = build_plan(mesh8, zero1=False)
        _, state, train_step, _, _ = setup_training(
            rcfg, mesh8, jax.random.PRNGKey(0), plan=plan)
        rng = np.random.RandomState(0)
        batch = shard_batch_to_mesh(
            {"images": rng.randint(0, 256, (16, RAW, RAW, 3),
                                   dtype=np.uint8),
             "label": rng.randint(0, 10, size=(16,)).astype(np.int32)},
            mesh8)
        with mesh8:
            off_text = train_step.__wrapped__.lower(state, batch).as_text()

        bare = jax.jit(
            make_train_step(build_net(rcfg), build_tx(rcfg)[0],
                            step_config(rcfg), get_policy(False)),
            in_shardings=(state_shardings(state, mesh8),
                          NamedSharding(mesh8, P(DATA_AXIS))),
            out_shardings=(state_shardings(state, mesh8),
                           NamedSharding(mesh8, P())),
            donate_argnums=(0,))
        with mesh8:
            bare_text = bare.lower(state, batch).as_text()
        assert off_text == bare_text

    def test_fused_on_lowers_a_different_program(self, mesh8):
        texts = {}
        rng = np.random.RandomState(0)
        batch = shard_batch_to_mesh(
            {"images": rng.randint(0, 256, (16, RAW, RAW, 3),
                                   dtype=np.uint8),
             "label": rng.randint(0, 10, size=(16,)).astype(np.int32)},
            mesh8)
        for fused in ("off", "on"):
            rcfg = make_rcfg(fused)
            _, state, train_step, _, _ = setup_training(
                rcfg, mesh8, jax.random.PRNGKey(0))
            with mesh8:
                texts[fused] = train_step.__wrapped__.lower(
                    state, batch).as_text()
        assert texts["on"] != texts["off"]


# ---------------------------------------------------------------------------
# ops/common.py hoist (satellite): shared helpers, behavior pinned
# ---------------------------------------------------------------------------

class TestOpsCommonHoist:
    def test_resolve_interpret_explicit_wins(self):
        from byol_tpu.ops import common
        assert common.resolve_interpret(True) is True
        assert common.resolve_interpret(False) is False
        # None: backend-derived — on the CPU test box that means interpret
        assert common.resolve_interpret(None) is True


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------

class TestGating:
    def _resolve(self, c):
        return config_lib.resolve(c, num_train_samples=128,
                                  num_test_samples=32, output_size=10,
                                  input_shape=(SIZE, SIZE, 3))

    def test_resolve_rejects_loader_placement(self):
        c = config_lib.Config()
        c = c.replace(task=dataclasses.replace(
            c.task, batch_size=16, fused_augment="on",
            augment_placement="loader"))
        with pytest.raises(ValueError, match="augment-placement step"):
            self._resolve(c)

    def test_resolve_rejects_global_bn_accum(self):
        c = config_lib.Config()
        c = c.replace(
            task=dataclasses.replace(c.task, batch_size=16,
                                     fused_augment="on",
                                     augment_placement="step"),
            optim=dataclasses.replace(c.optim, accum_steps=2,
                                      accum_bn_mode="global"))
        with pytest.raises(ValueError, match="global"):
            self._resolve(c)

    def test_resolve_rejects_model_parallel(self):
        c = config_lib.Config()
        c = c.replace(
            task=dataclasses.replace(c.task, batch_size=16,
                                     fused_augment="on",
                                     augment_placement="step"),
            device=dataclasses.replace(c.device, num_replicas=4,
                                       model_parallel=2))
        with pytest.raises(ValueError, match="data axis only"):
            self._resolve(c)

    def test_resolve_rejects_bogus_mode(self):
        c = config_lib.Config()
        c = c.replace(task=dataclasses.replace(c.task, batch_size=16,
                                               fused_augment="chip"))
        with pytest.raises(ValueError, match="fused_augment"):
            self._resolve(c)

    def test_make_train_step_rejects_loader_placement(self):
        from byol_tpu.training.steps import StepConfig, make_train_step
        with pytest.raises(ValueError, match="augment_in_step"):
            make_train_step(None, None,
                            StepConfig(total_train_steps=10,
                                       fused_augment=True))

    def test_make_train_step_rejects_global_vmap(self):
        from byol_tpu.training.steps import StepConfig, make_train_step
        with pytest.raises(ValueError, match="global"):
            make_train_step(None, None,
                            StepConfig(total_train_steps=10,
                                       augment_in_step=True, image_size=16,
                                       fused_augment=True, accum_steps=2,
                                       accum_bn_mode="global"))
