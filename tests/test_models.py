"""Model layer tests: backbone shapes, registry feature dims, heads."""
import jax
import jax.numpy as jnp
import pytest

from byol_tpu.models import registry
from byol_tpu.models.byol_net import build_byol_net
from byol_tpu.models.heads import MLPHead
from byol_tpu.models.resnet import make_resnet


class TestRegistry:
    def test_unknown_arch_raises(self):
        with pytest.raises(ValueError, match="unknown arch"):
            registry.get_spec("resnet9000")

    @pytest.mark.parametrize("name,dim", [
        ("resnet18", 512), ("resnet50", 2048), ("resnet50w2", 4096),
    ])
    def test_feature_dims_match_params(self, name, dim):
        # The registry's declared dim must equal the module's actual output
        # dim — this is the Quirk Q8 fix (no hand-matched
        # --representation-size).
        module, reg_dim = registry.get_backbone(name, small_inputs=True)
        assert reg_dim == dim
        variables = module.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 32, 32, 3)), train=False)
        out = module.apply(variables, jnp.zeros((2, 32, 32, 3)), train=False,
                           mutable=False)
        assert out.shape == (2, dim)


class TestResNet:
    def test_resnet18_imagenet_stem_downsamples(self):
        m = make_resnet("resnet18")
        variables = m.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, 64, 3)), train=False)
        out = m.apply(variables, jnp.ones((2, 64, 64, 3)), train=False,
                      mutable=False)
        assert out.shape == (2, 512)

    def test_bn_updates_in_train_mode_only(self):
        m = make_resnet("resnet18", small_inputs=True)
        variables = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=True)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
        _, upd = m.apply(variables, x, train=True, mutable=["batch_stats"])
        before = variables["batch_stats"]["stem_bn"]["mean"]
        after = upd["batch_stats"]["stem_bn"]["mean"]
        assert not jnp.allclose(before, after)
        out_eval = m.apply(variables, x, train=False, mutable=False)
        assert out_eval.shape == (4, 512)

    def test_space_to_depth_stem_matches_conv_stem_exactly(self):
        # The s2d stem is a pure reparametrization of the 7x7/2 conv: same
        # param tree (params/stem_conv/kernel, (7,7,3,w)), same outputs,
        # same gradients — so checkpoints are interchangeable between stems.
        conv_net = make_resnet("resnet18", stem="conv")
        s2d_net = make_resnet("resnet18", stem="space_to_depth")
        x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
        variables = conv_net.init(jax.random.PRNGKey(0), x, train=False)
        k_shape = variables["params"]["stem_conv"]["kernel"].shape
        assert k_shape == (7, 7, 3, 64)
        # each net ONE compiled program: output and gradient together
        def out_and_grad(net):
            def loss(v):
                out = net.apply(v, x, train=False, mutable=False)
                return jnp.sum(out ** 2), out
            (_, out), grad = jax.jit(jax.value_and_grad(
                loss, has_aux=True))(variables)
            return out, grad
        out_conv, g_conv = out_and_grad(conv_net)
        out_s2d, g_s2d = out_and_grad(s2d_net)
        assert jnp.max(jnp.abs(out_conv - out_s2d)) < 1e-4
        gk_conv = g_conv["params"]["stem_conv"]["kernel"]
        gk_s2d = g_s2d["params"]["stem_conv"]["kernel"]
        assert jnp.max(jnp.abs(gk_conv - gk_s2d)) < 1e-3

    def test_space_to_depth_stem_rejects_odd_spatial(self):
        m = make_resnet("resnet18", stem="space_to_depth")
        with pytest.raises(ValueError, match="even spatial"):
            m.init(jax.random.PRNGKey(0), jnp.zeros((1, 33, 33, 3)),
                   train=False)

    @pytest.mark.slow
    def test_space_to_depth_stem_through_setup_training(self):
        # The stem knob is inert below the CIFAR-stem threshold (image <=
        # 64), so this must run at a REAL imagenet-stem size — a 16px smoke
        # would silently test the wrong path.  With identical seeds the
        # two stems share init (same param tree), so one train step must
        # produce matching losses.
        import numpy as np
        from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                          TaskConfig, resolve)
        from byol_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                            shard_batch_to_mesh)
        from byol_tpu.training.build import setup_training

        losses = {}
        for stem in ("conv", "space_to_depth"):
            mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
            cfg = Config(
                task=TaskConfig(task="fake", batch_size=4, epochs=2,
                                image_size_override=96),
                model=ModelConfig(arch="resnet18", head_latent_size=32,
                                  projection_size=16, stem=stem),
                device=DeviceConfig(num_replicas=1, half=False, seed=0),
            )
            rcfg = resolve(cfg, num_train_samples=16, num_test_samples=4,
                           output_size=10, input_shape=(96, 96, 3))
            net, state, train_step, _, _ = setup_training(
                rcfg, mesh, jax.random.PRNGKey(0))
            k = state.params["backbone"]["stem_conv"]["kernel"]
            assert k.shape == (7, 7, 3, 64)    # reparametrized, not re-shaped
            rng = np.random.RandomState(0)
            batch = shard_batch_to_mesh({
                "view1": rng.rand(4, 96, 96, 3).astype(np.float32),
                "view2": rng.rand(4, 96, 96, 3).astype(np.float32),
                "label": rng.randint(0, 10, size=(4,)).astype(np.int32),
            }, mesh)
            _, metrics = train_step(state, batch)
            losses[stem] = float(metrics["loss_mean"])
        assert losses["conv"] == pytest.approx(losses["space_to_depth"],
                                               rel=1e-4)


class TestHeads:
    def test_mlp_head_shapes(self):
        # Projector contract: Linear(rep->4096)+BN+ReLU+Linear(4096->256)
        # (reference main.py:194-199).
        head = MLPHead(hidden_size=4096, output_size=256)
        variables = head.init(jax.random.PRNGKey(0), jnp.zeros((2, 512)),
                              train=True)
        k1 = variables["params"]["dense1"]["kernel"]
        k2 = variables["params"]["dense2"]["kernel"]
        assert k1.shape == (512, 4096) and k2.shape == (4096, 256)
        out, _ = head.apply(variables, jnp.ones((3, 512)), train=True,
                            mutable=["batch_stats"])
        assert out.shape == (3, 256)


class TestBYOLNet:
    def test_forward_dict_and_probe_stopgrad(self):
        net = build_byol_net("resnet18", num_classes=10,
                            head_latent_size=64, projection_size=32,
                            small_inputs=True)
        x = jnp.ones((2, 32, 32, 3))
        variables = net.init(jax.random.PRNGKey(0), x, train=True,
                             method="warmup")
        out, _ = net.apply(variables, x, train=True,
                           mutable=["batch_stats"])
        assert out["representation"].shape == (2, 512)
        assert out["projection"].shape == (2, 32)
        assert out["prediction"].shape == (2, 32)

        # Probe gradient must not flow into the representation input
        # (main.py:250-252 stop-grad; Quirk Q11).
        def probe_loss(reprs):
            logits = net.apply({"params": variables["params"]}, reprs,
                               method="classify")
            return jnp.sum(logits ** 2)

        g = jax.grad(probe_loss)(jnp.ones((2, 512)))
        assert jnp.allclose(g, 0.0)
