"""The reference's layer-by-layer gradient is the gradient: the same
numbers as ``jax.grad`` of the whole composition, with the segment inputs
kept on the device or pushed to the host."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference
from benchmarks.lib.weights import make_weights


def _like(arch):
    from byol_tpu.models.byol_net import build_byol_net
    kw = ({"attn_impl": "dense", "pooling": "cls"} if arch.startswith("vit")
          else {})
    net = build_byol_net(arch, num_classes=10, head_latent_size=32,
                         projection_size=16, small_inputs=True, **kw)
    v = jax.eval_shape(
        lambda k: net.init({"params": k}, jnp.zeros((2, 32, 32, 3)),
                           train=True, method="warmup"), jax.random.PRNGKey(0))
    return v["params"], v.get("batch_stats", {})


def test_layerwise_gradient_equals_jax_grad():
    # ResNet with every segment input pushed to the host; ViT kept on device
    for arch, heads, budget in (("resnet18", 0, 0), ("vit_s16", 6, 1 << 30)):
        _layerwise_equals_whole(arch, heads, budget)


def _layerwise_equals_whole(arch, heads, budget):
    params, _ = make_weights(*_like(arch), 11, zero_init_residual=False)
    target, _ = make_weights(*_like(arch), 12, zero_init_residual=False)
    rng = np.random.default_rng(0)
    v1, v2 = (rng.random((8, 32, 32, 3), dtype=np.float32) for _ in "ab")
    labels = rng.integers(0, 10, 8).astype(np.int32)
    kw = dict(image_size=32, vit_heads=heads)
    loss, grads = reference.loss_and_grads(
        params, target, v1, v2, labels, device_budget_bytes=budget, **kw)

    def whole(p):
        x = jnp.concatenate([v1, v2])
        t = reference.mlp_head(
            target["projector"],
            reference.encode(target["backbone"], None, x, **kw), "float32")
        heads_p = {k: p[k] for k in ("projector", "predictor", "probe")}
        return reference.tail_loss(
            heads_p, reference.encode(p["backbone"], None, x, **kw), t,
            jnp.asarray(labels), "float32")

    want_loss, want = jax.value_and_grad(whole)(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    # some gradients are all but zero (a bias ahead of a BatchNorm): each
    # leaf's difference is held against its norm or the median leaf's
    flat_got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    floor = float(np.median([np.linalg.norm(w) for _, w in flat_want]))
    for path, w in flat_want:
        w = np.asarray(w)
        diff = float(np.linalg.norm(np.asarray(flat_got[path]) - w))
        # float32 round-off, amplified by BatchNorm over 16 rows, reaches
        # 1e-3 between jit and eager of ONE function; a wrong order or a
        # lost segment is a difference of order 1
        assert diff <= 2e-2 * max(float(np.linalg.norm(w)), floor), path


def test_lower_precision_control_moves_the_numbers():
    """bfloat16 and fp8 operands move the served forward away from float32,
    fp8 by far the most: the control check.py has to fail."""
    params, stats = make_weights(*_like("resnet18"), 11,
                                 zero_init_residual=False)
    images = np.random.default_rng(1).random((8, 32, 32, 3),
                                             dtype=np.float32)
    out = {p: reference.embed(params, stats, images, image_size=32,
                              precision=p)
           for p in ("float32", "bfloat16", "fp8")}
    gap = {p: float(np.linalg.norm(out[p] - out["float32"])
                    / np.linalg.norm(out["float32"]))
           for p in ("bfloat16", "fp8")}
    assert 0 < gap["bfloat16"] < gap["fp8"] / 4
