"""What the ResNet hands from layer to layer under each precision policy.

Under the bf16 policy every normalisation layer and every block emits bf16 —
no float32 activation travels between layers — while parameters, BatchNorm
statistics and the normalisation arithmetic stay float32 (flax reduces and
normalises in float32 whatever ``dtype`` is; ``dtype`` only chooses the one
cast of the result).  Under the float32 policy nothing is bf16: the program
of ``--half`` off is the one it was.
"""
import flax.linen as nn
from flax.traverse_util import flatten_dict
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.core.precision import BF16, FP32
from byol_tpu.models.resnet import BasicBlock, Bottleneck, make_resnet

IMAGE = 32


def _init(net, batch):
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, IMAGE, IMAGE, 3))
    return net.init(jax.random.PRNGKey(0), x, train=True), x


def _captured(intermediates):
    """``{"stage1_block1/bn3": array}`` from a flax ``intermediates`` tree."""
    return {path.rsplit("/", 1)[0]: out[0] for path, out in
            flatten_dict(intermediates, sep="/").items()}


@pytest.mark.parametrize("policy", [BF16, FP32], ids=["bf16", "fp32"])
@pytest.mark.parametrize("arch", ["resnet18", "resnet50"])
def test_layers_hand_on_the_compute_dtype(arch, policy):
    net = make_resnet(arch, dtype=policy.compute_dtype)
    variables, x = _init(net, 2)
    _, state = net.apply(
        variables, x, train=True, mutable=["batch_stats", "intermediates"],
        capture_intermediates=lambda mdl, _: isinstance(
            mdl, (nn.BatchNorm, BasicBlock, Bottleneck)))
    outs = _captured(state["intermediates"])
    n_blocks = sum(net.stage_sizes)
    per_block = 2 if arch == "resnet18" else 3
    # stem_bn, every bn1..bnN, the three downsample_bn (four where stage 1
    # widens, as the bottleneck's does), and every block's own output
    n_down = 3 if arch == "resnet18" else 4
    assert len(outs) == 1 + n_blocks * (per_block + 1) + n_down
    wrong = {k: v.dtype for k, v in outs.items()
             if v.dtype != policy.compute_dtype}
    assert not wrong
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            {"params": variables["params"],
             "batch_stats": state["batch_stats"]}):
        assert leaf.dtype == jnp.float32, jax.tree_util.keystr(path)


@pytest.mark.parametrize("bn,conv", [
    ("stem_bn", "stem_conv"), ("stage1_block1/bn3", "stage1_block1/conv3")])
def test_statistics_are_float32_reductions_of_the_bf16_input(bn, conv):
    """The running averages a bf16 train-mode forward writes are the
    momentum blend of float32 mean / variance of the layer's bf16 input
    upcast to float32 — to 1e-6, where a bf16 reduction would be off by
    1e-3."""
    net = make_resnet("resnet50", dtype=jnp.bfloat16)
    variables, x = _init(net, 4)
    _, state = net.apply(
        variables, x, train=True, mutable=["batch_stats", "intermediates"],
        capture_intermediates=lambda mdl, _: mdl.name == conv.split("/")[-1])
    y = _captured(state["intermediates"])[conv]
    assert y.dtype == jnp.bfloat16
    y = np.asarray(y.astype(jnp.float32), np.float64)
    mean = y.mean(axis=(0, 1, 2))
    var = (y * y).mean(axis=(0, 1, 2)) - mean * mean
    m = net.bn_momentum
    got = flatten_dict(state["batch_stats"], sep="/")
    # initial running mean 0, variance 1
    np.testing.assert_allclose(got[f"{bn}/mean"], (1 - m) * mean,
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(got[f"{bn}/var"], m + (1 - m) * var,
                               rtol=1e-6)


def _cosine(a, b):
    a, b = np.ravel(a).astype(np.float64), np.ravel(b).astype(np.float64)
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


@pytest.mark.parametrize("zero_init,min_cosine,min_leaves", [
    (True, 0.99, 4), (False, 0.9, 20)], ids=["zero_init", "ones_init"])
def test_bf16_gradient_agrees_with_float32(zero_init, min_cosine, min_leaves):
    """Stated tolerance: loss to 1e-2 relative, cosine >= 0.99 on every
    kernel leaf whose float32 gradient is not zero.  The zero-initialised
    last BatchNorm scale of a block silences the kernels before it, which
    leaves the stem and the three downsample convolutions (0.9967 read
    here); with it initialised to one all 20 kernels are compared, and bf16
    through 20 BatchNorms over 8 samples reads 0.929 (0.933 when the norm
    layers still handed on float32), held to 0.9."""
    target = jax.random.normal(jax.random.PRNGKey(2), (8, 512))

    def loss_and_grad(dtype):
        net = make_resnet("resnet18", dtype=dtype,
                          zero_init_residual=zero_init)
        variables, x = _init(net, 8)

        def loss(params):
            feats, _ = net.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            return jnp.mean((feats.astype(jnp.float32) - target) ** 2)
        return jax.jit(jax.value_and_grad(loss))(variables["params"])

    loss32, grad32 = loss_and_grad(jnp.float32)
    loss16, grad16 = loss_and_grad(jnp.bfloat16)
    assert abs(float(loss16) - float(loss32)) <= 1e-2 * abs(float(loss32))
    flat32 = dict(jax.tree_util.tree_leaves_with_path(grad32))
    flat16 = dict(jax.tree_util.tree_leaves_with_path(grad16))
    compared = 0
    for path, g32 in flat32.items():
        assert flat16[path].dtype == jnp.float32
        if path[-1].key != "kernel" or not np.any(np.asarray(g32)):
            continue
        compared += 1
        assert _cosine(g32, flat16[path]) >= min_cosine, \
            jax.tree_util.keystr(path)
    assert compared == min_leaves
