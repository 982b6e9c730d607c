"""The kernels of Gated DeltaNet's two elementwise stages (ops/gdn_passes.py)
against the ``jax.numpy`` bodies of ``models/gated_delta.py``, on the CPU under
the Pallas interpreter: ``GatedDeltaNet`` chooses the kernels from the backend
and the shapes, so the tests call them directly (and answer
``gdn_passes.applies`` for the layer) at sizes the interpreter is quick at;
the layer itself, on both lowerings, against the benchmark's plain reference
of the published layer (``benchmarks/lib/reference_hybrid_trunk.py``).

Tolerances, relative to the norm.  The kernels work in float32 and round
once on the way out.  The gated norm's body does the same: in float32 a few
roundings apart, in bfloat16 the same values but where a sum's order tips a
rounding.  ``causal_conv`` works in the COMPUTE dtype from taps rounded to it
— every tap's product and every partial sum a bfloat16 rounding — so in
bfloat16 the kernel is held to the float32 body on the same operands within
ONE rounding (tight), and to the bfloat16 body within the roundings that
body makes (loose: the kernel may only be the more exact of the two).
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_hybrid_trunk as reference
from byol_tpu.models import gated_delta
from byol_tpu.ops import gdn_passes

EPS = 1e-6
ONE_ROUNDING = {"float32": 1e-5, "bfloat16": 3e-3}
BODY_ROUNDINGS = {"float32": 1e-5, "bfloat16": 1.5e-2}


def _f32(x):
    return x.astype(jnp.float32)


def _gap(got, want):
    return float(jnp.linalg.norm(_f32(got) - _f32(want))
                 / jnp.linalg.norm(_f32(want)))


def _normal(rng, *shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, dtype)


def _value_and_grads(fn, operands, weight):
    """The value and EVERY operand's gradient of ``sum(fn(..) * weight)``, as
    one compiled program (op by op the interpreter's every step was a
    dispatch of its own)."""
    def loss(*a):
        out = fn(*a)
        return jnp.sum(_f32(out) * weight), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(operands))), has_aux=True))(*operands)
    return (out,) + grads


def _conv_body(x, taps):
    return nn.silu(gated_delta.causal_conv(x, taps.astype(x.dtype)))


def _norm_body(out, gate, gain):
    return gated_delta.gated_rms_norm(out, gate, gain, EPS, out.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,seq,channels", [
    (2, 80, 128),       # one trip of the loop and a shorter one; one block
    (1, 208, 384),      # three trips and a shorter one; three column blocks
    (2, 16, 256)])      # shorter than a trip; one block two lane tiles wide
def test_conv_silu_is_the_jnp_body(batch, seq, channels, dtype):
    rng = np.random.default_rng(seq + channels)
    x = _normal(rng, batch, seq, channels, dtype=jnp.dtype(dtype))
    taps = _normal(rng, 4, channels, scale=0.5)
    weight = _normal(rng, batch, seq, channels)
    got = _value_and_grads(gdn_passes.conv_silu, (x, taps), weight)
    exact = _value_and_grads(_conv_body, (_f32(x), taps), weight)
    body = _value_and_grads(_conv_body, (x, taps), weight)
    assert got[0].shape == x.shape and got[0].dtype == x.dtype
    assert got[1].dtype == x.dtype and got[2].dtype == jnp.float32
    for name, g, e, w in zip(("y", "dx", "dtaps"), got, exact, body):
        assert g.shape == w.shape, name
        assert _gap(g, e) <= ONE_ROUNDING[dtype], name
        assert _gap(g, w) <= BODY_ROUNDINGS[dtype], name
    # the kernel is the more exact of the two lowerings
    assert _gap(got[0], exact[0]) <= _gap(body[0], exact[0]) + 1e-6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_read_their_columns_where_they_lie(dtype):
    """What ``GatedDeltaNet`` hands over: ONE ``(B, S, W)`` product of which
    the convolution meets the first ``C`` columns and the norm's gate is the
    ``H d`` columns from ``C`` on — the values and every gradient of the cut
    out operands, the product's gradient zero where a stage reads nothing."""
    rng = np.random.default_rng(11)
    kind = jnp.dtype(dtype)
    batch, seq, conv, heads, width = 2, 48, 256, 2, 128
    wide = _normal(rng, batch, seq, conv + heads * width + 128, dtype=kind)
    taps = _normal(rng, 4, conv, scale=0.5)
    out = _normal(rng, batch, seq, heads, width, dtype=kind, scale=3.0)
    gain = 1.0 + _normal(rng, width, scale=0.3)
    weight = _normal(rng, batch, seq, conv)
    got = _value_and_grads(gdn_passes.conv_silu, (wide, taps), weight)
    want = _value_and_grads(gdn_passes.conv_silu, (wide[..., :conv], taps),
                            weight)
    np.testing.assert_array_equal(_f32(got[0]), _f32(want[0]))
    np.testing.assert_array_equal(_f32(got[1][..., :conv]), _f32(want[1]))
    assert not np.any(_f32(got[1][..., conv:]))
    np.testing.assert_array_equal(got[2], want[2])
    weight = _normal(rng, batch, seq, heads, width)
    got = _value_and_grads(
        lambda o, z, g: gdn_passes.gated_norm(o, z, g, EPS, column=conv),
        (out, wide, gain), weight)
    cut = wide[..., conv:conv + heads * width]
    want = _value_and_grads(
        lambda o, z, g: gdn_passes.gated_norm(o, z, g, EPS),
        (out, cut, gain), weight)
    for g, w in zip(got[:2] + got[3:], want[:2] + want[3:]):
        np.testing.assert_array_equal(_f32(g), _f32(w))
    np.testing.assert_array_equal(
        _f32(got[2][..., conv:conv + heads * width]), _f32(want[2]))
    assert not np.any(_f32(got[2][..., :conv]))
    assert not np.any(_f32(got[2][..., conv + heads * width:]))
    with pytest.raises(ValueError):        # not whole heads
        gdn_passes.gated_norm(out, wide, gain, EPS, column=conv + 64)
    with pytest.raises(ValueError):        # not the 4 taps the halo is for
        gdn_passes.conv_silu(wide, taps[:3])


def test_conv_silu_reads_nothing_before_the_sequence_starts():
    """Token 0 meets the last tap alone, token 1 the last two, token 2 the
    last three — in the first program of a column and in a later one."""
    rng = np.random.default_rng(3)
    x = _normal(rng, 2, 32, 256)
    taps = _normal(rng, 4, 256)
    got = gdn_passes.conv_silu(x, taps)
    for t in range(3):
        want = sum(taps[3 - k] * x[:, t - k] for k in range(t + 1))
        np.testing.assert_allclose(got[:, t], nn.silu(want), rtol=1e-5,
                                   atol=1e-6)
    # and whatever a sequence holds leaves the one before it alone
    other = gdn_passes.conv_silu(x.at[1].set(7.0), taps)
    np.testing.assert_array_equal(other[0], got[0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,seq,heads,width", [
    (2, 528, 1, 128),   # one trip and a shorter one; one head, one block
    (1, 1040, 3, 128),  # two trips and a shorter one; three blocks of a head
    (2, 16, 2, 128),    # shorter than a trip; two heads a block
    (1, 32, 2, 256)])   # a head two lane tiles wide
def test_gated_norm_is_the_jnp_body(batch, seq, heads, width, dtype):
    rng = np.random.default_rng(seq + heads)
    kind = jnp.dtype(dtype)
    out = _normal(rng, batch, seq, heads, width, dtype=kind, scale=3.0)
    gate = _normal(rng, batch, seq, heads, width, dtype=kind)
    gain = 1.0 + _normal(rng, width, scale=0.3)
    weight = _normal(rng, batch, seq, heads, width)
    got = _value_and_grads(
        lambda o, z, g: gdn_passes.gated_norm(
            o, z.reshape(batch, seq, -1), g, EPS), (out, gate, gain), weight)
    want = _value_and_grads(_norm_body, (out, gate, gain), weight)
    for name, g, w in zip(("y", "d_out", "d_gate", "d_gain"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _gap(g, w) <= ONE_ROUNDING[dtype], name


@pytest.mark.parametrize("seq,channels,head,dtype,taps,backend,taken", [
    (4096, 8192, 128, "bfloat16", 4, "tpu", True),    # the published sizes
    (1024, 8192, 128, "float32", 4, "tpu", True),
    (4096, 8192, 128, "bfloat16", 4, "cpu", False),   # not lowered for a TPU
    (20, 24, 8, "float32", 4, "tpu", False),          # HYBRID_TINY
    (4096, 6144, 64, "bfloat16", 4, "tpu", False),    # half a lane tile a head
    (4096, 8192 + 64, 128, "bfloat16", 4, "tpu", False),
    (4096 + 8, 8192, 128, "bfloat16", 4, "tpu", False),   # half a bf16 tile
    (4096, 8192, 128, "float16", 4, "tpu", False),
    (4096, 8192, 128, "bfloat16", 3, "tpu", False),   # not the 4 taps
    (4096, 8192, 128, "float32", 4, "tpu", False),    # the norm's five blocks
    (65536, 8192, 128, "bfloat16", 4, "tpu", False),  # ... outgrow VMEM
])
def test_the_kernels_are_chosen_from_backend_and_shapes(
        seq, channels, head, dtype, taps, backend, taken):
    assert gdn_passes.applies(seq, channels, head, jnp.dtype(dtype),
                              taps=taps, backend=backend) is taken


SIZES = gated_delta.GatedDeltaSizes(
    num_key_heads=2, num_value_heads=4, key_head_dim=128, value_head_dim=128,
    conv_kernel=4, chunk=16, group=0)


def _layer(dtype=jnp.float32):
    return gated_delta.GatedDeltaNet(SIZES, key_heads=2, value_heads=4,
                                     dtype=dtype)


def _published(params, x):
    """The layer as the public modelling code writes it — ``qkvz`` cut PER
    KEY HEAD into ``q | k | v | z`` and put together again, the rule token by
    token: the benchmark's plain reference, which shares no line with
    ``GatedDeltaNet`` and knows nothing of its reordered product."""
    sizes = dict(key_heads=2, value_heads=4, dk=128, dv=128, eps=EPS)
    return jax.vmap(lambda one: reference.gated_delta_net(
        params["params"], one, sizes, "float32"))(x)


@pytest.mark.parametrize("taken", [False, True])
def test_the_layer_is_the_published_layer_on_both_lowerings(taken,
                                                            monkeypatch):
    """``GatedDeltaNet`` with 2 key heads of 2 value heads each — so that the
    reorder of ``qkvz``'s columns is a real permutation — and ``applies``
    answered both ways: the published layer's output, its gradient of every
    parameter LEAF BY LEAF (``qkvz/kernel`` in the published column order)
    and of the input."""
    monkeypatch.setattr(gdn_passes, "applies", lambda *a, **k: taken)
    layer = _layer()
    rng = np.random.default_rng(0)
    x = _normal(rng, 2, 32, 32)
    params = layer.init(jax.random.PRNGKey(0), x)
    # away from their initial ones, and a gradient of its own a column
    params = jax.tree_util.tree_map(
        lambda leaf: leaf + _normal(rng, *leaf.shape, scale=0.1), params)

    def value_and_grads(fn):
        def loss(params, x):
            return jnp.sum(jnp.sin(fn(params, x)))
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
                params, x)
    got, grads = value_and_grads(layer.apply)
    want, want_grads = value_and_grads(_published)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    leaves = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert any("qkvz" in jax.tree_util.keystr(path) for path, _ in leaves)
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want_grads)):
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        assert _gap(g, w) <= 1e-4, jax.tree_util.keystr(path)


def test_on_the_cpu_the_layer_lowers_to_no_kernel():
    """What tier-1 and every CPU run of ``train.py`` take: the ``jax.numpy``
    bodies, at shapes the kernels would take on a TPU."""
    layer = _layer(jnp.bfloat16)
    x = jnp.zeros((1, 32, 32), jnp.bfloat16)
    assert gdn_passes.applies(32, 1024, 128, backend="tpu")
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    text = jax.jit(layer.apply).lower(params, x).as_text()
    assert "conv_silu" not in text and "gated_norm" not in text
    assert "pallas" not in text and "tpu_custom_call" not in text
