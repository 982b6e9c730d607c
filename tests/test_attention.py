"""Attention backends: dense oracle vs ring (sequence-parallel).  Both
share one signature (ops/attention.py) — these tests pin their numerical
equivalence, which is what lets the ViT swap impls by config name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.ops.attention import dense_attention, get_attention_fn


def _qkv(key, b=2, h=2, s=64, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, h, s, d)
    return (jax.random.normal(kq, shape, dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


def _reference(q, k, v):
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * scale
    w = np.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", w, np.asarray(v, np.float64))


def test_dense_matches_float64_reference():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    np.testing.assert_allclose(dense_attention(q, k, v),
                               _reference(q, k, v), rtol=1e-5, atol=1e-5)


def test_ring_matches_dense_shard_map(mesh_dp_sp):
    """Ring attention over a real 2-way sequence axis (4 data x 2 sequence
    CPU mesh) must reproduce dense attention on the gathered sequence."""
    from byol_tpu.parallel.ring_attention import ring_attention
    q, k, v = _qkv(jax.random.PRNGKey(4), b=4, h=2, s=32, d=8)
    with mesh_dp_sp:
        out = ring_attention(q, k, v, mesh=mesh_dp_sp)
    np.testing.assert_allclose(out, dense_attention(q, k, v),
                               rtol=1e-5, atol=1e-5)


def test_ring_inside_jit(mesh_dp_sp):
    from byol_tpu.parallel.ring_attention import ring_attention
    q, k, v = _qkv(jax.random.PRNGKey(5), b=4, h=2, s=32, d=8)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, mesh=mesh_dp_sp)

    np.testing.assert_allclose(f(q, k, v), dense_attention(q, k, v),
                               rtol=1e-5, atol=1e-5)


def test_ring_requires_sequence_axis():
    from byol_tpu.parallel.ring_attention import ring_attention
    q, k, v = _qkv(jax.random.PRNGKey(6), s=8, d=4)
    with pytest.raises(ValueError, match="sequence"):
        ring_attention(q, k, v)  # no mesh in scope


def test_get_attention_fn_registry():
    assert get_attention_fn("dense") is dense_attention
    from byol_tpu.parallel.ring_attention import ring_attention
    assert get_attention_fn("ring") is ring_attention
    for gone in ("flash", "bogus"):     # the forward-only kernel: ISSUE 44
        with pytest.raises(ValueError, match="unknown"):
            get_attention_fn(gone)


# ---------------------------------------------------------------------------
# the fused kernel over the packed qkv (ops/packed_attention.py), under the
# interpreter, against dense_attention on the unpacked heads
# ---------------------------------------------------------------------------

def _unpacked_dense(qkv, num_heads):
    """What models/vit.SelfAttention does off-TPU: slice, transpose,
    dense_attention, transpose back."""
    b, s, width3 = qkv.shape
    x = qkv.reshape(b, s, 3, num_heads, width3 // (3 * num_heads))
    q, k, v = (x[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    return dense_attention(q, k, v).transpose(0, 2, 1, 3).reshape(
        b, s, width3 // 3)


@pytest.mark.parametrize("heads,head_dim", [(2, 64), (8, 32)])
@pytest.mark.parametrize("seq", [13, 50, 197])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_packed_matches_dense_forward_and_grad(dtype, tol, seq, heads,
                                               head_dim):
    """Forward and d(scalar loss)/d(packed qkv): sequence lengths that are
    and are not multiples of 8 (the block pads them to 128 with rows that
    lie outside the array), heads that are two or four to a 128-lane block."""
    from byol_tpu.ops.packed_attention import packed_self_attention
    kq, kw = jax.random.split(jax.random.PRNGKey(seq + heads))
    qkv = jax.random.normal(kq, (2, seq, 3 * heads * head_dim),
                            jnp.dtype(dtype))
    w = jax.random.normal(kw, (2, seq, heads * head_dim), jnp.float32)

    def loss(fn, x):
        out = fn(x)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out), grad = jax.value_and_grad(
        lambda x: loss(lambda y: packed_self_attention(
            y, heads, interpret=True), x), has_aux=True)(qkv)
    (_, ref), ref_grad = jax.value_and_grad(
        lambda x: loss(lambda y: _unpacked_dense(y, heads), x),
        has_aux=True)(qkv)
    assert out.shape == ref.shape and out.dtype == qkv.dtype
    assert grad.shape == qkv.shape and grad.dtype == qkv.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(ref_grad, np.float32),
                               rtol=tol, atol=tol)


def test_packed_one_head_per_block_and_aligned_sequence():
    """Head width 128 (no lane is masked) at a sequence that IS the block."""
    from byol_tpu.ops.packed_attention import packed_self_attention
    qkv = jax.random.normal(jax.random.PRNGKey(11), (4, 128, 3 * 128))
    f = lambda fn: jax.value_and_grad(lambda x: jnp.sum(jnp.sin(fn(x))))(qkv)
    got = f(lambda x: packed_self_attention(x, 1, interpret=True))
    want = f(lambda x: _unpacked_dense(x, 1))
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, rtol=2e-5, atol=2e-5)


def test_packed_over_the_data_axis(mesh8):
    """On a mesh the kernels run inside a shard_map over the batch."""
    from byol_tpu.ops.packed_attention import packed_self_attention
    qkv = jax.random.normal(jax.random.PRNGKey(12), (8, 13, 3 * 128))

    @jax.jit
    def f(x):
        return jax.value_and_grad(lambda y: jnp.sum(jnp.sin(
            packed_self_attention(y, 2, mesh=mesh8, interpret=True))))(x)

    want = jax.value_and_grad(
        lambda y: jnp.sum(jnp.sin(_unpacked_dense(y, 2))))(qkv)
    for g, w_ in zip(f(qkv), want):
        np.testing.assert_allclose(g, w_, rtol=2e-5, atol=2e-5)


def test_packed_refuses_what_it_cannot_take():
    from byol_tpu.ops.packed_attention import packed_self_attention
    with pytest.raises(ValueError, match="72"):
        packed_self_attention(jnp.zeros((1, 8, 3 * 16 * 72)), 16)
    with pytest.raises(ValueError, match="heads"):
        packed_self_attention(jnp.zeros((1, 8, 100)), 3)


class _Mesh:
    """What the rule reads of a mesh: its axis sizes."""
    def __init__(self, **shape):
        self.shape = shape
        self.size = int(np.prod(list(shape.values())))


@pytest.mark.parametrize("case,kwargs,want", [
    ("vit_b16", dict(batch=128, seq_len=197, num_heads=12, head_dim=64),
     True),
    ("vit_l16_384", dict(batch=8, seq_len=512, num_heads=16, head_dim=64),
     True),
    ("one_head_a_block", dict(batch=8, seq_len=50, num_heads=4,
                              head_dim=128), True),
    ("data_mesh", dict(batch=128, seq_len=197, num_heads=12, head_dim=64,
                       mesh=_Mesh(data=4, sequence=1, model=1)), True),
    ("trunk_1024_causal", dict(batch=16, seq_len=1024, num_heads=4,
                               head_dim=64, causal=True), False),
    ("causal_short", dict(batch=16, seq_len=197, num_heads=12, head_dim=64,
                          causal=True), False),
    ("a_mask", dict(batch=128, seq_len=197, num_heads=12, head_dim=64,
                    masked=True), False),
    ("head_width_72", dict(batch=8, seq_len=256, num_heads=16, head_dim=72),
     False),
    ("odd_width", dict(batch=8, seq_len=197, num_heads=3, head_dim=64),
     False),
    ("too_long", dict(batch=8, seq_len=577, num_heads=12, head_dim=64),
     False),
    ("model_axis", dict(batch=128, seq_len=197, num_heads=12, head_dim=64,
                        mesh=_Mesh(data=2, sequence=1, model=2)), False),
    ("batch_not_split", dict(batch=6, seq_len=197, num_heads=12, head_dim=64,
                             mesh=_Mesh(data=4, sequence=1, model=1)),
     False),
])
def test_packed_kernel_selection_rule(case, kwargs, want):
    """The choice is the code's, from what it can see: the backend, the
    shapes, a mask, the mesh — no flag."""
    from byol_tpu.ops.attention import packed_kernel_applies
    assert packed_kernel_applies(backend="tpu", **kwargs) is want
    assert packed_kernel_applies(backend="cpu", **kwargs) is False


def test_dense_path_off_tpu_is_the_einsums():
    """Tier-1 runs on the CPU: the model's dense path stays what it was."""
    from byol_tpu.ops.attention import packed_kernel_applies
    assert jax.default_backend() == "cpu"
    assert not packed_kernel_applies(128, 197, 12, 64)


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-5), ("bfloat16", 5e-2)])
def test_vit_block_same_through_kernel_and_einsums(monkeypatch, dtype, tol):
    """One encoder block, the same variables (same tree): forward and every
    parameter's gradient agree between the fused kernel and the einsums."""
    from byol_tpu.models import vit
    from byol_tpu.ops.attention import packed_kernel_applies
    block = vit.EncoderBlock(num_heads=2, dtype=jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 21, 128), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)

    def run():
        def loss(params):
            y = block.apply({"params": params}, x)
            return jnp.mean(jnp.square(y.astype(jnp.float32))), y
        return jax.value_and_grad(loss, has_aux=True)(variables["params"])

    (_, y_einsum), g_einsum = run()
    calls = []

    def on_tpu(*a, **kw):
        calls.append(packed_kernel_applies(*a, backend="tpu", **kw))
        return calls[-1]
    monkeypatch.setattr(vit, "packed_kernel_applies", on_tpu)
    (_, y_kernel), g_kernel = run()
    assert calls and all(calls), "the block did not take the kernel"
    assert (jax.tree_util.tree_structure(g_kernel)
            == jax.tree_util.tree_structure(g_einsum))
    np.testing.assert_allclose(np.asarray(y_kernel, np.float32),
                               np.asarray(y_einsum, np.float32),
                               rtol=tol, atol=tol)
    scale = max(float(jnp.max(jnp.abs(g))) for g in
                jax.tree_util.tree_leaves(g_einsum))
    for a, b in zip(jax.tree_util.tree_leaves(g_kernel),
                    jax.tree_util.tree_leaves(g_einsum)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol * scale)
