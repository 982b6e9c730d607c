"""The kernels of sparse attention's core (ops/causal_attention.py with a
selection) against the ``jax.numpy`` body of ``ops/attention.
selected_attention``, on the CPU under the Pallas interpreter:
``selected_attention`` chooses the kernels from the backend and the shapes, so
the tests answer ``causal_attention.applies`` for it and run the same kernel
bodies at sizes the interpreter is quick at.  The same kernels WITHOUT a
selection are tests/test_causal_attention_kernel.py's.

Tolerances.  The two paths are the same equations over the same tiles in the
same order of key blocks; what differs is the order of sums inside a product
and where ``d_k, d_v`` are added up (the kernel sums the query heads of a key
head one product at a time): a few float32 roundings, and in bfloat16 now
and then one rounding of an element the other way.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.ops import attention, key_selection
from byol_tpu.ops import causal_attention as kernels

BATCH, KV_HEADS, DIM = 2, 2, 16


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _qkv(seed, seq, group, dtype, *, batch=BATCH, kv_heads=KV_HEADS, dim=DIM):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape),
                                   jnp.float32).astype(dtype)
    return (f(batch, kv_heads * group, seq, dim), f(batch, kv_heads, seq, dim),
            f(batch, kv_heads, seq, dim))


def _tiles(square, block):
    """``(B, S, S)`` -> the tile layout of ops/attention.py, a last short
    block filled with zeros."""
    batch, seq, _ = square.shape
    blocks = -(-seq // block)
    whole = np.zeros((batch,) + (blocks * block,) * 2, square.dtype)
    whole[:, :seq, :seq] = square
    cut = lambda i: slice(i * block, (i + 1) * block)
    return jnp.asarray(np.stack([whole[:, cut(i), cut(j)] for i, j in zip(
        *attention.causal_pairs(blocks))]))


def _selection(kind, seed, batch, seq, block):
    """The set of keys of every query, as tiles, of the kinds that break
    kernels."""
    rng = np.random.default_rng(seed)
    causal = np.broadcast_to(np.tril(np.ones((seq, seq), bool)),
                             (batch, seq, seq))
    index = rng.normal(size=(batch, seq, seq)).astype(np.float32)
    at = np.arange(seq)
    if kind == "every_causal_key":
        return _tiles(causal, block)
    if kind == "window":                    # the 5 most recent keys
        return _tiles(causal & (at[None, :] > at[:, None] - 5), block)
    if kind == "empty_rows":
        # odd queries shun the first block of keys: past the middle of the
        # second block of queries their rows of tile (1, 0) keep NOTHING,
        # and the running max of such a row starts masked
        index[:, 1::2, :block] -= 100.0
    selected = key_selection.select_top_keys(_tiles(index, block),
                                             block // 2, block=block)
    if kind == "empty_rows":
        kept = np.asarray(selected[1]).any(axis=-1)         # tile (1, 0)
        assert not kept[:, 1::2][:, -2:].any() and kept[:, 0::2].all()
    return selected


def _value_and_grads(monkeypatch, taken, q, k, v, selected, block, seq):
    """``selected_attention`` as the layer calls it — sequences filled up to
    whole blocks — and the gradients of a loss of the real rows."""
    whole = lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, -seq % block), (0, 0)])

    def loss(q, k, v):
        out, lse = attention.selected_attention(
            whole(q), whole(k), whole(v), selected, block=block)
        return jnp.sum(jnp.sin(out[:, :, :seq].astype(jnp.float32))), (
            out, lse)
    with monkeypatch.context() as patch:   # the plain core asks it too
        patch.setattr(kernels, "applies", lambda *a, **kw: taken)
        (_, aux), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
    return aux + grads


# relative to the norm of each of out, d_q, d_k, d_v
TOLERANCE = {"float32": 1e-5, "bfloat16": 1e-3}
NAMES = "out lse d_q d_k d_v".split()


def _assert_both_paths_agree(monkeypatch, q, k, v, selected, block, seq):
    want = _value_and_grads(monkeypatch, False, q, k, v, selected, block, seq)
    got = _value_and_grads(monkeypatch, True, q, k, v, selected, block, seq)
    tol = TOLERANCE[q.dtype.name]
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        if name == "lse":                   # float32 on both, whatever q is
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        else:
            assert np.linalg.norm(f32(g) - f32(w)) <= tol * np.linalg.norm(
                f32(w)), name
    return got


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("seq,block", [
    (32, 16),       # three tiles
    (64, 16),       # ten
    (40, 16)])      # the last block is padded
@pytest.mark.parametrize("kind", ["every_causal_key", "top_keys",
                                  "empty_rows", "window"])
def test_the_kernels_are_the_jnp_body(monkeypatch, kind, seq, block, group,
                                      dtype):
    q, k, v = _qkv(seq + group, seq, group, jnp.dtype(dtype))
    selected = _selection(kind, seq + 1, BATCH, seq, block)
    out = _assert_both_paths_agree(monkeypatch, q, k, v, selected, block,
                                   seq)[0]
    if kind == "every_causal_key" and dtype == "float32":
        np.testing.assert_allclose(
            out[:, :, :seq], attention.blockwise_causal_attention(
                q, k, v, block=block), rtol=1e-5, atol=1e-6)


def test_the_kernels_at_the_published_tile(monkeypatch):
    """Blocks of 512, heads of 128, two query heads a key head, bfloat16:
    shapes ``supported`` asks for, through the interpreter once."""
    seq, block = 1024, 512
    assert kernels.supported(block, 128, seq, 2, selected=True)
    q, k, v = _qkv(7, seq, 2, jnp.bfloat16, batch=1, kv_heads=1, dim=128)
    selected = _selection("top_keys", 8, 1, seq, block)
    _assert_both_paths_agree(monkeypatch, q, k, v, selected, block, seq)


def test_the_statistics_leave_the_kernel_in_float32_a_row_a_head():
    q, k, v = _qkv(3, 32, 4, jnp.bfloat16)
    grouped = q.reshape(BATCH, KV_HEADS, 4, 32, DIM)
    out, lse = kernels.attend(
        grouped, k, v, selected=_selection("top_keys", 4, BATCH, 32, 16),
        scale=DIM ** -0.5, block=16)
    assert out.shape == grouped.shape and out.dtype == jnp.bfloat16
    assert lse.shape == (BATCH, KV_HEADS, 4, 32) and lse.dtype == jnp.float32


@pytest.mark.parametrize(
    "block,dim,seq,heads,kv_heads,dtype,backend,taken", [
        (512, 128, 4096, 32, 4, "bfloat16", "tpu", True),   # the published
        (128, 128, 256, 4, 1, "float32", "tpu", True),
        (512, 128, 4096, 32, 4, "bfloat16", "cpu", False),  # not for a TPU
        (8, 16, 24, 4, 2, "float32", "tpu", False),         # SPARSE_TINY
        (96, 128, 4032, 32, 4, "bfloat16", "tpu", False),   # 3/4 lane tile
        (512, 64, 4096, 32, 4, "bfloat16", "tpu", True),    # half a lane tile
        (512, 128, 4000, 32, 4, "bfloat16", "tpu", False),  # a short block
        (512, 128, 4096, 32, 5, "bfloat16", "tpu", False),  # heads unshared
        (512, 128, 32768, 32, 4, "bfloat16", "tpu", False),  # d_k, d_v of a
    ])                                  # key head's sequence outgrow VMEM
def test_the_kernels_are_chosen_from_backend_and_shapes(
        block, dim, seq, heads, kv_heads, dtype, backend, taken):
    assert kernels.applies(block, dim, seq, heads, kv_heads,
                           jnp.dtype(dtype), selected=True,
                           backend=backend) is taken


def test_on_the_cpu_the_core_lowers_to_no_kernel():
    """What tier-1 and every CPU run of ``train.py`` take: today's
    ``jax.numpy`` program, whatever the shapes."""
    q, k, v = _qkv(0, 256, 2, jnp.bfloat16, batch=1, kv_heads=1, dim=128)
    selected = _selection("top_keys", 1, 1, 256, 128)
    text = jax.jit(lambda *a: attention.selected_attention(
        *a, block=128)).lower(q, k, v, selected).as_text()
    assert "selected_attention_" not in text and "while" in text


# ---- one kernel pair (ops/causal_attention.py): a selection is one operand --

def _kernel_value_and_grads(q, k, v, block, *, selected=None, shared=()):
    """The kernels as ``attend`` calls them — ``out``, ``lse`` and the
    gradients of a loss of ``out`` for ``q, k, v`` and the shared pair —
    with ``selected`` and without."""
    scale = (q.shape[-1] + (shared[0].shape[-1] if shared else 0)) ** -0.5

    def loss(q, k, v, shared):
        out, lse = kernels.attend(q, k, v, scale=scale, block=block,
                                  selected=selected, shared=shared or None)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, lse)
    (_, aux), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(q, k, v, shared)
    return aux + grads[:3] + tuple(grads[3])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [1, 4])
def test_with_every_causal_key_kept_a_selection_changes_no_bit(group, dtype):
    """What a selection changes is where a tile's bias comes from — the
    pair's int8 tile on every pair, two iotas on the diagonal pair alone:
    with every causal key kept the biases are equal (0 under the diagonal),
    and so are ``out``, ``lse`` and the three gradients, to the bit.  The
    kernel-level twin of tests/test_sparse_trunk.py::
    test_with_every_key_kept_the_core_is_blockwise_causal_attention."""
    seq, block = 48, 16
    q, k, v = _qkv(group, seq, group, jnp.dtype(dtype))
    grouped = q.reshape(BATCH, KV_HEADS, group, seq, DIM)
    every = _selection("every_causal_key", 0, BATCH, seq, block)
    want = _kernel_value_and_grads(grouped, k, v, block)
    got = _kernel_value_and_grads(grouped, k, v, block, selected=every)
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)),
                                      err_msg=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_selection_with_a_shared_part_and_a_value_width(dtype):
    """Nothing refuses a selection beside latent attention's operands: a
    shared part of the key (one vector for all heads) and values narrower
    than keys, against the ``jax.numpy`` body on the JOINED ``q, k`` — as
    ``blockwise_causal_attention``'s fallback joins them."""
    seq, block, group, shared_dim, vdim = 48, 16, 2, 8, 8
    dt = jnp.dtype(dtype)
    q, k, v = _qkv(11, seq, group, dt)
    q_s, k_s, _ = _qkv(12, seq, group, dt, dim=shared_dim)
    grouped = lambda x: x.reshape((BATCH, KV_HEADS, group) + x.shape[2:])
    q, q_s, k_s, v = grouped(q), grouped(q_s), k_s[:, 0], v[..., :vdim]
    selected = _selection("top_keys", 13, BATCH, seq, block)
    scale = (DIM + shared_dim) ** -0.5

    def joined(q, k, v, shared):
        q_s, k_s = shared
        whole_k = jnp.concatenate([k, jnp.broadcast_to(
            k_s[:, None], (BATCH, KV_HEADS, seq, shared_dim))], axis=-1)
        out, lse = attention._selected(
            jnp.concatenate([q, q_s], axis=-1), whole_k, v, selected, scale,
            block)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, lse)
    (_, aux), grads = jax.jit(jax.value_and_grad(
        joined, argnums=(0, 1, 2, 3), has_aux=True))(q, k, v, (q_s, k_s))
    want = aux + grads[:3] + tuple(grads[3])
    got = _kernel_value_and_grads(q, k, v, block, selected=selected,
                                  shared=(q_s, k_s))
    assert got[0].shape == (BATCH, KV_HEADS, group, seq, vdim)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    for name, g, w in zip(NAMES + ["d_q_s", "d_k_s"], got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        if name == "lse":
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        else:
            # (the kernel adds the heads' ``d_k_s`` in float32 and rounds
            # once, the body rounds a head's and adds in the input dtype:
            # tests/test_latent_trunk.py has the same factor)
            limit = TOLERANCE[dtype] * (5 if name == "d_k_s" else 1)
            assert np.linalg.norm(f32(g) - f32(w)) <= limit * \
                np.linalg.norm(f32(w)), name
