"""The kernels of the causal grouped-query core (ops/causal_attention.py
without a selection; with one: tests/test_selected_attention_kernel.py)
against the ``jax.numpy`` body of ``ops/attention.blockwise_causal_attention``,
on the CPU under the Pallas interpreter: ``blockwise_causal_attention`` chooses
the kernels from the backend and the shapes, so the tests answer
``causal_attention.applies`` for it and run the same kernel bodies at sizes the
interpreter is quick at.

Tolerances: as tests/test_selected_attention_kernel.py — the same equations
over the same tiles in the same order of key blocks; the order of sums inside a
product and where ``d_k, d_v`` are added up differ.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.ops import attention
from byol_tpu.ops import causal_attention as kernels

BATCH, KV_HEADS, BLOCK = 2, 2, 128


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _qkv(seed, seq, group, dim, dtype, *, batch=BATCH, kv_heads=KV_HEADS):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape),
                                   jnp.float32).astype(dtype)
    return (f(batch, kv_heads * group, seq, dim), f(batch, kv_heads, seq, dim),
            f(batch, kv_heads, seq, dim))


@functools.partial(jax.jit, static_argnums=(0,))
def _value_and_grads(block, q, k, v):
    """``blockwise_causal_attention`` as the layer calls it and the gradients
    of a loss of its output (ONE program a lowering: the interpreter runs op
    by op otherwise)."""
    def loss(q, k, v):
        out = attention.blockwise_causal_attention(q, k, v, block=block)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


def _both(monkeypatch, block, q, k, v):
    got = []
    for taken in (False, True):
        monkeypatch.setattr(kernels, "applies", lambda *a, **kw: taken)
        _value_and_grads.clear_cache()
        got.append(_value_and_grads(block, q, k, v))
    return got


# relative to the norm of each of out, d_q, d_k, d_v
TOLERANCE = {"float32": 1e-5, "bfloat16": 1e-3}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 3])     # the diagonal tile alone; six
@pytest.mark.parametrize("dim,group", [(64, 4), (128, 8), (256, 8), (64, 1)])
def test_the_kernels_are_the_jnp_body(monkeypatch, dim, group, blocks, dtype):
    """Values, all three gradients and the rows' log-sum-exp, at the widths
    and groups of the two cells (and one head a key head)."""
    seq = blocks * BLOCK
    q, k, v = _qkv(dim + group + blocks, seq, group, dim, jnp.dtype(dtype))
    want, got = _both(monkeypatch, BLOCK, q, k, v)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    for name, g, w in zip("out d_q d_k d_v".split(), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.linalg.norm(f32(g) - f32(w)) <= TOLERANCE[dtype] * \
            np.linalg.norm(f32(w)), name
    grouped = q.reshape(BATCH, KV_HEADS, group, seq, dim)
    scale = dim ** -0.5
    tiles = attention.causal_tiles(blocks)
    lse = kernels._call(True, scale, BLOCK, True, tiles, grouped, k, v, None,
                        ())[1]
    assert lse.shape == (BATCH, KV_HEADS, group, seq)
    assert lse.dtype == jnp.float32          # a row a head, whatever q is
    want_lse = attention._blockwise_fwd(grouped, k, v, scale, BLOCK,
                                        tiles)[1][-1]
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "block,dim,seq,heads,kv_heads,dtype,backend,selected,taken", [
        (512, 64, 4096, 32, 8, "bfloat16", "tpu", False, True),   # lfm2's
        (512, 256, 4096, 16, 2, "bfloat16", "tpu", False, True),  # qwen3next
        (512, 128, 4096, 32, 4, "bfloat16", "tpu", False, True),
        (128, 64, 256, 4, 4, "float32", "tpu", False, True),
        (512, 64, 4096, 32, 8, "bfloat16", "cpu", False, False),  # no TPU
        (512, 256, 4096, 16, 2, "bfloat16", "cpu", False, False),
        (8, 8, 24, 4, 2, "float32", "tpu", False, False),   # the tiny presets
        (512, 96, 4096, 32, 8, "bfloat16", "tpu", False, False),  # 3/4 tile
        (512, 32, 4096, 32, 8, "bfloat16", "tpu", False, False),  # a quarter
        (96, 128, 4032, 32, 4, "bfloat16", "tpu", False, False),  # block 3/4
        (512, 64, 4000, 32, 8, "bfloat16", "tpu", False, False),  # short block
        (512, 64, 4096, 32, 5, "bfloat16", "tpu", False, False),  # unshared
        (512, 256, 16384, 16, 2, "bfloat16", "tpu", False, False),  # d_k, d_v
        # of a key head's sequence outgrow VMEM.  With a selection (one rule
        # of shapes for both uses; more: test_selected_attention_kernel.py):
        (512, 128, 4096, 32, 4, "bfloat16", "tpu", True, True),   # keye's
        (512, 64, 4096, 32, 4, "bfloat16", "tpu", True, True),    # compiles:
        # test_tpu_compile.py::test_selected_attention_kernels_at_the_...
        (512, 128, 4096, 32, 4, "bfloat16", "cpu", True, False),  # no TPU
    ])
def test_the_kernels_are_chosen_from_backend_and_shapes(
        block, dim, seq, heads, kv_heads, dtype, backend, selected, taken):
    assert kernels.applies(block, dim, seq, heads, kv_heads,
                           jnp.dtype(dtype), selected=selected,
                           backend=backend) is taken


def test_a_narrow_head_counts_a_whole_lane_tile_of_vmem():
    count = lambda dim, fwd: kernels._vmem_bytes(512, dim, 4096, 4, 2, fwd)
    assert count(64, True) == count(128, True)
    assert count(64, False) == count(128, False) < count(256, False)
    # the widest published case fits, narrowly (ISSUE 39: 39.6 of 48 MiB
    # with the mask operand; less without)
    assert 32 * 2 ** 20 < kernels._vmem_bytes(
        512, 256, 4096, 8, 2, False) <= kernels.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("group", [0, 2])
def test_on_the_cpu_the_core_lowers_to_no_kernel(group):
    """What tier-1 and every CPU run of ``train.py`` take: the ``jax.numpy``
    program, whatever the shapes — ``group`` sequences a pass under a
    ``lax.map`` where asked."""
    q, k, v = _qkv(0, 1024, 4, 64, jnp.bfloat16, batch=4, kv_heads=1)
    text = jax.jit(lambda *a: attention.blockwise_causal_attention(
        *a, block=512, group=group)).lower(q, k, v).as_text()
    assert "causal_attention_" not in text
    assert ("while" in text) is bool(group)


def test_sequences_a_pass_do_not_change_the_jnp_body():
    q, k, v = _qkv(1, 48, 2, 16, jnp.float32, batch=4)
    core = lambda group: jax.jit(functools.partial(
        attention.blockwise_causal_attention, block=16, group=group))
    np.testing.assert_allclose(core(2)(q, k, v), core(0)(q, k, v),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(core(3)(q, k, v), core(0)(q, k, v),
                               rtol=1e-6, atol=1e-6)   # 3 does not divide 4
