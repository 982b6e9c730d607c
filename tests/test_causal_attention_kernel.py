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


def _draws(seed, dtype):
    rng = np.random.default_rng(seed)
    return lambda *shape: jnp.asarray(rng.normal(size=shape),
                                      jnp.float32).astype(dtype)


def _qkv(seed, seq, group, dim, dtype, *, batch=BATCH, kv_heads=KV_HEADS):
    f = _draws(seed, dtype)
    return (f(batch, kv_heads * group, seq, dim), f(batch, kv_heads, seq, dim),
            f(batch, kv_heads, seq, dim))


def _shared(seed, seq, heads, rope, dtype, *, batch=BATCH):
    """``(q_s, k_s)``: a part of every head whose key is one for all heads,
    or None."""
    if not rope:
        return None
    f = _draws(seed, dtype)
    return f(batch, heads, seq, rope), f(batch, seq, rope)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _value_and_grads(block, tiles, q, k, v, shared=None):
    """``blockwise_causal_attention`` as the layer calls it and the gradients
    of a loss of its output — ``d_q, d_k, d_v`` and, with a shared part,
    ``d_q_s, d_k_s`` (ONE program a lowering: the interpreter runs op by op
    otherwise)."""
    def loss(q, k, v, shared):
        out = attention.blockwise_causal_attention(
            q, k, v, block=block, tiles=tiles, shared=shared)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                         has_aux=True)(q, k, v, shared)
    return (out,) + tuple(jax.tree.leaves(grads))


def _both(monkeypatch, block, q, k, v, shared=None, tiles=None):
    got = []
    for taken in (False, True):
        monkeypatch.setattr(kernels, "applies", lambda *a, **kw: taken)
        _value_and_grads.clear_cache()
        got.append(_value_and_grads(block, tiles, q, k, v, shared))
    return got


@pytest.fixture
def heads_a_program(monkeypatch):
    """``set(target)``: ``key_heads``'s target patched; ``_call`` keeps one
    trace a shape, so its cache goes with every change and at the end."""
    def set_to(target):
        monkeypatch.setattr(kernels, "HEADS_A_PROGRAM", target)
        kernels._call.clear_cache()
    yield set_to
    kernels._call.clear_cache()


# a rule's list of tile pairs over ``blocks`` tiles, and the tiles of its row
RULES = {
    "causal": lambda blocks: (None, blocks),
    # a band of a tile and a quarter: FULL and WITHIN pairs, bounds a pair
    "band": lambda blocks: (attention.window_tiles(
        blocks, BLOCK + BLOCK // 4, BLOCK), blocks),
    # [noised | clean] halves, beta over blocks of 32: four kinds, flagged
    "halves": lambda blocks: (attention.block_diffusion_tiles(blocks, 32),
                              2 * blocks),
}

# relative to the norm of each of out, d_q, d_k, d_v (, d_q_s, d_k_s)
TOLERANCE = {"float32": 1e-5, "bfloat16": 1e-3}
NAMES = "out d_q d_k d_v d_q_s d_k_s".split()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 3])     # the diagonal tile alone; six
@pytest.mark.parametrize("dim,group,kv_heads,rope,rule", [
    (64, 4, 2, 0, "causal"), (128, 8, 2, 0, "causal"),
    (256, 8, 2, 0, "causal"),
    (64, 1, 2, 0, "causal"),        # one head a key head: 2 key heads a
    # program.  More of ``key_heads``'s answers, (forward, backward):
    (128, 1, 8, 0, "causal"),       # (4, 4), two programs a sequence
    (128, 1, 8, 64, "causal"),      # ... d_k_s summed in a program AND across
    (128, 1, 2, 64, "causal"),      # (2, 2): the key heads there are
    (128, 2, 2, 0, "causal"),       # (2, 2) at two query heads a key head
    (128, 2, 4, 64, "causal"),      # ... with the shared pair, two programs
    (128, 1, 6, 64, "causal"),      # (3, 3): the largest divisor of 6
    (128, 1, 8, 64, "band"),        # (4, 4) under a list with bounds
    (64, 2, 2, 0, "halves"),        # (2, 2) under a flagged list
    (128, 1, 4, 64, "halves"),      # (4, 4), all the key heads one program
])
def test_the_kernels_are_the_jnp_body(monkeypatch, dim, group, kv_heads, rope,
                                      rule, blocks, dtype):
    """Values, every gradient and the rows' log-sum-exp, at the widths and
    groups of the cells, and at each number of key heads a program the rule
    gives — with and without the shared pair, on the triangle and on listed
    tiles."""
    tiles, row = RULES[rule](blocks)
    seq, heads = row * BLOCK, kv_heads * group
    q, k, v = _qkv(dim + group + blocks, seq, group, dim, jnp.dtype(dtype),
                   kv_heads=kv_heads)
    shared = _shared(rope + blocks, seq, heads, rope, jnp.dtype(dtype))
    want, got = _both(monkeypatch, BLOCK, q, k, v, shared, tiles)
    assert len(got) == (6 if rope else 4)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        # (the kernel adds the heads' ``d_k_s`` in float32 and rounds once;
        # the body rounds a head's and adds in the input dtype: in bfloat16
        # its own error grows with the heads — float32 holds the sum sharp)
        limit = TOLERANCE[dtype] * (
            max(heads, 5) if (name, dtype) == ("d_k_s", "bfloat16") else 1)
        assert np.linalg.norm(f32(g) - f32(w)) <= limit * np.linalg.norm(
            f32(w)), name
    group_heads = lambda x: x.reshape(
        (BATCH, kv_heads, group) + x.shape[2:])
    grouped = group_heads(q)
    scale = (dim + rope) ** -0.5
    tiles = attention.causal_tiles(row) if tiles is None else tiles
    lse = kernels._call(True, scale, BLOCK, True, tiles, grouped, k, v, None,
                        (group_heads(shared[0]), shared[1]) if rope else ())[1]
    assert lse.shape == (BATCH, kv_heads, group, seq)
    assert lse.dtype == jnp.float32          # a row a head, whatever q is
    if rope:                        # the jnp body's one 192-wide operand
        grouped = jnp.concatenate([grouped, group_heads(shared[0])], -1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            shared[1][:, None], (BATCH, kv_heads, seq, rope))], -1)
    want_lse = attention._blockwise_fwd(grouped, k, v, scale, BLOCK,
                                        tiles)[1][-1]
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group,kv_heads,rope,rule,heads", [
    (1, 8, 64, "causal", 4),        # joyai's forward: four key heads
    (1, 8, 64, "causal", 2),        # ... its backward: two
    (2, 4, 0, "band", 2),           # phi4flash's, under its band
    (2, 2, 64, "halves", 2),
])
def test_key_heads_a_program_change_no_bit(heads_a_program, group, kv_heads,
                                           rope, rule, heads, dtype):
    """Heads are independent: ``out, lse`` and every cotangent are ONE key
    head a program's to the bit, but ``d_k_s``, whose float32 terms over
    heads and pairs are the same and summed in another order."""
    tiles, row = RULES[rule](2)
    seq, dim = row * BLOCK, 128
    tiles = attention.causal_tiles(row) if tiles is None else tiles
    q, k, v = _qkv(heads, seq, group, dim, jnp.dtype(dtype),
                   kv_heads=kv_heads)
    shared = _shared(3, seq, kv_heads * group, rope, jnp.dtype(dtype))
    group_heads = lambda x: x.reshape((BATCH, kv_heads, group) + x.shape[2:])

    @jax.jit
    def outputs(q, k, v, shared):
        def loss(q, k, v, shared):
            out, lse = kernels.attend(
                group_heads(q), k, v, scale=(dim + rope) ** -0.5, block=BLOCK,
                tiles=tiles, interpret=True, shared=shared and (
                    group_heads(shared[0]), shared[1]))
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, lse)
        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(q, k, v, shared)
        return aux + tuple(jax.tree.leaves(grads))
    got = []
    for target in (1, heads * group):
        heads_a_program(target)
        assert [kernels.key_heads(BLOCK, dim, seq, group, kv_heads, 4, fwd,
                                  shared=rope) for fwd in (True, False)] \
            == [max(target // group, 1)] * 2
        got.append(outputs(q, k, v, shared))
    for name, one, more in zip(["out", "lse"] + NAMES[1:], *got):
        if name == "d_k_s":
            np.testing.assert_allclose(
                np.asarray(more, np.float32), np.asarray(one, np.float32),
                rtol=2e-2 if dtype == "bfloat16" else 1e-5,
                atol=2e-2 if dtype == "bfloat16" else 1e-5, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(more, np.float32),
                                          np.asarray(one, np.float32), name)


@pytest.mark.parametrize("cell,sizes,forward,backward", [
    # (block, head, tokens, G, Hkv), what else; the benchmark's kernel shapes
    ("joyai", ((512, 128, 4096, 1, 32), dict(vdim=128, shared=64)), 4, 2),
    ("phi4flash", ((512, 64, 8192, 2, 20), dict(vdim=128)), 2, 2),
    ("lfm2", ((512, 64, 4096, 4, 8), {}), 1, 1),
    ("qwen3next", ((512, 256, 4096, 8, 2), {}), 1, 1),
    ("keye", ((512, 128, 4096, 8, 4), dict(selected=True)), 1, 1),
    ("sdar", ((512, 128, 8192, 8, 4), {}), 1, 1),
    # the target is four heads: three key heads where three divide them,
    # one where none of 4, 3, 2 does
    ("six key heads", ((512, 128, 4096, 1, 6), {}), 3, 3),
    ("seven key heads", ((512, 128, 4096, 1, 7), {}), 1, 1),
    ("five, in pairs", ((512, 128, 4096, 2, 5), {}), 1, 1),
    ("three query heads", ((512, 128, 4096, 3, 8), {}), 1, 1),
    # a longer sequence's resident d_k, d_v leave the backward no second head
    ("16,384 tokens", ((512, 128, 16384, 1, 32), {}), 4, 1),
])
def test_key_heads_a_program_come_from_the_shapes(cell, sizes, forward,
                                                  backward):
    """The rule's answers, forward and backward apart: the largest divisor of
    the key heads that fills a program to four heads and fits VMEM by the
    count; 1 for every group of four and more."""
    args, kw = sizes
    assert [kernels.key_heads(*args, 2, fwd, **kw)
            for fwd in (True, False)] == [forward, backward], cell


def test_the_vmem_count_refuses_key_heads_that_do_not_fit():
    """joyai's backward: the float32 ``d_k, d_v`` of a key head's 4,096
    tokens are 4 MB, counted twice — four heads of them outgrow the limit,
    and what the count takes a head is what one head counts."""
    count = lambda heads, fwd: kernels._vmem_bytes(
        512, 128, 4096, 1, 2, fwd, vdim=128, shared=64, heads=heads)
    assert count(2, False) <= kernels.VMEM_LIMIT_BYTES < count(4, False)
    assert count(4, True) <= kernels.VMEM_LIMIT_BYTES
    # the shared key and d_k_s have no head axis: counted once
    per_head = count(2, False) - count(1, False)
    assert count(4, False) == count(1, False) + 3 * per_head
    assert kernels._vmem_bytes(512, 128, 4096, 2, 2, False, heads=1) < \
        kernels._vmem_bytes(512, 128, 4096, 1, 2, False, heads=2)  # k, v, d_k, d_v twice


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
