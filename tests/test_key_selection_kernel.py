"""The search of ``select_top_keys`` as a kernel (``top_keys_search``,
ops/key_selection.py) against its ``jax.numpy`` body, on the CPU under the
Pallas interpreter: ``select_top_keys`` chooses the kernel from the backend
and the shapes, so the tests answer ``key_selection.applies`` for it and run
the same kernel body at a size the interpreter is quick at — blocks of 128,
three of them, the top 200: query block 0 keeps every causal key, blocks 1
and 2 are searched (rows of two and of three tiles), and queries 128-199
must keep their whole causal length.

No tolerance: the sets are the same BITS.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.ops import key_selection

BATCH, BLOCK, BLOCKS, TOPK = 2, 128, 3, 200
TILES = BLOCKS * (BLOCKS + 1) // 2
REAL = 300                      # tokens of the "padded" case's sequences


@pytest.fixture(scope="module")
def lowerings():
    """``select_top_keys`` as ``(jax.numpy body, kernel)``, each compiled
    ONCE for every case."""
    like = jax.ShapeDtypeStruct((TILES, BATCH, BLOCK, BLOCK), jnp.float32)

    def compiled(taken):
        # a function of its own a lowering: ``jax.jit`` keeps ONE trace of a
        # function and shapes, whatever ``applies`` says by then
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(key_selection, "applies", lambda *a, **kw: taken)
            traced = jax.jit(lambda scores: key_selection.select_top_keys(
                scores, TOPK, block=BLOCK)).trace(like)
        assert ("pallas_call" in str(traced.jaxpr)) is taken
        return traced.lower().compile()
    return compiled(False), compiled(True)


def _scores(kind):
    rng = np.random.default_rng(sum(map(ord, kind)))
    shape = (TILES, BATCH, BLOCK, BLOCK)
    x = rng.normal(size=shape).astype(np.float32)
    if kind == "many_exact_zeros":          # the ReLU's: the tie path
        x = np.maximum(x, 0.0)
    elif kind == "negative_zero":           # -0.0 ties with 0.0
        x = rng.choice(np.float32([-0.0, 0.0, -1.0, 1.0, 1e-45]), size=shape)
    elif kind == "infinities":
        x[rng.random(shape) < 0.2] = np.inf
        x[rng.random(shape) < 0.2] = -np.inf
    elif kind == "the_ends_of_float32":
        x = rng.choice(np.float32([3.4028235e38, -3.4028235e38, 1e-45, -1e-45,
                                   1.1754944e-38, 0.5, -0.5]), size=shape)
    elif kind == "one_value_a_row":         # every causal key ties
        x = np.broadcast_to(x[..., :1], shape).copy()
    elif kind == "padded_last_block":       # the layer's zero rows past REAL
        q_of, k_of = key_selection.causal_pairs(BLOCKS)
        at = lambda of: of[:, None] * BLOCK + np.arange(BLOCK)
        x[np.broadcast_to((at(q_of) >= REAL)[:, None, :, None], shape)] = 0.0
        x[np.broadcast_to((at(k_of) >= REAL)[:, None, None, :], shape)] = 0.0
    else:
        assert kind == "random"
    return jnp.asarray(x)


@pytest.mark.parametrize("kind", [
    "random", "many_exact_zeros", "negative_zero", "infinities",
    "the_ends_of_float32", "one_value_a_row", "padded_last_block"])
def test_the_kernel_finds_the_jnp_bodys_set(lowerings, kind):
    scores = _scores(kind)
    want, got = (f(scores) for f in lowerings)
    assert got.dtype == want.dtype == jnp.bool_ and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # exactly min(t + 1, TOPK) causal keys a row, whatever ties
    q_of, _ = key_selection.causal_pairs(BLOCKS)
    kept = np.zeros((BLOCKS, BATCH, BLOCK), np.int64)
    np.add.at(kept, q_of, np.asarray(got).sum(axis=-1))
    rows = np.arange(BLOCKS * BLOCK).reshape(BLOCKS, 1, BLOCK)
    np.testing.assert_array_equal(
        kept, np.broadcast_to(np.minimum(rows + 1, TOPK), kept.shape))


@pytest.mark.parametrize("backend,block,blocks,taken", [
    ("tpu", 512, 8, True),          # the cell: a row of 8 tiles, 17 MiB
    ("tpu", 512, 16, True),         # 8,192 tokens: 16 tiles, 25 MiB
    ("tpu", 128, 3, True),
    ("cpu", 512, 8, False),         # not lowered for a TPU
    ("tpu", 96, 8, False),          # 3/4 of a lane tile
    ("tpu", 8, 3, False),           # SPARSE_TINY's
    ("tpu", 512, 64, False),        # a row of 64 tiles does not fit VMEM
])
def test_applies_reads_the_backend_and_the_shapes(backend, block, blocks,
                                                  taken):
    assert key_selection.applies(block, blocks, backend=backend) is taken
    if backend == "cpu":            # and asks JAX where it is not told
        assert key_selection.applies(block, blocks) is False
