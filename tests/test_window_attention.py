"""Causal attention under a BAND (``ops/attention.window_tiles``): the list
of tile pairs against the dense ``[S, S]`` rule for windows below, equal to
and above a tile, and both lowerings of the blockwise core under it — the
``jax.numpy`` body and the kernels of ops/causal_attention.py under the
Pallas interpreter — against the masked softmax, value and every gradient,
with a 64-wide key on a 128-wide value among the shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.ops import attention
from byol_tpu.ops import causal_attention as kernels


def dense_rule(rows, window):
    ahead = np.arange(rows)[:, None] - np.arange(rows)[None, :]
    return (ahead >= 0) & (ahead < window)


def expanded(tiles, block):
    """What a list shows, as a ``[rows, rows]`` mask."""
    rows = (max(tiles.q_of) + 1) * block
    seen = np.zeros((rows, rows), bool)
    ahead = np.arange(block)[:, None] - np.arange(block)[None, :]
    for n, (i, j, kind) in enumerate(zip(tiles.q_of, tiles.k_of,
                                         tiles.kind)):
        at = np.s_[i * block:(i + 1) * block, j * block:(j + 1) * block]
        assert not seen[at].any()                          # listed once
        if kind == attention.FULL:
            seen[at] = True
        else:
            assert kind == attention.WITHIN
            lo, hi = tiles.bounds[n]
            seen[at] = (ahead >= lo) & (ahead <= hi)
    return seen


@pytest.mark.parametrize("blocks,block,window", [
    (4, 8, 1), (4, 8, 3), (4, 8, 8), (4, 8, 9), (4, 8, 12), (4, 8, 16),
    (4, 8, 17), (5, 4, 10), (4, 8, 32), (4, 8, 100), (1, 8, 5)])
def test_the_list_of_tile_pairs_is_the_dense_rule(blocks, block, window):
    tiles = attention.window_tiles(blocks, window, block)
    np.testing.assert_array_equal(expanded(tiles, block),
                                  dense_rule(blocks * block, window))
    # no tile without a visible pair, a FULL tile where every pair is
    for n, (i, j, kind) in enumerate(zip(tiles.q_of, tiles.k_of,
                                         tiles.kind)):
        at = dense_rule(blocks * block, window)[
            i * block:(i + 1) * block, j * block:(j + 1) * block]
        assert at.any() and (kind == attention.FULL) == bool(at.all())
    # a query tile's pairs side by side, its own tile last
    attention._tile_rows(tiles)
    assert all(tiles.k_of[n] == i for n, i in enumerate(tiles.q_of)
               if n + 1 == len(tiles.q_of) or tiles.q_of[n + 1] != i)


def test_the_published_band_is_31_of_the_triangles_136_tiles():
    tiles = attention.window_tiles(16, 512, 512)
    assert len(tiles.q_of) == 31 and attention.FULL not in tiles.kind
    assert len(attention.causal_tiles(16).q_of) == 136
    assert tiles.bounds[0] == (0, 511) and tiles.bounds[1] == (-512, -1)
    with pytest.raises(ValueError, match="window"):
        attention.window_tiles(4, 0, 8)


def masked_softmax(q, k, v, seen, scale):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def qkv(seed, rows, heads, kv_heads, dim, vdim, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    normal = lambda k, h, d: jax.random.normal(k, (batch, h, rows, d),
                                               jnp.float32)
    return (normal(keys[0], heads, dim), normal(keys[1], kv_heads, dim),
            normal(keys[2], kv_heads, vdim), normal(keys[3], heads, vdim))


@pytest.mark.parametrize("lowering", ["body", "kernels"])
@pytest.mark.parametrize("rows,block,window,dim,vdim", [
    (32, 8, 5, 16, 16), (32, 8, 8, 16, 32), (32, 8, 11, 16, 16),
    (32, 8, 40, 16, 16), (256, 128, 128, 64, 128)])
def test_the_core_under_a_band_is_the_masked_softmax(
        lowering, rows, block, window, dim, vdim):
    with jax.default_matmul_precision("highest"):
        q, k, v, cotangent = qkv(1, rows, 4, 2, dim, vdim)
        tiles = attention.window_tiles(rows // block, window, block)
        seen, scale = jnp.asarray(dense_rule(rows, window)), dim ** -0.5
        if lowering == "body":
            core = lambda q, k, v: attention.blockwise_causal_attention(
                q, k, v, block=block, tiles=tiles)
        else:
            core = lambda q, k, v: kernels.attend(
                q.reshape(q.shape[0], 2, 2, rows, dim), k, v, scale=scale,
                block=block, tiles=tiles, interpret=True)[0].reshape(
                    q.shape[:3] + (vdim,))
        each = lambda fn: jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * cotangent), argnums=(0, 1, 2)))(
                q, k, v)
        got = each(core)
        want = each(lambda q, k, v: masked_softmax(q, k, v, seen, scale))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_a_band_list_is_not_mistaken_for_the_triangle():
    """The kernels read the lower triangle's kinds from a pair's own ``(i,
    j)``; a band that covers the row lists the same pairs with bounds of
    its own and must go the flagged way."""
    assert kernels._flags(attention.causal_tiles(4)) is None
    band = attention.window_tiles(4, 100, 8)
    assert band.q_of == attention.causal_tiles(4).q_of
    flags = kernels._flags(band)
    assert flags is not None and len(flags) == len(band.q_of)
    assert [f & 3 for f in flags] == [
        0 if kind == attention.FULL else 1 for kind in band.kind]
