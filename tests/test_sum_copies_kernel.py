"""The expert layer's combine as one gather and a segment-sum kernel
(ops/sum_copies.py) against its ``jax.numpy`` body (``decoder_trunk.
_sum_copies`` without ``by_token``), on the CPU under the Pallas interpreter:
the layer chooses the lowering from the backend and the shapes, so the tests
answer ``sum_copies.applies`` for it and run the same kernel body at sizes
the interpreter is quick at.

Tolerances.  The addends are the same rows and both lowerings add them in
float32 and round once; the kernel adds a token's copies in expert order,
the ``jax.numpy`` body in slot order.  bfloat16: a sum of ≤ 4 bf16 numbers
is nearly always exact in float32, so the results are equal but for a
rounding tie now and then (at most 1 ulp; over the five windows below 0
elements of 491,520 differ, from the body and from a float64 sum rounded
once).  float32: three roundings in another order (27,853 of the 491,520
differ from the body, 34,419 from the float64 sum, by an ulp or two).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.ops import sum_copies

TOKENS, K, DIM = 768, 4, 128        # six token blocks of 128
EVERY = TOKENS * K
# the first 256 tokens: 0, 1, 2 and k copies; the next 256 (two blocks): no
# held copy at all; the last 256: every copy held, 512 rows a block of 128 —
# more than one window of 256
COPIES = np.concatenate([np.tile([0, 1, 2, 4], 64), np.zeros(256, int),
                         np.full(256, 4)])
HELD = int(COPIES.sum())            # 1,472


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _sorted_copies(experts=3, seed=11):
    """``here, token_of, place`` as ``ExpertLayer`` sorts the copies."""
    rng = np.random.default_rng(seed)
    here = np.zeros((TOKENS, K), bool)
    for t, n in enumerate(COPIES):
        here[t, rng.permutation(K)[:n]] = True
    bucket = np.where(here, rng.integers(0, experts, (TOKENS, K)),
                      experts).reshape(-1)
    order = np.argsort(bucket, kind="stable")
    place = np.argsort(order, kind="stable").reshape(TOKENS, K)
    return here, order // K, place


def _product(cap):
    """``idx, pos, ok, valid`` as ``ExpertLayer.product(cap)`` builds them."""
    here, token_of, place = _sorted_copies()
    return (token_of[:cap], np.minimum(place, cap - 1), here & (place < cap),
            np.arange(cap) < HELD)


def _slab(cap, start):
    """... and as ``ExpertLayer.slab(cap, start)`` does: the last slab
    starts early and masks the rows it shares with the one before."""
    here, token_of, place = _sorted_copies()
    first = min(start, EVERY - cap)
    row = first + np.arange(cap)
    return (token_of[first:first + cap], np.clip(place - first, 0, cap - 1),
            here & (place >= start) & (place < first + cap),
            (row >= start) & (row < HELD))


WINDOWS = {
    "every": lambda: _product(EVERY),          # the window is every copy
    "usual": lambda: _product(2048),           # only copies held elsewhere
    "short": lambda: _product(1024),           # lie past it; cuts 448 held
    "slab": lambda: _slab(1024, 1024),         # rows 1,024 .. 2,047
    "late_slab": lambda: _slab(1280, 2560),    # starts early, at 1,792: no
}                                              # held row is left in it


def _both(rows, idx, pos, ok, valid):
    plan = sum_copies.by_token(jnp.asarray(idx), jnp.asarray(valid), TOKENS)
    body = jax.jit(trunk_lib._sum_copies)(rows, pos, ok)
    kernel = jax.jit(trunk_lib._sum_copies)(rows, pos, ok, plan)
    return body, kernel, plan


def _ulps(a, b, dtype):
    """|a - b| in units of the last place of the larger magnitude."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    size = np.maximum(np.maximum(np.abs(a), np.abs(b)),
                      float(jnp.finfo(dtype).tiny))
    return np.abs(a - b) / (2.0 ** np.floor(np.log2(size))
                            * float(jnp.finfo(dtype).eps))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_the_kernel_is_the_jnp_body(window, dtype):
    """Tokens with 0, 1, 2 and k copies, token blocks with none, blocks
    whose 512 rows span three windows, rows ``valid`` leaves out inside the
    window, and every kind of window the layer cuts."""
    idx, pos, ok, valid = WINDOWS[window]()
    cap = idx.shape[0]
    counts = ok.sum(1)
    if window in ("every", "usual"):
        assert {0, 1, 2, 4} <= set(counts[:256].tolist())
        assert not counts[256:512].any() and (counts[512:] == K).all()
    assert counts.sum() == valid.sum() <= HELD
    assert (counts.sum() < HELD) == (window not in ("every", "usual"))
    rows = jnp.asarray(np.random.default_rng(12).normal(size=(cap, DIM)),
                       jnp.dtype(dtype))
    body, kernel, plan = _both(rows, idx, pos, ok, valid)
    assert kernel.dtype == rows.dtype and kernel.shape == (TOKENS, DIM)
    want = np.zeros((TOKENS, DIM))
    for t, j in zip(*np.nonzero(ok)):
        want[t] += np.asarray(rows, np.float64)[pos[t, j]]
    once = np.asarray(jnp.asarray(want, rows.dtype), np.float64)
    # a token without a copy in the window is an exact zero
    assert not np.asarray(kernel, np.float64)[counts == 0].any()
    if dtype == "bfloat16":
        for name, other in (("the body", body), ("rounded once", once)):
            off = _ulps(kernel, other, rows.dtype)
            assert off.max() <= 1.0, (name, off.max())
            assert (off > 0).mean() <= 1e-3, (name, int((off > 0).sum()))
    else:
        np.testing.assert_allclose(kernel, body, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(kernel, want, rtol=1e-6, atol=1e-6)
    # the items: each block's windows in turn, the spare ones do nothing
    flags, block = np.asarray(plan.flags), np.asarray(plan.block)
    real = flags != 0
    blocks = TOKENS // sum_copies.BLOCK
    assert block.shape == (blocks + cap // sum_copies.WINDOW,)
    assert (np.diff(block) >= 0).all()
    assert set(block[real]) == set(range(blocks))
    firsts = ((flags & sum_copies._FIRST) != 0).sum()
    assert firsts == ((flags & sum_copies._LAST) != 0).sum() == blocks
    if window in ("every", "usual"):
        # rows 0..223, 224..447, none, none, 448..959, 960..1,471
        assert np.bincount(block[real]).tolist() == [1, 2, 1, 1, 3, 3]


def test_rows_in_token_order_keep_a_tokens_copies_in_expert_order():
    idx, _, _, valid = WINDOWS["usual"]()
    plan = sum_copies.by_token(jnp.asarray(idx), jnp.asarray(valid), TOKENS)
    order, token = np.asarray(plan.order), np.asarray(plan.token)[0]
    assert sorted(order.tolist()) == list(range(idx.shape[0]))
    assert (token[:HELD] == idx[order[:HELD]]).all()
    assert (token[HELD:] == TOKENS).all()               # left out: last
    assert (np.diff(token) >= 0).all()
    same = np.diff(token[:HELD]) == 0
    assert (np.diff(order[:HELD])[same] > 0).all()      # stable


@pytest.mark.parametrize("window", ["usual", "slab"])
def test_dispatch_and_combine_stay_transposes_with_the_kernel(window):
    """``<take(x), r> = <x, put(r)>`` for rows ``r`` masked as the layer
    masks them, and each ``custom_vjp`` hands back the other's forward —
    the kernel on both sides."""
    idx, pos, ok, valid = WINDOWS[window]()
    plan = sum_copies.by_token(jnp.asarray(idx), jnp.asarray(valid), TOKENS)
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(TOKENS, DIM)), jnp.float32)
    r = jnp.asarray(np.where(valid[:, None],
                             rng.normal(size=(idx.shape[0], DIM)), 0),
                    jnp.float32)
    rows, take_vjp = jax.vjp(
        lambda x: trunk_lib._take_rows(x, idx, pos, ok, plan), x)
    out, put_vjp = jax.vjp(
        lambda r: trunk_lib._put_rows(r, idx, pos, ok, plan), r)
    np.testing.assert_allclose(jnp.vdot(rows, r), jnp.vdot(x, out),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(take_vjp(r)[0]),
                                  np.asarray(out))
    np.testing.assert_array_equal(np.asarray(put_vjp(x)[0]),
                                  np.asarray(rows))
    # the jax.numpy body's cotangents are the same but for rounding
    body = jax.vjp(lambda x: trunk_lib._take_rows(x, idx, pos, ok), x)[1]
    np.testing.assert_allclose(take_vjp(r)[0], body(r)[0], rtol=1e-6,
                               atol=1e-6)
    jaxpr = str(jax.make_jaxpr(lambda r: take_vjp(r)[0])(r))
    assert "pallas_call" in jaxpr and "sum_copies" in jaxpr


# (tokens, k, cap, D): the three cells' layer calls first
@pytest.mark.parametrize("backend,shape,dtype,want", [
    ("tpu", (32768, 10, 40960, 2048), "bfloat16", True),    # qwen3next
    ("tpu", (32768, 8, 65536, 2048), "bfloat16", True),     # keye
    ("tpu", (16384, 4, 16384, 3584), "bfloat16", True),     # xing4
    ("tpu", (32768, 8, 65536, 2048), "float32", True),
    ("cpu", (32768, 10, 40960, 2048), "bfloat16", False),   # no TPU
    ("gpu", (32768, 10, 40960, 2048), "bfloat16", False),
    ("tpu", (16384, 4, 65536, 3584), "bfloat16", False),    # every copy: k
    ("tpu", (32768, 10, 327680, 2048), "bfloat16", False),  # gathers do
    ("tpu", (32768, 10, 40960, 2040), "bfloat16", False),   # odd D
    ("tpu", (32768, 10, 40960, 64), "bfloat16", False),     # half a tile
    ("tpu", (32760, 10, 40960, 2048), "bfloat16", False),   # a short block
    ("tpu", (32768, 10, 40900, 2048), "bfloat16", False),   # a short window
    ("tpu", (32, 2, 16, 64), "float32", False),             # the tiny trunks
    ("tpu", (32768, 10, 40960, 32768), "float32", False),   # VMEM
])
def test_the_lowering_is_chosen_from_backend_and_shapes(backend, shape,
                                                        dtype, want):
    assert sum_copies.applies(*shape, jnp.dtype(dtype),
                              backend=backend) is want


def _layer_and_input(held):
    """An expert layer wide enough for the kernel: 512 tokens of 128, top-2
    of 8 experts, ``held`` of them here (``usual`` = 256 or 512 of the 1,024
    copies)."""
    z = dataclasses.replace(trunk_lib.TINY, hidden_size=128)
    x = 2.0 + jnp.asarray(np.random.default_rng(6).normal(
        size=(2, 256, 128)), jnp.float32)
    layer = trunk_lib.ExpertLayer(z, 8 - held, held)
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    return layer, params, x


@pytest.mark.parametrize("load", ["nominal", "whole_fallback", "in_slabs"])
def test_the_layer_is_the_same_with_either_lowering(load, monkeypatch):
    """``ExpertLayer`` forward and every gradient, the kernel engaged (the
    question ``applies`` asks answered as on a TPU) against the ``jax.numpy``
    body: at the nominal load (``product(usual)``), with every copy routed
    here (``product(every)``, which by its shape keeps the body) and the
    same in slabs (each slab a window with a ``start``)."""
    layer, params, x = _layer_and_input(held=2)
    if load != "nominal":       # x has a positive mean: ones win the softmax
        params = dict(params, router=params["router"].at[:, 6:].set(1.0))
    if load == "in_slabs":
        monkeypatch.setattr(trunk_lib, "WHOLE_FALLBACK_BYTES", 0)

    def run(p):
        out, sown = layer.apply({"params": p}, x, mutable=[trunk_lib.ROUTING])
        return out, sown[trunk_lib.ROUTING]["stats"][0]
    loss = lambda p: jnp.sum(jnp.sin(run(p)[0]))
    body_out, stats = run(params)
    body_grad = jax.grad(loss)(params)
    assert "pallas_call" not in str(jax.make_jaxpr(loss)(params))
    assert (float(stats[0]) > 512) == (load != "nominal")
    assert float(stats[3]) == 0.0                       # no row dropped
    monkeypatch.setattr(sum_copies, "applies", functools.partial(
        sum_copies.applies, backend="tpu"))
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert "sum_copies" in jaxpr
    out, _ = run(params)
    np.testing.assert_allclose(out, body_out, rtol=1e-5, atol=1e-5)
    for (path, got), want in zip(
            jax.tree_util.tree_flatten_with_path(jax.grad(loss)(params))[0],
            jax.tree_util.tree_leaves(body_grad)):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))),
            err_msg=jax.tree_util.keystr(path))
