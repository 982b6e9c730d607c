"""Mamba's selective scan (ops/selective_scan.py): the chunked ``jax.numpy``
body and the kernel pair, under the Pallas interpreter, against the
recurrence written out a step a position — the value and every cotangent
(``u``, ``delta``, ``A``, ``B``, ``C``, ``D``, all through one cotangent on
``m``) — at row lengths that are not whole chunks, with one and with several
column blocks of channels a row, in float32 and with bfloat16 operands."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.ops import selective_scan as scan_lib

NAMES = ("u", "delta", "A", "B", "C", "D")


def step_by_step(u, delta, a, b, c, d):
    """``m_t = H_t C_t + D u_t``, ``H_t = exp(delta_t A) H_{t-1} + (delta_t
    u_t) B_t``: one ``lax.scan`` over the positions, nothing chunked."""
    def step(h, row):
        u_t, d_t, b_t, c_t = row
        h = jnp.exp(d_t[..., None] * a) * h \
            + (d_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + d * u_t
    rows = tuple(jnp.moveaxis(x, 1, 0) for x in (u, delta, b, c))
    _, m = jax.lax.scan(step, jnp.zeros(u.shape[:1] + a.shape), rows)
    return jnp.moveaxis(m, 0, 1)


def operands(batch, seq, channels, state, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    return ((normal(keys[0], batch, seq, channels).astype(dtype),
             jax.nn.softplus(normal(keys[1], batch, seq, channels) - 1.0),
             -jnp.exp(0.5 * normal(keys[2], channels, state)),
             normal(keys[3], batch, seq, state).astype(dtype),
             normal(keys[4], batch, seq, state).astype(dtype),
             normal(keys[5], channels)),
            normal(keys[6], batch, seq, channels))


def value_and_cotangents(fn, args, cotangent):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cotangent),
        argnums=tuple(range(6))))(*args)


def kernels(chunk):
    return lambda u, delta, a, b, c, d: scan_lib.scan_kernels(
        u, delta, a, b, c, chunk=chunk, interpret=True) + d * u


def body(chunk):
    return lambda u, delta, a, b, c, d: scan_lib.chunked_scan(
        u, delta, a, b, c, chunk=chunk) + d * u


def assert_close(got, want, rtol):
    for name, g, w in zip(("m",) + NAMES, [got[0], *got[1]],
                          [want[0], *want[1]]):
        gap = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
        assert gap <= rtol * float(jnp.max(jnp.abs(w))) + 1e-6, (name, gap)


@pytest.mark.parametrize("lowering", ["body", "kernels"])
@pytest.mark.parametrize("batch,seq,channels,state,chunk,lanes", [
    (2, 40, 128, 8, 16, 512),        # 2.5 chunks a row, one column block
    (1, 24, 256, 16, 16, 128),       # 1.5 chunks, two column blocks of 128
    (2, 33, 384, 8, 32, 128),        # a row one step past a chunk, three
])
def test_the_scan_is_the_recurrence_value_and_all_seven_cotangents(
        monkeypatch, lowering, batch, seq, channels, state, chunk, lanes):
    monkeypatch.setattr(scan_lib, "MAX_LANES", lanes)
    args, cotangent = operands(batch, seq, channels, state)
    want = value_and_cotangents(step_by_step, args, cotangent)
    fn = {"body": body, "kernels": kernels}[lowering](chunk)
    assert_close(value_and_cotangents(fn, args, cotangent), want, 2e-5)


def test_the_kernels_take_bfloat16_rows_and_keep_float32_sums():
    """``u``, ``B`` and ``C`` in bfloat16 as the layer makes them: the
    result and its cotangents come back in bfloat16, ``delta``'s and
    ``A``'s in float32, each the float32 recurrence of the same rounded
    operands but for its own last rounding."""
    args, cotangent = operands(2, 48, 128, 16, seed=3, dtype=jnp.bfloat16)
    widened = tuple(x.astype(jnp.float32) for x in args)
    want = value_and_cotangents(step_by_step, widened, cotangent)
    fn = lambda u, delta, a, b, c, d: scan_lib.scan_kernels(
        u, delta, a, b, c, chunk=16, interpret=True)
    m = fn(*args)
    assert m.dtype == jnp.bfloat16
    got = value_and_cotangents(
        lambda *a: fn(*a).astype(jnp.float32)
        + a[5] * a[0].astype(jnp.float32), args, cotangent)
    assert [g.dtype for g in got[1]] == [
        jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
        jnp.float32]
    assert_close(got, want, 2e-2)


def test_a_step_of_zero_keeps_the_state_and_the_fill_reads_nothing():
    """What fills a row to whole chunks: ``delta = 0`` is ``exp(0) = 1``
    on the state and nothing added."""
    (u, delta, a, b, c, d), _ = operands(1, 32, 128, 8, seed=5)
    delta = delta.at[:, 16:].set(0.0)
    m = scan_lib.chunked_scan(u, delta, a, b, c, chunk=16)
    held = step_by_step(u, delta, a, b, c, jnp.zeros_like(d))
    np.testing.assert_allclose(m, held, rtol=1e-5, atol=1e-6)
    # from position 16 on nothing moves the state: a later row's output is
    # what an EARLIER position's C would have read there
    swapped = c.at[:, 20].set(c[:, 30])
    again = scan_lib.chunked_scan(u, delta, a, b, swapped, chunk=16)
    np.testing.assert_allclose(again[:, 20], m[:, 30], rtol=1e-5, atol=1e-6)


def test_which_shapes_take_the_kernels():
    assert scan_lib.supported(5120, 16) and scan_lib._lanes(5120) == 512
    assert scan_lib._lanes(128) == 128 and scan_lib._lanes(640) == 128
    assert not scan_lib.supported(5120, 4)         # half a sublane tile
    assert not scan_lib.supported(96, 16)          # no whole lane tile
    assert not scan_lib.supported(5120, 16, chunk=8)
    assert scan_lib.applies(5120, 16, backend="tpu")
    assert not scan_lib.applies(5120, 16, backend="cpu")
    assert not scan_lib.applies(5120, 16)          # here: the CPU
