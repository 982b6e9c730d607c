"""The within-chunk kernels of the gated delta rule (ops/delta_rule.py)
against the ``jax.numpy`` path of ``models/gated_delta.py``, on the CPU under
the Pallas interpreter: ``chunked_delta_rule`` chooses the kernels from the
backend and the shapes, so the tests answer ``delta_rule.applies`` for it
and run the same kernel bodies at sizes the interpreter is quick at.

Tolerances.  In float32 the two paths are the same equations in another
order of sums (the inverse by doubling from blocks of 2 against
substitution on blocks of 32; cumulative sums as products): a few float32
roundings.  In bfloat16 both round the same operands of the same products;
what differs is where a cotangent is rounded (the kernel adds the parts of
``dq``, ``dk`` in float32 and rounds once): a bfloat16 rounding of the
gradient's norm.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.models import gated_delta
from byol_tpu.ops import delta_rule

NAMES = "q k v g beta".split()


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seq, *, batch=2, key_heads=2, shared=1, dk=8, dv=16, seed=0,
            dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k = f(batch, seq, key_heads, dk)
    heads = key_heads * shared
    return ((f(batch, seq, key_heads, dk) * dk ** -0.5).astype(dtype),
            (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype),
            f(batch, seq, heads, dv).astype(dtype),
            -jnp.asarray(rng.uniform(0, 2, (batch, seq, heads)), jnp.float32),
            jnp.asarray(rng.uniform(0, 1, (batch, seq, heads)), jnp.float32))


def _value_and_grads(monkeypatch, kernels, inputs, **kw):
    monkeypatch.setattr(delta_rule, "applies", lambda *a, **k: kernels)

    def loss(*a):
        out = gated_delta.chunked_delta_rule(*a, **kw)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
    # value and gradients as ONE compiled program a path: op by op, the
    # interpreter's every step was a dispatch of its own
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*inputs)
    return out, grads


# relative to the norm: of the output, of each gradient
TOLERANCE = {"float32": (1e-5, 1e-5), "bfloat16": (4e-3, 1.5e-2)}


def _assert_close(dtype, names, got, grads, want, want_grads):
    """Output and gradients within ``TOLERANCE[dtype]`` of the norms."""
    out_tol, grad_tol = TOLERANCE[jnp.dtype(dtype).name]
    f32 = lambda x: x.astype(jnp.float32)
    gap = lambda a, b: float(jnp.linalg.norm(f32(a) - f32(b)))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert gap(got, want) <= out_tol * float(jnp.linalg.norm(f32(want)))
    for name, g, w in zip(names, grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert gap(g, w) <= grad_tol * float(jnp.linalg.norm(f32(w))), name


def _assert_both_paths_agree(monkeypatch, inputs, **kw):
    want, want_grads = _value_and_grads(monkeypatch, False, inputs, **kw)
    got, grads = _value_and_grads(monkeypatch, True, inputs, **kw)
    _assert_close(kw.get("dtype", "float32"), NAMES, got, grads, want,
                  want_grads)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [0, 2])
@pytest.mark.parametrize("shared", [1, 2])
@pytest.mark.parametrize("seq,chunk", [
    (16, 16),       # one chunk a sequence
    (64, 16),       # four
    (40, 16)])      # the last chunk is padded
def test_the_kernels_are_the_jnp_path(monkeypatch, seq, chunk, shared, group,
                                      dtype):
    inputs = _inputs(seq, batch=4, shared=shared, seed=seq + shared + group,
                     dtype=jnp.dtype(dtype))
    _assert_both_paths_agree(monkeypatch, inputs, chunk=chunk, group=group,
                             dtype=jnp.dtype(dtype))


def _scan_value_and_grads(between, operands):
    """``o`` and the six cotangents of ``between(*operands)`` as ONE compiled
    program."""
    def loss(*a):
        o = between(*a)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*operands)
    return o, grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
@pytest.mark.parametrize("key_heads", [
    2,      # programs of 2 heads (the tile), one or two of them
    3])     # 3 heads: programs of 1; 6: of 2 — the tile does not divide 3
@pytest.mark.parametrize("shared", [1, 2])
def test_the_scan_kernels_are_the_scan(monkeypatch, shared, key_heads, chunks,
                                       dtype):
    """``between_chunks`` (``delta_scan_fwd`` / ``delta_scan_bwd`` under the
    interpreter) against ``lax.scan(_chunk_step)`` on the SAME operands,
    ``within_chunk``'s: ``o`` and all six cotangents."""
    monkeypatch.setattr(delta_rule, "MAX_HEADS", 2)
    dt, chunk = jnp.dtype(dtype), 16
    q, k, v, g, beta = _inputs(chunks * chunk, key_heads=key_heads,
                               shared=shared, seed=chunks + key_heads,
                               dtype=dt)
    *operands, gamma = delta_rule.within_chunk(q, k, v, g, beta, chunk=chunk,
                                               dtype=dt)
    operands.append(jnp.exp(gamma[..., -1:]))
    (b, s, h, dv), dk = v.shape, k.shape[-1]

    def by_scan(*operands):
        _, out = jax.lax.scan(gated_delta._chunk_step(dt),
                              jnp.zeros((b, h, dk, dv), jnp.float32),
                              operands)
        return jnp.moveaxis(out, (0, 3), (1, 2)).reshape(b, s, h * dv)

    want, want_grads = _scan_value_and_grads(by_scan, operands)
    got, grads = _scan_value_and_grads(delta_rule.between_chunks, operands)
    assert got.dtype == dt and got.shape == (b, s, h * dv)
    assert [d.dtype for d in grads] == [x.dtype for x in operands]
    _assert_close(dt, "u w within q_in k_out decay".split(), got, grads,
                  want, want_grads)


@pytest.mark.parametrize("h,mode,heads", [
    (32, "forward", 8), (32, "keep", 8), (32, "backward", 8),   # the cell's
    (12, "forward", 6), (7, "backward", 7), (22, "keep", 2), (1, "keep", 1)])
def test_a_scan_program_holds_the_most_heads_that_divide(h, mode, heads):
    assert delta_rule._heads(h, 128, 128, 128, 2, mode) == heads


def test_a_scan_program_holds_what_fits_vmem():
    """Heads of 256, float32: the backward's blocks of 8 heads outgrow the
    16 MiB scope, so it holds fewer than the forward."""
    fit = lambda mode: delta_rule._heads(32, 128, 128, 256, 4, mode)
    assert fit("backward") < fit("forward") <= delta_rule.MAX_HEADS
    for mode in ("forward", "keep", "backward"):
        assert delta_rule._scan_vmem_bytes(
            128, 128, 256, fit(mode), 4, mode) <= delta_rule.VMEM_BYTES


def test_the_kernels_at_the_published_tile(monkeypatch):
    """Chunk 128, heads of 128, two value heads a key head, bfloat16: the
    shapes ``supported`` asks for, through the interpreter once."""
    inputs = _inputs(256, batch=1, key_heads=1, shared=2, dk=128, dv=128,
                     seed=5, dtype=jnp.bfloat16)
    assert delta_rule.supported(128, 128, 128)
    _assert_both_paths_agree(monkeypatch, inputs, chunk=128,
                             dtype=jnp.bfloat16)


def test_the_float32_parts_leave_the_kernel_in_float32():
    """``u`` and ``gamma`` float32, the scan's other operands in ``dtype``,
    chunk-major; the keys are read per KEY head, never repeated."""
    q, k, v, g, beta = _inputs(32, shared=2, dtype=jnp.bfloat16)
    u, w, within, q_in, k_out, gamma = delta_rule.within_chunk(
        q, k, v, g, beta, chunk=16, dtype=jnp.bfloat16)
    assert u.shape == (2, 2, 4, 16, 16) and u.dtype == jnp.float32
    assert gamma.shape == (2, 2, 4, 1, 16) and gamma.dtype == jnp.float32
    assert within.shape == (2, 2, 4, 16, 16)
    for x in (w, within, q_in, k_out):
        assert x.dtype == jnp.bfloat16
    want = jnp.cumsum(g.reshape(2, 2, 16, 4), axis=2)     # (B, N, C, H)
    np.testing.assert_allclose(gamma[:, :, :, 0], jnp.moveaxis(
        want, (1, 3), (0, 2)), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("chunk,dk,dv,dtype,backend,taken", [
    (128, 128, 128, "bfloat16", "tpu", True),     # the published sizes
    (128, 128, 256, "float32", "tpu", True),
    (128, 128, 128, "bfloat16", "cpu", False),    # not lowered for a TPU
    (8, 8, 8, "float32", "tpu", False),           # HYBRID_TINY
    (64, 128, 128, "bfloat16", "tpu", False),     # half a lane tile of tokens
    (128, 96, 128, "bfloat16", "tpu", False),
    (256, 128, 128, "bfloat16", "tpu", True),
    (512, 128, 128, "bfloat16", "tpu", False),    # the squares outgrow VMEM
])
def test_the_kernels_are_chosen_from_backend_and_shapes(chunk, dk, dv, dtype,
                                                        backend, taken):
    assert delta_rule.applies(chunk, dk, dv, jnp.dtype(dtype),
                              backend=backend) is taken


def test_on_the_cpu_the_rule_lowers_to_no_kernel():
    """What tier-1 and every CPU run of ``train.py`` take: today's
    ``jax.numpy`` program, whatever the shapes."""
    inputs = _inputs(256, batch=1, key_heads=1, dk=128, dv=128,
                     dtype=jnp.bfloat16)
    text = jax.jit(lambda *a: gated_delta.chunked_delta_rule(
        *a, chunk=128, dtype=jnp.bfloat16)).lower(*inputs).as_text()
    assert "delta_wy" not in text
    assert "lapack_strsm" in text             # solve_triangular, on the CPU
