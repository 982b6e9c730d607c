"""Test harness: simulate an 8-device TPU-like mesh on CPU.

The reference had no tests and could only validate multi-node behavior by
launching on SLURM (SURVEY.md §4).  JAX lets us run the full SPMD program on
N virtual CPU devices instead — this must be configured before jax imports.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

from byol_tpu.core import preflight  # noqa: E402  (imports jax, no backend)

preflight.force_cpu_devices(8)
# Persistent compilation cache: repeated test runs (and repeated fit() calls
# within one run) reuse compiled executables instead of paying 30-60s XLA
# compiles per jit instance.
preflight.place_compile_cache()

import functools  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402


def guard_steps(fn):
    """Runtime complement to graphlint GL101/GL102: wrap a jitted step so
    every call (including the first, tracing+compiling one) runs under

    - ``jax.transfer_guard("disallow")`` — an IMPLICIT host<->device
      transfer inside the step (a ``float()``/``np.asarray`` sync point, a
      numpy constant smuggled into the traced graph) fails the test on CPU
      instead of stalling a TPU run.  Explicit transfers (``device_put``,
      ``device_get``) stay allowed — reading metrics AFTER the call is
      legitimate and must be spelled explicitly.
    - ``jax.checking_leaks()`` — a tracer escaping the traced scope (the
      classic closure-capture bug) raises instead of baking in a constant.
    """
    @functools.wraps(fn)
    def guarded(*args, **kwargs):
        with jax.transfer_guard("disallow"), jax.checking_leaks():
            return fn(*args, **kwargs)
    return guarded


def tree_maxdiff(a, b):
    """Max abs elementwise difference over two pytrees' paired leaves (fp32
    compare) — the parity comparator test_zero1.py and test_checkpoint.py
    share."""
    import numpy as np

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return max(
        float(np.max(np.abs(np.asarray(x, np.float32)
                            - np.asarray(y, np.float32))))
        if np.asarray(x).size else 0.0
        for x, y in zip(la, lb))


@pytest.fixture(scope="session")
def step_guard():
    """Fixture handle for :func:`guard_steps` (importable directly as
    ``tests.conftest.guard_steps`` where a fixture is awkward)."""
    return guard_steps


@pytest.fixture(scope="session")
def mesh8():
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    return build_mesh(MeshSpec(data=8))


@pytest.fixture(scope="session")
def mesh_dp_sp():
    """4-way data x 2-way sequence mesh for context-parallel tests."""
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    return build_mesh(MeshSpec(data=4, sequence=2))
