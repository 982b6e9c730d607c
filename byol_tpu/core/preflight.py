"""Backend set-up shared by every entry point, run before the backend starts.

``train.py`` / ``python -m byol_tpu``, ``python -m byol_tpu serve``,
``bench.py`` and ``chip_smoke.py`` each run in ONE process that owns the
chip (a TPU belongs to one process at a time), so nothing here starts a
child.  Three steps, all of which must precede the first call that
initialises the XLA backend:

- :func:`force_cpu_devices` — the ``--cpu-devices N`` semantics: pin the
  CPU platform and size an N-device virtual mesh.
- :func:`place_compile_cache` — JAX's persistent compilation cache at a
  place that can be chosen from outside; and, because every entry point
  makes this call first, JAX's compile events onto the process's span
  recorder (``observability/spans.install_compile_listeners``).
- :func:`require_tpu` — the program runs on the CPU only when it was asked
  to; otherwise a default backend that is not ``tpu`` is an error at
  start-up, never a silent CPU run.
"""
from __future__ import annotations

import os

import jax

from byol_tpu.observability import spans

# <checkout>/.jax_cache — fixed, so the next process started from this
# checkout finds it again: never a temporary name, a pid or a time.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu_devices(n: int) -> None:
    """Pin the CPU platform and size an N-device virtual mesh — the
    ``--cpu-devices N`` semantics shared by bench.py, the serve CLI,
    chip_smoke.py's rehearsal mode and the tests.  Must be called before
    anything initialises the XLA backend."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def place_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and no
    directory is set in code; otherwise the cache lives in the one fixed
    directory ``<checkout>/.jax_cache`` (git-ignored), so a second process
    started from the same checkout finds what the first one compiled.
    From here on every trace, lowering and backend compile (or cache load)
    is a ``compile/*`` span on ``spans.PROCESS``.
    """
    spans.install_compile_listeners()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def cpu_requested() -> bool:
    """Was the CPU asked for?  ``--no-cuda`` and ``--cpu-devices N`` set
    ``jax_platforms`` to ``cpu`` through the config API, and
    ``JAX_PLATFORMS=cpu`` in the environment arrives in the same config
    value (the installed JAX honours it)."""
    return str(jax.config.jax_platforms or "") == "cpu"


def require_tpu(who: str) -> None:
    """Exit non-zero unless the default backend is ``tpu`` or the CPU was
    asked for.  Call before any model is built (multi-host: after the
    rendezvous, which must precede backend initialisation)."""
    if cpu_requested():
        return
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"{who}: the default JAX backend is {backend!r}, not 'tpu', "
            "and the CPU was not asked for.  Refusing to run on the CPU "
            "in silence: pass --no-cuda or --cpu-devices N (or set "
            "JAX_PLATFORMS=cpu) to run there on purpose.")


def describe_device() -> dict:
    """The device as JAX reports it — ``platform``, ``kind`` and ``count``
    — for every record that must say what it ran on (the ``device`` field
    of ``run_header``, chip_smoke.py's result line).  Initialises the
    backend."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
