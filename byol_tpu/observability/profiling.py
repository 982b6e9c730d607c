"""Profiling hooks — jax.profiler integration.

The reference has no tracing at all (SURVEY.md §5.1: a single time.time()
per epoch plus cudnn.benchmark).  TPU-native profiling is first-class here:

- ``trace(logdir)``: capture a device trace (no host tracer) viewable in
  TensorBoard's profile plugin or Perfetto;
- ``start_server(port)``: on-demand profiling of a live run from another
  machine (``jax.profiler.start_server`` — the production pod workflow);
- ``annotate(name)``: named host-side regions (TraceAnnotation) that show up
  in the timeline alongside device ops when a capture has the host tracer
  on (``start_server`` captures do; ``trace`` does not).
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax


def start_server(port: int = 9999):
    """Expose this process to on-demand profile capture."""
    return jax.profiler.start_server(port)


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a DEVICE trace for the enclosed steps.

    On a TPU the host and Python tracers are off: with the host tracer on,
    at any level, the runtime traces every chunk of the host-side transposes
    that lay a batch out for the device, and a ResNet-50 step took
    1,061-1,196 ms instead of 329 (chip runs of PR 24).  The program's own
    host spans (observability/spans.py) meet the device trace by CLOCK
    instead: both are in epoch time, an event's ``start_ns`` counting from
    the trace's ``Task Environment`` ``profile_start_time``.  The CPU
    backend's ops ARE host events, absent at level 0, so there the host
    tracer runs at level 1.
    """
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = int(jax.default_backend() == "cpu")
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region in the profiler timeline."""
    return jax.profiler.TraceAnnotation(name)
