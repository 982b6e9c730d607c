"""Span-based flight recorder: attribute every second of a run.

The run log (events.py) says WHAT happened; this module records WHERE the
time went.  A :class:`SpanRecorder` collects host-side begin/end spans —
monotonic clock (``time.perf_counter``), nestable, per-thread depth
tracking, bounded ring buffer — cheap enough to wrap every hot-loop phase
(input wait, train dispatch, epoch readback, eval, checkpoint, telemetry
readback, startup/compile) without moving the throughput needle (the
``bench.py --spans-ab`` budget is < 2%, same bar as telemetry).

Every span also opens the matching :func:`profiling.annotate` region
(``jax.profiler.TraceAnnotation``), which a capture with the host tracer on
shows beside the device ops.  ``profiling.trace`` keeps the host tracer off
(it slows the host-to-device path threefold), so the two meet by CLOCK: one
anchor between ``perf_counter`` and the epoch clock, taken when this module
is imported (:func:`epoch_ns`), puts every span on the clock the device
trace is stamped in, and :func:`export_chrome_trace` writes epoch
microseconds — the export overlays a device trace in Perfetto as it is.

Two consumers fold the ring:

- :mod:`byol_tpu.observability.goodput` partitions wall time into
  productive step time vs named badput buckets per epoch and per run;
- :func:`export_chrome_trace` writes a Chrome-trace-event JSON file
  (load it in ``chrome://tracing`` or https://ui.perfetto.dev) so a run's
  timeline is inspectable with zero custom tooling.

Spans-off contract: :data:`NULL` (a :class:`NullRecorder`) is a shared
no-op whose ``span()`` returns one reusable context manager — no clock
read, no allocation, no ring append — so ``--spans off`` leaves the hot
loop untouched (``tests/test_spans.py`` pins it).

Host-side ONLY: a span inside jit-traced code would run ONCE at trace
time and be constant-folded into the executable — it would measure
nothing.  graphlint GL101 flags host clocks and span entry points inside
traced scopes (``tests/graphlint_fixtures/bad_span_clock.py``).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

from byol_tpu.observability import profiling

# The token feed (data/loader.py ``--task synth_tokens``): one span per host
# batch of masked id views, opened in the thread that makes the batch — the
# prefetch producer — on the module default recorder, which the trainer
# points at its own for the run.  Not under ``input/``: that prefix is the
# CONSUMER's wait, which goodput counts as badput.
TOKEN_FEED_SPAN = "feed/tokens"

# default ring capacity: ~3 spans/step x 20k steps; beyond it the OLDEST
# spans are evicted (``dropped`` counts them) — the recorder must never
# grow without bound on a week-long run
_CAPACITY = 1 << 16

# The two host clocks, read together once: spans are taken on perf_counter
# (monotonic, arbitrary origin), the profiler stamps a trace in epoch
# nanoseconds.  One anchor per process serves every recorder, and the
# export takes records, not a recorder.
_ANCHOR = (time.time_ns(), time.perf_counter())


def epoch_ns(t: float) -> int:
    """The ``perf_counter`` reading ``t`` (a span's ``t0`` / ``t1``) in
    epoch nanoseconds."""
    return _ANCHOR[0] + round((t - _ANCHOR[1]) * 1e9)


class Span:
    """One closed span: ``[t0, t1]`` on the perf_counter clock."""

    __slots__ = ("name", "t0", "t1", "tid", "depth", "seq", "attrs")

    def __init__(self, name: str, t0: float, t1: float, tid: int,
                 depth: int, seq: int, attrs: Optional[Dict[str, Any]]):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.depth = depth
        self.seq = seq
        self.attrs = attrs

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # debugging/test-failure readability
        return (f"Span({self.name!r}, {self.seconds * 1e3:.3f}ms, "
                f"depth={self.depth}, seq={self.seq})")


class _ActiveSpan:
    """The context manager one ``span()`` call returns.  Closing appends
    the record; the span is also a ``profiling.annotate`` region so host
    phases show up in captured XLA traces."""

    __slots__ = ("_rec", "_name", "_attrs", "_t0", "_depth", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 attrs: Optional[Dict[str, Any]]):
        self._rec = rec
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_ActiveSpan":
        local = self._rec._local
        self._depth = getattr(local, "depth", 0)
        local.depth = self._depth + 1
        self._ann = profiling.annotate(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        self._rec._local.depth = self._depth
        self._rec._append(Span(self._name, self._t0, t1,
                               threading.get_ident(), self._depth,
                               next(self._rec._seq), self._attrs))
        return False


class SpanRecorder:
    """Bounded, thread-safe-enough flight recorder.

    ``span(name, **attrs)`` returns a context manager; nesting tracks a
    per-thread depth so aggregators can attribute only TOP-LEVEL spans
    (nested spans would double-count their parents' wall time).  Appends
    are a deque push under the GIL; the only lock-worthy state (the seq
    counter) is an ``itertools.count``, which is atomic in CPython.
    """

    enabled = True

    def __init__(self, capacity: int = _CAPACITY) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: "deque[Span]" = deque(maxlen=capacity)
        self._seq = itertools.count()
        self._total = 0
        self._local = threading.local()

    def span(self, name: str, **attrs: Any) -> _ActiveSpan:
        return _ActiveSpan(self, name, attrs or None)

    def _append(self, rec: Span) -> None:
        self._ring.append(rec)
        self._total += 1

    # ---- readout ----------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Spans evicted by the ring bound (recorded minus retained)."""
        return max(0, self._total - len(self._ring))

    def records(self, since_seq: int = -1) -> List[Span]:
        """Snapshot of retained spans with ``seq > since_seq``, oldest
        first.  ``list(deque)`` is atomic under the GIL, so a snapshot
        taken while other threads append is consistent (it may simply
        miss spans that close after the copy)."""
        snap = list(self._ring)
        if since_seq < 0:
            return snap
        return [r for r in snap if r.seq > since_seq]

    def last_seq(self) -> int:
        snap = list(self._ring)
        return snap[-1].seq if snap else -1

    def clear(self) -> None:
        self._ring.clear()
        self._total = 0


class _NullSpan:
    """Shared no-op context manager — the whole spans-off hot path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """Spans-off: ``span()`` hands back one shared no-op context manager —
    no clock read, no allocation, no ring append, no annotate region."""

    enabled = False
    capacity = 0
    dropped = 0

    def span(self, name: str, **attrs: Any) -> _NullSpan:
        return _NULL_SPAN

    def records(self, since_seq: int = -1) -> List[Span]:
        return []

    def last_seq(self) -> int:
        return -1

    def clear(self) -> None:
        pass


NULL = NullRecorder()

# Module-level default recorder: convenience for scripts/fixtures that
# want ``spans.span("...")`` without threading a recorder through every
# call.  Defaults to NULL (recording is an explicit opt-in); the trainer
# and the serving stack construct and pass their OWN recorders.
_default: Any = NULL


def set_default(recorder: Any) -> None:
    global _default
    _default = recorder


def get_default() -> Any:
    return _default


def span(name: str, **attrs: Any):
    """Record on the module default recorder (host-side code only — under
    a jit trace this runs once and measures nothing; graphlint GL101)."""
    return _default.span(name, **attrs)


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def _json_safe(value: Any) -> Any:
    if isinstance(value, float):
        # strict-JSON discipline (GL110): a non-finite span attr must
        # not become a bare NaN token chrome://tracing refuses to load —
        # events.sanitize owns the float -> string mapping
        from byol_tpu.observability.events import sanitize
        return sanitize(value)
    if isinstance(value, (str, int, bool)) or value is None:
        return value
    if isinstance(value, (list, tuple)):
        return [_json_safe(v) for v in value]
    if isinstance(value, dict):
        return {str(k): _json_safe(v) for k, v in value.items()}
    return str(value)


def export_chrome_trace(records: Iterable[Span], path: str, *,
                        process_name: str = "byol_tpu") -> int:
    """Write spans as Chrome trace events (the ``traceEvents`` JSON array
    format); returns the event count.  ``ts`` is in EPOCH microseconds
    (:func:`epoch_ns`), the clock of a profiler trace, so the file overlays
    a device trace taken in the same run.  One complete-event (``ph:
    "X"``) per span; a metadata event names the process so multi-file
    sessions stay legible."""
    pid = os.getpid()
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    for r in sorted(records, key=lambda r: r.t0):
        ev: Dict[str, Any] = {
            "name": r.name,
            "cat": r.name.split("/", 1)[0],
            "ph": "X",
            "ts": epoch_ns(r.t0) / 1e3,
            "dur": (r.t1 - r.t0) * 1e6,
            "pid": pid,
            "tid": r.tid,
        }
        if r.attrs:
            ev["args"] = _json_safe(r.attrs)
        events.append(ev)
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        # ts/dur come from perf_counter deltas (always finite) and attrs
        # pass through _json_safe — strict dump so nothing lenient slips
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f,
                  allow_nan=False)
        f.write("\n")
    return len(events) - 1
