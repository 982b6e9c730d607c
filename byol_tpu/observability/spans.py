"""Span-based flight recorder: attribute every second of a run.

The run log (events.py) says WHAT happened; this module records WHERE the
time went.  A :class:`SpanRecorder` collects host-side begin/end spans —
monotonic clock (``time.perf_counter``), nestable, per-thread depth
tracking, bounded ring buffer — cheap enough to wrap every hot-loop phase
(input wait, train dispatch, epoch readback, eval, checkpoint, telemetry
readback, startup/compile) without moving the throughput needle: a span
is two clock reads and a deque push, 2.9 us on the chip machine's host; a
compile listener's call 2.9-4.1 us, 10,768 of them in a ResNet-50 cell's
set-up — 0.03 s of its 38 s, and ``setup_s`` read the same with and
without them (PERF.md section 6, PR 36, timed on that host and counted
from the ring itself).

One recorder, :data:`PROCESS`, is made when this module is imported and
always records: the program's SET-UP (``startup/*``: config, mesh,
resolve, plan, and the build with its seven parts) and JAX's own compile
events (``compile/trace``, ``/lower``, ``/backend``; see
:func:`install_compile_listeners`) land on it whoever the caller is — the
trainer, the server, ``chip_smoke.py`` or a benchmark driver that passes
nothing.  That is a few dozen spans and a few thousand listener calls a
process, none inside a step.  ``trainer.fit`` under ``--spans on`` records
its hot loop on the same recorder, so start-up, compiles and steps are one
timeline and one export.  Each span knows the span that caused it
(``parent``: the ``seq`` of the span open on its thread when it opened),
so self time can be read from a ring (:func:`goodput.self_seconds`).

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

import functools
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


def from_epoch(seconds: float) -> float:
    """The inverse of :func:`epoch_ns`: an epoch reading in SECONDS
    (``time.time()``, the clock JAX stamps its compile events in) as a
    ``perf_counter`` reading."""
    return _ANCHOR[1] + (seconds - _ANCHOR[0] / 1e9)


class Span:
    """One closed span: ``[t0, t1]`` on the perf_counter clock.  ``seq`` is
    taken when the span OPENS, ``parent`` is the ``seq`` of the span that
    was open on the same thread then (-1 at depth 0)."""

    __slots__ = ("name", "t0", "t1", "tid", "depth", "seq", "parent",
                 "attrs")

    def __init__(self, name: str, t0: float, t1: float, tid: int,
                 depth: int, seq: int, parent: int,
                 attrs: Optional[Dict[str, Any]]):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.depth = depth
        self.seq = seq
        self.parent = parent
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

    __slots__ = ("_rec", "_name", "_attrs", "_t0", "_depth", "_seq",
                 "_parent", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 attrs: Optional[Dict[str, Any]]):
        self._rec = rec
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_ActiveSpan":
        local = self._rec._local
        self._depth = getattr(local, "depth", 0)
        self._parent = getattr(local, "open", -1)
        self._seq = next(self._rec._seq)
        local.depth = self._depth + 1
        local.open = self._seq
        self._ann = profiling.annotate(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def note(self, **attrs: Any) -> None:
        """Attrs learned while the span is open (a count of what it
        made)."""
        self._attrs = {**(self._attrs or {}), **attrs}

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        local = self._rec._local
        local.depth = self._depth
        local.open = self._parent
        self._rec._append(Span(self._name, self._t0, t1,
                               threading.get_ident(), self._depth,
                               self._seq, self._parent, self._attrs))
        return False


class SpanRecorder:
    """Bounded, thread-safe-enough flight recorder.

    ``span(name, **attrs)`` returns a context manager; nesting tracks a
    per-thread depth and the open span's ``seq``, so aggregators can
    attribute only TOP-LEVEL spans (nested spans would double-count their
    parents' wall time) and a reducer can take a span's children off its
    time.  The ring is in CLOSING order.  Appends are a deque push under
    the GIL; the only lock-worthy state (the seq counter) is an
    ``itertools.count``, which is atomic in CPython.
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

    def add(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        """Record a span that was timed elsewhere and has just ended on
        this thread (``t0``, ``t1`` on the ``perf_counter`` clock: see
        :func:`from_epoch`).  Its parent is the span open on this thread
        now.  Spans this thread closed INSIDE ``[t0, t1]`` ran under it —
        JAX reports the trace of a jitted function after those of the
        functions it calls — so they move one level down, and those that
        named its parent as theirs now name it."""
        local = self._local
        depth = getattr(local, "depth", 0)
        parent = getattr(local, "open", -1)
        seq = next(self._seq)
        tid = threading.get_ident()
        ring = self._ring
        i = len(ring) - 1
        while i >= 0:
            try:    # another thread may append or clear() meanwhile
                r = ring[i]
            except IndexError:
                break
            if r.t1 < t0:
                break
            if r.tid == tid and r.t0 >= t0:
                r.depth += 1
                if r.parent == parent:
                    r.parent = seq
            i -= 1
        self._append(Span(name, t0, t1, tid, depth, seq, parent,
                          attrs or None))

    def _append(self, rec: Span) -> None:
        self._ring.append(rec)
        self._total += 1

    # ---- readout ----------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Spans evicted by the ring bound (recorded minus retained)."""
        return max(0, self._total - len(self._ring))

    def records(self, since_seq: int = -1) -> List[Span]:
        """Snapshot of retained spans, oldest first; with ``since_seq``
        (a :meth:`last_seq` reading) only those that CLOSED after the span
        of that ``seq`` did.  A ``seq`` is taken when a span opens, so a
        parent's is lower than its children's though it closes after them:
        the cursor is a place in the ring, not a comparison.  Where that
        span was evicted, everything retained is newer.  ``list(deque)``
        is atomic under the GIL, so a snapshot taken while other threads
        append is consistent (it may simply miss spans that close after
        the copy)."""
        snap = list(self._ring)
        if since_seq < 0:
            return snap
        for i in range(len(snap) - 1, -1, -1):
            if snap[i].seq == since_seq:
                return snap[i + 1:]
        return snap

    def last_seq(self) -> int:
        """``seq`` of the span that closed last (-1 for an empty ring)."""
        try:
            return self._ring[-1].seq
        except IndexError:
            return -1

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

# The process's own recorder: set-up and compile spans land here whoever
# the caller is (module docstring).  Always recording — there is no "off"
# to pay for outside a step.
PROCESS = SpanRecorder()

# Module-level default recorder: convenience for scripts/fixtures that
# want ``spans.span("...")`` without threading a recorder through every
# call.  Defaults to NULL (recording is an explicit opt-in); the trainer
# points it at PROCESS for a ``--spans on`` run.
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


def spanned(name: str):
    """Decorator: every call of the function is one span ``name`` on
    :data:`PROCESS` (the set-up functions: host code, never traced)."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with PROCESS.span(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate


# ---------------------------------------------------------------------------
# JAX's own compile events, as spans on PROCESS
# ---------------------------------------------------------------------------

# JAX reports every jaxpr trace, every lowering to MLIR and every backend
# compile with its start and end in ``time.time()`` and the function's name
# (jax/_src/dispatch.py: log_elapsed_time).  A persistent-cache hit runs
# INSIDE the backend-compile event (jax/_src/compiler.py:
# compile_or_get_cached), so from outside "compiled" and "loaded 72 MiB
# from the cache" look the same: the cache's own events, which fire on the
# compiling thread before that event closes, say which it was.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_COUNTS = ("compile.requests", "compile.cache_hits",
                  "compile.cache_misses", "compile.backend_s",
                  "compile.retrieval_s")
_counts: Dict[str, float] = dict.fromkeys(COMPILE_COUNTS, 0)
_counts_lock = threading.Lock()     # threads may compile side by side
_cache_said = threading.local()     # what the cache reported, per thread
_listening = False


def _on_cache_event(event: str, **_kw: Any) -> None:
    if event == _CACHE_ASKED:
        _cache_said.asked = True
    elif event == _CACHE_HIT:
        _cache_said.hit = True


def _on_cache_duration(event: str, seconds: float, **_kw: Any) -> None:
    if event == _CACHE_RETRIEVAL:
        _cache_said.retrieval_s = seconds


def _on_compile_span(event: str, start: float, end: float,
                     **kw: Any) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    attrs: Dict[str, Any] = {"fun": kw.get("fun_name")}
    if name == "compile/backend":
        said = _cache_said.__dict__
        asked = said.pop("asked", False)
        hit = said.pop("hit", False)
        retrieval_s = said.pop("retrieval_s", None)
        attrs["cache"] = "hit" if hit else "miss" if asked else "off"
        if retrieval_s is not None:
            attrs["retrieval_s"] = retrieval_s
        with _counts_lock:
            _counts["compile.requests"] += 1
            _counts["compile.backend_s"] += end - start
            if hit:
                _counts["compile.cache_hits"] += 1
            elif asked:
                _counts["compile.cache_misses"] += 1
            _counts["compile.retrieval_s"] += retrieval_s or 0.0
    PROCESS.add(name, from_epoch(start), from_epoch(end), **attrs)


def install_compile_listeners() -> None:
    """Turn JAX's compile events into ``compile/trace``, ``/lower`` and
    ``/backend`` spans on :data:`PROCESS` (attr ``fun``: JAX's name for the
    function; on ``compile/backend`` also ``cache``: ``hit`` | ``miss`` |
    ``off`` and, where the cache was read, ``retrieval_s``) and into the
    running :func:`counts`.  A miss is a compile the cache was asked for
    and did not have, written back or not.  Idempotent; called by
    ``preflight.place_compile_cache`` — the one call every entry point
    makes before the backend starts."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax
    jax.monitoring.register_event_listener(_on_cache_event)
    jax.monitoring.register_event_duration_secs_listener(_on_cache_duration)
    jax.monitoring.register_event_time_span_listener(_on_compile_span)


def counts() -> Dict[str, float]:
    """The compile listeners' running totals (a copy): ``compile.requests``
    (backend compiles asked for), ``.cache_hits``, ``.cache_misses``,
    ``.backend_s`` (seconds inside them, cache loads included) and
    ``.retrieval_s`` (seconds reading the persistent cache)."""
    return dict(_counts)


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
    sessions stay legible.  ``args`` carries the span's ``seq`` and its
    ``parent``'s beside its attrs."""
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
        args = {"seq": r.seq, "parent": r.parent}
        if r.attrs:
            args.update(_json_safe(r.attrs))
        ev["args"] = args
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
