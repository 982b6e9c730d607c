"""Append-only, schema-versioned JSONL run log.

Every training run (trainer.fit) and every bench row (bench.py) emits the
SAME machine-readable event stream, so tooling that reads one run log reads
them all: a run-header event with the full config and environment, interval
step records carrying the unpacked health vector, epoch records folding in
the MetricAccumulator and InputPipelineMeter results, anomaly / checkpoint
/ halt events, and a run-end marker.

Format: one JSON object per line (newline-delimited), STRICT JSON: the
events most worth machine-reading are the failure records, and those are
exactly the ones carrying non-finite floats (a NaN loss in an anomaly
snapshot) — Python's lenient writer would emit bare ``NaN`` tokens that
jq/JS/serde reject.  :func:`_sanitize` maps non-finite floats to the
strings ``"NaN"`` / ``"Infinity"`` / ``"-Infinity"`` at emit time (the
envelope is dumped with ``allow_nan=False`` so nothing lenient can slip
through).  Line-buffered, append-only writes — a crash mid-run loses at
most the line being written, and every complete line is a complete event
(no trailing state, no footer to rewrite).  Each line stamps
``"v": SCHEMA_VERSION``; readers validate per-kind required fields via
:func:`validate_event`, and :func:`read_events` is the strict reader the
tests round-trip through.

This is the machine-facing complement of the Grapher's metrics.jsonl (a
flat scalar stream for plots): the run log carries STRUCTURED events — a
collapse anomaly is a typed record with the rule and the offending health
snapshot, not a scalar to eyeball.
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict, Iterator, Optional

import numpy as np

SCHEMA_VERSION = 1

# kind -> required payload fields (beyond the envelope v/kind/t).
# Append-only like HEALTH_FIELDS: adding a kind or an OPTIONAL field is
# compatible; changing required fields bumps SCHEMA_VERSION.
EVENT_KINDS: Dict[str, tuple] = {
    "run_header": ("config", "jax_version", "backend"),
    "step": ("step", "health"),
    "epoch": ("epoch", "split", "metrics"),
    "anomaly": ("step", "rule"),
    "checkpoint": ("epoch",),
    "halt": ("step", "reason"),
    "state_dump": ("step",),
    "bench_row": ("config",),
    # serving/meter.py window snapshot: request count, coalesced-batch
    # count, and the latency tail — the serving analog of "step"/"epoch".
    # Additive kind (no SCHEMA_VERSION bump); optional payload carries
    # fill ratio, queue depth, the engine compile counter, and the
    # per-request lifecycle phase breakdown (``phase_ms``).
    "serve_stats": ("requests", "batches", "p50_ms", "p99_ms"),
    # observability/goodput.py wall-time partition (additive kinds):
    # one ``goodput`` event per epoch window + one run-scope total;
    # ``span_stats`` carries the window's per-span-name aggregates
    # (count / total seconds / p50 / p99 / max).  The partition identity
    # — productive + sum(badput) == wall — is validated below.
    "goodput": ("scope", "wall_seconds", "productive_seconds", "badput"),
    "span_stats": ("scope", "spans"),
    "run_end": (),
}

# run_header.sharding_plan (CompilePlan.describe()): OPTIONAL — bench
# headers have no mesh — but when present it must carry the full plan
# provenance, or a run log could claim a plan it cannot name.  Optional-
# field shape checks are additive (no SCHEMA_VERSION bump).
SHARDING_PLAN_FIELDS = ("mesh_shape", "axis_names", "zero1",
                        "donate_argnums")

# run_header.device (core/preflight.describe_device()): what the run ran
# on, as JAX reported it.  OPTIONAL like sharding_plan — bench.py stamps
# its header before the backend exists — but when present it names all
# three, so no log can claim "a TPU" without saying which and how many.
DEVICE_FIELDS = ("platform", "kind", "count")


def sanitize(obj: Any) -> Any:
    """JSON-strict deep copy of a payload: non-finite floats become the
    strings ``"NaN"`` / ``"Infinity"`` / ``"-Infinity"``.  Run on every
    event before ``json.dumps(..., allow_nan=False)`` so the lines a NaN
    run produces — the ones this log exists to capture — stay parseable
    by every standard JSON consumer, not just Python's lenient reader.

    This module OWNS the convention (GL110): every other strict-JSON
    writer — grapher metrics lines, span chrome-trace attrs, checkpoint
    meta.json, the wire /statsz endpoint — delegates here rather than
    growing a drift-prone copy of the mapping."""
    if isinstance(obj, dict):
        return {k: sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [sanitize(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return sanitize(obj.tolist())
    if isinstance(obj, (float, np.floating)):
        f = float(obj)
        if math.isnan(f):
            return "NaN"
        if math.isinf(f):
            return "Infinity" if f > 0 else "-Infinity"
        return f
    return obj


# internal call sites predate the public promotion
_sanitize = sanitize


def _json_default(obj: Any):
    """Serialize numpy/jax leaves that reach an event payload."""
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return _sanitize(obj)
    if isinstance(obj, np.ndarray):
        return _sanitize(obj)
    tolist = getattr(obj, "tolist", None)   # jax.Array and friends
    if callable(tolist):
        return _sanitize(tolist())
    raise TypeError(
        f"event payload value of type {type(obj).__name__} is not "
        "JSON-serializable")


def validate_event(event: Any) -> Dict[str, Any]:
    """Validate one event object against the schema; returns it.

    Raises ``ValueError`` on: non-dict, missing/mismatched schema version,
    unknown kind, or a missing required field for the kind.
    """
    if not isinstance(event, dict):
        raise ValueError(f"event must be a JSON object, got {type(event)}")
    v = event.get("v")
    if v != SCHEMA_VERSION:
        raise ValueError(
            f"event schema version {v!r} != supported {SCHEMA_VERSION}")
    kind = event.get("kind")
    if kind not in EVENT_KINDS:
        raise ValueError(
            f"unknown event kind {kind!r}; known: {sorted(EVENT_KINDS)}")
    missing = [f for f in EVENT_KINDS[kind] if f not in event]
    if missing:
        raise ValueError(
            f"event kind {kind!r} missing required field(s) {missing}")
    if kind == "run_header" and "sharding_plan" in event:
        sp = event["sharding_plan"]
        if not isinstance(sp, dict):
            raise ValueError(
                f"run_header.sharding_plan must be an object, got "
                f"{type(sp).__name__}")
        sp_missing = [f for f in SHARDING_PLAN_FIELDS if f not in sp]
        if sp_missing:
            raise ValueError(
                f"run_header.sharding_plan missing field(s) {sp_missing} "
                f"(expected {list(SHARDING_PLAN_FIELDS)})")
        if sp.get("zero1") not in ("off", "on"):
            raise ValueError(
                f"run_header.sharding_plan.zero1 must be 'off'|'on', got "
                f"{sp.get('zero1')!r}")
    if kind == "run_header" and "device" in event:
        dev = event["device"]
        if not isinstance(dev, dict) or any(f not in dev
                                            for f in DEVICE_FIELDS):
            raise ValueError(
                f"run_header.device must be an object with fields "
                f"{list(DEVICE_FIELDS)}, got {dev!r}")
    if kind == "goodput":
        bp = event["badput"]
        if not isinstance(bp, dict):
            raise ValueError(
                f"goodput.badput must be an object of bucket seconds, got "
                f"{type(bp).__name__}")
        vals = [event["wall_seconds"], event["productive_seconds"],
                *bp.values()]
        if all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in vals):
            # the accounting identity the whole feature exists to provide:
            # the partition must SUM to wall time (1% tolerance covers the
            # reader-side float round-trip; the writer computes it exactly)
            wall = float(event["wall_seconds"])
            total = (float(event["productive_seconds"])
                     + sum(float(v) for v in bp.values()))
            if abs(total - wall) > max(0.01 * abs(wall), 1e-6):
                raise ValueError(
                    f"goodput buckets sum to {total:.6f}s but wall is "
                    f"{wall:.6f}s (off by more than 1%): the partition "
                    "must be exhaustive (goodput.py fold contract)")
    return event


class RunLog:
    """Line-buffered append-only JSONL event writer.

    ``emit(kind, **payload)`` stamps the envelope (schema version, kind,
    wall time), validates, and writes one line.  Line buffering means each
    event reaches the OS on its own newline — crash-safe without fsync
    latency in the hot loop.  Open in append mode so a resumed run extends
    its predecessor's log instead of erasing the evidence.

    ``best_effort=True`` makes environment failures (OSError: disk full,
    NFS quota, read-only fs) — at CONSTRUCTION (makedirs/open) and on
    every write alike — disable the log with a one-line warning instead
    of propagating, so both emitters (trainer.fit, bench.py) get the
    'observability must never kill the hours-long run it observes'
    contract from one place.  Schema violations (ValueError) always
    raise: those are caller bugs, not environment weather.
    """

    def __init__(self, path: str, *, best_effort: bool = False) -> None:
        self.path = path
        self.best_effort = best_effort
        self.disabled = False
        self._f = None
        try:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a", buffering=1)
        except OSError as e:
            if not best_effort:
                raise
            self._write_failed(e)

    def _write_failed(self, exc: OSError) -> None:
        import sys
        self.disabled = True
        print(f"events: {self.path} failed ({exc!r}); run log "
              "disabled for the rest of the run", file=sys.stderr)
        try:
            if self._f is not None:
                self._f.close()
        except OSError:
            pass

    def emit(self, kind: str, **payload: Any) -> Dict[str, Any]:
        event = {"v": SCHEMA_VERSION, "kind": kind, "t": time.time(),
                 **payload}
        validate_event(event)
        if self.disabled:
            return event
        try:
            self._f.write(json.dumps(_sanitize(event), default=_json_default,
                                     allow_nan=False) + "\n")
        except OSError as e:
            if not self.best_effort:
                raise
            self._write_failed(e)
        return event

    def flush(self) -> None:
        if not self.disabled:
            self._f.flush()

    def close(self) -> None:
        if not self.disabled and self._f is not None and not self._f.closed:
            self._f.close()

    def __enter__(self) -> "RunLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_events(path: str) -> Iterator[Dict[str, Any]]:
    """Strict reader: yields every event, validated; raises ``ValueError``
    naming the line number on a corrupt or schema-invalid line."""
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{lineno}: corrupt JSONL line: {e}") from e
            try:
                yield validate_event(obj)
            except ValueError as e:
                raise ValueError(f"{path}:{lineno}: {e}") from e
