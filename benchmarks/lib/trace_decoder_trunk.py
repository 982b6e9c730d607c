"""Device time per step under the decoder trunk's own scopes (``mla``,
``moe``, ``moe/experts``, ``mhc``, ``ffn``: models/decoder_trunk.py), from
this run's trace.

``trace_scopes.for_sources`` answers only a driver that counts images; this
reads the same file through ``trace_scopes.find_xplane`` / ``load`` /
``whole_steps`` for a driver that counts sequences.  A scope's time is the
summed duration of the ops, inside whole ``jit_train_step`` executions,
whose path holds the scope's segments in order — forward, backward,
recomputed forward and target forward alike; the ragged products, which
the compiler renames, count under ``moe/experts``; an op the compiler
inserted carries no path and counts under no scope (a lower bound).  Everything
returns ``None`` off the chip, for another driver, and where the program
names no such scope (the parent of the PR that added them).
"""
from __future__ import annotations

import os
import re

from benchmarks.lib import trace_scopes

RATE_COUNTER = "train_sequences_per_s_per_chip"
_CACHE: dict = {}


# The TPU compiler turns ``jax.lax.ragged_dot`` into a kernel of its own and
# names the op, and its path, after the kernel (``ragged-dot-none``): the
# scope it was traced under is lost.  The expert layer is the only caller.
RAGGED_KERNEL = "ragged-dot"
RAGGED_SCOPE = ["moe", "experts"]


def _segments(path: str) -> list:
    if path.startswith(RAGGED_KERNEL):
        return RAGGED_SCOPE
    return [s for s in re.split(r"[/()]", path) if s]


def _holds(segments: list, scope: tuple) -> bool:
    n = len(scope)
    return any(tuple(segments[i:i + n]) == scope
               for i in range(len(segments) - n + 1))


def step_trace(sources: dict):
    """``{"steps": n, "ops": [(path, seconds)], "reduced": ...}`` of the
    whole steps in this run's trace, or ``None``."""
    if sources.get("trace") is None or \
            RATE_COUNTER not in sources["counters"]:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        path = trace_scopes.find_xplane(os.path.join(
            root, ".bench_out", f"profile_{sources['cell']['name']}"))
    except FileNotFoundError:
        return None
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        trace = trace_scopes.load(path)
        steps = trace_scopes.whole_steps(trace["steps"])
        ops = []
        for start, duration in steps:
            ops += [(o[1], o[3] * 1e-12) for o in trace["ops"]
                    if start <= o[2] < start + duration]
        _CACHE[key] = {"steps": len(steps), "ops": ops,
                       "reduced": trace_scopes.reduce(trace)}
    return _CACHE[key] if _CACHE[key]["steps"] else None


def scope_ms(sources: dict, scope: str):
    """Milliseconds per step under ``scope`` (``"moe/experts"``: those two
    segments in a row); ``None`` where no op carries it."""
    got = step_trace(sources)
    if got is None:
        return None
    want = tuple(scope.split("/"))
    total = sum(seconds for path, seconds in got["ops"]
                if path and _holds(_segments(path), want))
    return 1e3 * total / got["steps"] if total else None


def update_share(sources: dict):
    """Percent of a step's op time in the ``update`` phase."""
    got = step_trace(sources)
    if got is None or not got["reduced"]["op_s"]:
        return None
    reduced = got["reduced"]
    return 100.0 * reduced["phase_s"].get("update", 0.0) / reduced["op_s"]


def table(path: str) -> str:
    """The scope table PERF.md holds, from a trace file."""
    trace = trace_scopes.load(path)
    steps = trace_scopes.whole_steps(trace["steps"])
    rows = {}
    total = 0.0
    for start, duration in steps:
        for o in trace["ops"]:
            if not start <= o[2] < start + duration:
                continue
            total += o[3]
            segs = _segments(o[1] or "")
            if (o[1] or "").startswith(RAGGED_KERNEL):
                rows["ragged-dot kernels"] = rows.get(
                    "ragged-dot kernels", 0.0) + o[3]
            for scope in ("mla", "moe", "moe/route", "moe/experts",
                          "moe/shared", "mhc", "ffn"):
                if _holds(segs, tuple(scope.split("/"))):
                    rows[scope] = rows.get(scope, 0.0) + o[3]
    n = max(len(steps), 1)
    lines = [f"{len(steps)} whole steps; ops {total * 1e-9 / n:9.3f} ms"]
    lines += [f"{scope:18s} | {ps * 1e-9 / n:9.3f} ms | "
              f"{100 * ps / max(total, 1):6.2f} %"
              for scope, ps in sorted(rows.items())]
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    where = sys.argv[1]
    print(table(where if os.path.isfile(where)
                else trace_scopes.find_xplane(where)))
