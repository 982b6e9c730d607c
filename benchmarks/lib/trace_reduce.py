"""From a profiler trace to the few numbers the benchmark reports.

:func:`load` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
plain structure — ``{plane: {line: [(name, start_ns, duration_ns), ...]}}``
— and :func:`reduce` works on that alone, so the arithmetic is tested on a
hand-built trace (tests/test_trace_reduce.py) and needs no chip.

Device planes are ``/device:TPU:N``; of their lines the reduction reads
``XLA Ops`` (one event per executed HLO op, XLA's own names).  Busy time is
the UNION of those intervals, so nested or overlapping events count once.
Each long idle gap is named by where it falls: by the ``bench/...`` host
span that covers most of it where the trace holds host spans
(``jax.profiler.TraceAnnotation``; the harness traces the device only,
because host tracing slowed the host-to-device path threefold), and
otherwise by the programs on the device's ``XLA Modules`` line that it lies
inside or between.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter")
SPAN_PREFIX = "bench/"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def describe(planes: dict) -> str:
    """Planes, lines and event counts: what to look at when a trace does
    not reduce."""
    out = []
    for pname, lines in planes.items():
        for lname, events in lines.items():
            names = sorted({n for n, _, _ in events})[:3]
            out.append(f"{pname} | {lname} | {len(events)} events | {names}")
    return "\n".join(out)


def short_name(event_name: str) -> str:
    """XLA's own name of an op, without the HLO text that follows it:
    ``%fusion.12 = f32[..] fusion(...), kind=kOutput`` -> ``fusion.12
    kOutput f32[..]``."""
    head, sep, rest = event_name.partition(" = ")
    name = head.strip().lstrip("%")
    if not sep:
        return name[:96]
    kind = re.search(r"kind=(k\w+)", rest)
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    extra = " ".join(x.group(1) if x is kind else x.group(0)
                     for x in (kind, shape) if x)
    return f"{name} {extra}".strip()[:96]


def _union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_spans(planes):
    spans = []
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for events in lines.values():
            spans += [(n, s, s + d) for n, s, d in events
                      if n.startswith(SPAN_PREFIX)]
    return spans


def _between_programs(modules, start, end):
    """``inside <program>`` or ``between <program> and <program>``."""
    clean = lambda n: re.sub(r"\(\d+\)$", "", n)
    before, after = "window start", "window end"
    for n, s, d in modules:
        if s <= start and end <= s + d:
            return f"inside {clean(n)}"
        if s + d <= start:
            before = clean(n)
        elif s >= end:
            after = clean(n)
            break
    return f"between {before} and {after}"


def _covering_span(spans, start, end, skip):
    """The host span that covers most of ``[start, end]``; of two that
    cover as much, the shorter (the inner one)."""
    best, best_key = "(no host span)", (0.0, 0.0)
    for n, s, e in spans:
        cover = min(e, end) - max(s, start)
        if n != skip and cover > 0 and (cover, s - e) > best_key:
            best, best_key = n, (cover, s - e)
    return best


def reduce(planes: dict, *, devices: int, top: int = 10,
           window_span: str = SPAN_PREFIX + "window") -> dict:
    """``devices``: how many chips the cell used; a trace with fewer device
    planes that ran an op raises.  Returns seconds (floats):

    ``busy_s``           union of device-op intervals, mean over the chips
    ``window_s``         the traced window: the ``bench/window`` host span
                         when present, else first op start to last op end
    ``collective_s``     summed collective-op time on device 0
    ``device_ops``       ``[[name, seconds], ...]`` device 0, by total time
    ``idle_gaps``        ``[[host span, seconds], ...]`` longest gaps on
                         device 0 inside the window
    """
    per_device = {}
    for pname, lines in planes.items():
        m = DEVICE_PLANE.match(pname)
        if m and lines.get(OPS_LINE):
            per_device[int(m.group(1))] = lines[OPS_LINE]
    if len(per_device) < devices:
        raise ValueError(
            f"trace holds device-op lines for {sorted(per_device)}; the "
            f"cell used {devices} chip(s)")
    spans = _host_spans(planes)
    window = [(s, e) for n, s, e in spans if n == window_span]
    ops0 = per_device[min(per_device)]
    modules0 = sorted(planes[f"/device:TPU:{min(per_device)}"].get(
        MODULES_LINE, []), key=lambda e: e[1])
    merged0 = _union((s, s + d) for _, s, d in ops0)
    if window:
        w_start, w_end = window[-1]
    else:
        w_start, w_end = merged0[0][0], merged0[-1][1]
    busy = [sum(max(0.0, min(e, w_end) - max(s, w_start))
                for s, e in _union((s, s + d) for _, s, d in ops))
            for ops in per_device.values()]
    totals = {}
    for n, _, d in ops0:
        n = short_name(n)
        totals[n] = totals.get(n, 0.0) + d
    collective = sum(d for n, d in totals.items() if COLLECTIVE.search(n))
    gaps, cursor = [], w_start
    for s, e in merged0 + [[w_end, w_end]]:
        s = min(max(s, w_start), w_end)
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, min(e, w_end))
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (w_end - w_start) * ns,
        "collective_s": collective * ns,
        "device_ops": [[n, d * ns] for n, d in sorted(
            totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_covering_span(spans, s, e, window_span) if spans
                       else _between_programs(modules0, s, e),
                       (e - s) * ns] for s, e in gaps[:top]],
        "devices_traced": len(per_device),
    }
