"""From a profiler trace to device time per step by PHASE of the train step.

The program wraps the phases of ``byol_tpu/training/steps.py`` in
``jax.named_scope`` (PERF.md section 3 lists them); XLA carries the scope
path of every instruction as its ``op_name``, and the TPU profiler writes it
into the trace as the stat ``tf_op`` of the op's EVENT METADATA
(``jit(train_step)/target_forward/BYOLNet/backbone/block11/mlp/fc2/
dot_general:``).  ``jax.profiler.ProfileData`` shows an event's own stats
only, and the HLO text that is the event's name holds no metadata, so
:func:`load` reads the ``XSpace`` protobuf itself — a wire-format reader of
the few fields it needs, no generated code — and keeps, for the first TPU
plane, the ``XLA Ops`` line as ``(hlo op, scope path, start_ps,
duration_ps, flops)`` and the ``jit_train_step`` executions of the
``XLA Modules`` line.  :func:`reduce` works on that plain structure alone
and is tested on a hand-built one.

The phase names below are the benchmark's own copy of the contract; nothing
here imports the program.

Phase of an op, by its path: contains ``transpose(`` -> ``backward``; else
the token ``target_forward``, ``augment`` or ``update``; else the token
``online_forward`` or ``loss`` -> ``online_forward``; else ``unscoped``.
Ops the compiler inserted carry NO path (layout copies, the ``-done`` of an
asynchronous copy or slice: a tenth of a ViT-B/16 step).  The chip runs one
op at a time in schedule order, and such an op is scheduled just before
the op that needs its result, so it INHERITS the phase of the next op of
its step that has a path (of the previous one at the end of a step); what
each phase inherited is reported beside it.  A fusion counts whole by the
path of its root instruction.

    python3 -m benchmarks.lib.trace_scopes <file.xplane.pb | profile dir>
"""
from __future__ import annotations

import functools
import os
import re
import sys

from benchmarks.lib.trace_reduce import (DEVICE_PLANE, MODULES_LINE,
                                         OPS_LINE, find_xplane)

PHASES = ("target_forward", "online_forward", "backward", "update",
          "augment", "unscoped")
STEP_MODULE = "jit_train_step"
# an op that only CONTAINS other ops on the same line (their time is its)
CONTAINERS = ("while", "conditional", "call")
NORM_SEGMENT = re.compile(r"^(bn.*|.*_bn|ln.*)$")
# a path's module, for the phase x module table: the first of these that
# matches any segment of the path (``backbone``: what an encoder runs
# outside its stages and blocks: pooling, the stem's ReLU, the last norm)
MODULES = (
    ("stem", re.compile(r"^stem")),
    ("stage1", re.compile(r"^stage1_")), ("stage2", re.compile(r"^stage2_")),
    ("stage3", re.compile(r"^stage3_")), ("stage4", re.compile(r"^stage4_")),
    ("patch_embed", re.compile(r"^patch_embed$")),
    ("blocks", re.compile(r"^block\d+$")),
    ("projector", re.compile(r"^projector$")),
    ("predictor", re.compile(r"^predictor$")),
    ("probe", re.compile(r"^probe$")),
    ("backbone", re.compile(r"^backbone$")),
)

_CACHE: dict = {}


# -- the XSpace protobuf, by wire format --------------------------------------
# XSpace.planes=1 | XPlane name=2 lines=3 event_metadata=4 stat_metadata=5
# | XLine name=2 events=4 | XEvent metadata_id=1 offset_ps=2 duration_ps=3
# | XEventMetadata id=1 name=2 display_name=4 stats=5 | XStatMetadata id=1
# name=2 | XStat metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7

def _varint(buf, i):
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, value)`` of one message; a length-delimited value
    is its ``(start, end)`` in ``buf``, a 64-/32-bit one is skipped."""
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, value


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf, spans):
    """The values of a ``map<int64, Message>`` field's entries."""
    for s, e in spans:
        for num, value in _fields(buf, s, e):
            if num == 2:
                yield value


def _events(buf, span):
    """``[(metadata_id, offset_ps, duration_ps)]`` of one line: the hot
    loop (200,000 events in a six-second trace), so written flat."""
    out = []
    i, end = span
    while i < end:
        tag, i = _varint(buf, i)
        if tag & 7 != 2:
            if tag & 7 == 0:
                _, i = _varint(buf, i)
            else:
                i += 8 if tag & 7 == 1 else 4
            continue
        n, i = _varint(buf, i)
        stop = i + n
        if tag >> 3 != 4:
            i = stop
            continue
        mid = off = dur = 0
        while i < stop:
            t = buf[i]
            i += 1
            if t == 0x22:                       # stats: not needed
                n, i = _varint(buf, i)
                i += n
            elif t == 0x08:
                mid, i = _varint(buf, i)
            elif t == 0x10:
                off, i = _varint(buf, i)
            elif t == 0x18:
                dur, i = _varint(buf, i)
            elif t & 7 == 0:
                _, i = _varint(buf, i)
            else:                               # nothing else is defined
                i = stop
        out.append((mid, off, dur))
    return out


def _plane(buf, span) -> dict:
    name, lines, event_meta, stat_meta = "", [], [], []
    for num, value in _fields(buf, *span):
        if num == 2:
            name = _text(buf, value)
        elif num == 3:
            lines.append(value)
        elif num == 4:
            event_meta.append(value)
        elif num == 5:
            stat_meta.append(value)
    return {"name": name, "lines": lines, "event_meta": event_meta,
            "stat_meta": stat_meta}


def _stat_names(buf, plane) -> dict:
    names = {}
    for s, e in _map_entries(buf, plane["stat_meta"]):
        sid, sname = 0, ""
        for num, value in _fields(buf, s, e):
            if num == 1:
                sid = value
            elif num == 2:
                sname = _text(buf, value)
        names[sid] = sname
    return names


def _event_metadata(buf, plane, stat_names) -> dict:
    """``{id: (hlo op, path or None, category, flops)}``."""
    wanted = {sid: n for sid, n in stat_names.items()
              if n in ("tf_op", "hlo_category", "flops")}
    meta = {}
    for s, e in _map_entries(buf, plane["event_meta"]):
        mid, name, display, got = 0, "", "", {}
        for num, value in _fields(buf, s, e):
            if num == 1:
                mid = value
            elif num == 2:
                name = _text(buf, value)
            elif num == 4:
                display = _text(buf, value)
            elif num == 5:
                sid, sval = 0, None
                for n2, v2 in _fields(buf, *value):
                    if n2 == 1:
                        sid = v2
                    elif n2 in (3, 4):
                        sval = v2
                    elif n2 == 5:
                        sval = _text(buf, v2)
                    elif n2 == 7:               # a string kept once, as
                        sval = stat_names.get(v2)   # a stat's name
                if sid in wanted:
                    got[wanted[sid]] = sval
        op = display or name.partition(" = ")[0].strip().lstrip("%")
        path = got.get("tf_op")
        meta[mid] = (op[:96], path.rstrip(":") if path else None,
                     got.get("hlo_category") or "",
                     int(got.get("flops") or 0))
    return meta


def load(path: str) -> dict:
    """``{"ops": [(hlo op, path, start_ps, duration_ps, flops)],
    "steps": [(start_ps, duration_ps)]}`` of the first TPU plane that ran
    an op; both lists empty where the file holds none."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for num, value in _fields(buf, 0, len(buf)):
        if num == 1:
            plane = _plane(buf, value)
            m = DEVICE_PLANE.match(plane["name"])
            if m:
                planes.append((int(m.group(1)), plane))
    trace = {"ops": [], "steps": []}
    for _, plane in sorted(planes, key=lambda p: p[0]):
        by_name = {}
        for span in plane["lines"]:
            for num, value in _fields(buf, *span):
                if num == 2:
                    by_name[_text(buf, value)] = span
                    break
        if OPS_LINE not in by_name:
            continue
        meta = _event_metadata(buf, plane, _stat_names(buf, plane))
        ops = [meta[mid][:2] + (off, dur) + meta[mid][3:]
               for mid, off, dur in _events(buf, by_name[OPS_LINE])
               if mid in meta and meta[mid][2] not in CONTAINERS]
        if not ops:
            continue
        steps = [(off, dur) for mid, off, dur in
                 _events(buf, by_name.get(MODULES_LINE, (0, 0)))
                 if mid in meta and meta[mid][0].startswith(STEP_MODULE)]
        trace = {"ops": ops, "steps": steps}
        break
    return trace


def reduced_file(path: str) -> dict:
    """``reduce(load(path))``, kept for the file as it is now: seven
    readers, one parse and one reduction."""
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(load(path))
    return _CACHE[key]


# -- the reduction ------------------------------------------------------------

def phase_of(path):
    """The phase of a scope path; ``None`` for an op that has no path."""
    if not path:
        return None
    if "transpose(" in path:
        return "backward"
    for token in ("target_forward", "augment", "update"):
        if token in path:
            return token
    if "online_forward" in path or "loss" in path:
        return "online_forward"
    return "unscoped"


def _segments(path: str) -> list:
    return [s for s in re.split(r"[/()]", path) if s]


def module_of(path) -> str:
    segments = _segments(path or "")
    for name, pattern in MODULES:
        if any(pattern.match(s) for s in segments):
            return name
    return "other"


def in_norm(path) -> bool:
    """Is the op ROOTED in a normalisation module (``bn*``, ``*_bn``,
    ``ln*``)?  The last segment is the primitive, not a module."""
    return any(NORM_SEGMENT.match(s) for s in _segments(path or "")[:-1])


@functools.lru_cache(maxsize=None)
def _classify(path):
    """A few thousand distinct paths stand behind 200,000 events."""
    return phase_of(path), module_of(path), in_norm(path)


def whole_steps(steps: list) -> list:
    """The executions that ran whole: one the trace cut short is shorter
    than nine tenths of the median."""
    if not steps:
        return []
    durations = sorted(d for _, d in steps)
    median = durations[len(durations) // 2]
    return sorted((s, d) for s, d in steps if d >= 0.9 * median)


def reduce(trace: dict) -> dict:
    """Seconds PER STEP: the summed durations of the ops that started
    inside a whole ``jit_train_step`` execution, over the number of those
    executions.  ``phase_s[phase]`` and ``inherited_s[phase]`` (the part of
    it that came from ops with no path), ``module_s[(phase, module)]``,
    ``norm_s[phase]`` (ops rooted in a normalisation module; no inherited
    time), ``flops[phase]`` per step by the compiler's count, ``op_s``
    (all phases) and ``step_s`` (mean execution)."""
    steps = whole_steps(trace["steps"])
    out = {"steps": len(steps), "op_s": 0.0, "step_s": 0.0,
           "phase_s": {}, "inherited_s": {}, "module_s": {}, "norm_s": {},
           "flops": {}}
    if not steps:
        return out
    ops = sorted(trace["ops"], key=lambda o: o[2])
    n = len(steps)
    scale = 1e-12 / n

    def add(table, key, value):
        table[key] = table.get(key, 0.0) + value

    i = 0
    for start, duration in steps:
        end = start + duration
        while i < len(ops) and ops[i][2] < start:
            i += 1
        j = i
        while j < len(ops) and ops[j][2] < end:
            j += 1
        phases = [_classify(o[1])[0] for o in ops[i:j]]
        pathless = [p is None for p in phases]
        for k in range(len(phases) - 2, -1, -1):    # the next op's phase,
            if phases[k] is None:
                phases[k] = phases[k + 1]
        for k in range(len(phases)):                # or, at the end of a
            if phases[k] is None:                   # step, the previous
                phases[k] = phases[k - 1] if k else "unscoped"
        for k, phase in enumerate(phases):
            _, path, _, dur, flops = ops[i + k]
            add(out["phase_s"], phase, dur * scale)
            add(out["flops"], phase, flops / n)
            if pathless[k]:
                add(out["inherited_s"], phase, dur * scale)
                add(out["module_s"], (phase, "(no path)"), dur * scale)
                continue
            _, module, norm = _classify(path)
            add(out["module_s"], (phase, module), dur * scale)
            if norm:
                add(out["norm_s"], phase, dur * scale)
        i = j
    out["op_s"] = sum(out["phase_s"].values())
    out["step_s"] = sum(d for _, d in steps) * scale
    return out


def for_sources(sources: dict):
    """What a per-layer reader reads: the reduction of this run's trace, or
    ``None`` off the chip, outside a training cell, and where the trace
    holds no whole ``jit_train_step``.  ``benchmarks/run.py`` keeps the
    profile in ``.bench_out/profile_<cell>`` until the readers have run."""
    if sources.get("trace") is None or \
            "train_images_per_s_per_chip" not in sources["counters"]:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        path = find_xplane(os.path.join(
            root, ".bench_out", f"profile_{sources['cell']['name']}"))
    except FileNotFoundError:
        return None
    reduced = reduced_file(path)
    return reduced if reduced["steps"] else None


def phase_ms(sources: dict, phase: str):
    """Milliseconds per step under ``phase``; ``None`` where none ran."""
    reduced = for_sources(sources)
    if reduced is None or not reduced["phase_s"].get(phase):
        return None
    return 1e3 * reduced["phase_s"][phase]


def table(reduced: dict) -> str:
    """The phase table and the phase x module table, as PERF.md holds
    them."""
    if not reduced["steps"]:
        return "no whole jit_train_step execution in the trace"
    ms = lambda s: f"{1e3 * s:9.3f}"
    op_s = reduced["op_s"]
    lines = [f"{reduced['steps']} whole steps; step {ms(reduced['step_s'])}"
             f" ms, ops {ms(op_s)} ms",
             "phase           |  ms/step | share % | inherited | norm ms |"
             " TFLOP/s"]
    for phase in PHASES:
        s = reduced["phase_s"].get(phase, 0.0)
        if not s:
            continue
        lines.append(
            f"{phase:15s} | {ms(s)}| {100 * s / op_s:7.2f} | "
            f"{ms(reduced['inherited_s'].get(phase, 0.0))} | "
            f"{ms(reduced['norm_s'].get(phase, 0.0))}| "
            f"{reduced['flops'].get(phase, 0.0) / s / 1e12:7.1f}")
    modules = [m for m, _ in MODULES] + ["other", "(no path)"]
    used = [p for p in PHASES if reduced["phase_s"].get(p)]
    lines += ["", "module (ms/step) | " + " | ".join(
        f"{p[:14]:>14s}" for p in used)]
    for module in modules:
        row = [reduced["module_s"].get((p, module), 0.0) for p in used]
        if any(row):
            lines.append(f"{module:16s} | " + " | ".join(
                f"{1e3 * s:14.3f}" for s in row))
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    where = argv[0]
    print(table(reduce(load(
        where if os.path.isfile(where) else find_xplane(where)))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
