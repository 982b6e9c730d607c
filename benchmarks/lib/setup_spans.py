"""Where ``setup_s`` goes: the program's own set-up spans and JAX's compile
events, reduced once to the six ``setup.*`` numbers.

The program records its set-up on one process-wide recorder
(``byol_tpu/observability/spans.PROCESS``): ``startup/*`` spans where the
work happens, and every jaxpr trace, lowering and backend compile (or cache
load) JAX reports as ``compile/trace``, ``/lower``, ``/backend`` spans with
the function's name (``fun``) and, on a backend span, what the persistent
cache did (``cache``: ``hit`` | ``miss`` | ``off``).  The readers run in the
driver's process (``run.py``) after the cell has run, so the ring is simply
read; a program without that recorder (a parent commit) gives ``None`` for
every number, and so does a ring that has dropped spans.

THE END OF THE PROGRAM'S SET-UP is the end of the ``compile/backend`` span
of the train step's program (``jit(train_step)``: the one the device trace
calls ``jit_train_step``; the first, if a run compiles it twice).  What
follows until ``setup_s`` is read — the driver's checked first steps and
their read-backs — is the benchmark's own code, which the program cannot
see.  THE PROCESS'S START is the operating system's (``/proc/self/stat``
against ``/proc/uptime``, both on the boot clock: 10 ms grain), a little
before ``run.py``'s own ``T0``.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Any, Dict, List, Optional

STEP_FUNCTION = "train_step"


def function_of(span: Any) -> str:
    """A compile span's function, as the program named it: ``train_step``
    for a trace's ``train_step`` and for a lowering's or a backend
    compile's ``jit(train_step)``."""
    fun = str((span.attrs or {}).get("fun") or "")
    return fun[4:-1] if fun.startswith("jit(") and fun.endswith(")") else fun


def process_start() -> Optional[float]:
    """The instant the operating system started this process, on the
    ``perf_counter`` clock; ``None`` where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's closing bracket; the 22nd of
            # the line is the start time in clock ticks since boot
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        now = time.perf_counter()
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return now - age if age > 0 else None


def split(records: List[Any], start: Optional[float]
          ) -> Optional[Dict[str, float]]:
    """The six numbers from a ring's ``records`` (``name``, ``t0``, ``t1``,
    ``seq``, ``parent``, ``attrs``) and the process's ``start``; ``None``
    where no train step's program was compiled.  A number that cannot be
    had (no ``startup/build`` span; no ``start``) is left out."""
    from byol_tpu.observability.goodput import covered_seconds, self_seconds
    compiles = [r for r in records if r.name.startswith("compile/")]
    step = [r for r in compiles if function_of(r) == STEP_FUNCTION]
    backend = min((r for r in step if r.name == "compile/backend"),
                  key=lambda r: r.t1, default=None)
    if backend is None:
        return None
    end = backend.t1
    # the step's own trace and lowering come before its backend compile
    mine = [backend] + [r for r in step if r.name != "compile/backend"
                        and r.t1 <= backend.t0]
    out = {"step_compile_s": sum(r.t1 - r.t0 for r in mine)}
    # every other compile span up to there, by KIND: what nests under the
    # step's own spans (the functions its trace calls) is already counted
    by_seq = {r.seq: r for r in records}
    under = {r.seq for r in mine}

    def under_step(r: Any) -> bool:
        while r is not None:
            if r.seq in under:
                return True
            r = by_seq.get(r.parent)
        return False

    own = self_seconds(records)
    out["other_compile_s"] = sum(
        own[r.seq] for r in compiles
        if r.t1 <= end and not under_step(r))
    out["cache_misses"] = float(sum(
        1 for r in compiles if r.name == "compile/backend" and r.t1 <= end
        and (r.attrs or {}).get("cache") == "miss"))
    for key, name in (("build_s", "startup/build"),
                      ("init_s", "startup/build/init")):
        first = next((r for r in records if r.name == name
                      and r.t1 <= end), None)
        if first is not None:
            out[key] = first.t1 - first.t0
    if start is not None and start < end:
        out["unattributed_s"] = (end - start) - covered_seconds(
            [(max(r.t0, start), min(r.t1, end)) for r in records
             if r.t1 > start and r.t0 < end])
    return out


@functools.lru_cache(maxsize=1)
def of_this_process() -> Optional[Dict[str, float]]:
    """:func:`split` of this process's own recorder, computed once for the
    six readers."""
    from byol_tpu.observability import spans
    recorder = getattr(spans, "PROCESS", None)
    if recorder is None or recorder.dropped:
        return None
    return split(recorder.records(), process_start())


def read(key: str, sources: Dict[str, Any]) -> Optional[float]:
    """One of the six numbers, for ``layer_metrics/setup.<key>.py``.
    Absent off the chip (``sources["peaks"]`` is ``None`` there): what the
    CPU backend takes to compile a rehearsal is nobody's set-up."""
    if sources.get("peaks") is None:
        return None
    numbers = of_this_process()
    return None if numbers is None else numbers.get(key)
