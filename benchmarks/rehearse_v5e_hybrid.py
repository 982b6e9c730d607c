#!/usr/bin/env python3
"""Compile a patterned trunk's cell for a DESCRIBED v5e (no chip attached)
and check what its compiled step may not hold.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_v5e_hybrid.py <cell> [--per-chip-batch N] [--dump-hlo FILE]

``rehearse_v5e_tokens.py`` does the compiling (its ``main``, unchanged); this
adds, for a cell whose sequences are long: every field of the compiler's
memory account (``peak_memory_in_bytes`` beside the argument / output /
temporary sizes the other script sums), and two readings of the compiled
module's text —

* every array under the attention core's scope with TWO dimensions of at
  least ``seq_len``: attention that never writes ``[S, S]`` leaves none;
* the longest loop (the largest bound a ``while``'s condition compares
  with): a recurrence run in chunks has ``seq_len / chunk`` trips, not
  ``seq_len``.

A count from shapes, never a time.  Exit 1 if either reading is ``seq_len``
or more.
"""
from __future__ import annotations

import json
import os
import re
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import rehearse_v5e, rehearse_v5e_tokens  # noqa: E402

_report = rehearse_v5e.report


def report(tag, compiled):
    """``rehearse_v5e.report`` and every number the account has."""
    ma = compiled.memory_analysis()
    fields = {k: getattr(ma, k) for k in dir(ma)
              if k.endswith("_in_bytes") and isinstance(getattr(ma, k), int)}
    print("memory account (GiB): " + ", ".join(
        f"{k[:-len('_in_bytes')]} {v / 2**30:.2f}"
        for k, v in sorted(fields.items()) if v), flush=True)
    return _report(tag, compiled)


CORE_SCOPE = "gqa/core"


def square_arrays(text: str, seq_len: int) -> list:
    """Shapes with two dimensions >= ``seq_len`` among the instructions
    traced under the attention core's scope.  (Elsewhere such a shape is
    innocent: 32 value heads of 128, or 16 query heads of 256, are 4,096
    channels beside 4,096 positions.)"""
    found = set()
    for line in text.splitlines():
        if CORE_SCOPE not in line:
            continue
        for dims in re.findall(r"\b(?:pred|[a-z]+\d+)\[([\d,]+)\]", line):
            sizes = [int(d) for d in dims.split(",")]
            if sum(d >= seq_len for d in sizes) >= 2:
                found.add(dims)
    return sorted(found)


def longest_loop(text: str) -> int:
    """The largest bound a ``while`` of the module counts to (the compiled
    module carries no trip count: ``scripts/hlo_bytes_by_scope.py`` reads
    it off each loop's condition)."""
    from scripts import hlo_bytes_by_scope
    return max(hlo_bytes_by_scope.loop_bounds(
        hlo_bytes_by_scope.parse(text)), default=0)


def main() -> int:
    rehearse_v5e.report = report       # looked up inside the other main
    argv = sys.argv[1:]
    if "--dump-hlo" in argv:
        path = argv[argv.index("--dump-hlo") + 1]
    else:
        path = os.path.join(tempfile.mkdtemp(), "step.hlo")
        sys.argv += ["--dump-hlo", path]
    rehearse_v5e_tokens.main()
    cell = next(a for a in argv if not a.startswith("-"))
    with open(os.path.join(HERE, "workloads", f"{cell}.json")) as f:
        conf_name = json.load(f)["config"]
    with open(os.path.join(HERE, "configs", f"{conf_name}.json")) as f:
        seq_len = json.load(f)["seq_len"]
    with open(path) as f:
        text = f.read()
    squares, trips = square_arrays(text, seq_len), longest_loop(text)
    print(f"arrays under {CORE_SCOPE} with two dimensions >= {seq_len}: "
          f"{squares or 'none'}; "
          f"longest loop {trips} trips", flush=True)
    return int(bool(squares) or trips >= seq_len)


if __name__ == "__main__":
    sys.exit(main())
