#!/usr/bin/env python3
"""Read, on the chip, the two numbers every limit is set from.

    python3 benchmarks/control.py --workload <cell> --seeds 11,12,13 \\
        [--seconds 4] [--control fp8] [--out chiprun_out/control_<cell>.jsonl]

For each seed, in ONE process (set-up is long, the compile cache is
shared): a short run of the cell through its driver gives the SOUND
numbers (program at the configuration's precision against the float32
reference); then the CONTROL — the reference itself computed in the
nearest precision below the configuration's (``fp8`` under bfloat16,
``bfloat16`` under float32) — is put in the program's place and compared
the same way.  A limit goes above the largest sound reading and below the
smallest control reading (PERF.md section 2 records both).  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import run as harness  # noqa: E402

BELOW = {"bfloat16": "fp8", "float32": "bfloat16"}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control", default="")
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args()
    cell = harness.load_json("workloads", f"{a.workload}.json")
    config = harness.load_json("configs", f"{cell['config']}.json")
    devices, _ = harness.start_backend(int(cell["chips"]), a.rehearse_cpu)
    driver = harness.load_driver(cell["driver"])
    precision = a.control or BELOW[config["precision"]]
    compiles = harness.count_compiles()
    out = open(a.out, "a") if a.out else None
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        ctx = harness.make_context(args, cell, config, devices, compiles)
        ctx.t0 = time.perf_counter()
        ctx.verbose_memory = False
        result = driver.run(ctx)
        row = {"cell": a.workload, "seed": seed, "sound": result["numbers"],
               "failed": result["failed"],
               "end_to_end": {k: v for k, (v, _) in
                              result["end_to_end"].items()},
               "setup_s": result["setup_s"]}
        if not a.no_control:
            t0 = time.perf_counter()
            row["control"] = driver.control(ctx, precision)
            row["control_precision"] = precision
            row["control_s"] = time.perf_counter() - t0
        line = json.dumps(row)
        print("CONTROL " + line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        ctx.scratch.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
