#!/usr/bin/env python3
"""Bytes a compiled step moves, by scope, counted from its HLO text.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_v5e_tokens.py <cell> --dump-hlo F
    python3 scripts/hlo_bytes_by_scope.py F [--top 12]

A count from shapes, never a time (PERF.md section 5 holds the tables).  Every
instruction that reads and writes HBM on its own — the ones outside the fused
computations — counts its operands plus its result; an asynchronous slice or
copy counts its result's size (it reads no more of its operand); a
``conditional`` counts its CHEAPER branch (the expert layer's usual product,
not the fallback over every copy) and a ``while`` its body once.  Parameters,
constants, tuples and bitcasts move nothing.  An op goes to the first of
``SCOPES`` its ``op_name`` path holds, an op without a path to the scope of
the conditional it sits in (the conditional's own, else that of the first op
in a branch that has one), else to ``(no scope)``.
"""
from __future__ import annotations

import argparse
import collections
import re
import sys

SCOPES = ("update", "mhc", "combine", "moe/experts", "moe/shared",
          "moe/route", "mla", "ffn")
ENCLOSING = {"combine": "moe/experts"}     # what a pathless op inherits
FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "after-all", "partition-id", "replica-id", "iota"}
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_INSTR = re.compile(r"\s*(?:ROOT )?(%[^\s=]+) = (.*?) ([\w-]+)\((.*)$")
_OPERAND = re.compile(r"%[^\s,)]+")
_CALLED = re.compile(
    r"(?:calls|body|to_apply|true_computation|false_computation)=(%[^\s,}]+)"
    r"|branch_computations=\{([^}]*)\}")


def type_bytes(text: str) -> int:
    """Bytes of an HLO type: one array or a tuple of them."""
    total = 0
    for dtype, dims in _ARRAY.findall(text):
        if dtype in ITEMSIZE:
            n = 1
            for d in dims.split(","):
                n *= int(d) if d else 1
            total += n * ITEMSIZE[dtype]
    return total


def parse(text: str) -> dict:
    """``{computation: [(name, result bytes, opcode, operands, path,
    called computations)]}`` and the entry's name under ``None``."""
    comps, current = {}, None
    for line in text.splitlines():
        if line.endswith("{") and "->" in line:
            name = line.split()[1] if line.startswith("ENTRY") \
                else line.split()[0]
            current = comps.setdefault(name, [])
            if line.startswith("ENTRY"):
                comps[None] = name
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        name, result, opcode, rest = m.groups()
        args = rest.split("), ")[0] if "), " in rest else rest
        path = re.search(r'op_name="([^"]*)"', rest)
        called = [c for single, many in _CALLED.findall(rest)
                  for c in ([single] if single else
                            [b.strip() for b in many.split(",")])]
        current.append((name, type_bytes(result), opcode,
                        _OPERAND.findall(args),
                        path.group(1) if path else "", called))
    return comps


def scope_of(path: str):
    segments = [s for s in re.split(r"[/()]", path) if s]
    for scope in SCOPES:
        want = scope.split("/")
        if any(segments[i:i + len(want)] == want
               for i in range(len(segments) - len(want) + 1)):
            return scope
    return None


def count(comps: dict, name: str, inherited, into, ops) -> int:
    """Add computation ``name``'s traffic to ``into[scope]``; returns it."""
    sizes = {row[0]: row[1] for row in comps[name]}
    total = 0
    for op_name, result, opcode, operands, path, called in comps[name]:
        scope = scope_of(path) or inherited
        if opcode == "conditional":
            scope = scope or next(
                (ENCLOSING.get(found, found) for branch in called
                 for row in comps[branch]
                 for found in [scope_of(row[4])] if found), None)
            trial = []
            for branch in called:
                got = collections.Counter()
                trial.append((count(comps, branch, scope, got, None), got))
            moved, got = min(trial, key=lambda t: t[0])
            for key, value in got.items():
                into[key] += value
            total += moved
            continue
        if opcode in ("while", "call"):
            total += sum(count(comps, c, scope, into, ops) for c in called
                         if not c.startswith("%fused"))
            continue
        if opcode in FREE or opcode.endswith("-start"):
            continue
        if opcode.endswith("-done"):
            moved = result
        else:
            moved = result + sum(sizes.get(o, 0) for o in operands)
        into[scope or "(no scope)"] += moved
        total += moved
        if ops is not None:
            ops.append((moved, scope or "(no scope)", opcode, op_name, path))
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("hlo")
    ap.add_argument("--top", type=int, default=0,
                    help="also list the N largest ops outside conditionals")
    args = ap.parse_args(argv)
    with open(args.hlo) as f:
        comps = parse(f.read())
    into, ops = collections.Counter(), []
    total = count(comps, comps[None], None, into, ops)
    print(f"whole step {total / 1e9:8.1f} GB  "
          f"({total / 819e9 * 1e3:.0f} ms at 819 GB/s)")
    for scope, moved in into.most_common():
        print(f"{scope:20s} {moved / 1e9:8.1f} GB")
    for moved, scope, opcode, name, path in sorted(ops, reverse=True)[
            :args.top]:
        print(f"  {moved / 1e9:6.3f} GB {scope:12s} {opcode:12s} {name} "
              f"{path[-70:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
