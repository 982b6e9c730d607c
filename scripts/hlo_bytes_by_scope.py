#!/usr/bin/env python3
"""Bytes a compiled step moves, by scope, counted from its HLO text.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_v5e_tokens.py <cell> --dump-hlo F
    python3 scripts/hlo_bytes_by_scope.py F [--top 12]

A count from shapes, never a time (PERF.md section 5 holds the tables).  Every
instruction that reads and writes HBM on its own — the ones outside the fused
computations — counts its operands plus its result; an asynchronous slice or
copy counts its result's size (it reads no more of its operand); a
``conditional`` counts its CHEAPER branch (the expert layer's usual product,
not the fallback over every copy) and a ``while`` its body times the largest
integer its condition compares with (a scan's trip count; once where it has
none).  A buffer that an op only SLICES — the operand of a
``dynamic-slice`` or the target of a ``dynamic-update-slice``, alone or inside
a fusion, as a scan reads its stacked inputs and writes its stacked outputs in
place — counts the slices, not itself.  Parameters,
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
          "moe/route", "mla", "ffn", "gdn/core", "gdn/proj", "gdn/conv",
          "gdn/gate_norm", "gdn", "gqa/core", "gqa")
BOUNDS = "#bounds"       # parse()'s key: {computation: its largest integer}
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
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=(%[^\s,}]+)"
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
    comps, current = {BOUNDS: collections.Counter()}, None
    for line in text.splitlines():
        if line.endswith("{") and "->" in line:
            name = line.split()[1] if line.startswith("ENTRY") \
                else line.split()[0]
            current, where = comps.setdefault(name, []), name
            if line.startswith("ENTRY"):
                comps[None] = name
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        name, result, opcode, rest = m.groups()
        if opcode == "constant" and result.startswith("s32[]") \
                and rest.split(")")[0].isdigit():
            comps[BOUNDS][where] = max(comps[BOUNDS][where],
                                       int(rest.split(")")[0]))
        args = rest.split("), ")[0] if "), " in rest else rest
        path = re.search(r'op_name="([^"]*)"', rest)
        called = [c for single, many in _CALLED.findall(rest)
                  for c in ([single] if single else
                            [b.strip() for b in many.split(",")])]
        if opcode == "parameter":          # its number, where operands go
            args = "%" + rest.split(")")[0]
        current.append((name, type_bytes(result), opcode,
                        _OPERAND.findall(args),
                        path.group(1) if path else "", called))
    return comps


def loop_bounds(comps) -> list:
    """The trip bound of every ``while`` in the module: the largest integer
    its condition compares with (0 where it has none)."""
    return [comps[BOUNDS][row[5][0]] for name, rows in comps.items()
            if name not in (None, BOUNDS) for row in rows
            if row[2] == "while"]


def sliced_reads(comps, rows) -> dict:
    """``{parameter number: bytes read of it}`` for the parameters of the
    computation ``rows`` that are only ever SLICED — every use operand 0 of
    a ``dynamic-slice`` or ``dynamic-update-slice``, through bitcasts and
    through nested fusions that do the same; a parameter read whole is not
    in the result."""
    alias = {r[0]: int(r[3][0][1:]) for r in rows if r[2] == "parameter"}
    touched, whole = collections.Counter(), set()
    for name, res, opcode, operands, _, called in rows:
        if opcode == "parameter":
            continue
        if opcode == "bitcast" and operands[0] in alias:
            alias[name] = alias[operands[0]]
            continue
        inner = sliced_reads(comps, comps[called[0]]) \
            if opcode == "fusion" else {}
        for pos, o in enumerate(operands):
            if o not in alias:
                continue
            if pos == 0 and opcode == "dynamic-slice":
                touched[alias[o]] += res
            elif pos == 0 and opcode == "dynamic-update-slice":
                touched[alias[o]] += 0      # overwritten, not read
            elif pos in inner:
                touched[alias[o]] += inner[pos]
            else:
                whole.add(alias[o])
    return {i: b for i, b in touched.items() if i not in whole}


def sliced_traffic(comps, rows, operand_bytes, result):
    """``(read, written)`` bytes of one op given as the ``rows`` that compute
    it (a fusion's computation, or the op's own row over stand-in
    parameters): a parameter that is only sliced is read at the slices'
    size, and a root (or an element of a root tuple) that updates such a
    parameter in place writes the update's size."""
    sliced = sliced_reads(comps, rows)
    read = sum(sliced.get(i, b) for i, b in enumerate(operand_bytes))
    by_name = {r[0]: r for r in rows}
    alias = {r[0] for r in rows if r[2] == "parameter"
             and int(r[3][0][1:]) in sliced}

    def in_place(name):
        """The update's bytes where ``name`` overwrites part of a sliced
        parameter, else None."""
        row = by_name[name]
        while row[2] == "bitcast" and row[3][0] in by_name:
            row = by_name[row[3][0]]
        if row[2] == "dynamic-update-slice" and row[3][0] in alias:
            return by_name[row[3][1]][1]
        return None
    root = rows[-1]
    if root[2] == "tuple":
        return read, sum(by_name[o][1] if in_place(o) is None
                         else in_place(o) for o in root[3])
    return read, result if in_place(root[0]) is None else in_place(root[0])


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
        if opcode == "while":
            condition, body = called          # the text's own order
            once = collections.Counter()
            moved = count(comps, body, scope, once, None)
            trips = max(comps[BOUNDS][condition], 1)
            for key, value in once.items():
                into[key] += value * trips
            total += moved * trips
            continue
        if opcode == "call":
            total += sum(count(comps, c, scope, into, ops) for c in called
                         if not c.startswith("%fused"))
            continue
        if opcode in FREE or opcode.endswith("-start"):
            continue
        if opcode.endswith("-done"):
            moved = result
        else:
            operand_bytes = [sizes.get(o, 0) for o in operands]
            if opcode == "fusion":
                rows = comps[called[0]]
            else:                   # the op itself, over stand-in parameters
                rows = [(f"%{i}", b, "parameter", [f"%{i}"], "", [])
                        for i, b in enumerate(operand_bytes)] + [
                    (op_name, result, opcode,
                     [f"%{i}" for i in range(len(operands))], path, [])]
            moved = sum(sliced_traffic(comps, rows, operand_bytes, result))
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
