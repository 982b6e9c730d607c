"""GL109 — pallas_call outside byol_tpu/ops/ or without an interpret= path.

Two hazards around Pallas kernels, both invisible until the wrong
environment runs them:

1. **Kernels outside ``byol_tpu/ops/``.**  A ``pl.pallas_call`` inlined in
   a model or training module bypasses the in-tree kernel discipline
   (ops/packed_attention.py, ops/causal_attention.py): the interpret fallback,
   the tiling/docstring conventions, and the one place reviewers audit for
   TPU lowering constraints.  The kernel still traces fine — the drift
   only shows up when someone greps ops/ for "every kernel we ship" and
   misses one.
2. **No ``interpret=`` fallback.**  ``pallas_call`` without an
   ``interpret=`` argument compiles Mosaic-only: every CPU environment —
   tier-1, CI, a laptop repro — either fails or silently skips the code
   path, so the kernel's numerics are exactly as tested as the last TPU
   window is recent.  The in-tree contract is an ``interpret`` plumbed
   from config/backend detection (``interpret=interpret`` with a
   ``jax.default_backend() != 'tpu'`` default), which is what lets CPU
   tier-1 pin kernel-vs-reference equivalence on the REAL kernel code.

Zero-false-positive contract: only calls whose qualified name resolves to
``pallas_call`` are judged; a call forwarding ``**kwargs`` may carry
``interpret`` invisibly, so it stands down.  The location check applies
only to files inside a ``byol_tpu/`` tree (fixtures and third-party
snippets are judged on the interpret arm alone).
"""
from __future__ import annotations

import ast
from typing import List

from tools.graphlint.astutil import qualname
from tools.graphlint.engine import Context, Finding, LintedFile, Rule

_OPS_DIR = "byol_tpu/ops/"
_PKG_DIR = "byol_tpu/"


def _is_pallas_call(node: ast.Call, f: LintedFile) -> bool:
    q = qualname(node.func, f.imports)
    return bool(q) and (q == "pallas_call" or q.endswith(".pallas_call"))


class PallasInterpretRule(Rule):
    id = "GL109"
    name = "pallas-kernel-discipline"
    doc = ("pl.pallas_call belongs in byol_tpu/ops/ and must plumb an "
           "interpret= fallback so CPU tier-1 runs the real kernel")

    def check(self, f: LintedFile, ctx: Context) -> List[Finding]:
        findings: List[Finding] = []
        rel = f.rel.replace("\\", "/")
        in_pkg = _PKG_DIR in rel
        in_ops = _OPS_DIR in rel
        for node in ast.walk(f.tree):
            if not isinstance(node, ast.Call) or not _is_pallas_call(node,
                                                                     f):
                continue
            if in_pkg and not in_ops:
                findings.append(self.finding(
                    f, node, "pl.pallas_call outside byol_tpu/ops/ — "
                    "kernels live in ops/ (the packed_attention/fused_augment "
                    "pattern: interpret fallback, tiling conventions, one "
                    "auditable home for TPU lowering constraints)"))
            kwarg_names = {kw.arg for kw in node.keywords}
            if None in kwarg_names:
                continue           # **kwargs may forward interpret=
            if "interpret" not in kwarg_names:
                findings.append(self.finding(
                    f, node, "pallas_call without an interpret= argument — "
                    "off-TPU environments (tier-1, CI) cannot run the "
                    "kernel, so its numerics go untested everywhere but "
                    "live TPU; plumb interpret= from config/backend "
                    "detection (default: jax.default_backend() != 'tpu')"))
        return findings
