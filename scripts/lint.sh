#!/usr/bin/env bash
# Static-analysis gate: graphlint over the shipped byol_tpu/ tree, over
# tools/graphlint/ itself (self-hosting, ISSUE 17: the linter must hold
# to its own rules — GL103 name hygiene, GL110 strict JSON, ...), and
# (wave 4, ISSUE 19) over the driver/tooling surface too: scripts/*.py,
# bench.py, train.py — the files that print the evidence JSON and bind
# the jitted entry points, where GL110/GL102-shaped bugs actually lived.
#
# Default run (no args) produces both outputs from ONE engine run:
#   - human text on stdout (findings as path:line:col: RULE message),
#     ending with the schema-v3 timing footer — total wall time + the
#     slowest rules, incl. the shared whole-program "project-resolution"
#     pass — so the cross-module layer can't silently blow up lint time;
#   - machine JSON at evidence/graphlint.json (schema in
#     tools/graphlint/reporters.py), committed so rule-count trends are
#     diffable across PRs.
# It also enforces the suppression-trend ratchet (--trend-baseline): the
# run FAILS when any rule's suppression count grew vs the committed
# evidence file, and on an alarm the evidence file is left untouched so
# the grown count can never silently become the new baseline.
#
# Extra args (e.g. `scripts/lint.sh --select GL103`) pass through but
# SKIP the evidence write and the trend ratchet — a partial-rule sweep
# must never overwrite (or ratchet against) the committed full-sweep
# trend file.
#
# Exit: 0 clean, 1 findings, 2 usage error — same contract as
# `python -m tools.graphlint`.  Tier-1 shells the same entrypoint
# (tests/test_graphlint.py::TestTreeGate), so DOTS_PASSED gates the lint
# even where this script never runs.
set -uo pipefail
cd "$(dirname "$0")/.."

# pure-AST tool: pin the CPU backend so a lint never claims the chip
export JAX_PLATFORMS=cpu

if [ "$#" -eq 0 ]; then
    mkdir -p evidence
    exec python -m tools.graphlint byol_tpu/ tools/graphlint/ \
        scripts/ bench.py train.py \
        --trend-baseline evidence/graphlint.json \
        --out evidence/graphlint.json
fi
exec python -m tools.graphlint byol_tpu/ tools/graphlint/ \
    scripts/ bench.py train.py "$@"
