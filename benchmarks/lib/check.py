"""The comparison that decides ``correct``.

Every number compared is printed beside its limit in every run
(:func:`verdict`).  The limits live in the cell's workload file, each set
from readings on the chip (PERF.md section 2 lists them); a number without
a limit there is printed as read and not compared.

Training numbers (program at the configuration's precision vs the float32
reference, same seeded weights, same batches, same steps):

``loss_rel_gap``      largest |loss - loss_ref| / |loss_ref| over the steps
``grad_norm_gap``     the first gradient as the optimizer got it, read from
                      the momentum after one step: worst 1-D leaf (biases,
                      normalisation scales: LARS leaves those untouched) of
                      | ||m|| - ||m_ref|| | / max(||m_ref||, median leaf),
                      the median over the leaves whose reference is not 0
``grad_dir_gap``      worst kernel leaf of 1 - cos(m, m_ref): LARS rescales a
                      kernel's gradient to 1e-3 ||p||, so its norm says
                      nothing and its direction says everything
``update_norm_gap``   worst leaf of | ||dp|| - ||dp_ref|| | /
                      max(||dp_ref||, median leaf), dp = change of the
                      parameters over the steps followed

Served numbers: ``embed_rel_gap``, worst sampled row of
||e - e_ref|| / ||e_ref||.
"""
from __future__ import annotations

import math

import numpy as np


def _leaves(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], prefix + (k,))
    else:
        yield "/".join(prefix), np.asarray(tree, np.float64)


def _norm_gap(got: dict, ref: dict, keep) -> float:
    ref_norms = {k: float(np.linalg.norm(v)) for k, v in _leaves(ref)
                 if keep(v)}
    got_norms = {k: float(np.linalg.norm(v)) for k, v in _leaves(got)
                 if keep(v)}
    # the median leaf that HAS a gradient: in a ResNet with zero-initialised
    # residual scales most leaves get exactly none at the first step
    nonzero = [r for r in ref_norms.values() if r > 0.0]
    floor = float(np.median(nonzero)) if nonzero else 0.0
    worst = 0.0
    for k, r in ref_norms.items():
        g = got_norms[k]
        if not math.isfinite(g):
            return float("inf")
        worst = max(worst, abs(g - r) / max(r, floor, 1e-30))
    return worst


def _dir_gap(got: dict, ref: dict) -> float:
    worst = 0.0
    got = dict(_leaves(got))
    for k, r in _leaves(ref):
        if r.ndim <= 1:
            continue
        g = got[k]
        denom = float(np.linalg.norm(g) * np.linalg.norm(r))
        if not math.isfinite(denom):
            return float("inf")
        if denom == 0.0:
            worst = max(worst, 0.0 if not (g.any() or r.any()) else 1.0)
            continue
        worst = max(worst, 1.0 - float(np.vdot(g, r)) / denom)
    return worst


def _delta(after: dict, before: dict) -> dict:
    b = dict(_leaves(before))
    return {k: v - b[k] for k, v in _leaves(after)}


def training_numbers(got: dict, ref: dict, params0) -> dict:
    """``got`` / ``ref``: ``{"losses", "first_trace", "params"}``."""
    loss_gap = max(
        (abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a) else
         float("inf")) for a, b in zip(got["losses"], ref["losses"]))
    d_got = _delta(got["params"], params0)
    d_ref = _delta(ref["params"], params0)
    return {
        "loss_rel_gap": loss_gap,
        "grad_norm_gap": _norm_gap(got["first_trace"], ref["first_trace"],
                                   lambda v: v.ndim <= 1),
        "grad_dir_gap": _dir_gap(got["first_trace"], ref["first_trace"]),
        "update_norm_gap": _norm_gap(d_got, d_ref, lambda v: True),
    }


def serving_numbers(got: np.ndarray, ref: np.ndarray) -> dict:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    err = np.linalg.norm(got - ref, axis=-1)
    scale = np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    gap = err / scale
    return {"embed_rel_gap":
            float(gap.max()) if np.isfinite(gap).all() else float("inf")}


def verdict(numbers: dict, limits: dict, say=print) -> bool:
    """Print every number beside its limit; True when all that have a
    limit keep it."""
    ok = True
    for name in sorted(numbers):
        value, limit = numbers[name], limits.get(name)
        if limit is None:
            say(f"check: {name} = {value:.6g} (read, not compared)")
            continue
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        say(f"check: {name} = {value:.6g} limit {limit:.6g} "
            f"{'ok' if good else 'OVER'}")
    missing = sorted(set(limits) - set(numbers))
    if missing:
        say(f"check: limits without a number: {missing}")
        ok = False
    return ok
