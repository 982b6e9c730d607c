"""Device time per step under a sparse-attention trunk's scopes (``dsa``
with ``index``, ``select``, ``core``, ``index_loss``; ``moe``:
models/decoder_trunk.py ``SPARSE_SCOPES``), from this run's trace, and the
step's key-selection counters.

A reader here answers a configuration whose ``arch`` is a sparse-attention
trunk (``flops_sparse_trunk.applies``), whatever its driver called the rate.
``lib/trace_decoder_trunk.py`` does the reading; it answers only a driver
that wrote the latent-attention trunk's rate counter, so this hands it the
run's sources with THIS run's rate under that name.  Everything returns
``None`` off the chip, for another architecture, and where the program names
no such scope or sows no such counter.
"""
from __future__ import annotations

import statistics

from benchmarks.lib import flops_sparse_trunk, trace_decoder_trunk

RATE_COUNTER = "train_sparse_sequences_per_s_per_chip"


def rate(sources: dict):
    """Sequences per second and chip of a sparse-attention trunk's run."""
    if not flops_sparse_trunk.applies(sources["config"]):
        return None
    return sources["counters"].get(RATE_COUNTER)


def _as_trunk(sources: dict):
    got = rate(sources)
    if got is None:
        return None
    return dict(sources, counters=dict(
        sources["counters"], **{trace_decoder_trunk.RATE_COUNTER: got}))


def scope_ms(sources: dict, *scopes: str):
    """Milliseconds per step under ``scopes`` together; ``None`` where no op
    carries any of them."""
    seen = _as_trunk(sources)
    if seen is None:
        return None
    found = [ms for ms in (trace_decoder_trunk.scope_ms(seen, s)
                           for s in scopes) if ms is not None]
    return sum(found) if found else None


def update_share(sources: dict):
    """Percent of a step's op time in the ``update`` phase."""
    seen = _as_trunk(sources)
    return None if seen is None else trace_decoder_trunk.update_share(seen)


def pairs_a_pass(sources: dict, name: str):
    """Median over the window's steps of ``name`` (``causal_pairs`` /
    ``selected_pairs``) in ONE layer's fused pass."""
    if rate(sources) is None:
        return None
    steps = sources["counters"].get(f"sel_{name}")
    if not steps:
        return None
    return statistics.median(steps) / sources["config"]["num_hidden_layers"]


def roofline_share(sources: dict, scope: str, flops: float, nbytes: float):
    """Percent of the device time under ``scope`` that the chip's peaks
    allow for ``flops`` operations and ``nbytes`` bytes a step."""
    ms = scope_ms(sources, scope)
    if ms is None or sources["peaks"] is None:
        return None
    least_s = max(flops / sources["peaks"]["bf16_flops_per_s"],
                  nbytes / sources["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
