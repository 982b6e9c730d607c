"""Device time per step under a decoder-hybrid-decoder trunk's scopes
(``ssm`` with ``proj``, ``conv``, ``scan``, ``gate``; ``diff`` with ``core``;
``gmu``; ``ffn``: models/decoder_trunk.py ``SAMBAY_SCOPES``), from this
run's trace.

A reader here answers a configuration whose ``arch`` is such a trunk
(``flops_sambay_trunk.applies``) and a driver that wrote this trunk's rate
counter.  ``lib/trace_decoder_trunk.py`` does the reading; it answers only a
driver that wrote the latent-attention trunk's rate counter, so this hands
it the run's sources with THIS run's rate under that name, as
``lib/trace_blockdiff_trunk.py`` does.  Everything returns ``None`` off the
chip, for another architecture, and where the program names no such scope
(the parent of the PR that added them).
"""
from __future__ import annotations

from benchmarks.lib import flops_sambay_trunk, trace_decoder_trunk

RATE_COUNTER = "train_sambay_samples_per_s_per_chip"


def rate(sources: dict):
    """Samples per second and chip of such a trunk's run."""
    if not flops_sambay_trunk.applies(sources["config"]):
        return None
    return sources["counters"].get(RATE_COUNTER)


def _as_trunk(sources: dict):
    got = rate(sources)
    if got is None:
        return None
    return dict(sources, counters=dict(
        sources["counters"], **{trace_decoder_trunk.RATE_COUNTER: got}))


def scope_ms(sources: dict, scope: str):
    """Milliseconds per step under ``scope``; ``None`` where no op carries
    it."""
    seen = _as_trunk(sources)
    return None if seen is None else trace_decoder_trunk.scope_ms(seen, scope)


def update_share(sources: dict):
    """Percent of a step's op time in the ``update`` phase."""
    seen = _as_trunk(sources)
    return None if seen is None else trace_decoder_trunk.update_share(seen)


def roofline_share(sources: dict, scope: str, flops: float, nbytes: float):
    """Percent of the device time under ``scope`` that the chip's peaks
    allow for ``flops`` matrix operations and ``nbytes`` bytes a step."""
    ms = scope_ms(sources, scope)
    if ms is None or sources["peaks"] is None:
        return None
    least_s = max(flops / sources["peaks"]["bf16_flops_per_s"],
                  nbytes / sources["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def counter_median(sources: dict, name: str):
    """The median over the window's steps of a per-step program counter."""
    if rate(sources) is None:
        return None
    values = sorted(sources["counters"].get(name) or [])
    return values[len(values) // 2] if values else None
