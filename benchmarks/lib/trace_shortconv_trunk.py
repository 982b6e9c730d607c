"""Device time per step under a short-convolution trunk's scopes
(``shortconv`` with ``proj``, ``core``; ``gqa`` with ``core``; ``ffn``;
``moe``: models/decoder_trunk.py ``SHORTCONV_SCOPES``), from this run's
trace.

A reader here answers a configuration whose ``arch`` is a short-convolution
trunk (``flops_shortconv_trunk.applies``), whatever its driver called the
rate, as ``lib/trace_sparse_trunk.py`` does for its trunk.
``lib/trace_decoder_trunk.py`` does the reading; it answers only a driver
that wrote the latent-attention trunk's rate counter, so this hands it the
run's sources with THIS run's rate under that name.  Everything returns
``None`` off the chip, for another architecture, and where the program names
no such scope.
"""
from __future__ import annotations

from benchmarks.lib import flops_shortconv_trunk, trace_decoder_trunk

RATE_COUNTER = "train_shortconv_sequences_per_s_per_chip"


def rate(sources: dict):
    """Sequences per second and chip of a short-convolution trunk's run."""
    if not flops_shortconv_trunk.applies(sources["config"]):
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
    allow for ``flops`` operations and ``nbytes`` bytes a step."""
    ms = scope_ms(sources, scope)
    if ms is None or sources["peaks"] is None:
        return None
    least_s = max(flops / sources["peaks"]["bf16_flops_per_s"],
                  nbytes / sources["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
