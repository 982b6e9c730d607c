"""Device time per step under the patterned trunk's scopes (``gdn``,
``gdn/core``, ``gqa``, ``gqa/core``, ``moe``: models/decoder_trunk.py
``HYBRID_SCOPES``), from this run's trace.

``lib/trace_decoder_trunk.py`` does the reading; it answers only a driver
that wrote the latent-attention trunk's rate counter, so this hands it the
run's sources with THIS driver's counter under that name.  Everything
returns ``None`` off the chip, for another driver, and where the program
names no such scope.
"""
from __future__ import annotations

from benchmarks.lib import trace_decoder_trunk

RATE_COUNTER = "train_hybrid_sequences_per_s_per_chip"


def _as_trunk(sources: dict):
    rate = sources["counters"].get(RATE_COUNTER)
    if rate is None:
        return None
    return dict(sources, counters=dict(
        sources["counters"], **{trace_decoder_trunk.RATE_COUNTER: rate}))


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
