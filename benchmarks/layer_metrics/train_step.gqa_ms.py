"""Device time per step in ops traced under the patterned trunk's ``gqa``
scope — the gated grouped-query attention layers: projections, head norms,
rotary, the blockwise causal core, the output gate — every pass together
(benchmarks/lib/trace_hybrid_trunk.py).  Absent off the chip and for a
program that names no such scope."""
from benchmarks.lib import trace_hybrid_trunk

NAME = "train_step.gqa_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_hybrid_trunk.scope_ms(sources, "gqa")
