"""Device time per step in ops traced under the patterned trunk's ``gdn``
scope — the Gated DeltaNet layers: projections, causal convolution, the
chunked delta rule, the gated output norm — forward, backward, recomputed
forward and target forward together (benchmarks/lib/trace_hybrid_trunk.py).
Absent off the chip and for a program that names no such scope."""
from benchmarks.lib import trace_hybrid_trunk

NAME = "train_step.gdn_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_hybrid_trunk.scope_ms(sources, "gdn")
