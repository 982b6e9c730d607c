"""Device time per step in ops traced under a decoder-hybrid-decoder
trunk's ``gmu`` scope — a gated memory unit: two projections and a gate on
an earlier layer's scan output — every pass together."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.gmu_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.scope_ms(sources, "gmu")
