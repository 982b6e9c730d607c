"""Device time per step in ops traced under a decoder-hybrid-decoder
trunk's ``ssm`` scope — a Mamba layer's projections, its convolution, the
selective scan and the gate — every pass together
(benchmarks/lib/trace_sambay_trunk.py).  Absent off the chip, for another
architecture, and for a program that names no such scope."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.ssm_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.scope_ms(sources, "ssm")
