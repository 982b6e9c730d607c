"""Device time per step in ops traced under a short-convolution trunk's
``shortconv`` scope — both projections, the two gates and the taps between
them — every pass of every such layer together
(benchmarks/lib/trace_shortconv_trunk.py).  Absent off the chip, for another
architecture, and for a program that names no such scope."""
from benchmarks.lib import trace_shortconv_trunk

NAME = "train_step.shortconv_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_shortconv_trunk.scope_ms(sources, "shortconv")
