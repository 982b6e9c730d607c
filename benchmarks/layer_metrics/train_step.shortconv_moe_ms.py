"""Device time per step in ops traced under ``moe`` in a short-convolution
trunk's cell: router, sort, the held experts' ragged products, the combine
(benchmarks/lib/trace_shortconv_trunk.py).  Absent off the chip, for another
architecture, and for a program that names no such scope."""
from benchmarks.lib import trace_shortconv_trunk

NAME = "train_step.shortconv_moe_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_shortconv_trunk.scope_ms(sources, "moe")
