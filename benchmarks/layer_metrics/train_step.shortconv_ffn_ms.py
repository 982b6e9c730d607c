"""Device time per step in ops traced under ``ffn`` in a short-convolution
trunk's cell: the leading dense layer's SwiGLU, every pass together
(benchmarks/lib/trace_shortconv_trunk.py).  Absent off the chip, for another
architecture, and for a program that names no such scope."""
from benchmarks.lib import trace_shortconv_trunk

NAME = "train_step.shortconv_ffn_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_shortconv_trunk.scope_ms(sources, "ffn")
