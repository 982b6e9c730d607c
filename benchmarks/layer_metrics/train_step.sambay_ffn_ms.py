"""Device time per step in ops traced under ``ffn`` in a
decoder-hybrid-decoder trunk's cell: the dense SwiGLU every layer has, every
pass together."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.sambay_ffn_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.scope_ms(sources, "ffn")
