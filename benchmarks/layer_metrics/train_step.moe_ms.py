"""Device time per step in ops traced under the decoder trunk's ``moe``
scope — the expert layer: routing, the held experts' ragged products, the shared expert — forward, backward, recomputed forward and target forward
together (benchmarks/lib/trace_decoder_trunk.py).  Absent off the chip and
for a program that names no such scope."""
from benchmarks.lib import trace_decoder_trunk

NAME = "train_step.moe_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_decoder_trunk.scope_ms(sources, "moe")
