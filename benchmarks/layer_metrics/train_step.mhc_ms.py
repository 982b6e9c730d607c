"""Device time per step in ops traced under the decoder trunk's ``mhc``
scope — the hyper-connections: the three maps with their Sinkhorn iterations, reading and writing the residual streams — forward, backward, recomputed forward and target forward
together (benchmarks/lib/trace_decoder_trunk.py).  Absent off the chip and
for a program that names no such scope."""
from benchmarks.lib import trace_decoder_trunk

NAME = "train_step.mhc_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_decoder_trunk.scope_ms(sources, "mhc")
