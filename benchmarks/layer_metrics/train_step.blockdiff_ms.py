"""Device time per step in ops traced under a block-diffusion trunk's
``blockdiff`` scope — projections, head norms, rotary by position, the core
under the three-part mask and the output projection — every pass together
(benchmarks/lib/trace_blockdiff_trunk.py).  Absent off the chip, for another
architecture, and for a program that names no such scope."""
from benchmarks.lib import trace_blockdiff_trunk

NAME = "train_step.blockdiff_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_blockdiff_trunk.scope_ms(sources, "blockdiff")
