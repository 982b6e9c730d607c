"""Device time per step in ops traced under a sparse-attention trunk's ``dsa``
scope — projections, head norms, rotary, the indexer, the selection, the core,
the index loss and the output projection — every pass together
(benchmarks/lib/trace_sparse_trunk.py).  Absent off the chip, for another
architecture, and for a program that names no such scope."""
from benchmarks.lib import trace_sparse_trunk

NAME = "train_step.dsa_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sparse_trunk.scope_ms(sources, "dsa")
