"""Share of a step's device op time in the ``update`` phase (optimizer, EMA
tick, statistics merge, the step's counters) in a patterned trunk's cell:
the twin of ``train_step.update_share``, which keys on the latent-attention
trunk's driver (PERF.md section 7)."""
from benchmarks.lib import trace_hybrid_trunk

NAME = "train_step.hybrid_update_share"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_hybrid_trunk.update_share(sources)
