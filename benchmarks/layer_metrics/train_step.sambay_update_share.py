"""Share of a step's device op time in the ``update`` phase (optimizer, EMA tick,
statistics merge, the step's counters) in a decoder-hybrid-decoder trunk's
cell."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.sambay_update_share"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.update_share(sources)
