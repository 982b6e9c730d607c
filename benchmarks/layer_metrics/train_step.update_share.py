"""Share of a step's device op time in the ``update`` phase (optimizer, EMA
tick, statistics merge, the step's counters), for a driver that counts
sequences: with 618 M parameters the update moves some 17 GB a step, where
it is under 2% of an image cell's step."""
from benchmarks.lib import trace_decoder_trunk

NAME = "train_step.update_share"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_decoder_trunk.update_share(sources)
