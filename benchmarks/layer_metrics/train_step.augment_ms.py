"""Device time per step in ops traced under the ``augment`` scope (the
in-step two-view augmentation).  Absent where no such op ran — every cell
whose augmentation placement is ``loader`` — off the chip and outside a
training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.augment_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_scopes.phase_ms(sources, "augment")
