"""Device time per step in the backward pass: ops whose scope path holds
``transpose(`` (the transpose of ``online_forward`` and ``loss``), with the
pathless ops scheduled before them (benchmarks/lib/trace_scopes.py).
Absent off the chip and outside a training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.backward_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_scopes.phase_ms(sources, "backward")
