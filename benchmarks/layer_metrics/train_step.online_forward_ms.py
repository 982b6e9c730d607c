"""Device time per step in the forward of the differentiated function: ops
traced under ``online_forward`` or ``loss`` (loss and probe) and not under
``transpose(``, with the pathless ops scheduled before them
(benchmarks/lib/trace_scopes.py).  Absent off the chip and outside a
training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.online_forward_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_scopes.phase_ms(sources, "online_forward")
