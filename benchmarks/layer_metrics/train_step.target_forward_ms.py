"""Device time per step in ops traced under the ``target_forward`` scope
(the EMA network's two views), with the pathless ops scheduled before them
(benchmarks/lib/trace_scopes.py).  Absent off the chip and outside a
training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.target_forward_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_scopes.phase_ms(sources, "target_forward")
