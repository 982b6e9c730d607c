"""Device time per step in ops traced under the ``update`` scope: optimizer,
EMA tick, BatchNorm-statistics merge, telemetry, and the ZeRO-1 / resident
target gather at the top of the step (benchmarks/lib/trace_scopes.py).
Absent off the chip and outside a training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.update_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_scopes.phase_ms(sources, "update")
