"""Share of the ``jit_train_step`` device time in ops that carry a scope path
with no phase in it: the coverage counter that says how far to trust the
phase times.  (Ops with no path at all inherit a neighbour's phase;
``python3 -m benchmarks.lib.trace_scopes`` prints how much each phase
inherited.)  Absent off the chip and outside a training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.unscoped_share"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    reduced = trace_scopes.for_sources(sources)
    if reduced is None:
        return None
    return 100.0 * reduced["phase_s"].get("unscoped", 0.0) / reduced["op_s"]
