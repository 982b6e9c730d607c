"""Device time per step, forward and backward of both networks, in ops
ROOTED in a normalisation module (a path segment ``bn*``, ``*_bn`` or
``ln*``).  A fusion counts by the path of its root instruction, so the
normalise-and-ReLU tail that XLA fused into a neighbouring convolution or
matmul is NOT in it, a reduction rooted in a norm layer that swallowed a
neighbour's elementwise work is in it whole, and pathless ops are not in it
at all: a lower bound of what normalisation costs, not its full price.
Absent off the chip and outside a training cell."""
from benchmarks.lib import trace_scopes

NAME = "train_step.norm_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    reduced = trace_scopes.for_sources(sources)
    if reduced is None:
        return None
    total = sum(reduced["norm_s"].get(phase, 0.0) for phase in (
        "target_forward", "online_forward", "backward"))
    return 1e3 * total if total > 0 else None
