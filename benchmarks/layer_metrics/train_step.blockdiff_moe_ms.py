"""Device time per step under ``moe`` in a block-diffusion trunk's cell: the
128-wide softmax router and its sorts, the held experts' ragged products and
combine (no shared expert) over all 2 L positions of every row — every pass
together."""
from benchmarks.lib import trace_blockdiff_trunk

NAME = "train_step.blockdiff_moe_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_blockdiff_trunk.scope_ms(sources, "moe")
