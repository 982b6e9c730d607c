"""Device time per step under ``moe`` in a sparse-attention trunk's cell: the
128-wide softmax router and its sorts, the held experts' ragged products and
combine (no shared expert) — every pass together."""
from benchmarks.lib import trace_sparse_trunk

NAME = "train_step.sparse_moe_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sparse_trunk.scope_ms(sources, "moe")
