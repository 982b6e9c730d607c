"""Device time per step under ``moe`` in a patterned trunk's cell: the
512-wide softmax router and its sorts, the held experts' ragged products
and combine, the gated shared expert — every pass together.  The twin of
``train_step.moe_ms``, which keys on the latent-attention trunk's driver
(PERF.md section 7)."""
from benchmarks.lib import trace_hybrid_trunk

NAME = "train_step.hybrid_moe_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_hybrid_trunk.scope_ms(sources, "moe")
