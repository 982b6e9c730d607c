"""Device time per step in ops traced under a decoder-hybrid-decoder
trunk's ``diff`` scope — differential attention under the band, in full and
as cross attention alike: projections, the core, ``lambda``, the sub-norm
and the output projection — every pass together."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.diff_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.scope_ms(sources, "diff")
