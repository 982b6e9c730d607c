"""Device time per step under ``diff/core``: the tiled attention kernels
over the tiles each layer's rule forms (the band's 31, the triangle's 136
twice, a row of 8,192 at tiles of 512), both softmaxes of a pair in one
call, and the transposes, the repeated value heads and the backward's
``rowsum(dO . O)`` round them — every pass together."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.diff_core_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.scope_ms(sources, "diff/core")
