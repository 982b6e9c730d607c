"""Device time per step under ``blockdiff/core``: the tiled attention
kernels over the tile pairs that hold a visible pair of the block-diffusion
mask (80 a row of 2 x 4,096 at tiles of 512), and the transposes and the
backward's ``rowsum(dO . O)`` round them — every pass together."""
from benchmarks.lib import trace_blockdiff_trunk

NAME = "train_step.blockdiff_core_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_blockdiff_trunk.scope_ms(sources, "blockdiff/core")
