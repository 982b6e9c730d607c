"""Device time per step under ``dsa/select``: the exact top-k of every query's
index scores (2,048 of up to 4,096 candidates a row), every pass together."""
from benchmarks.lib import trace_sparse_trunk

NAME = "train_step.dsa_select_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sparse_trunk.scope_ms(sources, "dsa/select")
