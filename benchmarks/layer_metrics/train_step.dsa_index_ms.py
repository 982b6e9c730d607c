"""Device time per step under ``dsa/index`` (the indexer's three projections, its
rotary and the index score of every causal pair) and ``dsa/index_loss`` (the
core's head-mean probabilities once more and the KL against them), every pass
together: what the learned selection costs beside the selection itself."""
from benchmarks.lib import trace_sparse_trunk

NAME = "train_step.dsa_index_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sparse_trunk.scope_ms(sources, "dsa/index", "dsa/index_loss")
