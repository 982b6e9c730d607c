"""Roofline share of a sparse-attention trunk's indexer: the least time for
the index score of every CAUSAL pair the step's counter reports (forward 1,
backward 3 with the recomputed scores; target, online and recomputed
forward) and for one float32 score a pair a pass
(benchmarks/lib/flops_sparse_trunk.py) over the device time under
``dsa/index``."""
from benchmarks.lib import flops_sparse_trunk as flops
from benchmarks.lib import trace_sparse_trunk

NAME = "dsa.index_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    pairs = trace_sparse_trunk.pairs_a_pass(sources, "causal_pairs")
    if pairs is None:
        return None
    conf = sources["config"]
    return trace_sparse_trunk.roofline_share(
        sources, "dsa/index", flops.index_flops(pairs, conf),
        flops.index_bytes(pairs, conf))
