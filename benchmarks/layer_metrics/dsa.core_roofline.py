"""Roofline share of a sparse-attention trunk's core: the least time for
``Q K^T`` and ``P V`` over the pairs the step's counter says were SELECTED
(forward 1, backward 2.5 with the recomputed scores; target, online and
recomputed forward) and for ``q, k, v, o`` and the mask once a pass
(benchmarks/lib/flops_sparse_trunk.py) over the device time under
``dsa/core``.  A core that forms every causal pair and masks reads low by
design: the count is the selection's."""
from benchmarks.lib import flops_sparse_trunk as flops
from benchmarks.lib import trace_sparse_trunk

NAME = "dsa.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    pairs = trace_sparse_trunk.pairs_a_pass(sources, "selected_pairs")
    if pairs is None:
        return None
    conf = sources["config"]
    return trace_sparse_trunk.roofline_share(
        sources, "dsa/core", flops.core_flops(pairs, conf),
        flops.core_bytes(conf))
