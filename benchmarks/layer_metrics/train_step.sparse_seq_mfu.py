"""Model FLOP/s utilization of a sparse-attention trunk's cell: measured
sequences/s/chip x the operations one sequence needs in one BYOL step (8
forward-equivalents at nominal routing, the indexer over causal pairs, the
core over SELECTED pairs; benchmarks/lib/flops_sparse_trunk.py) over the
chip's published bf16 peak.  Recomputed operations do not count.  Absent
off the chip and for another architecture."""
from benchmarks.lib import flops_sparse_trunk as flops
from benchmarks.lib import trace_sparse_trunk

NAME = "train_step.sparse_seq_mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = trace_sparse_trunk.rate(sources)
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_sequence = flops.train_flops_per_sequence(conf, conf["seq_len"])
    return 100.0 * rate * per_sequence / sources["peaks"]["bf16_flops_per_s"]
