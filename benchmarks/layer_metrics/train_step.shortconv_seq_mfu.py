"""Model FLOP/s utilization of a short-convolution trunk's cell: measured
sequences/s/chip x the operations one sequence needs in one BYOL step (8
forward-equivalents at nominal routing, the attention core over the causal
pairs; benchmarks/lib/flops_shortconv_trunk.py) over the chip's published
bf16 peak.  Recomputed operations do not count.  Absent off the chip and for
another architecture."""
from benchmarks.lib import flops_shortconv_trunk as flops
from benchmarks.lib import trace_shortconv_trunk

NAME = "train_step.shortconv_seq_mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = trace_shortconv_trunk.rate(sources)
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_sequence = flops.train_flops_per_sequence(conf, conf["seq_len"])
    return 100.0 * rate * per_sequence / sources["peaks"]["bf16_flops_per_s"]
