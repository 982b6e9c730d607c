"""Model FLOP/s utilization of a decoder-hybrid-decoder trunk's cell:
measured samples/s/chip x the operations one sample needs in one BYOL step
(8 forward-equivalents of its row, the cores over the VISIBLE pairs of each
layer's rule; the scan has no matrix product and counts nothing:
benchmarks/lib/flops_sambay_trunk.py) over the chip's published bf16 peak.
Recomputed operations do not count.  Absent off the chip and for another
architecture."""
from benchmarks.lib import flops_sambay_trunk as flops
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.sambay_seq_mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = trace_sambay_trunk.rate(sources)
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_sample = flops.train_flops_per_sample(conf, conf["seq_len"])
    return 100.0 * rate * per_sample / sources["peaks"]["bf16_flops_per_s"]
