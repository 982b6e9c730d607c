"""Model FLOP/s utilization of a block-diffusion trunk's cell: measured
samples/s/chip x the operations one sample needs in one BYOL step (8
forward-equivalents of its row of 2 L positions at nominal routing, the core
over the VISIBLE pairs; benchmarks/lib/flops_blockdiff_trunk.py) over the
chip's published bf16 peak.  Recomputed operations do not count.  Absent off
the chip and for another architecture."""
from benchmarks.lib import flops_blockdiff_trunk as flops
from benchmarks.lib import trace_blockdiff_trunk

NAME = "train_step.blockdiff_seq_mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = trace_blockdiff_trunk.rate(sources)
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_sample = flops.train_flops_per_sample(conf, conf["seq_len"])
    return 100.0 * rate * per_sample / sources["peaks"]["bf16_flops_per_s"]
