"""Model FLOP/s utilization of a cell that trains on sequences: measured
sequences/s/chip x the operations one sequence needs in one BYOL step (8
forward-equivalents at nominal routing, top-k x held / published experts;
benchmarks/lib/flops_decoder_trunk.py) over the chip's published bf16 peak.
Recomputed operations do not count.  Absent off the chip."""
from benchmarks.lib import flops_decoder_trunk as flops

NAME = "train_step.seq_mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = sources["counters"].get("train_sequences_per_s_per_chip")
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_sequence = flops.train_flops_per_sequence(conf, conf["seq_len"])
    return 100.0 * rate * per_sequence / sources["peaks"]["bf16_flops_per_s"]
