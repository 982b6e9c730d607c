"""Model FLOP/s utilization of a patterned trunk's cell: measured
sequences/s/chip x the operations one sequence needs in one BYOL step (8
forward-equivalents at nominal routing, the rule at chunk 64;
benchmarks/lib/flops_hybrid_trunk.py) over the chip's published bf16 peak.
Recomputed operations do not count.  Absent off the chip.  The twin of
``train_step.seq_mfu`` (PERF.md section 7)."""
from benchmarks.lib import flops_hybrid_trunk as flops
from benchmarks.lib.trace_hybrid_trunk import RATE_COUNTER

NAME = "train_step.hybrid_seq_mfu"
LAYER = "train step"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    rate = sources["counters"].get(RATE_COUNTER)
    if rate is None or sources["peaks"] is None:
        return None
    conf = sources["config"]
    per_sequence = flops.train_flops_per_sequence(conf, conf["seq_len"])
    return 100.0 * rate * per_sequence / sources["peaks"]["bf16_flops_per_s"]
