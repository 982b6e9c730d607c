"""Roofline share of the gated delta rule: the least time the chip could take
for one step's rule — the larger of its operations over the bf16 peak and
its bytes over the HBM peak (benchmarks/lib/flops_hybrid_trunk.py: the
chunked form COUNTED AT CHUNK 64 whatever chunk the program uses; ``q, k, v,
g, beta`` in and ``o`` out once a pass plus one state per chunk boundary;
five passes under remat) — over the device time under ``gdn/core``, which
also holds the L2 norms and the gates."""
from benchmarks.lib import flops_hybrid_trunk as flops
from benchmarks.lib import trace_hybrid_trunk

NAME = "gdn.delta_rule_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    conf = sources["config"]
    if "linear_num_value_heads" not in conf:
        return None
    return trace_hybrid_trunk.roofline_share(
        sources, "gdn/core", flops.delta_rule_flops(conf),
        flops.delta_rule_bytes(conf))
