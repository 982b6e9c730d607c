"""Roofline share of the blockwise causal attention core: the least time for
the causal half of ``Q K^T`` and ``P V`` (forward 1, backward 2.5 with the
recomputed scores; target, online and recomputed forward) and for ``q, k, v,
o`` once a pass (benchmarks/lib/flops_hybrid_trunk.py) over the device time
under ``gqa/core``."""
from benchmarks.lib import flops_hybrid_trunk as flops
from benchmarks.lib import trace_hybrid_trunk

NAME = "gqa.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    conf = sources["config"]
    if "full_attention_interval" not in conf:
        return None
    return trace_hybrid_trunk.roofline_share(
        sources, "gqa/core", flops.attention_core_flops(conf),
        flops.attention_core_bytes(conf))
