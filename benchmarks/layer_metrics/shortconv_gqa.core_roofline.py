"""Roofline share of the blockwise causal attention core at 64-wide heads:
the least time for the causal half of ``Q K^T`` and ``P V`` (forward 1,
backward 2.5 with the recomputed scores; target, online and recomputed
forward) and for ``q, k, v, o`` once a pass — the conventions of
``gqa.core_roofline`` (benchmarks/lib/flops_shortconv_trunk.py) — over the
device time under ``gqa/core`` in a short-convolution trunk's cell."""
from benchmarks.lib import flops_shortconv_trunk as flops
from benchmarks.lib import trace_shortconv_trunk

NAME = "shortconv_gqa.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    if trace_shortconv_trunk.rate(sources) is None:
        return None
    conf = sources["config"]
    return trace_shortconv_trunk.roofline_share(
        sources, "gqa/core", flops.core_flops(conf), flops.core_bytes(conf))
