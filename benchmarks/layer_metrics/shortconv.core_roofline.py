"""Roofline share of the gated short convolution's core (gate, taps, gate):
the least time for ``[tokens, 3D]`` in and ``[tokens, D]`` out a forward —
target, online and recomputed — and the backward's ``[tokens, 3D] + [tokens,
D]`` in and ``[tokens, 3D]`` out, at the chip's HBM rate
(benchmarks/lib/flops_shortconv_trunk.py: the same count whatever implements
it) over the device time under ``shortconv/core``."""
from benchmarks.lib import flops_shortconv_trunk as flops
from benchmarks.lib import trace_shortconv_trunk

NAME = "shortconv.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    if trace_shortconv_trunk.rate(sources) is None:
        return None
    conf = sources["config"]
    return trace_shortconv_trunk.roofline_share(
        sources, "shortconv/core", flops.conv_core_flops(conf),
        flops.conv_core_bytes(conf))
