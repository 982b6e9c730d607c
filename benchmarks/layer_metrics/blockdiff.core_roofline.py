"""Roofline share of a block-diffusion trunk's core: the least time for
``Q K^T`` and ``P V`` over the VISIBLE pairs of the mask, ``L^2 + L b`` a row
and head (forward 1, backward 2.5 with the recomputed scores; target, online
and recomputed forward) and for ``q, k, v, o`` once a pass
(benchmarks/lib/flops_blockdiff_trunk.py) over the device time under
``blockdiff/core``.  A core that forms whole tiles reads low by design: of
the 80 tiles a row it forms, 20.97 M pairs, 16.79 M are visible."""
from benchmarks.lib import flops_blockdiff_trunk as flops
from benchmarks.lib import trace_blockdiff_trunk

NAME = "blockdiff.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    if trace_blockdiff_trunk.rate(sources) is None:
        return None
    conf = sources["config"]
    return trace_blockdiff_trunk.roofline_share(
        sources, "blockdiff/core", flops.core_flops(conf),
        flops.core_bytes(conf))
