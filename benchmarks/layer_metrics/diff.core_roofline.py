"""Roofline share of differential attention's cores: the least time for
``Q K^T`` and ``P V`` of both softmaxes over the tiles FORMED (forward 1,
backward five products with the recomputed scores; target, online and
recomputed forward) and for ``q, k, v, o`` once a pass
(benchmarks/lib/flops_sambay_trunk.py) over the device time under
``diff/core``.  A 64-wide key half-fills the matrix unit's depth: the share
reads low by design."""
from benchmarks.lib import flops_sambay_trunk as flops
from benchmarks.lib import trace_sambay_trunk

NAME = "diff.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    if trace_sambay_trunk.rate(sources) is None:
        return None
    conf = sources["config"]
    return trace_sambay_trunk.roofline_share(
        sources, "diff/core", flops.core_flops(conf), flops.core_bytes(conf))
