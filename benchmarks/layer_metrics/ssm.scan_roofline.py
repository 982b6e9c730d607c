"""Roofline share of the selective scan: the least time for the bytes its
kernel pair MUST move a step (``c``, ``delta``, ``B``, ``C`` in, ``m`` out,
the border states, and backward their cotangents:
benchmarks/lib/flops_sambay_trunk.py) at the chip's HBM bandwidth, over the
device time under ``ssm/scan``.  The scan has no matrix product, so the
matrix unit's peak is not its roofline, and ``lib/peaks.py`` publishes no
vector-unit peak: where the vector unit binds — one ``exp`` and five
multiply-adds a state element and step — the share reads low by design."""
from benchmarks.lib import flops_sambay_trunk as flops
from benchmarks.lib import trace_sambay_trunk

NAME = "ssm.scan_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    if trace_sambay_trunk.rate(sources) is None:
        return None
    return trace_sambay_trunk.roofline_share(
        sources, "ssm/scan", 0.0, flops.scan_bytes(sources["config"]))
