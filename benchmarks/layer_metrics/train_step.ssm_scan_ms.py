"""Device time per step under ``ssm/scan``: the selective scan's kernel pair
(``selective_scan_fwd`` / ``selective_scan_bwd``) and what XLA does round
it — the broadcast of ``B`` and ``C`` along the lanes, ``D * c``, the
layer's counters — every pass together."""
from benchmarks.lib import trace_sambay_trunk

NAME = "train_step.ssm_scan_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    return trace_sambay_trunk.scope_ms(sources, "ssm/scan")
