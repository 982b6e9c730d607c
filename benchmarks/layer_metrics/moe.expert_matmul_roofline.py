"""Roofline share of the held experts' ragged products: the least time the
chip could take for the rows the step routed — the larger of operations
over the bf16 peak and bytes over the HBM peak
(benchmarks/lib/flops_decoder_trunk.py: three products per row and pass,
five forward-equivalents a step under remat; the experts' bf16 matrices once
per pass and layer plus the rows in and out) — over the device time under
``moe/experts``, which also holds the sort's gathers and the casts.  Rows
are the ONLINE forward's, by the step's counter; the target's lagged router
is taken to route as many."""
import statistics

from benchmarks.lib import flops_decoder_trunk as flops
from benchmarks.lib import trace_decoder_trunk

NAME = "moe.expert_matmul_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    rows = sources["counters"].get("moe_rows_held")
    ms = trace_decoder_trunk.scope_ms(sources, "moe/experts")
    if not rows or ms is None or sources["peaks"] is None:
        return None
    rows, conf, peaks = statistics.median(rows), sources["config"], \
        sources["peaks"]
    least_s = max(
        flops.expert_matmul_flops(rows, conf) / peaks["bf16_flops_per_s"],
        flops.expert_matmul_bytes(rows, conf) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
