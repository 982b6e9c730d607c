"""Roofline share of latent attention's causal core: the least time for the
causal half of ``Q K^T`` at the 192-wide keys and of ``P V`` at the 128-wide
values over every head and layer (forward 1, backward 2.5 with the recomputed
scores; target, online and recomputed forward) and for ``q, k, v, o`` once a
pass, the rotary key once for all heads — the conventions of
``gqa.core_roofline`` (benchmarks/lib/flops_latent_core.py) — over the device
time under ``mla/core``."""
from benchmarks.lib import flops_latent_core as flops
from benchmarks.lib import trace_decoder_trunk

NAME = "mla.core_roofline"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    conf, peaks = sources["config"], sources["peaks"]
    if not flops.applies(conf) or peaks is None:
        return None
    ms = trace_decoder_trunk.scope_ms(sources, flops.SCOPE)
    if ms is None:
        return None
    least_s = max(flops.core_flops(conf) / peaks["bf16_flops_per_s"],
                  flops.core_bytes(conf) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
