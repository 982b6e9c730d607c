"""Device time per step in ops traced under ``mla/core`` — latent attention's
causal core alone (the blockwise kernels or the ``jax.numpy`` tiles, with the
copies and casts the scope holds), every pass: target, online and recomputed
forward and the backward (benchmarks/lib/trace_decoder_trunk.py).  Answers a
one-stream latent-attention trunk's configuration
(``flops_latent_core.applies``); absent off the chip and for a program that
names no such scope."""
from benchmarks.lib import flops_latent_core, trace_decoder_trunk

NAME = "train_step.mla_core_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "device_trace"


def read(sources):
    if not flops_latent_core.applies(sources["config"]):
        return None
    return trace_decoder_trunk.scope_ms(sources, flops_latent_core.SCOPE)
