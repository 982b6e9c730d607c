"""Drive a whole run of a short-convolution trunk's cell with the router's
selection bias LEAKING into the weights: the chosen experts are weighed by
``s + b`` over its sum instead of by ``s`` — the same experts, other weights,
and a bias that now takes a gradient.  ``correct`` has to come out false.
Started by test_shortconv_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp                                   # noqa: E402

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402

_bias = []                      # the bias the layer being traced just read
_param = decoder_trunk.ExpertLayer.param


def param(self, name, *args, **kwargs):
    value = _param(self, name, *args, **kwargs)
    if name == "e_score_correction_bias":
        _bias.append(value)
    return value


class _LeakyNumpy:
    """``decoder_trunk``'s ``jnp`` with ONE function changed: the gather of
    the chosen experts' scores (the expert layer's only call of it) reads
    the biased scores."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def take_along_axis(scores, chosen, axis):
        return jnp.take_along_axis(scores + _bias.pop(), chosen, axis=axis)


decoder_trunk.ExpertLayer.param = param
decoder_trunk.jnp = _LeakyNumpy()
sys.exit(harness.main())
