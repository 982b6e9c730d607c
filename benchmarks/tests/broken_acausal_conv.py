"""Drive a whole run of a short-convolution trunk's cell with the
convolution's taps CENTRED on the token (``t - 1, t, t + 1``) instead of
ending at it (``t - 2, t - 1, t``): the same taps, the same number of
multiply-adds, one token of the future.  ``correct`` has to come out false.
Started by test_shortconv_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp                                   # noqa: E402

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402


def centred_conv(x, taps):
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k // 2, k - 1 - k // 2), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j] for j in range(k))


decoder_trunk.causal_conv = centred_conv       # ShortConv's; gdn keeps its own
sys.exit(harness.main())
