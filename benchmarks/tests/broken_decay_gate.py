"""Drive a whole run of a patterned trunk's cell with part of the
mathematics left out underneath: the program's Gated DeltaNet layers run
without their decay gate (``g = 0``: the state never forgets).  ``correct``
has to come out false.  Started by test_hybrid_trunk.py as a process of its
own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp                                   # noqa: E402

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import gated_delta                   # noqa: E402

_whole = gated_delta.chunked_delta_rule


def without_decay(q, k, v, g, beta, **kw):
    return _whole(q, k, v, jnp.zeros_like(g), beta, **kw)


gated_delta.chunked_delta_rule = without_decay
sys.exit(harness.main())
