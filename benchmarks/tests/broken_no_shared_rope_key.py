"""Drive a whole run of a one-stream latent-attention trunk's cell with the
attention scores WITHOUT their rotary term: ``q_nope . k_nope`` alone under
the same scale — the one key all heads share (``q_rope . k_rope``) never
reaches the core, so no position is encoded at all.  ``correct`` has to come
out false.  Started by test_latent_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402

_core = decoder_trunk.blockwise_causal_attention


def without_the_shared_key(q, k, v, *, shared, **kw):
    del shared
    return _core(q, k, v, **kw)


decoder_trunk.blockwise_causal_attention = without_the_shared_key
sys.exit(harness.main())
