"""Drive a whole run of a decoder-hybrid-decoder trunk's cell with every
pair's SECOND softmax taken away: differential attention at ``lambda = 0``,
one softmax a pair.  ``correct`` has to come out false.  Started by
test_sambay_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402

whole_core = decoder_trunk.blockwise_causal_attention


def first_softmax_only(q, k, v, **kw):
    """The core's heads lie ``(pair, softmax, query of the group)``: the
    second softmax's outputs are zeroed, so ``o1 - lambda o2 = o1``."""
    out = whole_core(q, k, v, **kw)
    b, h, s, dv = out.shape
    pairs = k.shape[1] // 2
    split = out.reshape(b, pairs, 2, h // (2 * pairs), s, dv)
    return split.at[:, :, 1].set(0).reshape(out.shape)


decoder_trunk.blockwise_causal_attention = first_softmax_only
sys.exit(harness.main())
