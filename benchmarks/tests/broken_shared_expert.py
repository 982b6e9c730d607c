"""Drive a whole run of a token cell with part of the mathematics left out
underneath: the program's expert layer adds nothing for its shared expert.
``correct`` has to come out false.  Started by test_decoder_trunk.py as a
process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402

_whole = decoder_trunk.GatedMLP.__call__


def without_shared(self, x):
    y = _whole(self, x)
    return y * 0 if self.name == "shared" else y


decoder_trunk.GatedMLP.__call__ = without_shared
sys.exit(harness.main())
