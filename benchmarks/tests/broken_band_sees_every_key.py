"""Drive a whole run of a decoder-hybrid-decoder trunk's cell with the band
layer's list of tile pairs replaced by the causal one: the self-decoder's
attention sees EVERY causal key and no window.  ``correct`` has to come out
false.  Started by test_sambay_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402
from byol_tpu.ops import attention                        # noqa: E402

decoder_trunk.window_tiles = \
    lambda blocks, window, block: attention.causal_tiles(blocks)
sys.exit(harness.main())
