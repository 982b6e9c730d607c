"""Drive a whole run of a sparse-attention trunk's cell with the mechanism
switched off underneath: the program's selection keeps EVERY causal key
(plain causal attention; the indexer's loss then ranges over all of them).
``correct`` has to come out false.  Started by test_sparse_trunk.py as a
process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.ops import key_selection                    # noqa: E402


select = key_selection.select_top_keys


def keep_everything(scores, topk, *, block=512):
    """The real selection with room for every key of the sequence."""
    return select(scores, block * key_selection._blocks(scores.shape[0]),
                  block=block)


key_selection.select_top_keys = keep_everything
sys.exit(harness.main())
