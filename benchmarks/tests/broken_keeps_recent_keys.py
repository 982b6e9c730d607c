"""Drive a whole run of a sparse-attention trunk's cell with the selection
taken out of the indexer's hands: every query keeps its ``topk`` most RECENT
keys (a sliding window) instead of the ``topk`` the indexer ranks highest —
the same number of keys, the wrong ones.  ``correct`` has to come out false.
Started by test_sparse_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp                                   # noqa: E402

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.ops import key_selection                    # noqa: E402
from byol_tpu.ops.attention import causal_pairs           # noqa: E402


def keep_recent(scores, topk, *, block=512):
    """The tiles of the sliding window: a key no older than ``topk``."""
    at = lambda of: of[:, None] * block + jnp.arange(block)
    q_of, k_of = causal_pairs(key_selection._blocks(scores.shape[0]))
    age = at(q_of)[:, :, None] - at(k_of)[:, None, :]
    return jnp.broadcast_to(((age >= 0) & (age < topk))[:, None],
                            scores.shape)


key_selection.select_top_keys = keep_recent
sys.exit(harness.main())
