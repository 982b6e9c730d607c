"""The benchmark's seeded weights for a block-diffusion decoder trunk:
``lib/weights_sparse_trunk.py``'s rules, which cover every leaf this tree
has (LeCun-normal kernels and router, an expert kernel's fan-in its own
rows, embedding N(0, 1), the trunk's gains ``1 + 0.1 N(0, 1)``), and ONE
rule more, for what only this trunk's traffic has:

* the MASK ID's row of the embedding (the last held row) is scaled to N(0,
  0.02^2), the checkpoint initialiser's value.  About a quarter of a pass's
  positions — half of every noised half — carry that one id.  At N(0, 1),
  the scale that keeps a token's OWN row the larger part of the stream the
  routers read, they all follow the one row to the same 8 experts of 128,
  and whether those are among this chip's 16 turns with the seed: the rows
  routed to held experts a step swung 134,622–184,417 (nominal 163,840) and
  ``train_images_per_s_per_chip`` 1.717–1.748, quartiles 0.98% apart, over
  six seeds (PR 45's chip runs; PERF.md section 6).  At 0.02 a masked
  position routes by its CONTEXT — what attention writes into the stream —
  as it does once the row is trained.  An assumption the configuration
  file lists.
"""
from __future__ import annotations

from benchmarks.lib import weights_sparse_trunk

MASK_ROW_STD = 0.02


def make_weights(like_params, like_stats, seed: int, *, copies: int = 1,
                 shardings=None):
    """``weights_sparse_trunk.make_weights`` with the mask id's row
    scaled (every copy alike: the target starts as the online network)."""
    *trees, stats = weights_sparse_trunk.make_weights(
        like_params, like_stats, seed, copies=copies, shardings=shardings)
    for tree in trees:
        embed = tree["backbone"]["embed"]
        embed["embedding"] = embed["embedding"].at[-1].multiply(MASK_ROW_STD)
    return (*trees, stats)
