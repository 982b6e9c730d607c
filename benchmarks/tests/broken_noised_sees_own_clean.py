"""Drive a whole run of a block-diffusion trunk's cell with ``<=`` for ``<``
in the noised-on-clean rule: a noised query also sees the CLEAN ids of its
own block — the leak that makes the published objective trivial (the masked
ids are read off the clean copy).  ``correct`` has to come out false.
Started by test_blockdiff_trunk.py as a process of its own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402
from byol_tpu.ops import attention                        # noqa: E402


def leaking_tiles(blocks, span):
    """``block_diffusion_tiles`` with the noised-on-clean edge tiles
    ``NOT_AFTER`` where they are ``BEFORE``."""
    tiles = attention.block_diffusion_tiles(blocks, span)
    return tiles._replace(kind=tuple(
        attention.NOT_AFTER if kind == attention.BEFORE else kind
        for kind in tiles.kind))


decoder_trunk.block_diffusion_tiles = leaking_tiles
sys.exit(harness.main())
