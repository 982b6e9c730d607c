"""Share of the causal triangle's tiles that the band layer's core forms:
the length of the program's own list of tile pairs under the window
(``ops/attention.window_tiles``) over the causal list's, as the driver
counted them when it built the program.  31 of 136 at 8,192 keys, tiles of
512 and a window of 512."""
from benchmarks.lib import trace_sambay_trunk

NAME = "diff.band_tile_share"
LAYER = "kernels"
UNIT = "%"
MOVES = "train_images_per_s_per_chip"
SOURCE = "program_counter"


def read(sources):
    if trace_sambay_trunk.rate(sources) is None:
        return None
    counters = sources["counters"]
    band, whole = (counters.get(k) for k in ("diff_band_tiles",
                                             "diff_causal_tiles"))
    return 100.0 * band / whole if band and whole else None
