"""Self time of every OTHER ``compile/*`` span that ended before the end of
the program's set-up: the eager init's small programs, placement, the
benchmark's seeded weights.  A cut by KIND, not by place: most of it lies
inside ``setup.build_s`` (and ``setup.init_s``), so the two overlap and are
not to be added."""
from benchmarks.lib import setup_spans

NAME = "setup.other_compile_s"
LAYER = "entry / set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(sources):
    return setup_spans.read("other_compile_s", sources)
