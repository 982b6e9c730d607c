"""Seconds in the eager flax ``init`` (the program's span
``startup/build/init``, inside ``setup.build_s``): one small program traced,
compiled or loaded, and run for every distinct initializer call."""
from benchmarks.lib import setup_spans

NAME = "setup.init_s"
LAYER = "entry / set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(sources):
    return setup_spans.read("init_s", sources)
