"""Seconds in ``training/build.setup_training`` (the program's span
``startup/build``): the net, the eager flax init, the remat-tag trace, the
optimizer, the state, its placement on the mesh and the jit wiring — with
the compiles of the small programs the eager parts run."""
from benchmarks.lib import setup_spans

NAME = "setup.build_s"
LAYER = "entry / set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(sources):
    return setup_spans.read("build_s", sources)
