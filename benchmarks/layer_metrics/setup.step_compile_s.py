"""Seconds to get the train step's program: JAX's ``compile/trace`` +
``compile/lower`` + ``compile/backend`` spans of ``jit(train_step)``.  A load
from the persistent cache counts here: it is what the run paid
(``setup.cache_misses`` says which kind of run it was)."""
from benchmarks.lib import setup_spans

NAME = "setup.step_compile_s"
LAYER = "entry / set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(sources):
    return setup_spans.read("step_compile_s", sources)
