"""Backend compiles the persistent cache was asked for and did not have
(the program's count ``compile.cache_misses``, read at the end of the
program's set-up as the ``compile/backend`` spans marked ``cache: miss`` up
to there).  0 in a warm run: the number that says whether a ``setup_s``
reading was a warm or a cold one."""
from benchmarks.lib import setup_spans

NAME = "setup.cache_misses"
LAYER = "entry / set-up"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(sources):
    return setup_spans.read("cache_misses", sources)
