"""From the process's start (the operating system's) to the end of the
program's set-up, less the union of all spans in between: imports, the
backend's start, and the benchmark's own work before the compile (seeded
weights, the host pool)."""
from benchmarks.lib import setup_spans

NAME = "setup.unattributed_s"
LAYER = "entry / set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(sources):
    return setup_spans.read("unattributed_s", sources)
