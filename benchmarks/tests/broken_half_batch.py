"""Drive a whole run with the feed broken underneath: every step gets the
FIRST HALF of its host batch twice, so half the samples never reach the
program.  ``correct`` has to come out false.  Started by
test_blockdiff_trunk.py as a process of its own."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from benchmarks.drivers import train_loop                 # noqa: E402

whole_step = train_loop.Program.step


def half_step(self, host_batch):
    return whole_step(self, {
        name: np.concatenate([rows[:len(rows) // 2]] * 2)
        for name, rows in host_batch.items()})


train_loop.Program.step = half_step
sys.exit(harness.main())
