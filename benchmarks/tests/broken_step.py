"""Drive a whole run with the timed path broken underneath: the step
returns its state unchanged (it computes the loss, and updates nothing).
``correct`` has to come out false.  Started by test_end_to_end.py as a
process of its own; skips nothing but the harness's look for a chip."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as harness                     # noqa: E402
from benchmarks.drivers import train_loop                 # noqa: E402


def frozen_step(self, host_batch):
    import jax
    keep = jax.tree_util.tree_map(lambda x: x.copy(), self.state)
    _, metrics = self.compiled(self.state, self._shard(host_batch))
    self.state = keep
    return metrics


train_loop.Program.step = frozen_step
sys.exit(harness.main())
