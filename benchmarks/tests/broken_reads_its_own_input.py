"""Drive a whole run of a decoder-hybrid-decoder trunk's cell with the
cross-decoder cut off from what it reads: a gated memory unit gates ITS OWN
layer's input (twice, side by side) in place of the scan output the Mamba
layer handed on, and a cross attention layer attends keys and values cut out
of ITS OWN layer's input in place of the full attention layer's.  ``correct``
has to come out false.  Started by test_sambay_trunk.py as a process of its
own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp                                   # noqa: E402

from benchmarks import run as harness                     # noqa: E402
from byol_tpu.models import decoder_trunk                 # noqa: E402


class OwnInputGate(decoder_trunk.GatedMemoryUnit):
    def __call__(self, x, m):
        return super().__call__(
            x, jnp.concatenate([x, x], axis=-1).astype(m.dtype))


class OwnInputKeys(decoder_trunk.DifferentialAttention):
    def __call__(self, x, kv=None):
        if kv is not None:
            z, (b, s, _) = self.sizes, x.shape
            n = z.num_kv_heads * z.head_dim
            heads = lambda t, count: t.reshape(b, s, count, -1).transpose(
                0, 2, 1, 3)
            kv = (heads(x[..., :n], z.num_kv_heads).astype(kv[0].dtype),
                  heads(x[..., n:2 * n], z.num_kv_heads // 2).astype(
                      kv[1].dtype))
        return super().__call__(x, kv)


decoder_trunk.GatedMemoryUnit = OwnInputGate
decoder_trunk.DifferentialAttention = OwnInputKeys
sys.exit(harness.main())
