"""ZeRO-1 weight-update sharding: flat leaf-partitioned optimizer state.

The reference (and the replicated default here) keeps THREE full copies of
the parameter tree on every chip: online params, LARS momentum, EMA target.
Online params must stay replicated — every chip runs the forward — but the
other two are touched only by the per-step elementwise update, and
*Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training* (arXiv 2004.13336) shows that update can be computed on a 1/N
shard per chip with near-zero throughput cost.  *How to Scale Your EMA*
(arXiv 2307.13813) frames BYOL's target tick as exactly such an elementwise
update, so the EMA tree shards by the same mechanism for free.

Layout: every array leaf of the sharded trees is raveled to 1-D and
zero-padded to the next multiple of the mesh's ``data``-axis size, then
given ``P(DATA_AXIS)`` — flat leaf-partitioning, so the shard split never
depends on a divisible tensor dimension (the old ``fsdp`` heuristic
replicated any leaf without one).  The padding is invariant under the
whole update chain: gradients and params are padded with zeros, weight
decay (``g + wd*p``), momentum, trust-ratio scaling, and the EMA tick all
map ``(0, 0) -> 0``, and per-leaf l2 norms (LARS/LAMB trust ratios, the
telemetry health vector) are unchanged by zero padding — so flat-sharded
numerics match the replicated step exactly (pinned by
tests/test_zero1.py).

In-graph dataflow per optimizer step (GSPMD inserts the collectives from
the sharding constraints):

1. gradients mean over the batch (the data-axis all-reduce, as before);
2. ``shard``: flatten + constrain to ``P(data)`` — each chip keeps its
   1/N slice of the (replicated) gradient/params, no communication;
3. the optax chain runs on the flat trees — momentum read/write, trust
   ratios, LR scale are all shard-local;
4. ``gather``: the fresh flat params are constrained back to replicated —
   ONE all-gather, just in time for the next forward;
5. the EMA target ticks on its shard and STAYS sharded; the train/eval
   steps gather it just-in-time for the target forward.

Checkpoint canonicalization: the flat layout (and its padding) depends on
the mesh size, so checkpoints always store the CANONICAL (unflattened,
replicated) trees — ``to_canonical``/``from_canonical`` on the compile
plan convert at the save/restore boundary, which is what lets a ckpt
written on an 8-chip mesh restore onto 4 chips (reshard-on-restore,
tests/test_checkpoint.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byol_tpu.parallel.mesh import DATA_AXIS

# TrainState fields whose array leaves live flat-sharded under ZeRO-1.
# Online params / BN stats are forward-critical (replicated); polyak_params
# feed the eval forward directly and default off — kept replicated.
ZERO1_STATE_FIELDS = ("opt_state", "target_params")


def padded_size(size: int, n: int) -> int:
    """Smallest multiple of ``n`` >= ``size``."""
    return -(-size // n) * n


def flatten_leaf(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Ravel to 1-D and zero-pad to a multiple of ``n`` shards."""
    flat = jnp.ravel(x)
    pad = padded_size(flat.size, n) - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def unflatten_leaf(flat: jnp.ndarray, template: Any) -> jnp.ndarray:
    """Inverse of :func:`flatten_leaf` against a shape/dtype template."""
    size = math.prod(template.shape) if template.shape else 1
    return flat[:size].reshape(template.shape)


def flat_struct(template: Any, n: int) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct of a leaf's flat-padded form."""
    size = math.prod(template.shape) if template.shape else 1
    return jax.ShapeDtypeStruct((padded_size(size, n),), template.dtype)


def flatten_tree(tree: Any, n: int) -> Any:
    return jax.tree_util.tree_map(lambda x: flatten_leaf(x, n), tree)


def unflatten_tree(flat_tree: Any, template: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda f, t: unflatten_leaf(f, t), flat_tree, template)


def to_layout(tree: Any, template: Any, n: int) -> Any:
    """Convert ``tree`` leaf-by-leaf toward ``template``'s layout.

    The one rule both checkpoint directions share: a leaf whose shape
    already matches its template slot passes through (scalar counters, a
    leaf that was never flattened); anything else is flattened or
    unflattened to match.  Exact because the flat layout is a pure
    function of the canonical shape and ``n``.

    Direction cannot be read off the template's RANK alone — a canonical
    leaf may itself be 1-D and non-divisible (a size-10 bias under n=8
    flattens to (16,)), so a 1-D template only means canonical->flat when
    its length IS the leaf's own padded flat size; the flat->canonical
    case can never satisfy that (a flat leaf's padded size is itself,
    which would have hit the shape-equality passthrough).
    """
    def convert(leaf, tmpl):
        shape = tuple(getattr(leaf, "shape", ()))
        if shape == tuple(tmpl.shape):
            return leaf
        size = math.prod(shape) if shape else 1
        if (len(tmpl.shape) == 1
                and tmpl.shape[0] == padded_size(size, n)):
            out = flatten_leaf(leaf, n)          # canonical -> flat
        else:                                    # flat -> canonical
            tmpl_size = math.prod(tmpl.shape) if tmpl.shape else 1
            if len(shape) != 1 or shape[0] != padded_size(tmpl_size, n):
                raise ValueError(
                    f"zero1 layout conversion cannot map leaf {shape} onto "
                    f"template {tuple(tmpl.shape)} with {n} shards: not a "
                    f"flat-padded form of the template")
            out = unflatten_leaf(leaf, tmpl)
        if out.shape != tuple(tmpl.shape):
            raise ValueError(
                f"zero1 layout conversion produced {out.shape}, template "
                f"expects {tuple(tmpl.shape)}")
        return out
    return jax.tree_util.tree_map(convert, tree, template)


@dataclasses.dataclass(frozen=True)
class Zero1Context:
    """In-graph shard/gather helpers the train/eval steps close over.

    Built by the compile plan (the module that owns every sharding
    decision); ``None`` in the step builders means the replicated graph —
    ``--zero1 off`` traces exactly the pre-ZeRO-1 step (HLO identity
    pinned in tests/test_zero1.py).
    """

    mesh: Mesh
    num_shards: int
    param_template: Any          # tree of ShapeDtypeStruct for the params

    def _sharded(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(DATA_AXIS))

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard(self, tree: Any) -> Any:
        """Flatten a (replicated) tree and constrain each leaf to its
        ``P(data)`` shard — the scatter half of the weight-update sharding
        (free on already-replicated values: each chip just keeps a slice).
        """
        sh = self._sharded()
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                flatten_leaf(x, self.num_shards), sh), tree)

    def gather(self, flat_tree: Any, template: Any) -> Any:
        """All-gather flat shards back to the replicated, shaped tree —
        just-in-time for a forward pass (params, EMA target).

        One small all-gather PER LEAF (~leaf-count latency-bound
        collectives per tree; bucketing them is ROADMAP S8)."""
        rep = self._replicated()
        return jax.tree_util.tree_map(
            lambda f, t: unflatten_leaf(
                jax.lax.with_sharding_constraint(f, rep), t),
            flat_tree, template)
