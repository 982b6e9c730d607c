"""The compile plan: every sharding decision for every jitted entry point.

Before this module, each jit call site chose its own ``in_shardings``/
``out_shardings``/``donate_argnums`` inline (training/build.py for the
train/eval steps, training/linear_eval.py for the two feature extractors),
and the ZeRO-ish ``fsdp`` flag lived as a heuristic in partitioning.py —
three files to audit to answer "where does this array live?".  Now the
answer is declared data in ONE place:

- the :class:`CompilePlan` owns the mesh, the ``NamedSharding`` for every
  pytree the program moves (train state, batches, metrics/health outputs,
  extractor features), and the jit wiring — in/out shardings + donation —
  for every jitted entry point: the train step, the eval step, both
  linear-eval feature extractors (the bench ``--dry-compile`` path reuses
  the train step via ``setup_training``, so it is covered by
  construction), and the serving embed step (serving/engine.py AOT-lowers
  it per bucket shape);
- ZeRO-1 weight-update sharding (``--zero1 on``; parallel/zero1.py) is a
  property of the plan, not of the step code: the plan converts the state
  to the flat leaf-partitioned layout, assigns ``P(data)`` to the LARS
  momentum and EMA target leaves, hands the step builders a
  :class:`~byol_tpu.parallel.zero1.Zero1Context`, and canonicalizes state
  at the checkpoint boundary so ckpts stay mesh-size portable;
- graphlint GL107 polices the contract: a ``jax.jit(...,
  in_shardings=...)`` outside this module, or a PartitionSpec naming an
  axis the parallel/ modules never declared, is a lint failure.

``--zero1 off`` must lower the exact pre-plan graph: the plan then passes
the same partitioning.py shardings and the same donation the per-site jit
calls passed, pinned by an HLO-identity test (tests/test_zero1.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byol_tpu.observability import spans
from byol_tpu.parallel import zero1 as zero1_lib
from byol_tpu.parallel.mesh import DATA_AXIS
from byol_tpu.parallel.partitioning import _path_names, state_shardings
from byol_tpu.parallel.zero1 import ZERO1_STATE_FIELDS, Zero1Context

# donate_argnums per entry point — declared once, reported in the run
# header's ``sharding_plan`` so every run records what it donated.
DONATE = {
    "train_step": (0,),       # state is consumed: update in place in HBM
    "eval_step": (),          # state is read-only across eval batches
    "encoder_extractor": (),
    "spmd_extractor": (),
    "serve_step": (0,),       # the staged request batch is consumed: its
                              # HBM buffer is free for the embeddings
}


def _struct_of(leaf: Any) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)


@dataclasses.dataclass
class CompilePlan:
    """Mesh + shardings + jit wiring for every entry point.

    Build one via :func:`build_plan`; ``prepare_state`` must run before the
    zero1 context / checkpoint codec are used (it derives the state
    templates the conversions need).
    """

    mesh: Mesh
    zero1: bool = False
    # Templates derived by prepare_state (zero1): the canonical
    # (replicated, shaped) and flat (padded 1-D) skeletons of the converted
    # state fields, used by the in-graph gather and the checkpoint codec.
    _param_template: Any = None
    _canon_templates: Any = None     # {field: canonical template tree}
    _flat_templates: Any = None      # {field: flat template tree}
    # jitted layout conversions of the checkpoint codec, built on first use
    _codec: Any = dataclasses.field(default_factory=dict)

    # -- shardings ---------------------------------------------------------
    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def batch_sharding(self) -> NamedSharding:
        """Host batches: batch dim over the data axis (the DDP split)."""
        return NamedSharding(self.mesh, P(DATA_AXIS))

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[DATA_AXIS])

    def state_sharding(self, state: Any) -> Any:
        """NamedSharding tree for a TrainState in this plan's layout.

        Base layout comes from partitioning.py (replicated, or Megatron TP
        over ``model`` when that axis is >1); under ZeRO-1 the flat array
        leaves of ``opt_state``/``target_params`` get ``P(data)`` instead.
        """
        base = state_shardings(state, self.mesh)
        if not self.zero1:
            return base
        n = self.num_shards
        sharded = NamedSharding(self.mesh, P(DATA_AXIS))

        def spec_for(path, leaf, cur):
            names = _path_names(path)
            if (names and names[0] in ZERO1_STATE_FIELDS
                    and getattr(leaf, "ndim", 0) == 1
                    and leaf.shape[0] % n == 0):
                return sharded
            return cur

        return jax.tree_util.tree_map_with_path(spec_for, state, base)

    # -- state preparation -------------------------------------------------
    def prepare_state(self, state: Any, tx: Any) -> Tuple[Any, Any]:
        """Convert a freshly-created TrainState to this plan's layout and
        place it on the mesh; returns ``(state, state_sharding)``.

        Under ZeRO-1 this is where the layout is decided: the optimizer
        state is re-initialized on the FLAT params (so every momentum leaf
        is born 1-D padded) and the EMA target tree is flattened; the
        canonical/flat templates for the checkpoint codec are derived here
        from the same ``tx.init`` the live state uses, so codec and state
        can never disagree about which leaves are flat.
        """
        if self.zero1:
            n = self.num_shards
            params = state.params
            self._param_template = jax.tree_util.tree_map(_struct_of, params)
            flat_params_tmpl = jax.tree_util.tree_map(
                lambda t: zero1_lib.flat_struct(t, n), self._param_template)
            self._canon_templates = {
                "opt_state": jax.eval_shape(tx.init, self._param_template),
                "target_params": self._param_template,
            }
            self._flat_templates = {
                "opt_state": jax.eval_shape(tx.init, flat_params_tmpl),
                "target_params": flat_params_tmpl,
            }
            state = state.replace(
                opt_state=tx.init(zero1_lib.flatten_tree(params, n)),
                target_params=zero1_lib.flatten_tree(state.target_params, n))
            # re-break buffer aliasing: tx.init on the flat params may store
            # the very flat arrays it was passed (scale_by_lbfgs), and the
            # train step donates the state (training/state._dedupe_buffers)
            from byol_tpu.training.state import _dedupe_buffers
            state = _dedupe_buffers(state)
        sharding = self.state_sharding(state)
        state = jax.device_put(state, sharding)
        return state, sharding

    def _require_prepared(self, what: str) -> None:
        if self._param_template is None:
            raise ValueError(
                f"{what} before prepare_state(): the plan has not derived "
                "its state templates yet")

    def zero1_context(self) -> Optional[Zero1Context]:
        """The in-graph shard/gather helper for the step builders; ``None``
        when the plan is replicated (the step then traces the pre-ZeRO-1
        graph unchanged)."""
        if not self.zero1:
            return None
        self._require_prepared("zero1_context()")
        return Zero1Context(mesh=self.mesh, num_shards=self.num_shards,
                            param_template=self._param_template)

    # -- jit wiring: the six entry points ----------------------------------
    def jit_train_step(self, fn: Callable, state_sharding: Any):
        """(state, batch) -> (state, metrics): state in plan layout (donated),
        batch over ``data``, metrics (incl. the telemetry health vector)
        replicated."""
        return jax.jit(
            fn,
            in_shardings=(state_sharding, self.batch_sharding),
            out_shardings=(state_sharding, self.replicated),
            donate_argnums=DONATE["train_step"])

    def jit_eval_step(self, fn: Callable, state_sharding: Any):
        """(state, batch) -> metrics: state read-only, metrics replicated."""
        return jax.jit(
            fn,
            in_shardings=(state_sharding, self.batch_sharding),
            out_shardings=self.replicated)

    def jit_spmd_extractor(self, fn: Callable):
        """(x, y, mask) -> (features, y, mask), all REPLICATED out — the
        replicated out_shardings IS the cross-host all-gather of the
        multi-host linear-eval extraction (linear_eval.py)."""
        rep = self.replicated
        return jax.jit(fn, out_shardings=(rep, rep, rep))

    def jit_serve_step(self, fn: Callable):
        """The serving hot path (serving/engine.py): ``x -> embeddings``.

        The staged request batch is sharded over ``data`` (every chip
        encodes its slice of the coalesced batch), embeddings come back
        REPLICATED — the out_shardings is the gather the host reads one
        contiguous fp32 array from.  The input buffer is donated: a
        serving process runs this step forever, and the request staging
        buffer's HBM is dead the moment the forward has consumed it.

        Returns the UNCOMPILED jit wrapper; the serving engine AOT-lowers
        and compiles it once per bucket shape at startup/first-touch
        (``.lower(struct).compile()``), so the steady-state dispatch path
        can never trigger a trace or compile (the GL102 hazard, enforced
        at runtime by the engine's compile counter).
        """
        return jax.jit(
            fn,
            in_shardings=(self.batch_sharding,),
            out_shardings=self.replicated,
            donate_argnums=DONATE["serve_step"])

    # -- checkpoint codec --------------------------------------------------
    def _convert(self, state: Any, templates: Any, n: int) -> Any:
        fields = {
            f: zero1_lib.to_layout(getattr(state, f), templates[f], n)
            for f in ZERO1_STATE_FIELDS}
        return state.replace(**fields)

    def to_canonical(self, state: Any) -> Any:
        """Plan layout -> the mesh-size-portable checkpoint layout
        (unflattened, replicated).  Identity when the plan is replicated,
        so ``--zero1 off`` checkpoints exactly as before — and a ckpt
        written either way restores under either flag and any device
        count."""
        if not self.zero1:
            return state
        self._require_prepared("to_canonical()")
        return self._run_codec(
            "to_canonical", state,
            lambda s: self._convert(s, self._canon_templates,
                                    self.num_shards),
            lambda out: jax.tree_util.tree_map(lambda _: self.replicated,
                                               out))

    def _run_codec(self, name: str, state: Any, convert: Callable,
                   sharding_of: Callable) -> Any:
        """Run one whole-state layout conversion as ONE jitted program
        with the target layout as its out_shardings — not an eager
        multi-device slice/reshape per leaf followed by a device_put.
        Hundreds of back-to-back eager 8-device dispatches are what the
        CPU runtime aborted in under a loaded test run (PR 22), and on a
        chip they are hundreds of launches per checkpoint.  The jitted
        callable is kept: every later save or restore is a cache hit."""
        fn = self._codec.get(name)
        if fn is None:
            fn = jax.jit(convert, out_shardings=sharding_of(
                jax.eval_shape(convert, state)))
            self._codec[name] = fn
        return fn(state)

    def from_canonical(self, state: Any) -> Any:
        """Canonical (restored) layout -> plan layout, placed on the mesh."""
        if not self.zero1:
            return state
        self._require_prepared("from_canonical()")
        return self._run_codec(
            "from_canonical", state,
            lambda s: self._convert(s, self._flat_templates,
                                    self.num_shards),
            self.state_sharding)

    def canonical_template(self, state: Any) -> Any:
        """Abstract canonical-state skeleton for checkpoint restore: shapes
        from the canonical templates, everything placed replicated.  Pure
        metadata — the stored templates already carry the canonical shapes,
        so no concrete flat->canonical conversion of the live state runs."""
        if not self.zero1:
            return state
        self._require_prepared("canonical_template()")
        rep = self.replicated

        def abstract(leaf):
            return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype,
                                        sharding=rep)
        canon = state.replace(
            **{f: self._canon_templates[f] for f in ZERO1_STATE_FIELDS})
        return jax.tree_util.tree_map(abstract, canon)

    # -- provenance --------------------------------------------------------
    def describe(self) -> dict:
        """The ``sharding_plan`` record every run log header carries
        (observability/events.py validates the shape): which mesh, which
        axes, whether the weight update is sharded, what each entry point
        donates — enough to know which plan produced a given run."""
        return {
            "mesh_shape": {str(k): int(v)
                           for k, v in self.mesh.shape.items()},
            "axis_names": [str(a) for a in self.mesh.axis_names],
            "zero1": "on" if self.zero1 else "off",
            "donate_argnums": {k: list(v) for k, v in DONATE.items()},
        }


@spans.spanned("startup/plan")
def build_plan(mesh: Mesh, *, zero1: bool = False) -> CompilePlan:
    """The one constructor: cfg.device.zero1 == 'on' -> a ZeRO-1 plan.

    ZeRO-1 shards over the ``data`` axis only; combining it with tensor
    parallelism would need TP-aware flat layouts (the opt-state leaves of
    a TP-sharded kernel live sharded over ``model`` already) — rejected at
    config resolve(), re-checked here for programmatic callers.
    """
    if zero1 and mesh.shape.get("model", 1) > 1:
        raise ValueError(
            "zero1='on' is data-parallel weight-update sharding; it does "
            "not compose with model_parallel > 1 (the TP rules in "
            "partitioning.py already shard those opt-state leaves)")
    return CompilePlan(mesh=mesh, zero1=zero1)


def jit_encoder_extractor(fn: Callable):
    """The single-host frozen-encoder extractor (linear_eval.py): default
    device placement, no explicit shardings — declared here so every jit
    entry point's placement decision lives in this module, even the trivial
    one."""
    return jax.jit(fn)
