"""Jitted BYOL train / eval steps.

TPU-first redesign of the reference hot path (``execute_graph``,
main.py:559-692 + ``BYOL.forward``, main.py:242-276):

- The target branch is the same ``apply`` with the EMA pytree — no parameter
  vector swaps (SURVEY.md §3.2 flags 6 full-parameter copies per step in the
  reference) and no wasted autodiff graph (targets are computed outside the
  differentiated function, not built-then-detached).
- Under GSPMD jit with the batch dim sharded over the ``data`` mesh axis,
  every mean over the batch is a GLOBAL mean: gradient reduction (DDP's NCCL
  allreduce, main.py:440-443) and SyncBN statistics (main.py:433) fall out of
  partitioning — XLA inserts the ICI collectives.
- ``fuse_views=True`` concatenates the two views into one encoder call
  (2 forwards instead of 4, better MXU utilization).  This makes BN batch
  statistics span both views, unlike the reference's per-view forwards
  (main.py:244-247), so it is a perf opt-in.

Semantics deltas from the reference, both deliberate and documented:
- BN running stats are updated by the ONLINE forwards only; the reference
  also mutates them during target forwards because buffers are not swapped
  (main.py:214-227 swaps parameters only).  Affects eval-time stats slightly.
- EMA update timing: reference updates the EMA with PRE-update params inside
  forward (main.py:255, before optimizer.step()); the paper (and default
  here) EMAs the POST-update params.  ``ema_update_mode='reference_pre'``
  reproduces the reference.

Microbatched gradient accumulation (``accum_steps > 1``): the effective
batch is split into ``accum_steps`` microbatches INSIDE the jitted step and
scanned (``lax.scan``), with ``jax.grad`` applied per microbatch — so the
backward residuals of only ONE microbatch are ever live, which is what
breaks the HBM spill wall (RESULTS.md §1: bs512 spills, bs1024 OOMs).
Gradients and loss metrics are mean-accumulated with equal microbatch
weights (exactly the big-batch mean), then ONE optimizer update + ONE EMA
tick runs — counters, LR schedule, and EMA tau all see optimizer steps.
Semantics match a single batch-(k*m) step up to BN-statistics granularity,
controlled by ``accum_bn_mode``:

- ``average`` (default): per-microbatch normalization; one running-stat tick
  per optimizer step using the microbatch-averaged batch statistics.
- ``microbatch``: per-microbatch normalization; k sequential running-stat
  ticks (the semantics of k small steps between updates).
- ``global``: EXACT big-batch semantics — microbatches run under a vmapped
  named axis (``ACCUM_AXIS``) and every BatchNorm syncs its statistics
  across it, so normalization, gradients (AD through the psum), and the
  single running-stat tick reproduce the monolithic step to fp tolerance.
  No memory savings (all microbatches in flight): a semantics oracle.

The microbatch partition is STRIDED (microbatch i takes rows i, i+k, ...),
which keeps the reshape device-local under the GSPMD batch sharding — no
resharding collectives.  Batch order is i.i.d. so the partition choice is
semantically free.

Step-fused augmentation (``augment_in_step``, the ``--augment-placement
step`` mode): the batch is ``{'images': (B,H,W,C) uint8, 'label': (B,)}``
— raw pixels, ~8x fewer H2D bytes than two float32 views — and the two-view
augmentation (data/device_augment.py, the SAME program the loader-placement
device backend dispatches) runs per microbatch INSIDE the accumulation
scan: only one microbatch of float32 views is ever live in HBM, and the
augment fuses with the forward instead of costing a separate dispatch.
Per-microbatch PRNG keys derive from ``state.step`` (:func:`augment_keys`),
so every optimizer step sees fresh, reproducible randomness with no key
reuse across microbatches.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental.xla_metadata import set_xla_metadata

from byol_tpu.core import rng as rng_lib
from byol_tpu.core.precision import Policy, FP32
from byol_tpu.data import device_augment
from byol_tpu.models.decoder_trunk import (DIFFERENTIAL,
                                           DIFFERENTIAL_FIELDS, LAYER_LOSS,
                                           ROUTING, ROUTING_FIELDS, SELECTION,
                                           SELECTION_FIELDS, STATE_SPACE,
                                           STATE_SPACE_FIELDS)
from byol_tpu.objectives.byol_loss import loss_function
from byol_tpu.objectives.metrics import cross_entropy, topk_accuracy
from byol_tpu.observability import health as health_lib
from byol_tpu.optim import lars as lars_lib
from byol_tpu.optim.schedules import cosine_ema_decay
from byol_tpu.training.state import TrainState


# Named axis microbatches are vmapped over in accum_bn_mode='global'; BN
# modules receive it as bn_axis_name (build.py) and pmean their statistics
# across it.
ACCUM_AXIS = "accum"

# The phases of the train step, as ``jax.named_scope`` names in every HLO
# instruction's ``op_name`` (``jit(train_step)/target_forward/ResNet/...``;
# the backward of ``online_forward`` / ``loss`` reads
# ``transpose(jvp(online_forward))/...``).  Metadata only: nothing runs, no
# flag.  The device trace is split by these names (PERF.md section 3); the
# benchmark keeps its own copy of them.
PHASE_SCOPES = ("augment", "target_forward", "online_forward", "loss",
                "update")


def _phase(name: str):
    if name not in PHASE_SCOPES:
        raise ValueError(f"{name!r} is not one of {PHASE_SCOPES}")
    return jax.named_scope(name)

# ImageNet channel statistics (torchvision convention) behind the
# ``normalize_inputs`` parity switch (Quirk Q3: the reference feeds raw
# [0,1] pixels; the BYOL paper standardizes its inputs).
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def normalize_images(x: jnp.ndarray) -> jnp.ndarray:
    """Standardize NHWC [0,1] pixels with the ImageNet mean/std.

    Non-RGB inputs (grayscale tasks) use the channel-averaged statistics so
    the switch stays usable on every task the loader serves.
    """
    mean = jnp.asarray(IMAGENET_MEAN, x.dtype)
    std = jnp.asarray(IMAGENET_STD, x.dtype)
    if x.shape[-1] != len(IMAGENET_MEAN):
        mean, std = jnp.mean(mean), jnp.mean(std)
    return (x - mean) / std


@dataclasses.dataclass(frozen=True)
class StepConfig:
    total_train_steps: int
    base_decay: float = 0.996            # --base-decay (main.py:65-66)
    norm_mode: str = "paper"             # Quirk Q2 switch
    fuse_views: bool = False
    polyak_ema: float = 0.0
    ema_update_mode: str = "post"        # 'post' | 'reference_pre'
    accum_steps: int = 1                 # microbatches per optimizer step
    accum_bn_mode: str = "average"       # 'average'|'microbatch'|'global'
    normalize_inputs: bool = False       # Quirk Q3: ImageNet mean/std
                                         # standardization inside the step
    augment_in_step: bool = False        # --augment-placement step: batch is
                                         # raw uint8; two-view augmentation
                                         # runs inside the accumulation scan
    fused_augment: bool = False          # --fused-augment on: the in-step
                                         # two-view augmentation runs as the
                                         # Pallas kernel (ops/fused_augment
                                         # .py) — uint8 convert + crop +
                                         # flip + jitter + grayscale in one
                                         # VMEM round trip per image, blur
                                         # as an MXU conv on its output;
                                         # randomness still drawn from the
                                         # augment_keys stream outside the
                                         # kernel.  False traces the exact
                                         # unfused graph (HLO identity
                                         # pinned by tests/
                                         # test_fused_augment.py)
    image_size: int = 0                  # augment target size (= model input
                                         # H); required when augment_in_step
    color_jitter_strength: float = 1.0   # augment strength (step placement)
    aug_seed: int = 0                    # base seed of the in-step key stream
    telemetry: str = "off"               # --telemetry off|epoch|step: when
                                         # not 'off', the train step packs
                                         # the in-graph health vector
                                         # (observability/health.py) into
                                         # metrics['health'].  'off' traces
                                         # the exact pre-telemetry graph
                                         # (pinned by an HLO-identity test).
    weight_decay: float = 0.0            # telemetry only: LARS folds wd
                                         # into the gradient BEFORE the
                                         # trust ratio (optim/lars.py step
                                         # 1), so the health vector's trust
                                         # stats must see g + wd*p too or
                                         # they drift from what was applied
    lars_in_chain: bool = True           # telemetry only: the optimizer
                                         # chain contains the LARS wrapper
                                         # (build.py: 'lars_' prefix).
                                         # False packs identity (1.0) trust
                                         # stats — no transform applied a
                                         # ratio, and reporting a computed
                                         # one as "applied" would be
                                         # fiction (LAMB's internal ratio
                                         # is not surfaced here)


# The collections a backbone's layers may sow into during a training forward.
SOWN = (ROUTING, SELECTION, LAYER_LOSS, STATE_SPACE, DIFFERENTIAL)
# ... and those whose last field counts the layers (and views) that wrote:
# the sum over them becomes their mean, under these prefixes
MEANS_OVER_LAYERS = (("_ssm_", STATE_SPACE, STATE_SPACE_FIELDS),
                     ("_diff_", DIFFERENTIAL, DIFFERENTIAL_FIELDS))


def _forward_views(net, params, batch_stats, aug1, aug2, *, train: bool,
                   fuse: bool, update_stats: bool):
    """Run both views through encoder+projector+predictor.

    Returns (out1, out2, new_batch_stats, sown); each out is the dict
    from ``BYOLNet.__call__`` (representation/projection/prediction);
    ``sown`` holds, for each collection of ``SOWN`` a layer of the backbone
    wrote to (models/decoder_trunk.py), the sum over layers and views of
    what it wrote: the routing counters (``ROUTING_FIELDS``), the
    key-selection counters (``SELECTION_FIELDS``), a selective scan's step
    sizes and differential attention's lambda (``STATE_SPACE_FIELDS``,
    ``DIFFERENTIAL_FIELDS``: each with the count of what was summed), and
    ``LAYER_LOSS``, the scalar losses layers add to the step's — each a
    mean over the rows of its forward, so two unfused views' are averaged.
    A backbone that sows nothing leaves it empty.
    """
    variables = {"params": params, "batch_stats": batch_stats}
    # flax BatchNorm writes running stats whenever train=True, so the
    # collection must be mutable even for the target forward; updates are
    # simply discarded when update_stats=False.
    mutable = ["batch_stats", *SOWN] if train else False

    def apply(v, x):
        if mutable:
            out, upd = net.apply(v, x, train=train, mutable=mutable)
            new_bs = upd["batch_stats"] if update_stats else v["batch_stats"]
            leaves = {name: jax.tree_util.tree_leaves(upd.get(name, {}))
                      for name in SOWN}
            return out, new_bs, {name: sum(found)
                                 for name, found in leaves.items() if found}
        out = net.apply(v, x, train=train, mutable=False)
        return out, v["batch_stats"], {}

    if fuse:
        n = aug1.shape[0]
        out, bs, sown = apply(variables,
                              jnp.concatenate([aug1, aug2], axis=0))
        out1 = jax.tree_util.tree_map(lambda x: x[:n], out)
        out2 = jax.tree_util.tree_map(lambda x: x[n:], out)
        return out1, out2, bs, sown
    out1, bs, sown = apply(variables, aug1)
    out2, bs, sown2 = apply({"params": params, "batch_stats": bs}, aug2)
    sown = {name: sown[name] + sown2[name] for name in sown}
    if LAYER_LOSS in sown:
        sown[LAYER_LOSS] = sown[LAYER_LOSS] / 2
    return out1, out2, bs, sown


def _microbatch_split(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """``(B, ...) -> (k, B//k, ...)``: microbatch i takes rows i, i+k, ...

    The strided partition is deliberate: reshaping ``(B,)`` to ``(B//k, k)``
    splits the GSPMD-sharded batch dim with the sharded factor MAJOR, so
    each device reshapes/transposes only its own contiguous shard — no
    cross-device resharding, unlike the contiguous ``(k, B//k)`` reshape
    (whose microbatches would straddle device boundaries).  Which rows land
    in which microbatch is semantically free (i.i.d. batch).
    """
    b = x.shape[0]
    if b % k:
        raise ValueError(f"batch {b} not divisible by accum_steps {k}")
    x = x.reshape((b // k, k) + x.shape[1:])
    return jnp.swapaxes(x, 0, 1)


def augment_keys(seed: int, step, k: int) -> jnp.ndarray:
    """(k, ...) per-microbatch augmentation keys for optimizer step ``step``.

    Fresh per step (fold_in on the traced counter), decorrelated across
    microbatches (fold_in on the microbatch index).  Module-level on purpose:
    tests and tools reproduce the in-step view stream exactly by feeding
    these keys to ``device_augment.two_view_batch`` on the strided
    microbatch partition (:func:`_microbatch_split`).
    """
    step_key = rng_lib.for_step(rng_lib.root_key(seed), step)
    return jax.vmap(lambda i: rng_lib.for_step(step_key, i))(
        jnp.arange(k, dtype=jnp.uint32))


def apply_update(state: TrainState, grads, new_bs, metrics, *,
                 tx: optax.GradientTransformation, scfg: StepConfig,
                 zero1_ctx=None, layer_scopes: Tuple[str, ...] = ()
                 ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    """Everything of the step after the gradients exist — the optimizer
    chain, the EMA tick, the telemetry vector, the new state — traced by
    :func:`make_train_step` under the ``update`` scope.

    One path for both layouts.  Replicated (``zero1_ctx`` None) the update
    runs on the shaped trees.  Under ZeRO-1 (arXiv 2004.13336)
    ``state.opt_state`` and ``state.target_params`` arrive flat, leaf-
    partitioned over the data axis: the reduced gradient and the params
    scatter to their flat 1/N shards (free: both are replicated, each chip
    keeps a slice), the optax chain runs shard-local — LARS norms are
    unchanged by the zero padding — the EMA ticks on the shards (it is
    elementwise; the target STAYS sharded and is re-gathered at the top of
    the next step), and ONE all-gather rebuilds the fresh params for the
    next forward.
    """
    if zero1_ctx is None:
        to_update_layout = from_update_layout = lambda tree: tree
    else:
        to_update_layout = zero1_ctx.shard
        from_update_layout = functools.partial(
            zero1_ctx.gather, template=zero1_ctx.param_template)
    old_params = to_update_layout(state.params)
    updates, new_opt_state = tx.update(to_update_layout(grads),
                                       state.opt_state, old_params)
    fresh_params = optax.apply_updates(old_params, updates)
    new_params = from_update_layout(fresh_params)

    # Cosine-annealed EMA of the full tree (main.py:156-162,255).
    tau = cosine_ema_decay(state.ema_step, scfg.total_train_steps,
                           scfg.base_decay)
    ema_src = (old_params if scfg.ema_update_mode == "reference_pre"
               else fresh_params)
    new_target = jax.tree_util.tree_map(
        lambda t, p: tau * t + (1.0 - tau) * p,
        state.target_params, ema_src)

    new_polyak = state.polyak_params
    if scfg.polyak_ema > 0.0 and state.polyak_params is not None:
        d = scfg.polyak_ema
        new_polyak = jax.tree_util.tree_map(
            lambda m, p: d * m + (1.0 - d) * p,
            state.polyak_params, new_params)

    if scfg.telemetry != "off":
        # Pack the step's health diagnostics (observability/health.py)
        # into ONE fp32 vector under metrics['health'] — a step OUTPUT
        # (replicated out_sharding like every metric), read back
        # asynchronously by the TelemetrySink with >= interval-step
        # lag, so telemetry adds reductions to the graph but zero host
        # syncs to the dispatch loop.  Trust ratios use the PRE-update
        # params — what the LARS transform saw this step.
        metrics = dict(metrics)
        collapse = (metrics.pop("_collapse_feature_std"),
                    metrics.pop("_collapse_cosine_mean"))
        # The ratio LARS APPLIES is computed on the post-wd gradient:
        # run the SAME fold-in transform the optimizer chain runs
        # (lars_weight_decay — shared code, so the reported spread
        # can never drift from the applied one).  Non-LARS chains
        # applied no ratio: pack identity rather than a fictitious
        # "applied" value.  Residual caveat: --clip > 0 clips before
        # LARS and is not replicated (value clipping is off in every
        # recipe this telemetry targets).
        if scfg.lars_in_chain:
            wd_tx = lars_lib.lars_weight_decay(scfg.weight_decay)
            trust_grads, _ = wd_tx.update(
                grads, wd_tx.init(state.params), state.params)
            trust = lars_lib.trust_ratio_vector(trust_grads, state.params)
        else:
            trust = jnp.ones((1,), jnp.float32)
        # The drift subtraction needs the params in the target's layout
        # (flat shards under ZeRO-1); zero padding contributes nothing to
        # any norm, so every reported value equals the replicated step's.
        metrics["health"] = health_lib.health_stats(
            grads=grads, updates=updates, params=fresh_params,
            target_params=new_target, loss=metrics["loss_mean"],
            collapse=collapse, trust_ratios=trust,
            routing={key[1:]: value for key, value in metrics.items()
                     if key[1:] in health_lib.OPTIONAL_FIELDS})

    # One real attribute on one scalar add.  The persistent compilation
    # cache keys a program with its debug info stripped, scope names
    # included, so a step whose scopes alone were renamed would be
    # served the executable cached before the rename, stale names and
    # all — and the device trace is read by those names.
    with set_xla_metadata(
            phase_scopes=" ".join(PHASE_SCOPES + tuple(layer_scopes))):
        next_step = state.step + 1
    new_state = state.replace(
        step=next_step,
        params=new_params,
        batch_stats=new_bs,
        target_params=new_target,
        ema_step=state.ema_step + 1,
        opt_state=new_opt_state,
        polyak_params=new_polyak,
    )
    return new_state, metrics


def make_train_step(net, tx: optax.GradientTransformation, scfg: StepConfig,
                    policy: Policy = FP32, zero1_ctx=None,
                    lr_schedule=None, mesh=None
                    ) -> Callable[[TrainState, Dict[str, jnp.ndarray]],
                                  Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Build the jittable train step: (state, batch) -> (state, metrics).

    ``batch`` = {'view1': (B,H,W,C), 'view2': (B,H,W,C), 'label': (B,)},
    pixels in [0,1] (the reference input contract, main.py:486-490).
    B is the EFFECTIVE batch; with ``accum_steps`` k > 1 it is split into k
    microbatches inside the step (module docstring).

    ``zero1_ctx`` (parallel.zero1.Zero1Context, from the compile plan):
    ZeRO-1 weight-update sharding.  When set, ``state.target_params`` and
    ``state.opt_state`` arrive FLAT leaf-partitioned over the data axis:
    the step all-gathers the EMA target just-in-time for the target
    forward, scatters the reduced gradients + params to their flat shards,
    runs the whole optax chain shard-local, all-gathers only the fresh
    params for the next forward, and ticks the EMA on its shard (the tick
    is elementwise, arXiv 2307.13813 — it never needs the full tree).
    ``None`` traces the replicated graph unchanged (``--zero1 off`` HLO
    identity, tests/test_zero1.py).

    ``scfg.fused_augment`` swaps the in-step two-view augmentation
    (``augment_in_step``) for the fused Pallas kernel
    (ops/fused_augment.py) inside the same accumulation scan — identical
    ``augment_keys`` stream, views matching ``device_augment.two_view``
    to fp32 tolerance, shard-local over ``mesh``'s data axis when it
    spans several devices.  False traces the unfused augmentation graph
    byte-identically.

    ``lr_schedule`` is accepted and unused: the schedule lives in ``tx``.
    ``benchmarks/rehearse_v5e*.py`` still pass it; it goes once they stop.
    """
    del lr_schedule
    if scfg.accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {scfg.accum_steps}")
    if scfg.accum_bn_mode not in ("average", "microbatch", "global"):
        raise ValueError(
            f"unknown accum_bn_mode {scfg.accum_bn_mode!r}; "
            "'average' | 'microbatch' | 'global'")
    if scfg.augment_in_step and scfg.image_size <= 0:
        raise ValueError(
            "augment_in_step requires image_size > 0 (the augment target "
            f"size), got {scfg.image_size}")
    if scfg.telemetry not in ("off", "epoch", "step"):
        raise ValueError(
            f"unknown telemetry mode {scfg.telemetry!r}; "
            "'off' | 'epoch' | 'step'")
    if scfg.fused_augment:
        # config resolve() rejects these at the CLI; re-checked for
        # programmatic callers handing a StepConfig straight to the builder
        if not scfg.augment_in_step:
            raise ValueError(
                "fused_augment=True requires augment_in_step=True: the "
                "kernel fuses the IN-STEP augmentation path (raw uint8 "
                "batches); loader placement has no in-step chain to fuse")
        if scfg.accum_bn_mode == "global" and scfg.accum_steps > 1:
            raise ValueError(
                "fused_augment=True with accum_bn_mode='global': the "
                "global oracle vmaps microbatches, and a pallas_call/"
                "shard_map cannot run under that vmap — use 'average' or "
                "'microbatch'")

    # A backbone that names scopes of its own inside the phases (the decoder
    # trunk's ``mla``, ``moe/...``, ``mhc``) has them stamped beside the
    # phases' (apply_update); one that names none keeps the stamp, and its
    # program, as it was.
    layer_scopes = tuple(getattr(getattr(net, "backbone", None),
                                 "trace_scopes", ()))

    def micro_grads(params, target_params, batch_stats, view1, view2,
                    labels):
        """Gradients + new BN stats + metrics for ONE microbatch (= the
        whole batch when accumulation is off).  The dtype cast happens here
        so accumulation never materializes a full-effective-batch bf16 copy
        — only the live microbatch is cast."""
        aug1 = policy.cast_to_compute(view1)
        aug2 = policy.cast_to_compute(view2)
        if scfg.normalize_inputs:
            aug1, aug2 = normalize_images(aug1), normalize_images(aug2)

        # Target branch: outside the differentiated function — autodiff never
        # sees it (vs reference building + detaching the graph, Quirk Q10).
        with _phase("target_forward"):
            tgt1, tgt2, _, _ = _forward_views(
                net, target_params, batch_stats, aug1, aug2,
                train=True, fuse=scfg.fuse_views, update_stats=False)
        target_proj1 = jax.lax.stop_gradient(tgt1["projection"])
        target_proj2 = jax.lax.stop_gradient(tgt2["projection"])

        def loss_fn(params):
            with _phase("online_forward"):
                on1, on2, new_bs, sown = _forward_views(
                    net, params, batch_stats, aug1, aug2,
                    train=True, fuse=scfg.fuse_views, update_stats=True)
            with _phase("loss"):
                byol_loss = loss_function(
                    on1["prediction"], on2["prediction"],
                    target_proj1, target_proj2, norm_mode=scfg.norm_mode)
                # Probe on stop-grad features of both views; labels doubled
                # in train mode (main.py:249-252,596-597, Quirk Q11).
                reprs = jnp.concatenate(
                    [on1["representation"], on2["representation"]], axis=0)
                logits = net.apply({"params": params}, reprs,
                                   method="classify")
                cls_labels = jnp.concatenate([labels, labels], axis=0)
                cls_loss = cross_entropy(logits, cls_labels)
                total = byol_loss + cls_loss
                if LAYER_LOSS in sown:
                    # what the backbone's layers add of their own (each
                    # with its own stop-gradients: models/decoder_trunk.py)
                    total = total + sown[LAYER_LOSS]
                top1, top5 = topk_accuracy(logits, cls_labels)
            metrics = {"loss_mean": total,
                       "byol_loss_mean": byol_loss,
                       "linear_loss_mean": cls_loss,
                       "top1_mean": top1,
                       "top5_mean": top5}
            if LAYER_LOSS in sown:
                metrics["layer_loss_mean"] = sown[LAYER_LOSS]
            # the online forward's routing and key-selection counters, as
            # scalars like every metric (the underscore keeps them off the
            # plots)
            for prefix, collection, fields in (
                    ("_moe_", ROUTING, ROUTING_FIELDS),
                    ("_sel_", SELECTION, SELECTION_FIELDS)):
                if collection in sown:
                    metrics.update({prefix + name: sown[collection][i]
                                    for i, name in enumerate(fields)})
            for prefix, collection, fields in MEANS_OVER_LAYERS:
                if collection in sown:
                    metrics.update({
                        prefix + name: sown[collection][i]
                        / sown[collection][-1]
                        for i, name in enumerate(fields[:-1])})
            return total, (new_bs, metrics)

        grads, (new_bs, metrics) = jax.grad(
            loss_fn, has_aux=True)(params)
        if scfg.telemetry != "off":
            # Collapse signature of the STOP-GRAD target projections,
            # computed here (not after the update) because accumulation
            # keeps only ONE microbatch's projections live — the per-
            # microbatch scalars mean-accumulate through the scan like
            # every other metric, and train_step pops them into the
            # packed health vector.  The leading underscore keeps them
            # out of the grapher's *_mean plotting filter by contract.
            fstd, cosm = health_lib.collapse_stats(
                jnp.concatenate([target_proj1, target_proj2], axis=0))
            metrics = dict(metrics, _collapse_feature_std=fstd,
                           _collapse_cosine_mean=cosm)
        return policy.cast_to_param(grads), new_bs, metrics

    def micro_views(xs):
        """One microbatch's (view1, view2, labels) from the scan/vmap
        element: materialized views under loader placement, or raw uint8
        pixels augmented HERE — inside the accumulation scan, so only this
        microbatch's float32 views are ever live — under step placement."""
        if scfg.augment_in_step:
            with _phase("augment"):
                if scfg.fused_augment:
                    # Fused augmentation kernel (ops/fused_augment.py): the
                    # SAME keys and augmentation distribution, but the per-
                    # view op chain collapses into one Pallas pass per image
                    # (uint8 convert + crop + flip + jitter + grayscale) with
                    # the blur conv on its output — shard-local over the data
                    # axis on a multi-device mesh (GSPMD cannot partition a
                    # pallas_call).
                    from byol_tpu.ops import fused_augment as fused_aug_lib
                    v1, v2 = fused_aug_lib.fused_two_view(
                        xs["key"], xs["images"], scfg.image_size,
                        strength=scfg.color_jitter_strength, mesh=mesh)
                else:
                    v1, v2 = device_augment.two_view(
                        xs["key"], xs["images"], scfg.image_size,
                        strength=scfg.color_jitter_strength)
            return v1, v2, xs["label"]
        return xs["view1"], xs["view2"], xs["label"]

    def micro_step(state: TrainState, bs_in, xs):
        v1, v2, lbl = micro_views(xs)
        return micro_grads(state.params, state.target_params, bs_in,
                           v1, v2, lbl)

    def accumulate_scan(state: TrainState, xs):
        """'average' / 'microbatch' modes: lax.scan over microbatches with
        jax.grad INSIDE the body, so only one microbatch's backward
        residuals are live at a time (the HBM win).  ``xs`` is the stacked
        (leading dim k) per-microbatch input pytree (micro_views)."""
        k = scfg.accum_steps
        sequential_bn = scfg.accum_bn_mode == "microbatch"
        # Abstract eval gives the carry structure without running anything.
        xs0 = jax.tree_util.tree_map(lambda a: a[0], xs)
        g_shape, bs_shape, m_shape = jax.eval_shape(
            micro_step, state, state.batch_stats, xs0)
        zeros = lambda shapes: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        def body(carry, x):
            grad_sum, bs_acc, metric_sum = carry
            # 'microbatch': thread running stats through the scan (k ticks);
            # 'average': every microbatch ticks from the step's input stats,
            # and the tick results are averaged afterwards (one effective
            # tick with microbatch-averaged batch statistics).
            bs_in = bs_acc if sequential_bn else state.batch_stats
            g, new_bs, m = micro_step(state, bs_in, x)
            add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
            # the running sums are the first stage of the update: one
            # sweep over the gradients per microbatch
            with _phase("update"):
                grad_sum = add(grad_sum, g)
                bs_acc = new_bs if sequential_bn else add(bs_acc, new_bs)
                metric_sum = add(metric_sum, m)
            return (grad_sum, bs_acc, metric_sum), None

        init = (zeros(g_shape),
                state.batch_stats if sequential_bn else zeros(bs_shape),
                zeros(m_shape))
        (grad_sum, bs_acc, metric_sum), _ = jax.lax.scan(body, init, xs)
        mean = lambda t: jax.tree_util.tree_map(
            lambda x: (x / k).astype(x.dtype), t)
        # Equal-size microbatches: the mean over microbatch means IS the
        # effective-batch mean, for gradients and metrics alike.
        with _phase("update"):
            new_bs = bs_acc if sequential_bn else mean(bs_acc)
            return mean(grad_sum), new_bs, mean(metric_sum)

    def accumulate_global(state: TrainState, xs):
        """'global' mode: vmap over microbatches with ACCUM_AXIS bound, so
        every BatchNorm pmeans its statistics across the whole effective
        batch and AD through the psum recovers the exact big-batch gradient
        (mean over instances).  All microbatches are in flight — exact
        semantics, no memory savings."""
        grads_k, bs_k, metrics_k = jax.vmap(
            lambda x: micro_step(state, state.batch_stats, x),
            axis_name=ACCUM_AXIS)(xs)
        mean0 = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.mean(x, axis=0).astype(x.dtype), t)
        # Statistics are synced across the axis, so every instance computed
        # the identical running-stat tick: take instance 0.
        new_bs = jax.tree_util.tree_map(lambda x: x[0], bs_k)
        return mean0(grads_k), new_bs, mean0(metrics_k)

    def train_step(state: TrainState, batch):
        labels = batch["label"]
        k = scfg.accum_steps
        if zero1_ctx is not None:
            # ZeRO-1: the EMA target arrives flat-sharded; gather it
            # just-in-time for the target forwards.  The microbatch paths
            # read the target off the state they are handed, so hand them
            # a view with the gathered tree in place.  Scoped as
            # ``update``: it is the update's layout cost, paid early.
            with _phase("update"):
                micro_state = state.replace(
                    target_params=zero1_ctx.gather(
                        state.target_params, zero1_ctx.param_template))
        else:
            micro_state = state
        if scfg.augment_in_step:
            keys = augment_keys(scfg.aug_seed, state.step, k)
            parts = {"images": batch["images"], "label": labels}
        else:
            parts = {"view1": batch["view1"], "view2": batch["view2"],
                     "label": labels}
        if k == 1:
            if scfg.augment_in_step:
                parts["key"] = keys[0]
            grads, new_bs, metrics = micro_step(micro_state,
                                                state.batch_stats, parts)
        else:
            xs = {name: _microbatch_split(v, k)
                  for name, v in parts.items()}
            if scfg.augment_in_step:
                xs["key"] = keys
            accumulate = (accumulate_global
                          if scfg.accum_bn_mode == "global"
                          else accumulate_scan)
            grads, new_bs, metrics = accumulate(micro_state, xs)
        with _phase("update"):
            return apply_update(state, grads, new_bs, metrics, tx=tx,
                                scfg=scfg, zero1_ctx=zero1_ctx,
                                layer_scopes=layer_scopes)

    return train_step


def make_eval_step(net, scfg: StepConfig, policy: Policy = FP32,
                   zero1_ctx=None):
    """Eval step per reference semantics (main.py:574-606, §3.3): full BYOL
    loss computed in eval too; probe sees only view-1 representations with
    un-doubled labels (main.py:250-251); EMA frozen; BN uses running stats;
    Polyak params used for prediction when enabled (main.py:585-587).

    ``zero1_ctx``: as in :func:`make_train_step` — the flat-sharded EMA
    target is all-gathered just-in-time for the target forward."""

    def eval_step(state: TrainState, batch):
        aug1 = policy.cast_to_compute(batch["view1"])
        aug2 = policy.cast_to_compute(batch["view2"])
        if scfg.normalize_inputs:
            aug1, aug2 = normalize_images(aug1), normalize_images(aug2)
        labels = batch["label"]
        # Optional validity mask for pad+mask eval batching: the trainer pads
        # the final (non-divisible) test batch to the fixed batch shape so
        # every eval batch hits ONE compiled executable, and masks the pad
        # rows out of every metric.
        mask = batch.get("mask")

        params = state.params
        if scfg.polyak_ema > 0.0 and state.polyak_params is not None:
            params = state.polyak_params

        target_params = state.target_params
        if zero1_ctx is not None:
            target_params = zero1_ctx.gather(target_params,
                                             zero1_ctx.param_template)

        on1, on2, _, _ = _forward_views(
            net, params, state.batch_stats, aug1, aug2,
            train=False, fuse=scfg.fuse_views, update_stats=False)
        tgt1, tgt2, _, _ = _forward_views(
            net, target_params, state.batch_stats, aug1, aug2,
            train=False, fuse=scfg.fuse_views, update_stats=False)

        byol_loss = loss_function(
            on1["prediction"], on2["prediction"],
            tgt1["projection"], tgt2["projection"], norm_mode=scfg.norm_mode,
            mask=mask)
        logits = net.apply({"params": params}, on1["representation"],
                           method="classify")
        cls_loss = cross_entropy(logits, labels, mask=mask)
        top1, top5 = topk_accuracy(logits, labels, mask=mask)
        weight = (jnp.sum(mask) if mask is not None
                  else jnp.asarray(labels.shape[0], jnp.float32))
        return {"loss_mean": byol_loss + cls_loss,
                "byol_loss_mean": byol_loss,
                "linear_loss_mean": cls_loss,
                "top1_mean": top1,
                "top5_mean": top5,
                # sample count backing the means above; MetricAccumulator
                # weights by it so padded batches don't skew epoch metrics
                "_weight": weight}

    return eval_step
