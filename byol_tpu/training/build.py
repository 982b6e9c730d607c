"""Wiring: resolved config -> net, state, jitted steps on a mesh.

The analog of the reference's ``build_loader_model_grapher`` +
``build_optimizer`` wiring (main.py:403-462, 303-344), minus the loader/
grapher (owned by :mod:`byol_tpu.data` / :mod:`byol_tpu.observability`).

Sharding layout (GSPMD): declared by the compile plan
(parallel/compile_plan.py) — batch dims over the ``data`` mesh axis;
params/BN stats replicated for the forward; LARS momentum + the EMA target
replicated by default (the reference keeps full replicas too) or flat
leaf-partitioned over ``data`` under ``--zero1 on`` (parallel/zero1.py).
The jitted steps take their in/out shardings and donation from the plan;
XLA inserts all collectives (gradient allreduce, SyncBN psum, the ZeRO-1
scatter/gather) from the partitioning.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from byol_tpu.core.config import Config, ResolvedConfig
from byol_tpu.core.precision import get_policy
from byol_tpu.models.byol_net import BYOLNet, build_byol_net
from byol_tpu.observability import spans
from byol_tpu.optim.factory import build_optimizer, is_lars_optimizer
from byol_tpu.parallel.mesh import DATA_AXIS
from byol_tpu.training.state import TrainState, create_train_state
from byol_tpu.training.steps import StepConfig, make_eval_step, make_train_step


def build_net(rcfg: ResolvedConfig) -> BYOLNet:
    cfg = rcfg.cfg
    policy = get_policy(cfg.device.half)
    small = rcfg.input_shape[0] <= 64    # CIFAR-style stem
    from byol_tpu.models.registry import get_spec
    spec = get_spec(cfg.model.arch)
    if (spec.input_kind == "tokens") != (len(rcfg.input_shape) == 1):
        raise ValueError(
            f"arch {cfg.model.arch!r} takes {spec.input_kind} input but the "
            f"task yields samples of shape {rcfg.input_shape}")
    if spec.input_kind == "tokens":      # decoder-trunk knobs
        extra = {"remat": cfg.model.remat,
                 "remat_policy": cfg.model.remat_policy,
                 "layer_share": cfg.model.layer_share,
                 "trunk_depth": cfg.model.trunk_depth}
    elif spec.has_batchnorm:
        extra = {"zero_init_residual": cfg.parity.zero_init_residual,
                 "remat": cfg.model.remat,
                 "remat_policy": cfg.model.remat_policy,
                 "stem": cfg.model.stem}
    else:  # ViT-family knobs
        extra = {"remat": cfg.model.remat,
                 "remat_policy": cfg.model.remat_policy,
                 "attn_impl": cfg.model.attn_impl,
                 "pooling": cfg.model.pooling}
    # accum_bn_mode='global': every BatchNorm (backbone + MLP heads) syncs
    # statistics over the vmapped microbatch axis inside the train step, so
    # normalization spans the EFFECTIVE batch exactly as one big step would.
    from byol_tpu.training.steps import ACCUM_AXIS
    bn_axis = (ACCUM_AXIS
               if (cfg.optim.accum_steps > 1
                   and cfg.optim.accum_bn_mode == "global") else None)
    return build_byol_net(
        cfg.model.arch,
        num_classes=rcfg.output_size,
        head_latent_size=cfg.model.head_latent_size,
        projection_size=cfg.model.projection_size,
        dtype=policy.compute_dtype,
        small_inputs=small,
        bn_axis_name=bn_axis,
        **extra)


def _dummy_batch(rcfg: ResolvedConfig, batch: int) -> jnp.ndarray:
    """A batch of zeros of the task's sample shape: pixels, or token ids."""
    tokens = len(rcfg.input_shape) == 1
    return jnp.zeros((batch,) + tuple(rcfg.input_shape),
                     jnp.int32 if tokens else jnp.float32)


def init_variables(net: BYOLNet, rcfg: ResolvedConfig, rng: jax.Array,
                   *, batch: int = 2):
    """``batch`` must be divisible by the mesh's data axis when the model
    contains shard_map ops (ring attention) — setup_training sizes it to
    the mesh."""
    dummy = _dummy_batch(rcfg, batch)
    axis = getattr(net, "bn_axis_name", None)
    if axis:
        # BN modules pmean over the accumulation axis; init's train-mode
        # warmup forward must run with that axis BOUND.  A size-1 vmap binds
        # it without changing any statistic (pmean over 1 = identity).
        variables = jax.vmap(
            lambda d: net.init({"params": rng}, d, train=True,
                               method="warmup"),
            axis_name=axis)(dummy[None])
        return jax.tree_util.tree_map(lambda x: x[0], variables)
    return net.init({"params": rng}, dummy, train=True, method="warmup")


def build_tx(rcfg: ResolvedConfig, adapt_mask=None):
    cfg = rcfg.cfg
    epoch_granular = cfg.parity.schedule_granularity == "epoch"
    return build_optimizer(
        cfg.optim.optimizer,
        adapt_mask=adapt_mask,
        base_lr=cfg.optim.lr,
        global_batch_size=rcfg.global_batch_size,
        weight_decay=cfg.regularizer.weight_decay,
        # schedule units are epochs (warmup=10 epochs, main.py:87,290-291);
        # step granularity interpolates the same shape per step.
        total_units=(cfg.task.epochs if epoch_granular
                     else rcfg.total_train_steps),
        warmup_units=(cfg.optim.warmup if epoch_granular
                      else cfg.optim.warmup * rcfg.steps_per_train_epoch),
        lr_schedule_kind=cfg.optim.lr_update_schedule,
        steps_per_epoch=(rcfg.steps_per_train_epoch if epoch_granular
                         else None),
        clip=cfg.optim.clip)


def step_config(rcfg: ResolvedConfig) -> StepConfig:
    cfg = rcfg.cfg
    base_decay = cfg.model.base_decay
    polyak = cfg.regularizer.polyak_ema
    ref_b = cfg.model.ema_scaling_reference_batch
    if ref_b > 0:
        # EMA scaling rule (arXiv 2307.13813): tau -> tau^kappa keeps an
        # EMA's time constant (in SAMPLES, not steps) invariant when the
        # global batch deviates from the recipe's reference batch.  The
        # rule covers every model EMA — target decay AND Polyak averaging.
        kappa = rcfg.global_batch_size / ref_b
        base_decay = float(base_decay ** kappa)
        if polyak > 0.0:
            polyak = float(polyak ** kappa)
    return StepConfig(
        total_train_steps=rcfg.total_train_steps,
        base_decay=base_decay,
        norm_mode=cfg.parity.loss_norm_mode,
        fuse_views=cfg.model.fuse_views,
        polyak_ema=polyak,
        ema_update_mode=cfg.parity.ema_update_mode,
        accum_steps=cfg.optim.accum_steps,
        accum_bn_mode=cfg.optim.accum_bn_mode,
        normalize_inputs=cfg.parity.normalize_inputs,
        augment_in_step=cfg.task.augment_placement == "step",
        fused_augment=cfg.task.fused_augment == "on",
        image_size=rcfg.input_shape[0],
        color_jitter_strength=cfg.regularizer.color_jitter_strength,
        aug_seed=cfg.device.seed,
        telemetry=cfg.device.telemetry,
        weight_decay=cfg.regularizer.weight_decay,
        lars_in_chain=is_lars_optimizer(cfg.optim.optimizer))


def _validate_remat_tags(net, rcfg: ResolvedConfig, variables,
                         batch: int) -> None:
    """Runtime complement to graphlint GL105: a names-based remat policy
    must match at least one ``checkpoint_name`` tag in the traced forward,
    or core/remat.py raises instead of silently saving nothing."""
    from byol_tpu.core import remat as remat_lib
    cfg = rcfg.cfg
    policy_name = remat_lib.resolve_policy_name(cfg.model.remat,
                                                cfg.model.remat_policy)
    if policy_name not in remat_lib.NAMES_BASED_POLICIES:
        return
    dummy = _dummy_batch(rcfg, batch)
    axis = getattr(net, "bn_axis_name", None)

    def fwd(v, d):
        return net.apply(v, d, train=True, method="warmup",
                         mutable=["batch_stats"])

    if axis:
        # same size-1 vmap trick as init_variables: BN pmeans need the
        # accumulation axis bound during the trace
        fn = lambda v, d: jax.vmap(lambda dd: fwd(v, dd),
                                   axis_name=axis)(d[None])
    else:
        fn = fwd
    remat_lib.assert_tags_in_trace(fn, variables, dummy,
                                   policy_name=policy_name)


@spans.spanned("startup/build")
def setup_training(rcfg: ResolvedConfig, mesh: Mesh, rng: jax.Array,
                   plan: Optional[Any] = None
                   ) -> Tuple[BYOLNet, TrainState, Callable, Callable, Any]:
    """Returns (net, sharded_state, jitted_train_step, jitted_eval_step,
    lr_schedule).

    ALL sharding decisions — state layout (replicated / TP / ZeRO-1),
    batch placement, in/out shardings and donation of both jitted steps —
    come from the compile plan (parallel/compile_plan.py).  Callers that
    need the plan afterwards (the trainer: run-log provenance + the
    checkpoint canonicalization codec) build it themselves and pass it in;
    ``None`` builds the config-implied plan internally.

    The whole call is the span ``startup/build`` on the process's recorder
    (observability/spans.py), its seven parts ``startup/build/net``,
    ``/init`` (the eager flax init; attrs ``leaves``, ``parameters``),
    ``/remat_tags``, ``/optimizer``, ``/state``, ``/place``, ``/jit``.
    """
    cfg = rcfg.cfg
    policy = get_policy(cfg.device.half)
    span = spans.PROCESS.span       # host code: set-up is never traced
    with span("startup/build/net"):
        net = build_net(rcfg)
        scfg = step_config(rcfg)
    from byol_tpu.parallel.compile_plan import build_plan
    if plan is None:
        plan = build_plan(mesh, zero1=cfg.device.zero1 == "on")

    from byol_tpu.core.rng import split_named
    keys = split_named(rng, ("params", "weight_init"))
    with mesh:
        with span("startup/build/init") as init_span:
            variables = init_variables(
                net, rcfg, keys["params"],
                batch=max(2, mesh.shape[DATA_AXIS]))
            if cfg.model.weight_initialization:
                # --weight-initialization scheme re-draw (main.py:436 analog)
                from byol_tpu.models.init import apply_weight_init
                variables = dict(variables)
                variables["params"] = apply_weight_init(
                    variables["params"], keys["weight_init"],
                    cfg.model.weight_initialization)
            leaves = jax.tree_util.tree_leaves(variables["params"])
            init_span.note(leaves=len(leaves),
                           parameters=sum(x.size for x in leaves))
        with span("startup/build/remat_tags"):
            _validate_remat_tags(net, rcfg, variables,
                                 batch=max(2, mesh.shape[DATA_AXIS]))
        with span("startup/build/optimizer"):
            # Under ZeRO-1 the optax chain sees FLAT leaves (every leaf
            # 1-D), so the bias/BN exclusion mask must be fixed from the
            # REAL shapes here; the default ndim-derived mask stays for the
            # replicated layout (identical semantics, and bit-identical jit
            # cache keys).
            adapt_mask = None
            if plan.zero1:
                from byol_tpu.optim import lars as lars_lib
                adapt_mask = lars_lib.default_exclusion_mask(
                    variables["params"])
                if lars_lib.has_expert_axis(adapt_mask):
                    raise ValueError(
                        "--zero1 on flattens every leaf, and LARS adapts "
                        "each expert of a stacked expert kernel alone: it "
                        "needs the expert axis (run such a tree with "
                        "--zero1 off)")
            tx, schedule = build_tx(rcfg, adapt_mask=adapt_mask)
        with span("startup/build/state"):
            state = create_train_state(
                # under ZeRO-1 the plan inits the optimizer state on the
                # FLAT params in prepare_state; initializing the replicated
                # tree here too would double the setup-time momentum
                # footprint
                variables, None if plan.zero1 else tx,
                ema_init_mode=cfg.parity.ema_init_mode,
                polyak_ema=cfg.regularizer.polyak_ema)

    # The plan converts the state to its layout (ZeRO-1: flat-sharded
    # momentum/EMA), places it, and owns the jit wiring of both steps.
    with span("startup/build/place"):
        state, state_sh = plan.prepare_state(state, tx)
    with span("startup/build/jit"):
        z1 = plan.zero1_context()
        # mesh feeds only the fused augmentation's shard_map; without
        # --fused-augment it is inert and the traced graph is unchanged.
        train_step = plan.jit_train_step(
            make_train_step(net, tx, scfg, policy, zero1_ctx=z1, mesh=mesh),
            state_sh)
        eval_step = plan.jit_eval_step(
            make_eval_step(net, scfg, policy, zero1_ctx=z1), state_sh)

    def _with_mesh(fn):
        # keep the mesh in thread-local scope at call (=trace) time so
        # mesh-aware ops inside the step (ring attention's shard_map) can
        # resolve the ambient mesh; steady-state calls just hit the jit
        # cache and the context costs nothing.
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with mesh:
                return fn(*args, **kwargs)
        return wrapped

    return net, state, _with_mesh(train_step), _with_mesh(eval_step), schedule
