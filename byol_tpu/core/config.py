"""Immutable, typed configuration for byol_tpu.

Replaces the reference's module-global mutable ``args`` (see
/root/reference/main.py:35-119, mutated at main.py:119,128-130,420-425,725,
727-729,787).  Flag names mirror the reference CLI surface (SURVEY.md App B)
so users of the reference find the same knobs; derived quantities
(steps_per_epoch with drop-remainder, total_train_steps, per-replica sample
counts — reference main.py:420-425) are computed exactly once by
``resolve()`` and frozen.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Optional, Tuple

from byol_tpu.observability import spans


def _frozen(cls):
    return dataclasses.dataclass(frozen=True)(cls)


@_frozen
class TaskConfig:
    """Task / dataset group (reference main.py:37-53)."""

    task: str = "image_folder"          # ref default 'multi_augment_image_folder'
    data_dir: str = "./data"
    batch_size: int = 4096              # GLOBAL batch (ref main.py:41-42)
    epochs: int = 3000
    download: bool = False
    image_size_override: Optional[int] = 224  # ref main.py:46-47
    log_dir: str = "./runs"
    uid: str = ""                       # run identity (ref main.py:52-53)
    # Metric writer: 'tensorboard' | 'jsonl' | 'both' | 'null' — the
    # reference's visdom|tensorboard switch analog (main.py:452-460; visdom
    # dropped, jsonl added so committed evidence is machine-readable).
    grapher: str = "both"
    # Augmentation backend for array datasets: 'tf' (tf.data host), 'native'
    # (multithreaded C++ host kernel, data/native/), or 'device' (on-chip
    # jitted two-view augmentation, data/device_augment.py).  The latter two
    # are the DALI equivalents (reference main.py:356-382).
    data_backend: str = "tf"
    # Where the two-view train augmentation runs:
    # - 'loader': the train iterator yields materialized float32 views
    #   (whatever backend produced them) — ~8x the H2D bytes of the raw
    #   pixels at 224px (two float32 views per uint8 image).
    # - 'step'  : the train iterator yields RAW uint8 batches
    #   ({'images': (B,H,W,C) uint8, 'label': (B,)}) and the jitted train
    #   step derives per-microbatch PRNG keys from state.step and runs
    #   device_augment inside the accumulation scan — only ONE microbatch
    #   of float32 views is ever live in HBM and the separate augment
    #   dispatch disappears (training/steps.py).
    augment_placement: str = "loader"
    # Fused in-step augmentation (ops/fused_augment.py): 'on' replaces the
    # per-view chain of ~7 XLA ops the step-placement augmentation traces
    # (crop-gather, flip, jitter, grayscale — each an HBM sweep of the
    # microbatch) with one Pallas kernel pass per image (uint8 convert +
    # crop + flip + jitter + grayscale in VMEM; the separable blur stays
    # an MXU depthwise conv on the kernel's output), shard-local over the
    # data axis.  Requires augment_placement='step' (validated at
    # resolve()); 'off' lowers the exact unfused graph (HLO identity
    # pinned by test).
    fused_augment: str = "off"
    # Dataset size for the offline-learnable 'synth' task (test split is
    # 1/10th); committed evidence runs use this to stay reproducible from
    # the CLI alone.  0 = loader default (20k).
    num_synth_samples: int = 0
    # Fraction of the train split held out as a validation set (the
    # datasets-submodule loaders exposed num_valid_samples, reference
    # main.py:421-423).  0 = no valid split.  image_folder also accepts an
    # on-disk valid/ root, which wins over the fraction.
    valid_fraction: float = 0.0
    # Positions per sample of a token task ('synth_tokens'); the backbone
    # must declare input_kind='tokens' (models/registry.py).
    seq_len: int = 0


@_frozen
class ModelConfig:
    """Model group (reference main.py:56-70)."""

    arch: str = "resnet50"
    representation_size: int = 2048     # must match arch in the ref (Quirk Q8);
                                        # here it is DERIVED from the registry
                                        # unless explicitly overridden.
    projection_size: int = 256          # ref main.py:61-62
    head_latent_size: int = 4096        # ref main.py:63-64 (projector hidden)
    base_decay: float = 0.996           # EMA tau_0 (ref main.py:65-66)
    # EMA scaling rule ("How to Scale Your EMA", arXiv 2307.13813): when
    # training at a different global batch than the recipe was tuned for,
    # tau must scale as tau^kappa (kappa = batch/reference_batch) to keep
    # the target-network dynamics batch-size invariant.  0 disables.
    ema_scaling_reference_batch: int = 0
    weight_initialization: Optional[str] = None  # ref main.py:67-68
    model_dir: str = ".models"
    # TPU-native additions (no reference analog):
    fuse_views: bool = False            # concat the two views into one encoder
                                        # call (2 fwds instead of 4). Changes BN
                                        # batch statistics vs the reference's
                                        # per-view forwards (main.py:244-247),
                                        # so off by default; turn on for perf.
    remat: bool = False                 # legacy all-or-nothing jax.checkpoint
                                        # of every encoder block (= policy
                                        # 'full'); kept for back-compat.
    remat_policy: str = "none"          # named SELECTIVE checkpoint policy
                                        # (core/remat.py POLICY_NAMES:
                                        # none|full|nothing|dots|
                                        # dots_no_batch|save_block_out|
                                        # offload_block_out); wins over the
                                        # bool when not 'none'.
    stem: str = "conv"                  # resnet stem: 'conv' (7x7/2) or
                                        # 'space_to_depth' (identical numerics,
                                        # MXU-friendly 4x4/1 rearrangement).
    attn_impl: str = "dense"            # ViT attention backend: 'dense'
                                        # or 'ring' (sequence-parallel over
                                        # the mesh).
    pooling: str = "cls"                # ViT feature pooling: 'cls' | 'gap'.
    layer_share: str = "0/1"            # decoder trunk: 'i/n' = this chip is
                                        # chip i of the n that share every
                                        # layer; heads, routed experts and
                                        # vocabulary rows held follow from it
                                        # (',vocab=m,heads=m': a part that
                                        # divides over m of the n)
    trunk_depth: str = ""               # decoder trunk: 'D+S' builds D
                                        # leading dense and S expert layers,
                                        # 'A-B' published layers A to B;
                                        # '' = the published depth


@_frozen
class RegularizerConfig:
    """Regularizer group (reference main.py:72-78)."""

    color_jitter_strength: float = 1.0
    # 'reference': the symmetric torchvision stack (main.py:386-397).
    # 'paper': BYOL's asymmetric recipe (arXiv 2006.07733 App B — solarize +
    # asymmetric blur; the spec behind 74.3% that the reference never had).
    # tf data backend only.
    aug_spec: str = "reference"
    weight_decay: float = 1e-6
    polyak_ema: float = 0.0
    convert_to_sync_bn: bool = True     # under GSPMD jit, BN is cross-replica
                                        # by construction; False forces
                                        # per-device stats via shard_map.


@_frozen
class OptimConfig:
    """Optimization group (reference main.py:80-91)."""

    clip: float = 0.0                   # grad VALUE clip (ref main.py:619-622)
    lr: float = 0.2                     # base LR before linear scaling
    lr_update_schedule: str = "cosine"  # fixed | cosine (ref main.py:85-86)
    warmup: int = 10                    # warmup epochs (ref main.py:87)
    optimizer: str = "lars_momentum"    # registry key; 'lars_' prefix composes
    early_stop: bool = False
    # Microbatched gradient accumulation: split each global batch into
    # accum_steps microbatches inside the jitted step (lax.scan), accumulate
    # gradients, and apply ONE optimizer update + EMA tick.  The LR schedule,
    # step counters, EMA tau, and throughput accounting all see OPTIMIZER
    # steps — batch_size stays the EFFECTIVE global batch.  1 = off.
    accum_steps: int = 1
    # BN-statistics granularity under accumulation (per-microbatch
    # normalization is inherent to one-pass accumulation; this knob controls
    # how running stats tick and offers an exact-semantics oracle):
    # - 'average'    (default): normalize per microbatch; ONE running-stat
    #                tick per optimizer step using the microbatch-averaged
    #                statistics (big-batch tick granularity).
    # - 'microbatch': normalize per microbatch; k sequential running-stat
    #                ticks (the semantics of k small steps between updates).
    # - 'global'    : EXACT big-batch semantics — microbatches run under a
    #                vmapped named axis and every BatchNorm syncs statistics
    #                across it (SyncBN over microbatches), so normalization,
    #                gradients, and the single running-stat tick all match
    #                one batch-(k*m) step to fp tolerance.  Costs the
    #                big-batch memory back (all microbatches in flight):
    #                a semantics oracle for parity tests, not an HBM saver.
    accum_bn_mode: str = "average"


@_frozen
class DeviceConfig:
    """Device / debug / distributed group (reference main.py:99-117)."""

    num_replicas: int = 8               # data-parallel size (mesh 'data' axis)
    workers_per_replica: int = 2
    distributed_master: str = ""        # JAX coordinator address analog
    distributed_rank: int = 0           # process_index analog
    distributed_port: int = 29300
    debug_step: bool = False            # single-minibatch smoke (ref main.py:110)
    seed: int = 1234
    # Aux hygiene (SURVEY.md §5.2/§5.3 — absent in the reference):
    check_numerics: bool = False        # jax_debug_nans: fail fast on NaN/inf
                                        # (legacy blanket check; prefer
                                        # --telemetry + --nan-policy: the
                                        # in-graph nonfinite count costs no
                                        # per-op host sync)
    # Training-health telemetry (observability/{health,telemetry,events}):
    telemetry: str = "off"              # 'off' (identical HLO to a pre-
                                        # telemetry step) | 'epoch' (one
                                        # health record at the epoch
                                        # readback) | 'step' (async lagged
                                        # readback every telemetry_interval
                                        # optimizer steps)
    telemetry_interval: int = 50        # optimizer steps between sampled
                                        # health records under 'step'
    nan_policy: str = "warn"            # non-finite grads/loss response:
                                        # 'warn' (anomaly event) | 'halt'
                                        # (state-dump event + raise)
    spans: str = "on"                   # host-side flight recorder
                                        # (observability/spans.py): 'on'
                                        # records hot-loop phase spans +
                                        # goodput/span_stats events + a
                                        # Chrome trace per run (< 2%
                                        # overhead, bench --spans-ab);
                                        # 'off' hands the hot loop a
                                        # shared no-op (records nothing)
    fault_at_step: int = 0              # >0: kill the process at step N to
                                        # exercise preemption/resume paths
    save_on_signal: bool = True         # SIGTERM (pod preemption notice) ->
                                        # checkpoint immediately, exit 143
    watchdog_timeout: float = 0.0       # >0: dump all stacks + die if an
                                        # epoch readback stalls this many
                                        # seconds (hung-collective detector)
    shard_eval: bool = False            # shard the test set across hosts
                                        # (Quirk Q9: reference evaluates the
                                        # full test set on every rank)
    half: bool = True                   # bf16 compute policy (apex-O2 analog,
                                        # ref main.py:122-124; no loss scaling
                                        # needed on TPU bf16)
    # TPU-native mesh shape: data x model x sequence. model/sequence default 1.
    model_parallel: int = 1
    sequence_parallel: int = 1
    dcn_data_parallel: int = 1          # ICI slices the data axis spans
                                        # (multi-slice pods: in-slice ICI +
                                        # cross-slice DCN collectives)
    zero1: str = "off"                  # ZeRO-1 weight-update sharding
                                        # (arXiv 2004.13336): 'on' shards
                                        # LARS momentum + the EMA target
                                        # flat leaf-partitioned over the
                                        # data axis (params stay replicated
                                        # for the forward; ~Nx less aux-
                                        # state HBM per chip); 'off' lowers
                                        # the replicated graph unchanged.
                                        # parallel/{compile_plan,zero1}.py


@_frozen
class ParityConfig:
    """Faithfulness switches for reference quirks (SURVEY.md App A)."""

    loss_norm_mode: str = "paper"       # 'paper' per-row l2 | 'reference'
                                        # whole-tensor Frobenius (objective.py:8-9)
    ema_init_mode: str = "copy"         # 'copy' (paper) | 'reference'
                                        # (Quirk Q1: mean starts at 0.004*theta)
    schedule_granularity: str = "step"  # 'step' | 'epoch' (Quirk Q5)
    normalize_inputs: bool = False      # ref never normalizes (Quirk Q3)
    ema_update_mode: str = "post"       # 'post' (paper: EMA of post-update
                                        # params) | 'reference_pre' (ref EMAs
                                        # pre-update params inside forward,
                                        # main.py:255)
    zero_init_residual: bool = True     # zero-init last BN scale per block
                                        # (large-batch trick); False matches
                                        # torchvision/reference init
                                        # (main.py:436, default init)


@_frozen
class Config:
    task: TaskConfig = TaskConfig()
    model: ModelConfig = ModelConfig()
    regularizer: RegularizerConfig = RegularizerConfig()
    optim: OptimConfig = OptimConfig()
    device: DeviceConfig = DeviceConfig()
    parity: ParityConfig = ParityConfig()

    def replace(self, **sections) -> "Config":
        return dataclasses.replace(self, **sections)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        # config scalars are user-supplied finite knobs; a NaN landing in
        # one is a bug worth a loud ValueError, not a bare token in the
        # serialized config (GL110 strict-JSON discipline)
        return json.dumps(self.to_dict(), indent=2, sort_keys=True,
                          allow_nan=False)


@_frozen
class ResolvedConfig:
    """Config + derived quantities, computed once (vs reference smuggling them
    through the mutable global ``args`` at main.py:420-425,725)."""

    cfg: Config
    input_shape: Tuple[int, ...]            # (H, W, C) — NHWC, TPU-native
                                            # layout; (S,) for token ids
    num_train_samples: int                  # per-replica (ref main.py:421)
    num_test_samples: int                   # NOT sharded in ref (main.py:422)
    output_size: int                        # number of classes
    steps_per_train_epoch: int              # drop-remainder (ref main.py:424)
    total_train_steps: int                  # ref main.py:425
    batch_size_per_replica: int             # global // num_replicas (ref main.py:725)
    representation_size: int                # derived from arch registry (fixes Q8)
    num_valid_samples: int = 0              # per-replica (ref main.py:423).
                                            # Informational parity surface:
                                            # the reference derives it onto
                                            # args and barely consumes it;
                                            # loader counts stay the
                                            # authoritative split sizes.

    @property
    def global_batch_size(self) -> int:
        return self.cfg.task.batch_size

    @property
    def accum_steps(self) -> int:
        return self.cfg.optim.accum_steps

    @property
    def microbatch_size(self) -> int:
        """GLOBAL microbatch size: the batch each accumulation scan
        iteration forwards (= effective batch when accumulation is off)."""
        return self.cfg.task.batch_size // self.cfg.optim.accum_steps


@spans.spanned("startup/resolve")
def resolve(cfg: Config, *, num_train_samples: int, num_test_samples: int,
            output_size: int, input_shape: Tuple[int, ...],
            representation_size: Optional[int] = None,
            num_valid_samples: int = 0) -> ResolvedConfig:
    """Derive load-bearing quantities exactly as the reference does.

    Reference math (main.py:420-425,725):
      - per-replica batch  = global_batch // num_replicas
      - per-replica train samples = num_train_samples // num_replicas
      - per-replica valid samples = num_valid_samples // num_replicas
        (main.py:423 divides valid like train; test stays global)
      - steps_per_train_epoch = per_replica_samples // per_replica_batch  (drop remainder)
      - total_train_steps = epochs * steps_per_train_epoch
    These feed the EMA tau schedule (main.py:160,425) so they must match.
    """
    n_rep = cfg.device.num_replicas
    if cfg.task.batch_size % n_rep != 0:
        raise ValueError(
            f"global batch {cfg.task.batch_size} not divisible by "
            f"num_replicas {n_rep}")
    accum = cfg.optim.accum_steps
    if accum < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum}")
    if cfg.task.batch_size % (accum * n_rep) != 0:
        # each scan iteration must shard its microbatch over the data axis
        # without resharding: n_rep | (batch / accum)
        raise ValueError(
            f"global batch {cfg.task.batch_size} not divisible by "
            f"accum_steps x num_replicas = {accum} x {n_rep}")
    if cfg.optim.accum_bn_mode not in ("average", "microbatch", "global"):
        raise ValueError(
            f"unknown accum_bn_mode {cfg.optim.accum_bn_mode!r}; "
            "'average' | 'microbatch' | 'global'")
    if cfg.task.augment_placement not in ("loader", "step"):
        raise ValueError(
            f"unknown augment_placement {cfg.task.augment_placement!r}; "
            "'loader' | 'step'")
    if cfg.device.telemetry not in ("off", "epoch", "step"):
        raise ValueError(
            f"unknown telemetry mode {cfg.device.telemetry!r}; "
            "'off' | 'epoch' | 'step'")
    if cfg.device.telemetry_interval < 1:
        raise ValueError(
            f"telemetry_interval must be >= 1, got "
            f"{cfg.device.telemetry_interval}")
    if cfg.device.nan_policy not in ("warn", "halt"):
        raise ValueError(
            f"unknown nan_policy {cfg.device.nan_policy!r}; "
            "'warn' | 'halt'")
    if cfg.device.spans not in ("on", "off"):
        raise ValueError(
            f"unknown spans mode {cfg.device.spans!r}; 'on' | 'off'")
    if cfg.device.zero1 not in ("off", "on"):
        raise ValueError(
            f"unknown zero1 mode {cfg.device.zero1!r}; 'off' | 'on'")
    if cfg.device.zero1 == "on" and cfg.device.model_parallel > 1:
        # ZeRO-1 is data-parallel weight-update sharding; a TP'd head's
        # opt-state leaves are already sharded over 'model'
        # (parallel/partitioning.py) and the flat layout would clobber that
        raise ValueError(
            "--zero1 on does not compose with --model-parallel > 1 "
            "(tensor parallelism already shards those optimizer-state "
            "leaves over the 'model' axis)")
    if cfg.task.fused_augment not in ("off", "on"):
        raise ValueError(
            f"unknown fused_augment mode {cfg.task.fused_augment!r}; "
            "'off' | 'on'")
    if cfg.task.fused_augment == "on":
        if cfg.task.augment_placement != "step":
            raise ValueError(
                "--fused-augment on requires --augment-placement step: "
                "the kernel fuses the IN-STEP augmentation path (raw "
                "uint8 batches augmented inside the accumulation scan); "
                "with loader placement there is no in-step chain to fuse")
        if cfg.optim.accum_bn_mode == "global" and accum > 1:
            raise ValueError(
                "--fused-augment on does not compose with --accum-bn-mode "
                "global: the global oracle vmaps microbatches, and the "
                "augment kernel's pallas_call/shard_map cannot run under "
                "that vmap — use 'average' or 'microbatch'")
        if (cfg.device.model_parallel > 1
                or cfg.device.sequence_parallel > 1):
            raise ValueError(
                "--fused-augment on spans the data axis only (the "
                "kernel's shard_map augments each chip's batch shard); "
                "model/sequence-parallel meshes are not yet supported — "
                "run those with --fused-augment off")
    if cfg.device.nan_policy == "halt" and cfg.device.telemetry == "off":
        # the sink that enforces halt only exists when telemetry is on —
        # accepting this combination would silently train through NaNs,
        # the exact failure the policy exists to stop
        raise ValueError(
            "--nan-policy halt requires --telemetry epoch|step (the "
            "non-finite check lives in the telemetry health vector; with "
            "telemetry off nothing would enforce the halt)")
    from byol_tpu.core.remat import resolve_policy_name
    resolve_policy_name(cfg.model.remat, cfg.model.remat_policy)  # fail fast
    if len(input_shape) == 1:
        # a token sample: only what the step does to PIXELS is refused
        if cfg.task.augment_placement == "step" or \
                cfg.parity.normalize_inputs:
            raise ValueError(
                "token input: --augment-placement step and "
                "--normalize-inputs work on pixels")
    per_replica_batch = cfg.task.batch_size // n_rep
    per_replica_train = num_train_samples // n_rep
    steps_per_epoch = per_replica_train // per_replica_batch
    if steps_per_epoch == 0:
        raise ValueError(
            f"steps_per_train_epoch is 0: {per_replica_train} per-replica "
            f"samples < per-replica batch {per_replica_batch}")
    rep_size = representation_size
    if rep_size is None:
        # Derive from the backbone registry (the Quirk Q8 fix) — the config
        # field is only a fallback for archs not yet registered.
        try:
            from byol_tpu.models.registry import get_spec
            rep_size = get_spec(cfg.model.arch).feature_dim
        except ValueError:
            rep_size = cfg.model.representation_size
    return ResolvedConfig(
        cfg=cfg,
        input_shape=tuple(input_shape),
        num_train_samples=per_replica_train,
        num_test_samples=num_test_samples,
        output_size=output_size,
        steps_per_train_epoch=steps_per_epoch,
        total_train_steps=cfg.task.epochs * steps_per_epoch,
        batch_size_per_replica=per_replica_batch,
        representation_size=rep_size,
        num_valid_samples=num_valid_samples // n_rep,
    )


def run_name(cfg: Config) -> str:
    """Deterministic run name from config + uid.

    Contract of ``helpers.utils.get_name(args)`` (reference main.py:454,460):
    run identity names the TB logdir / checkpoint dir.
    """
    blob = cfg.to_json().encode()
    digest = hashlib.sha1(blob).hexdigest()[:8]
    uid = cfg.task.uid or "byol"
    return f"{uid}_{cfg.model.arch}_b{cfg.task.batch_size}_{digest}"
