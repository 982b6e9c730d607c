"""CLI — the reference's flag surface, resolved into an immutable Config.

Flag names mirror /root/reference/main.py:35-119 (inventory SURVEY.md App B)
so reference users find the same knobs; parsing happens exactly once inside
``main()`` (vs the reference's parse-at-import into a mutable module global,
main.py:119).  TPU-specific additions are grouped at the bottom and
documented inline.

Semantics preserved: --batch-size is GLOBAL (split across the data axis, the
main.py:725 analog); --lr is linearly scaled by global_batch/256 for
sgd/momentum inside the optimizer factory (main.py:333-334); 'lars_' prefix
composes (main.py:323).  Deltas: --half selects the bf16 policy and
--no-cuda forces the CPU backend; the visdom BACKEND is dropped (SURVEY.md
§5.5) but --visdom-url/--visdom-port still parse (warn + fall back to
--grapher, which offers tensorboard | jsonl | both | null);
--num-replicas defaults to the detected device count.
"""
from __future__ import annotations

import argparse
from typing import List, Optional

from byol_tpu.core.config import (Config, DeviceConfig, ModelConfig,
                                  OptimConfig, ParityConfig,
                                  RegularizerConfig, TaskConfig)
from byol_tpu.observability import spans


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="byol_tpu — TPU-native BYOL (jramapuram/BYOL capability "
                    "surface)")
    # Task (main.py:37-53)
    t = p.add_argument_group("task")
    t.add_argument("--task", type=str, default="image_folder",
                   help="image_folder | cifar10 | cifar100 | mnist | "
                        "fashion_mnist | digits (real images bundled with "
                        "sklearn, works offline) | fake | synth "
                        "(procedural learnable dataset, works offline) | "
                        "synth_tokens (seeded id sequences, two views by "
                        "independent 15%% token masking — for a "
                        "block-diffusion --arch by two noisings [noised | "
                        "clean], a rate a block; needs a token --arch and "
                        "--seq-len)")
    t.add_argument("--batch-size", type=int, default=4096,
                   help="GLOBAL batch size")
    t.add_argument("--epochs", type=int, default=3000)
    t.add_argument("--download", type=int, default=0)
    t.add_argument("--image-size-override", type=int, default=224)
    t.add_argument("--data-dir", type=str, default="./data")
    t.add_argument("--log-dir", type=str, default="./runs")
    t.add_argument("--grapher", type=str, default="both",
                   choices=("tensorboard", "jsonl", "both", "null"),
                   help="metric writer(s); the reference's visdom|TB switch "
                        "analog (visdom dropped, jsonl added)")
    t.add_argument("--uid", type=str, default="")
    t.add_argument("--num-synth-samples", type=int, default=0,
                   help="dataset size for --task synth (test = 1/10th); "
                        "0 = default 20000")
    t.add_argument("--seq-len", type=int, default=0,
                   help="ids per sample for --task synth_tokens (a "
                        "block-diffusion trunk reads twice as many "
                        "positions: each sample noised and clean)")
    t.add_argument("--valid-fraction", type=float, default=0.0,
                   help="hold out this fraction of train as a validation "
                        "split (num_valid_samples contract, reference "
                        "main.py:421-423); image_folder also accepts an "
                        "on-disk valid/ root, which wins")
    # Model (main.py:56-70)
    m = p.add_argument_group("model")
    m.add_argument("--arch", type=str, default="resnet50",
                   help="a backbone of models/registry.py: resnet*, vit_*, "
                        "or a decoder trunk over token ids (--task "
                        "synth_tokens): xing4_29b_a4b, qwen3_next_80b_a3b, "
                        "keye_vl2_30b_a3b, lfm2_24b_a2b, joyai_llm_flash, "
                        "sdar_30b_a3b and their test-size twins "
                        "decoder_trunk_tiny, hybrid_trunk_tiny, "
                        "sparse_trunk_tiny, shortconv_trunk_tiny, "
                        "latent_trunk_tiny, blockdiff_trunk_tiny")
    m.add_argument("--representation-size", type=int, default=None,
                   help="derived from the arch registry unless overridden")
    m.add_argument("--projection-size", type=int, default=256)
    m.add_argument("--head-latent-size", type=int, default=4096)
    m.add_argument("--base-decay", type=float, default=0.996)
    m.add_argument("--ema-scaling-reference-batch", type=int, default=0,
                   help="scale tau as tau^(batch/this) so target-EMA "
                        "dynamics stay batch-size invariant (the EMA "
                        "scaling rule, arXiv 2307.13813); 0 = off")
    m.add_argument("--weight-initialization", type=str, default=None)
    m.add_argument("--model-dir", type=str, default=".models")
    # Regularizer (main.py:72-78)
    r = p.add_argument_group("regularizer")
    r.add_argument("--color-jitter-strength", type=float, default=1.0)
    r.add_argument("--aug-spec", type=str, default="reference",
                   choices=("reference", "paper"),
                   help="'reference' = the symmetric reference stack; "
                        "'paper' = BYOL's asymmetric recipe (solarize + "
                        "asymmetric blur, arXiv 2006.07733 App B)")
    r.add_argument("--weight-decay", type=float, default=1e-6)
    r.add_argument("--polyak-ema", type=float, default=0.0)
    r.add_argument("--convert-to-sync-bn",
                   action=argparse.BooleanOptionalAction, default=True)
    # Optimization (main.py:80-91)
    o = p.add_argument_group("optimization")
    o.add_argument("--clip", type=float, default=0.0)
    o.add_argument("--lr", type=float, default=0.2)
    o.add_argument("--lr-update-schedule", type=str, default="cosine",
                   choices=("fixed", "cosine"))
    o.add_argument("--warmup", type=int, default=10, help="warmup epochs")
    o.add_argument("--optimizer", type=str, default="lars_momentum")
    o.add_argument("--early-stop", action="store_true")
    # Device / debug / distributed (main.py:99-117)
    d = p.add_argument_group("device")
    d.add_argument("--num-replicas", type=int, default=0,
                   help="data-axis size; 0 = all detected devices")
    d.add_argument("--workers-per-replica", type=int, default=2)
    d.add_argument("--distributed-master", type=str, default="",
                   help="JAX coordinator address (multi-host)")
    d.add_argument("--num-processes", type=int, default=0,
                   help="host PROCESS count for explicit multi-host "
                        "rendezvous; distinct from --num-replicas (a DEVICE "
                        "axis size — hosts usually drive several chips). "
                        "0 = let JAX auto-detect from the TPU pod metadata")
    d.add_argument("--distributed-rank", type=int, default=0)
    d.add_argument("--distributed-port", type=int, default=29300)
    d.add_argument("--debug-step", action="store_true",
                   help="single minibatch per train/eval pass (main.py:110)")
    d.add_argument("--seed", type=int, default=1234)
    d.add_argument("--check-numerics", action="store_true",
                   help="fail fast on NaN/inf (jax_debug_nans; legacy "
                        "blanket check — prefer --telemetry with "
                        "--nan-policy, whose in-graph nonfinite count "
                        "costs no per-op host sync)")
    d.add_argument("--telemetry", type=str, default="off",
                   choices=("off", "epoch", "step"),
                   help="in-graph training-health telemetry "
                        "(observability/health.py): 'off' lowers the "
                        "exact pre-telemetry step; 'epoch' reads one "
                        "health record per epoch at the existing "
                        "readback; 'step' reads back asynchronously "
                        "(>= interval-step lag, no host sync in the "
                        "dispatch loop) every --telemetry-interval steps")
    d.add_argument("--telemetry-interval", type=int, default=50,
                   help="optimizer steps between sampled health records "
                        "under --telemetry step")
    d.add_argument("--nan-policy", type=str, default="warn",
                   choices=("warn", "halt"),
                   help="response to a non-finite gradient/loss in the "
                        "telemetry health vector: 'warn' records an "
                        "anomaly event; 'halt' dumps step/state metadata "
                        "to the run log and raises")
    d.add_argument("--spans", type=str, default="on",
                   choices=("on", "off"),
                   help="host-side span flight recorder "
                        "(observability/spans.py): 'on' times every "
                        "hot-loop phase (input wait, dispatch, readback, "
                        "eval, checkpoint, compile), emits goodput/"
                        "span_stats events into run.jsonl and writes a "
                        "Chrome-trace trace.json per run, set-up "
                        "(startup/*) and JAX's compiles (compile/*) on the "
                        "same timeline; 'off' keeps the hot loop free of "
                        "spans and their TraceAnnotation regions (an "
                        "on-demand start_server capture then shows no host "
                        "phase marker) and writes neither events nor "
                        "trace.json — set-up and compiles are still "
                        "recorded in memory, as in every process")
    d.add_argument("--fault-at-step", type=int, default=0,
                   help="fault injection: kill the process at step N "
                        "(tests checkpoint/resume)")
    d.add_argument("--save-on-signal",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="on SIGTERM (pod preemption notice) checkpoint "
                        "immediately and exit 143")
    d.add_argument("--watchdog-timeout", type=float, default=0.0,
                   help="seconds without epoch progress before dumping all "
                        "thread stacks and dying (hung-collective "
                        "detector; 0 = off)")
    d.add_argument("--shard-eval", action="store_true",
                   help="shard the test set across hosts (reference "
                        "evaluates it fully on every rank, Quirk Q9)")
    d.add_argument("--half", action="store_true", default=True,
                   help="bf16 compute policy (apex O2 analog)")
    d.add_argument("--no-half", dest="half", action="store_false")
    d.add_argument("--no-cuda", action="store_true",
                   help="force the CPU backend (reference main.py:113; here "
                        "it means 'no accelerator': jax_platforms=cpu)")
    # Reference visdom flags (main.py:94-97) accepted for drop-in
    # compatibility; the backend itself is dropped (SURVEY §5.5) — setting
    # them warns and falls back to --grapher.
    d.add_argument("--visdom-url", type=str, default=None,
                   help=argparse.SUPPRESS)
    d.add_argument("--visdom-port", type=int, default=None,
                   help=argparse.SUPPRESS)
    # TPU-native extensions
    x = p.add_argument_group("tpu")
    x.add_argument("--model-parallel", type=int, default=1,
                   help="tensor-parallel axis size")
    x.add_argument("--sequence-parallel", type=int, default=1,
                   help="sequence/context-parallel axis size (ViT)")
    x.add_argument("--dcn-data-parallel", type=int, default=1,
                   help="ICI slices the data axis spans on multi-slice "
                        "pods (slice-major layout: gradient/SyncBN "
                        "all-reduces decompose into in-slice ICI + "
                        "cross-slice DCN phases)")
    x.add_argument("--zero1", type=str, default=None,
                   choices=("off", "on"),
                   help="ZeRO-1 weight-update sharding (arXiv "
                        "2004.13336): 'on' shards LARS momentum + the EMA "
                        "target flat leaf-partitioned over the data axis "
                        "— per-shard update after the gradient reduce, "
                        "one just-in-time all-gather of fresh params — "
                        "for ~Nx less optimizer-state HBM per chip; "
                        "'off' lowers the replicated graph unchanged "
                        "(parallel/compile_plan.py)")
    x.add_argument("--fsdp", action="store_true",
                   help=argparse.SUPPRESS)  # deprecated alias: --zero1 on
    x.add_argument("--fused-augment", type=str, default="off",
                   choices=("off", "on"),
                   help="fused in-step augmentation (ops/fused_augment.py "
                        "Pallas kernel): 'on' collapses the per-view "
                        "crop/flip/jitter/grayscale chain into one VMEM "
                        "pass per image (blur stays an MXU conv on the "
                        "kernel's output; randomness still drawn from the "
                        "augment_keys stream outside the kernel).  "
                        "Requires --augment-placement step; 'off' lowers "
                        "the exact unfused graph")
    x.add_argument("--fuse-views", action="store_true",
                   help="one fused encoder call for both views (perf; "
                        "changes BN batch statistics vs the reference)")
    x.add_argument("--remat", action="store_true",
                   help="legacy all-or-nothing per-block checkpoint "
                        "(= --remat-policy full); prefer a selective policy")
    x.add_argument("--remat-policy", type=str, default="none",
                   choices=("none", "full", "nothing", "dots",
                            "dots_no_batch", "save_block_out",
                            "offload_block_out"),
                   help="selective rematerialization policy per "
                        "residual/encoder block (core/remat.py): 'dots' "
                        "saves conv/matmul results and recomputes the "
                        "cheap chains between them — the recommended "
                        "HBM-for-FLOPs trade; 'save_block_out'/"
                        "'offload_block_out' keep only tagged block "
                        "outputs (the latter in pinned host memory)")
    x.add_argument("--accum-steps", type=int, default=1,
                   help="microbatched gradient accumulation: split each "
                        "global batch into this many microbatches inside "
                        "the jitted step (lax.scan), one optimizer update "
                        "+ EMA tick per global batch.  --batch-size stays "
                        "the EFFECTIVE batch; LR schedule / EMA tau / "
                        "counters see optimizer steps.  Breaks the HBM "
                        "spill wall: any effective batch runs at the "
                        "per-chip-optimal microbatch.  1 = off")
    x.add_argument("--accum-bn-mode", type=str, default="average",
                   choices=("average", "microbatch", "global"),
                   help="BN-statistics granularity under accumulation: "
                        "'average' = per-microbatch normalization, one "
                        "running-stat tick per step from averaged stats; "
                        "'microbatch' = k sequential ticks; 'global' = "
                        "exact big-batch semantics via cross-microbatch "
                        "stat sync (semantics oracle — costs the "
                        "big-batch memory back)")
    x.add_argument("--stem", type=str, default="conv",
                   choices=("conv", "space_to_depth"),
                   help="resnet stem: space_to_depth computes the 7x7/2 "
                        "conv as an MXU-friendly 4x4/1 rearrangement "
                        "(identical numerics and checkpoints)")
    x.add_argument("--attn-impl", type=str, default="dense",
                   choices=("dense", "ring"),
                   help="ViT attention backend")
    x.add_argument("--pooling", type=str, default="cls",
                   choices=("cls", "gap"), help="ViT feature pooling")
    x.add_argument("--layer-share", type=str, default="0/1",
                   help="decoder trunk: 'i/n' = this chip is chip i of the "
                        "n that share every layer (expert- and head-"
                        "parallel); the heads, routed experts and "
                        "vocabulary rows it holds follow from it.  A part "
                        "that divides over fewer chips is named after it: "
                        "'0/16,vocab=8,heads=1' = 16 expert-parallel chips, "
                        "the vocabulary over 8 of them, the heads whole on "
                        "each.  The layer runs without its exchange: what "
                        "the other chips would add is left out")
    x.add_argument("--trunk-depth", type=str, default="",
                   help="decoder trunk: 'D+S' builds D leading dense and S "
                        "expert layers instead of the published depth; "
                        "'A-B' builds the published layers A to B (both "
                        "counted), each with its published role")
    x.add_argument("--data-backend", type=str, default="tf",
                   choices=("tf", "native", "device"),
                   help="augmentation pipeline: tf.data host, native C++ "
                        "host kernel, or on-chip jitted augmentation "
                        "(both DALI analogs; 'device' ships uint8 to HBM)")
    x.add_argument("--augment-placement", type=str, default="loader",
                   choices=("loader", "step"),
                   help="where two-view train augmentation runs: 'loader' "
                        "= the train iterator yields float32 views; 'step' "
                        "= the loader ships RAW uint8 batches (~8x fewer "
                        "H2D bytes at 224px) and the jitted train step "
                        "augments per microbatch INSIDE the accumulation "
                        "scan (one microbatch of views live in HBM, no "
                        "separate augment dispatch)")
    x.add_argument("--loss-norm-mode", type=str, default="paper",
                   choices=("paper", "reference"), help="Quirk Q2 switch")
    x.add_argument("--ema-init-mode", type=str, default="copy",
                   choices=("copy", "reference"), help="Quirk Q1 switch")
    x.add_argument("--schedule-granularity", type=str, default="step",
                   choices=("step", "epoch"), help="Quirk Q5 switch")
    x.add_argument("--ema-update-mode", type=str, default="post",
                   choices=("post", "reference_pre"),
                   help="'post' = paper (EMA of post-update params); "
                        "'reference_pre' = reference (EMAs pre-update "
                        "params inside forward, main.py:255)")
    x.add_argument("--normalize-inputs",
                   action=argparse.BooleanOptionalAction, default=False,
                   help="Quirk Q3 switch: standardize pixels with the "
                        "ImageNet mean/std inside the jitted step (the "
                        "paper recipe; the reference feeds raw [0,1] "
                        "pixels)")
    x.add_argument("--zero-init-residual",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="zero-init each residual block's last BN scale "
                        "(large-batch trick); --no-zero-init-residual "
                        "matches torchvision/reference init (main.py:436)")
    x.add_argument("--profile-port", type=int, default=0,
                   help="start jax.profiler server on this port (0=off)")
    x.add_argument("--linear-eval", action="store_true",
                   help="after training, run the OFFLINE linear-evaluation "
                        "protocol (frozen encoder + fresh probe — the BYOL "
                        "paper's metric; the in-training probe is the "
                        "reference's concurrent metric, main.py:249-252)")
    return p


@spans.spanned("startup/config")
def config_from_args(args: argparse.Namespace) -> Config:
    import jax
    n_rep = args.num_replicas or jax.device_count() // (
        args.model_parallel * args.sequence_parallel)
    # --fsdp is the pre-ZeRO-1 spelling of --zero1 on; an explicit
    # --zero1 off alongside it is a contradiction, not an override —
    # silently picking either side would discard an explicit flag
    if args.fsdp and args.zero1 == "off":
        raise SystemExit(
            "cli: --fsdp is the deprecated alias for --zero1 on; it "
            "conflicts with the explicit --zero1 off also passed")
    zero1 = "on" if args.fsdp else (args.zero1 or "off")
    return Config(
        task=TaskConfig(
            task=args.task, data_dir=args.data_dir,
            batch_size=args.batch_size, epochs=args.epochs,
            download=bool(args.download),
            image_size_override=args.image_size_override,
            log_dir=args.log_dir, uid=args.uid,
            grapher=args.grapher,
            data_backend=args.data_backend,
            augment_placement=args.augment_placement,
            fused_augment=args.fused_augment,
            num_synth_samples=args.num_synth_samples,
            valid_fraction=args.valid_fraction,
            seq_len=args.seq_len),
        model=ModelConfig(
            arch=args.arch,
            representation_size=(args.representation_size
                                 if args.representation_size else 2048),
            projection_size=args.projection_size,
            head_latent_size=args.head_latent_size,
            base_decay=args.base_decay,
            ema_scaling_reference_batch=args.ema_scaling_reference_batch,
            weight_initialization=args.weight_initialization,
            model_dir=args.model_dir,
            fuse_views=args.fuse_views, remat=args.remat,
            remat_policy=args.remat_policy,
            stem=args.stem,
            attn_impl=args.attn_impl, pooling=args.pooling,
            layer_share=args.layer_share, trunk_depth=args.trunk_depth),
        regularizer=RegularizerConfig(
            color_jitter_strength=args.color_jitter_strength,
            aug_spec=args.aug_spec,
            weight_decay=args.weight_decay,
            polyak_ema=args.polyak_ema,
            convert_to_sync_bn=args.convert_to_sync_bn),
        optim=OptimConfig(
            clip=args.clip, lr=args.lr,
            lr_update_schedule=args.lr_update_schedule,
            warmup=args.warmup, optimizer=args.optimizer,
            early_stop=args.early_stop,
            accum_steps=args.accum_steps,
            accum_bn_mode=args.accum_bn_mode),
        device=DeviceConfig(
            num_replicas=n_rep,
            workers_per_replica=args.workers_per_replica,
            distributed_master=args.distributed_master,
            distributed_rank=args.distributed_rank,
            distributed_port=args.distributed_port,
            debug_step=args.debug_step, seed=args.seed, half=args.half,
            check_numerics=args.check_numerics,
            telemetry=args.telemetry,
            telemetry_interval=args.telemetry_interval,
            nan_policy=args.nan_policy,
            spans=args.spans,
            fault_at_step=args.fault_at_step,
            save_on_signal=args.save_on_signal,
            watchdog_timeout=args.watchdog_timeout,
            shard_eval=args.shard_eval,
            model_parallel=args.model_parallel,
            sequence_parallel=args.sequence_parallel,
            dcn_data_parallel=args.dcn_data_parallel,
            zero1=zero1),
        parity=ParityConfig(
            loss_norm_mode=args.loss_norm_mode,
            ema_init_mode=args.ema_init_mode,
            schedule_granularity=args.schedule_granularity,
            normalize_inputs=args.normalize_inputs,
            ema_update_mode=args.ema_update_mode,
            zero_init_residual=args.zero_init_residual),
    )


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    import jax
    from byol_tpu.core import preflight
    if args.no_cuda:
        # must precede any backend initialization
        jax.config.update("jax_platforms", "cpu")
    preflight.place_compile_cache()
    if args.visdom_url or args.visdom_port:
        print("byol_tpu: visdom backend is not supported (SURVEY §5.5); "
              f"metrics go to --grapher={args.grapher} under --log-dir")
    # Multi-host rendezvous MUST happen before anything initializes the local
    # XLA backend (config_from_args queries jax.device_count()).  The
    # reference had the same ordering constraint around init_process_group
    # (main.py:717-722).
    if args.distributed_master:
        from byol_tpu.parallel.mesh import initialize_distributed
        master = args.distributed_master
        if ":" not in master:
            master = f"{master}:{args.distributed_port}"
        # On TPU pods JAX auto-detects process identity; --num-processes +
        # --distributed-rank pin it explicitly (the reference's
        # one-process-per-node topology, main.py:807-810).  NB this is the
        # PROCESS count, not --num-replicas: a host usually drives several
        # chips, so device-axis size != process count.
        explicit = args.num_processes > 0
        initialize_distributed(
            master,
            num_processes=args.num_processes if explicit else None,
            process_id=args.distributed_rank if explicit else None)
    # This process owns the chip from here on; it runs on the CPU only
    # when asked to (--no-cuda / JAX_PLATFORMS=cpu).
    preflight.require_tpu("byol_tpu")
    cfg = config_from_args(args)
    print(cfg.to_json())  # full-config dump at startup (main.py:743)
    if args.profile_port:
        from byol_tpu.observability import profiling
        profiling.start_server(args.profile_port)
    from byol_tpu.data.loader import get_loader
    from byol_tpu.training.trainer import fit
    # one loader serves both training and the optional linear eval — at
    # ImageNet scale building it twice doubles the startup scan/IO
    loader = get_loader(cfg, shard_eval=cfg.device.shard_eval)
    result = fit(cfg, loader=loader)
    print(f"done: epoch {result.epoch}, test loss "
          f"{result.test_metrics.get('loss_mean', float('nan')):.4f}, "
          f"{result.images_per_sec_per_chip:.1f} images/sec/chip"
          + (f" (MFU {result.mfu:.1%})" if result.mfu is not None else ""))
    if args.linear_eval:
        from byol_tpu.observability.watchdog import Watchdog
        from byol_tpu.training.linear_eval import run_linear_eval_from_cfg
        # Multi-host: SPMD extraction over the training mesh — every host
        # computes and prints the identical result (linear_eval.py module
        # docstring).  Single-host: plain single-jit path.  The trainer's
        # watchdog stopped with fit(); the extraction readbacks are their
        # own pod-blocking windows, so they get their own.
        mesh = result.mesh if jax.process_count() > 1 else None
        with Watchdog(cfg.device.watchdog_timeout) as wd:
            le = run_linear_eval_from_cfg(cfg, result.state, loader=loader,
                                          mesh=mesh, seed=cfg.device.seed,
                                          watchdog=wd)
        print(f"linear_eval(offline): top1 {le.top1:.2f} "
              f"top5 {le.top5:.2f} (train acc {le.train_acc:.2f}, "
              f"{le.num_train} train / {le.num_test} test)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
