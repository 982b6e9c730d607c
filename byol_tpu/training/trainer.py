"""The training driver: epoch loop, eval, checkpointing, early stop, logging.

TPU-native rebuild of the reference's L5 layer (``run`` + ``execute_graph``,
/root/reference/main.py:559-783):

- one PROCESS PER HOST, all local devices driven through one jitted SPMD
  step (vs the reference's process-per-GPU mp.spawn, main.py:786-814);
- the hot loop is: host pipeline yields numpy -> device_put onto the mesh's
  ``data`` axis -> dispatch the donated-state train step -> tick the timer.
  Dispatch is async; the host runs ahead and only blocks when epoch metrics
  are read, so input pipeline and MXU overlap without explicit
  double-buffering;
- eval mirrors reference semantics (§3.3): full BYOL loss in eval, probe on
  view-1 only, EMA frozen, test set unsharded by default (Quirk Q9 —
  ``shard_eval`` opts out);
- checkpoint/early-stop via ModelSaver on the TEST loss with burn-in
  0.1*epochs and patience 10 (main.py:750-752); resume restores the full
  state incl. the EMA tau counter (Quirk Q6 fix);
- per-epoch: scalar plots (``*_mean`` filter), augmented-view image grids,
  lr plot, epoch log line; config text posted once at epoch 2
  (main.py:646-657,764,773-779).
"""
from __future__ import annotations

import dataclasses
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from byol_tpu.checkpoint import ModelSaver
from byol_tpu.core.config import Config, ResolvedConfig, resolve, run_name
from byol_tpu.core.preflight import describe_device
from byol_tpu.data.loader import LoaderBundle, get_loader, pad_batch
from byol_tpu.data.prefetch import prefetch_to_mesh
from byol_tpu.observability import (Grapher, InputPipelineMeter,
                                    MetricAccumulator, StepTimer,
                                    epoch_log_line, input_log_line)
from byol_tpu.observability import goodput as goodput_lib
from byol_tpu.observability import spans as spans_lib
from byol_tpu.observability.events import RunLog
from byol_tpu.observability.telemetry import NanHaltError, TelemetrySink
from byol_tpu.observability.watchdog import Watchdog
from byol_tpu.parallel.mesh import (MeshSpec, build_mesh, initialize_distributed,
                                    shard_batch_to_mesh)
from byol_tpu.training.build import setup_training


@dataclasses.dataclass
class FitResult:
    state: Any
    epoch: int
    train_metrics: Dict[str, float]
    test_metrics: Dict[str, float]
    stopped_early: bool
    images_per_sec_per_chip: float
    mfu: Optional[float] = None          # model-FLOPs utilization per chip
                                         # (None off-TPU / when XLA cost
                                         # analysis is unavailable)
    mesh: Any = None                     # the training mesh — needed by the
                                         # SPMD (multi-host) linear-eval path


def _range_check(batch: Dict[str, np.ndarray], vocab_rows: int = 0) -> None:
    """The reference's startup input contract: augmented pixels must stay in
    [0,1] (main.py:486-490) — hard failure, not a warning.  Step-placement
    batches ship RAW pixels instead of views; their contract is dtype
    uint8 (the step divides by 255 on device); a token batch's is ids
    below ``vocab_rows``, the embedding rows this chip holds."""
    if np.asarray(batch.get("view1", 0.0)).dtype.kind == "i":
        # token views: ids, whose range the embedding's rows bound
        for key in ("view1", "view2"):
            v = np.asarray(batch[key])
            if v.min() < 0 or v.max() >= vocab_rows:
                raise ValueError(
                    f"token batch {key} holds ids outside [0, {vocab_rows})"
                    f": min={v.min()} max={v.max()}")
        return
    if "images" in batch:
        v = np.asarray(batch["images"])
        if v.dtype != np.uint8:
            raise ValueError(
                f"augment_placement='step' raw batch must be uint8, got "
                f"{v.dtype} (the H2D-bandwidth contract, data/loader.py "
                f"_raw_pipeline)")
        return
    for key in ("view1", "view2"):
        v = np.asarray(batch[key])
        lo, hi = float(v.min()), float(v.max())
        if lo < 0.0 or hi > 1.0:
            raise ValueError(
                f"augmented batch {key} out of [0,1]: min={lo} max={hi} "
                f"(reference contract main.py:486-490)")


def fit(cfg: Config, *, loader: Optional[LoaderBundle] = None,
        grapher: Optional[Grapher] = None, verbose: bool = True) -> FitResult:
    """Train per the config; returns final state + last epoch metrics."""
    if cfg.device.distributed_master:
        initialize_distributed(cfg.device.distributed_master)
    if cfg.device.check_numerics:
        # NaN/inf fail-fast (the §5.2 hygiene the reference lacks)
        jax.config.update("jax_debug_nans", True)

    n_devices = jax.device_count()
    tp_sp = cfg.device.model_parallel * cfg.device.sequence_parallel
    if cfg.device.num_replicas * tp_sp != n_devices:
        # The reference asserts topology instead (main.py:809); we adapt the
        # data axis to the hardware and keep tp/sp as configured.
        if tp_sp > n_devices or n_devices % tp_sp != 0:
            raise ValueError(
                f"model_parallel x sequence_parallel = {tp_sp} does not "
                f"divide the {n_devices} available devices")
        cfg = cfg.replace(device=dataclasses.replace(
            cfg.device, num_replicas=n_devices // tp_sp))
    mesh = build_mesh(MeshSpec(data=cfg.device.num_replicas,
                               sequence=cfg.device.sequence_parallel,
                               model=cfg.device.model_parallel,
                               dcn_data=cfg.device.dcn_data_parallel))

    if loader is None:
        loader = get_loader(cfg, shard_eval=cfg.device.shard_eval)
    rcfg = resolve(cfg, num_train_samples=loader.num_train_samples,
                   num_test_samples=loader.num_test_samples,
                   output_size=loader.output_size,
                   input_shape=loader.input_shape,
                   num_valid_samples=loader.num_valid_samples)

    # The compile plan (parallel/compile_plan.py) owns every sharding
    # decision; the trainer holds it for run-log provenance and for the
    # checkpoint codec (ZeRO-1 state is canonicalized at the save/restore
    # boundary so checkpoints stay mesh-size portable).
    from byol_tpu.parallel.compile_plan import build_plan
    plan = build_plan(mesh, zero1=cfg.device.zero1 == "on")

    # Flight recorder (observability/spans.py): every hot-loop phase below
    # runs under a named span; goodput.py folds them into the wall-time
    # partition per epoch.  Under --spans on it is the PROCESS's recorder,
    # which holds the set-up and JAX's compile spans whatever this flag
    # says: start-up, compiles and the hot loop are one timeline and one
    # export.  --spans off hands every `with` below a shared no-op (records
    # nothing — the hot loop is byte-for-byte the unspanned one).
    recorder = (spans_lib.PROCESS if cfg.device.spans == "on"
                else spans_lib.NULL)
    # what records on the module default (the token feed) lands here too
    spans_lib.set_default(recorder)
    # The meter's first window opens HERE, before the model build, so
    # startup (build + first-step compile) is attributed, not lost.
    goodput_meter = goodput_lib.GoodputMeter(recorder)

    from byol_tpu.core.rng import root_key
    # setup_training opens ``startup/build`` itself (training/build.py)
    net, state, train_step, eval_step, schedule = setup_training(
        rcfg, mesh, root_key(cfg.device.seed), plan=plan)
    if verbose:
        from byol_tpu.utils import number_of_parameters
        print(f"model: {cfg.model.arch}, "
              f"{number_of_parameters(state.params) / 1e6:.2f}M params "
              f"(main.py:447-449 analog)")
        if rcfg.accum_steps > 1:
            # Accumulation happens INSIDE the jitted step: every count in
            # this loop (state.step, steps_per_train_epoch, the LR schedule
            # argument, EMA tau, throughput per effective batch) is an
            # OPTIMIZER step — microbatches are invisible above steps.py.
            print(f"grad accumulation: {rcfg.accum_steps} microbatches of "
                  f"{rcfg.microbatch_size} (global) per optimizer step, "
                  f"bn_mode={cfg.optim.accum_bn_mode}, effective batch "
                  f"{rcfg.global_batch_size}")

    name = run_name(cfg)
    if grapher is None:
        grapher = Grapher(cfg.task.grapher, logdir=cfg.task.log_dir,
                          run_name=name)
    saver = ModelSaver(
        os.path.join(cfg.model.model_dir, name),
        early_stop=cfg.optim.early_stop,
        burn_in_interval=int(0.1 * cfg.task.epochs),
        larger_is_better=False,
        max_early_stop_steps=10)

    # Structured run log (observability/events.py): every fit produces a
    # schema-versioned run.jsonl next to the grapher output — run header,
    # interval health records, epoch/checkpoint/anomaly events — the same
    # machine-readable format bench.py emits per row.  Rank-0 discipline
    # like the grapher.
    events: Optional[RunLog] = None
    if jax.process_index() == 0:
        # best_effort: an unopenable log_dir at startup or a disk filling
        # mid-run disables the log with a warning — the observability layer
        # must never kill the multi-hour training run it observes (same
        # contract bench.py applies)
        events = RunLog(os.path.join(cfg.task.log_dir, name, "run.jsonl"),
                        best_effort=True)
        events.emit(
            "run_header", config=cfg.to_dict(), jax_version=jax.__version__,
            backend=jax.default_backend(), device=describe_device(),
            run_name=name,
            mesh_shape={str(k): int(v) for k, v in mesh.shape.items()},
            n_devices=jax.device_count(),
            steps_per_train_epoch=rcfg.steps_per_train_epoch,
            global_batch_size=rcfg.global_batch_size,
            # which compile plan produced this run: mesh axes, zero1
            # on/off, per-entry-point donation (events.py validates shape)
            sharding_plan=plan.describe())

    # Telemetry sink: asynchronous (>= interval-step lag) readback of the
    # in-graph health vector + anomaly rules.  Created on EVERY process so
    # --nan-policy halt stops the whole pod, not just rank 0; only rank 0
    # writes events.
    sink: Optional[TelemetrySink] = None
    telemetry_mode = cfg.device.telemetry
    if telemetry_mode != "off":
        sink = TelemetrySink(cfg.device.telemetry_interval,
                             nan_policy=cfg.device.nan_policy,
                             events=events, verbose=verbose)

    # Hung-collective watchdog (§5.2): a lost host shows up as a readback
    # that never returns — in the train-epoch readback, but equally in the
    # eval loops, the linear-eval extraction, and the checkpoint flush.
    # Created up-front so every blocking window below can pet it.
    watchdog = Watchdog(cfg.device.watchdog_timeout)

    # Eval batches are padded to the fixed per-host batch so all of them
    # share one compiled executable and shard cleanly on the data axis.
    host_eval_batch = rcfg.global_batch_size // jax.process_count()
    tokens = len(rcfg.input_shape) == 1
    vocab_rows = 0
    if tokens:
        from byol_tpu.models.registry import held_vocab_rows
        vocab_rows = held_vocab_rows(cfg.model.arch, cfg.model.layer_share)

    def _all_pad_batch():
        """Zero-row batch for a host that drained its eval shard early;
        pad_batch fills it to the static shape with an all-zero mask."""
        z = np.zeros((0,) + tuple(rcfg.input_shape),
                     np.int32 if tokens else np.float32)
        return {"view1": z, "view2": z, "label": np.zeros((0,), np.int32)}

    def run_eval(state, batches=None) -> MetricAccumulator:
        # The eval dispatch loop + its eventual readback are a blocking
        # window on pods (eval_step collectives): pet the watchdog around
        # it so a collective that wedges HERE is caught, not just one in
        # the train-epoch readback.
        watchdog.pet()
        acc = MetricAccumulator()
        src = loader.test_loader if batches is None else batches
        if jax.process_count() > 1:
            # hosts' eval shards can differ by one batch (interleaved
            # image_folder shards): iterate in lockstep or the pod
            # deadlocks in eval_step's collectives
            from byol_tpu.parallel.lockstep import lockstep_iter
            src = lockstep_iter(src, _all_pad_batch)
        for batch in src:
            dev_batch = shard_batch_to_mesh(
                pad_batch(batch, host_eval_batch), mesh)
            acc.update(eval_step(state, dev_batch))
            if cfg.device.debug_step:
                break
        return acc

    # Checkpoints always store the CANONICAL state layout (replicated,
    # unflattened — identical to the plan layout unless zero1 is on), so a
    # ckpt written under either --zero1 flag or any mesh size restores
    # under any other (reshard-on-restore, tests/test_checkpoint.py).
    def _save_state(state):
        return plan.to_canonical(state)

    def _restore(template_state, *, best):
        restored, epoch = saver.restore(
            plan.canonical_template(template_state), best=best)
        return plan.from_canonical(restored), epoch

    init_epoch = 0
    if saver.stopped_early:
        # This run already early-stopped (durable marker in the checkpoint
        # metadata): restore the best state and return without re-burning
        # patience-worth of epochs.
        state, init_epoch = _restore(state, best=True)
        acc = run_eval(state)
        test_metrics = {k: float(v) for k, v in acc.result().items()}
        watchdog.stop()
        if verbose:
            print(f"run already early-stopped at best epoch "
                  f"{init_epoch - 1}; nothing to train")
        if events is not None:
            events.emit("run_end", epoch=init_epoch - 1, stopped_early=True,
                        already_stopped=True)
            events.close()
        saver.close()
        grapher.close()
        return FitResult(state=state, epoch=init_epoch - 1, train_metrics={},
                         test_metrics=test_metrics, stopped_early=True,
                         images_per_sec_per_chip=0.0, mesh=mesh)
    resume_skip = 0
    if saver.has_checkpoint():
        # Plain resume continues from the LAST checkpoint — restoring BEST
        # here would silently discard all post-best training and reset the
        # persisted patience counter on every relaunch.  Best-restore is
        # reserved for the early-stop terminal path (main.py:767-769).
        state, init_epoch = _restore(state, best=False)
        if not cfg.device.debug_step:
            # A preemption checkpoint (save-on-SIGTERM) lands mid-epoch: the
            # step counter is then not a multiple of steps_per_epoch.  Data
            # order is deterministic per (seed, epoch), so resume EXACTLY:
            # re-enter the interrupted epoch and skip the batches its saved
            # steps already consumed.  (debug_step runs one batch per epoch
            # regardless, so the counter arithmetic doesn't apply there.)
            done_in_epoch = int(state.step) % rcfg.steps_per_train_epoch
            if done_in_epoch:
                init_epoch -= 1
                resume_skip = done_in_epoch
        if verbose:
            print(f"resumed from epoch {init_epoch - 1} "
                  f"(best loss {saver.best_metric}"
                  + (f", re-entering epoch {init_epoch} at batch "
                     f"{resume_skip}" if resume_skip else "") + ")")
    resume_epoch = init_epoch

    timer = StepTimer(rcfg.global_batch_size, n_devices)
    flops_resolved = False
    first_dispatch = True
    train_metrics: Dict[str, float] = {}
    test_metrics: Dict[str, float] = {}
    stopped = False
    first_batch_checked = False
    epoch = init_epoch

    # Preemption notice (SIGTERM on TPU pods / SLURM) -> checkpoint NOW and
    # exit 143 so the scheduler requeues and the relaunch resumes from LAST
    # (§5.3; the reference loses everything since its last best-save).
    preempted = threading.Event()
    old_sigterm = None
    if cfg.device.save_on_signal:
        try:
            old_sigterm = signal.signal(
                signal.SIGTERM, lambda signum, frame: preempted.set())
        except ValueError:   # not the main thread (e.g. test runner worker)
            old_sigterm = None

    def _maybe_preempt_save():
        if not preempted.is_set():
            return
        # epoch is partially trained: persist it as LAST (never best).  The
        # step/EMA counters are exact; the relaunch detects the mid-epoch
        # counter (step % steps_per_epoch != 0), re-enters this epoch and
        # skips the batches already trained — an exact resume.
        saver.store.save(epoch, _save_state(state), is_best=False)
        saver.store._ckptr.wait_until_finished()
        print(f"SIGTERM: checkpointed epoch {epoch} at step "
              f"{int(state.step)}; exiting 143 for requeue")
        raise SystemExit(143)

    # Host-side optimizer-step counter for the telemetry sink: int() on the
    # INITIAL state is free (already materialized); per-step int(state.step)
    # would be the host sync the whole telemetry design avoids.
    global_step = int(state.step)

    def _export_trace() -> None:
        """Write the flight-recorder ring as a Chrome-trace JSON next to
        run.jsonl (rank 0, spans on).  Best-effort like the run log: the
        trace is evidence, never a reason to kill the run that produced
        it."""
        if events is None or not recorder.enabled:
            return
        try:
            spans_lib.export_chrome_trace(
                recorder.records(),
                os.path.join(cfg.task.log_dir, name, "trace.json"))
        except OSError as e:
            print(f"spans: trace export failed ({e!r}); continuing",
                  file=sys.stderr)

    def _halt_dump(err: NanHaltError, epoch: int) -> None:
        """--nan-policy halt tripped: dump step/state metadata to the run
        log before the raise propagates (the post-mortem the operator
        reads instead of a bare traceback).  The goodput totals and the
        flight-recorder trace land too — a halted run is exactly the one
        whose timeline gets read."""
        if events is not None:
            events.emit("state_dump", step=err.step, epoch=epoch,
                        state_step=int(state.step),
                        ema_step=int(state.ema_step),
                        lr=float(schedule(int(state.step))),
                        reason="nonfinite", health=err.record,
                        run_name=name)
            if recorder.enabled:
                goodput_meter.final(events=events, halted=True)
                _export_trace()
            events.close()
        watchdog.stop()
        saver.close()
        grapher.close()

    for epoch in range(init_epoch, cfg.task.epochs):
        # ---- train (execute_graph prefix='train', main.py:665-677) -------
        loader.set_all_epochs(epoch)
        acc = MetricAccumulator()
        t0 = time.time()
        sample_batch = None
        watchdog.pet()

        def epoch_batches():
            """Exactly ``steps_per_train_epoch`` batches, every epoch, on
            every host.  The step count is the load-bearing constant (it
            feeds the EMA tau schedule, reference main.py:424-425), and on
            pods each train step is an SPMD collective — so a host whose
            shard yields one batch fewer (interleaved image_folder shards)
            must WRAP to its shard's start rather than stop early and
            deadlock the others, and a host with one extra batch must stop
            at the count (the DistributedSampler pad/truncate analog)."""
            produced = 0
            since_reset = 0
            it = iter(loader.train_loader)
            while produced < rcfg.steps_per_train_epoch:
                batch = next(it, None)
                if batch is None:
                    if since_reset == 0:
                        raise ValueError(
                            "train loader yielded no batches: per-host "
                            "shard smaller than the host batch")
                    it = iter(loader.train_loader)
                    since_reset = 0
                    continue
                since_reset += 1
                yield batch
                produced += 1

        def tapped_batches():
            nonlocal first_batch_checked, sample_batch
            # exact mid-epoch resume: drop the leading batches the preempted
            # run already trained (deterministic order per (seed, epoch))
            skip = resume_skip if epoch == resume_epoch else 0
            for i, batch in enumerate(epoch_batches()):
                if i < skip:
                    continue
                if not first_batch_checked:
                    _range_check(batch, vocab_rows)
                    first_batch_checked = True
                if sample_batch is None and "view1" in batch and not tokens:
                    # step placement ships raw pixels — no host-side views
                    # to grid; the eval path still plots resized images
                    sample_batch = {k: np.asarray(batch[k][:64])
                                    for k in ("view1", "view2")}
                yield batch

        # double-buffered H2D: batch N+1 transfers while step N computes;
        # the meter reports this epoch's H2D payload + starvation next to
        # the throughput numbers
        input_meter = InputPipelineMeter()
        timer.reset_ticks()
        for dev_batch in prefetch_to_mesh(tapped_batches(), mesh,
                                          meter=input_meter,
                                          recorder=recorder):
            if not flops_resolved:
                # Once per fit: FLOPs of the real train step via XLA
                # cost analysis (observability/flops.py) -> MFU next to
                # every throughput number.  Lowering only traces; must
                # precede the first call because the step donates its
                # input state.
                flops_resolved = True
                from byol_tpu.observability import flops as flops_lib
                with recorder.span("startup/cost_analysis"), mesh:
                    step_flops = flops_lib.cost_analysis_flops(
                        train_step, state, dev_batch)
                if step_flops:
                    timer.set_flops(step_flops / rcfg.global_batch_size,
                                    flops_lib.chip_peak_tflops())
            # The FIRST dispatch of a fit pays trace + XLA compile
            # before the async dispatch returns: attribute it to the
            # startup_compile bucket, not to productive step time.
            with recorder.span("startup/compile" if first_dispatch
                               else "train/dispatch"):
                state, metrics = train_step(state, dev_batch)
            first_dispatch = False
            global_step += 1
            timer.tick()
            if sink is not None:
                # 'health' is the packed in-graph diagnostics vector —
                # popped so the scalar accumulator (and the epoch
                # float() conversions) only ever see scalars.  'step'
                # mode: lagged async readback; 'epoch' mode: hold the
                # newest, drained for free after the epoch readback.
                health_vec = metrics.pop("health")
                try:
                    with recorder.span("telemetry/readback"):
                        if telemetry_mode == "step":
                            sink.offer(global_step, health_vec)
                        else:
                            sink.hold(global_step, health_vec)
                except NanHaltError as e:
                    _halt_dump(e, epoch)
                    raise
            acc.update(metrics)  # device-side running sum; no host sync
            _maybe_preempt_save()
            if cfg.device.fault_at_step and \
                    int(state.step) == cfg.device.fault_at_step:
                # fault injection (§5.3): die mid-epoch like a
                # preempted pod worker; a relaunch must resume from
                # the last checkpoint.
                raise SystemExit(
                    f"fault injected at step {int(state.step)} "
                    f"(--fault-at-step)")
            if cfg.device.debug_step:  # single-minibatch smoke
                break                  # (main.py:630)
        with recorder.span("train/epoch_readback"):
            train_metrics = {k: float(v) for k, v in acc.result().items()}
        # acc.result() is a D2H readback of sums depending on every step —
        # the only sync this platform can't fake, so the elapsed time (and
        # the throughput derived from it) is honest (StepTimer docstring).
        # The span above is the device-catch-up window, counted as
        # PRODUCTIVE by goodput.py: the host blocks here exactly until the
        # queued compute drains.
        train_elapsed = time.time() - t0
        timer.record_epoch(acc.count, train_elapsed)
        watchdog.pet()  # readback returned: the collectives are alive
        if sink is not None:
            # epoch boundary: the readback above already synchronized, so
            # draining the pending/held vectors costs nothing extra
            try:
                with recorder.span("telemetry/drain"):
                    sink.drain()
            except NanHaltError as e:
                _halt_dump(e, epoch)
                raise
        # the readback/eval/checkpoint windows dominate the epoch's
        # wall-clock — a preemption notice landing there must not wait for
        # the next epoch's batch loop (the grace period would expire first)
        _maybe_preempt_save()
        if verbose:
            print(epoch_log_line("train", epoch,
                                 acc.count * rcfg.global_batch_size,
                                 train_elapsed, train_metrics))
            print(input_log_line(epoch, input_meter))

        if events is not None:
            # step-time p50/p99 (dispatch intervals; see StepTimer.tick):
            # optional additive fields — absent when the epoch had too few
            # steps for a tail (e.g. debug_step)
            events.emit("epoch", epoch=epoch, split="train",
                        step=global_step, metrics=train_metrics,
                        seconds=round(train_elapsed, 3),
                        input_pipeline=input_meter.result(),
                        images_per_sec_per_chip=(
                            timer.images_per_sec_per_chip()),
                        **(timer.epoch_step_quantiles() or {}))

        # ---- eval (prefix='test', main.py:680-692) -----------------------
        t0 = time.time()
        with recorder.span("eval/run", split="test"):
            acc = run_eval(state)
            test_metrics = {k: float(v) for k, v in acc.result().items()}
        watchdog.pet()  # eval readback returned
        _maybe_preempt_save()
        if verbose:
            # total_weight = exact valid rows (pad rows excluded)
            n_eval = acc.total_weight()
            print(epoch_log_line(
                "test", epoch,
                int(n_eval) if n_eval is not None
                else acc.count * rcfg.global_batch_size,
                time.time() - t0, test_metrics))
        if events is not None:
            events.emit("epoch", epoch=epoch, split="test",
                        step=global_step, metrics=test_metrics)

        # ---- valid split (num_valid_samples contract, main.py:421-423):
        # evaluated + logged per epoch; early stop still keys off TEST loss
        # (reference parity, main.py:752,766) -------------------------------
        if loader.make_valid_iter is not None:
            t0 = time.time()
            with recorder.span("eval/run", split="valid"):
                vacc = run_eval(state, loader.valid_loader)
                valid_metrics = {k: float(v)
                                 for k, v in vacc.result().items()}
            if verbose:
                n_va = vacc.total_weight()
                print(epoch_log_line(
                    "valid", epoch,
                    int(n_va) if n_va is not None
                    else vacc.count * rcfg.global_batch_size,
                    time.time() - t0, valid_metrics))
            grapher.register_plots(valid_metrics, epoch, prefix="valid")
            if events is not None:
                events.emit("epoch", epoch=epoch, split="valid",
                            step=global_step, metrics=valid_metrics)

        # ---- observability (main.py:646-657,764,773-779) -----------------
        grapher.register_plots(train_metrics, epoch, prefix="train")
        grapher.register_plots(test_metrics, epoch, prefix="test")
        grapher.add_scalar("lr_scalar", float(schedule(int(state.step))),
                           epoch)
        grapher.add_scalar("images_per_sec_per_chip",
                           timer.images_per_sec_per_chip(), epoch)
        for key, value in input_meter.result().items():
            grapher.add_scalar(f"{key}_scalar", value, epoch)
        epoch_mfu = timer.mfu()
        if epoch_mfu is not None:
            grapher.add_scalar("mfu_scalar", epoch_mfu, epoch)
        if sample_batch is not None:
            grapher.register_images(
                {"aug1_imgs": sample_batch["view1"],
                 "aug2_imgs": sample_batch["view2"]}, epoch, prefix="train")
        if epoch == 2:
            # config + cluster identity posted once (main.py:773-779; the
            # reference also stamps the AWS instance id, main.py:128-130)
            from byol_tpu.utils import (get_aws_instance_id, get_slurm_id,
                                        get_tpu_env)
            meta = {"slurm_id": get_slurm_id(),
                    "aws_instance_id": get_aws_instance_id(),
                    "tpu": get_tpu_env()}
            grapher.add_text("config", cfg.to_json() + "\n" + str(meta),
                             epoch)
        grapher.save()

        # ---- checkpoint + early stop (main.py:766-769) -------------------
        # The save serializes device state (a D2H readback window on pods):
        # pet around it so a wedged collective during the flush is caught.
        watchdog.pet()
        with recorder.span("checkpoint/save", epoch=epoch):
            stop_now = saver(test_metrics.get("loss_mean", float("inf")),
                             epoch, _save_state(state))
        watchdog.pet()
        if events is not None:
            events.emit("checkpoint", epoch=epoch, step=global_step,
                        metric=test_metrics.get("loss_mean"),
                        best_metric=saver.best_metric,
                        early_stop=bool(stop_now))
        # ---- goodput fold: close this epoch's wall-time window ------------
        # (train + eval + valid + grapher + checkpoint), attribute its
        # spans, and emit the goodput + span_stats events.  Every second
        # since the previous fold lands in exactly one bucket.  Spans off:
        # no fold — an empty ring would "attribute" the whole epoch to
        # host_other, a claim the run never measured.
        if recorder.enabled:
            goodput_meter.fold(scope="epoch", epoch=epoch, mfu=timer.mfu(),
                               events=events,
                               images_per_sec_per_chip=(
                                   timer.images_per_sec_per_chip()))
        if stop_now:
            state, _ = _restore(state, best=True)
            with recorder.span("eval/run", split="test_best"):
                acc = run_eval(state)
                test_metrics = {k: float(v)
                                for k, v in acc.result().items()}
            stopped = True
            if verbose:
                print(f"early stop at epoch {epoch}; restored best "
                      f"(loss {saver.best_metric:.4f})")
            break

    watchdog.stop()
    if old_sigterm is not None:
        signal.signal(signal.SIGTERM, old_sigterm)
    # run-scope goodput totals (the end-of-run waterfall `python -m
    # byol_tpu report` renders) + the Chrome-trace flight-recorder dump
    if recorder.enabled:
        goodput_meter.final(events=events, mfu=timer.mfu())
        _export_trace()
    if events is not None:
        events.emit(
            "run_end", epoch=epoch, stopped_early=stopped,
            images_per_sec_per_chip=timer.images_per_sec_per_chip(),
            anomalies=(len(sink.anomalies) if sink is not None else 0))
        events.close()
    saver.close()
    grapher.close()
    return FitResult(state=state, epoch=epoch, train_metrics=train_metrics,
                     test_metrics=test_metrics, stopped_early=stopped,
                     images_per_sec_per_chip=timer.images_per_sec_per_chip(),
                     mfu=timer.mfu(), mesh=mesh)
