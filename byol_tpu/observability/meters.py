"""Epoch metric accumulation + stdout logging + step timing.

Replaces the reference's dm-tree running-sum (main.py:607-608,634-635),
its per-epoch stdout line (main.py:638-643) and its coarse wall-clock
timing (main.py:572) with: a pytree accumulator (jax.tree_util — the
dm-tree TPU-native equivalent, SURVEY.md §2.4), the same log line format,
and a step timer reporting images/sec/chip — the BASELINE.json headline
metric the reference never measured (SURVEY.md §5.1).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, Optional

import jax
import numpy as np


class MetricAccumulator:
    """Running sum of metric pytrees, divided out at epoch end
    (main.py:607-608,634-635).

    The sum is accumulated with device ops (async dispatch) — no host sync
    per step, so the trainer's hot loop keeps running ahead of the chip;
    the only block is the ``result()`` readback at the epoch boundary.

    A metric dict containing ``_weight`` (per-batch valid-sample count, from
    pad+mask eval batching) is accumulated as a weighted mean instead: each
    metric is a mean over ``_weight`` samples, so the epoch value is
    sum(metric*w)/sum(w).  ``_weight`` never appears in ``result()``."""

    def __init__(self) -> None:
        self._sum: Optional[Any] = None
        self.count = 0

    def update(self, metrics: Any) -> None:
        if isinstance(metrics, dict) and "_weight" in metrics:
            w = metrics["_weight"]
            metrics = {k: (v if k == "_weight" else v * w)
                       for k, v in metrics.items()}
        if self._sum is None:
            self._sum = metrics
        else:
            self._sum = jax.tree_util.tree_map(
                lambda a, b: a + b, self._sum, metrics)
        self.count += 1

    def result(self) -> Dict[str, np.ndarray]:
        if self._sum is None:
            return {}
        if isinstance(self._sum, dict) and "_weight" in self._sum:
            total = float(np.asarray(self._sum["_weight"]))
            return {k: np.asarray(v) / max(total, 1.0)
                    for k, v in self._sum.items() if k != "_weight"}
        return jax.tree_util.tree_map(
            lambda s: np.asarray(s) / self.count, self._sum)

    def total_weight(self) -> Optional[float]:
        """Total valid-sample count when metrics carried ``_weight`` (pad+
        mask eval), else None — lets the epoch log report true samples."""
        if isinstance(self._sum, dict) and "_weight" in self._sum:
            return float(np.asarray(self._sum["_weight"]))
        return None


def epoch_log_line(prefix: str, epoch: int, num_samples: int,
                   elapsed_s: float, metrics: Dict[str, Any]) -> str:
    """The reference's one-line epoch summary (main.py:638-643):
    prefix, epoch, samples, seconds, loss, top1/top5; and, where a
    backbone's layers add a loss of their own to the step's
    (training/steps.py ``LAYER_LOSS``), that sum too."""
    def get(k):
        v = metrics.get(k)
        return float(np.asarray(v)) if v is not None else float("nan")
    return (f"{prefix}[Epoch {epoch}][{num_samples} samples]"
            f"[{elapsed_s:.2f} sec]: loss: {get('loss_mean'):.4f}\t"
            f"byol: {get('byol_loss_mean'):.4f}\t"
            f"linear: {get('linear_loss_mean'):.4f}\t"
            + (f"layers: {get('layer_loss_mean'):.4f}\t"
               if "layer_loss_mean" in metrics else "")
            + f"top1: {get('top1_mean'):.4f}\ttop5: {get('top5_mean'):.4f}")


class InputPipelineMeter:
    """Host input-pipeline health over one epoch (ISSUE 3 meters).

    Fed by ``prefetch_to_mesh``: the PRODUCER records how many host bytes
    each batch ships to the devices (the H2D payload) and the queue depth
    it leaves behind; the CONSUMER records how long it blocked waiting for
    the next device-resident batch (time-to-next-batch).  A wait above
    ``starvation_threshold_s`` counts as a STARVED step — the chip sat
    idle because the host pipeline could not keep up.

    Thread-safety: the producer thread writes byte/depth fields, the
    consumer thread writes wait fields; no field is written by both, and
    reads happen at the epoch boundary after iteration ends.
    """

    def __init__(self, starvation_threshold_s: float = 0.005) -> None:
        self.starvation_threshold_s = starvation_threshold_s
        self.h2d_bytes = 0           # host bytes shipped (producer)
        self.batches_produced = 0
        self._depth_sum = 0          # queue depth samples (producer)
        self.wait_seconds = 0.0      # consumer block time, total
        self.starved_seconds = 0.0   # consumer block time above threshold
        self.starved_steps = 0
        self.batches_consumed = 0
        self.first_fill_seconds = 0.0  # time-to-first-batch (pipeline
                                       # fill) — NOT starvation

    # ---- producer side ----------------------------------------------------
    def record_produced(self, nbytes: int, queue_depth: int) -> None:
        self.h2d_bytes += int(nbytes)
        self._depth_sum += int(queue_depth)
        self.batches_produced += 1

    # ---- consumer side ----------------------------------------------------
    def record_first_fill(self, seconds: float) -> None:
        """The epoch's first wait = producer startup + producing batch 1.
        Every pipeline pays it once; counting it as starvation would make
        a healthy run report a starved step per epoch."""
        self.first_fill_seconds += seconds
        self.batches_consumed += 1

    def record_wait(self, seconds: float) -> None:
        self.wait_seconds += seconds
        if seconds > self.starvation_threshold_s:
            self.starved_seconds += seconds
            self.starved_steps += 1
        self.batches_consumed += 1

    # ---- epoch-boundary readout -------------------------------------------
    def h2d_bytes_per_step(self) -> float:
        return (self.h2d_bytes / self.batches_produced
                if self.batches_produced else 0.0)

    def avg_queue_depth(self) -> float:
        return (self._depth_sum / self.batches_produced
                if self.batches_produced else 0.0)

    def result(self) -> Dict[str, float]:
        """Scalar dict for the grapher / epoch log."""
        return {"h2d_bytes_per_step": self.h2d_bytes_per_step(),
                "input_starved_seconds": self.starved_seconds,
                "input_starved_steps": float(self.starved_steps),
                "input_wait_seconds": self.wait_seconds,
                "input_first_fill_seconds": self.first_fill_seconds,
                "prefetch_queue_depth": self.avg_queue_depth()}


def input_log_line(epoch: int, meter: InputPipelineMeter) -> str:
    """One-line input-pipeline summary next to the train epoch line."""
    return (f"input[Epoch {epoch}]"
            f"[{meter.batches_consumed} batches]: "
            f"h2d: {meter.h2d_bytes_per_step() / 2 ** 20:.2f} MiB/step\t"
            f"starved: {meter.starved_seconds:.2f} sec "
            f"({meter.starved_steps} steps)\t"
            f"fill: {meter.first_fill_seconds:.2f} sec\t"
            f"queue depth: {meter.avg_queue_depth():.2f}")


class StepTimer:
    """images/sec/chip measured ONLY over host-synchronized intervals.

    Per-step host timestamps taken after async dispatch are meaningless —
    the host runs ahead of the chip (chip_smoke.py prints how far: the
    dispatch loop returns long before the steps finish).  The trainer
    instead calls ``record_epoch`` with an elapsed time whose endpoint is a
    D2H metric READBACK (``MetricAccumulator.result()``), which cannot
    complete before every step in the epoch has: the resulting rate is
    honest end-to-end throughput including the input pipeline.  (On the
    v5e under jax 0.9.0 a region ending in ``block_until_ready`` reads the
    same — 337.9 vs 340.9 ms/step, chip_smoke.py, PERF.md PR 22 — while
    the bare dispatch loop returned after 77 ms/step: it is dispatch-timed
    rates that lie, not ``block_until_ready``.)"""

    def __init__(self, global_batch: int, n_chips: int):
        self.global_batch = global_batch
        self.n_chips = max(n_chips, 1)
        self._rate = 0.0
        self._flops_per_sample: Optional[float] = None
        self._peak_tflops: Optional[float] = None
        # per-epoch dispatch timestamps for the step-time tail (bounded:
        # a pathological epoch must not grow host memory without limit)
        self._ticks: "deque[float]" = deque(maxlen=1 << 16)

    def set_flops(self, flops_per_sample: Optional[float],
                  peak_tflops: Optional[float]) -> None:
        """Arm MFU reporting (observability.flops); either None disarms."""
        self._flops_per_sample = flops_per_sample
        self._peak_tflops = peak_tflops

    def mfu(self) -> Optional[float]:
        from byol_tpu.observability.flops import mfu as _mfu
        return _mfu(self._rate, self._flops_per_sample, self._peak_tflops)

    def record_epoch(self, steps: int, elapsed_s: float) -> None:
        """Record one epoch's synchronized (steps, wall-clock) measurement;
        ``elapsed_s`` must end AFTER a device readback that depends on every
        step (see class docstring)."""
        if steps > 0 and elapsed_s > 0.0:
            self._rate = (self.global_batch * steps / elapsed_s
                          / self.n_chips)

    def images_per_sec_per_chip(self) -> float:
        """Most recent epoch's rate (0.0 before the first epoch ends)."""
        return self._rate

    # ---- step-time tail ---------------------------------------------------
    def tick(self) -> None:
        """Stamp one optimizer-step dispatch (one deque append — safe in
        the hot loop).  Consecutive tick intervals are DISPATCH-to-dispatch
        times: while the host runs ahead they understate true step time,
        but once the device queue applies backpressure they converge to
        it — the same signal the telemetry step_time_spike rule uses, and
        the only per-step timing a host can take without a sync.  The
        epoch MEAN stays the honest readback-synced number (record_epoch);
        these quantiles add the TAIL (p50/p99) that the mean hides."""
        self._ticks.append(time.perf_counter())

    def reset_ticks(self) -> None:
        """Start a fresh epoch window (epoch boundaries span eval/
        checkpoint — their gap must not pollute the next epoch's tail)."""
        self._ticks.clear()

    def epoch_step_quantiles(self) -> Optional[Dict[str, float]]:
        """p50/p99/max of this epoch's dispatch intervals, or None below
        3 intervals (a tail over one or two samples is noise, and the
        debug_step smoke has only one dispatch per epoch)."""
        if len(self._ticks) < 4:
            return None
        d = np.diff(np.asarray(self._ticks, np.float64))
        return {"step_time_p50_s": float(np.percentile(d, 50)),
                "step_time_p99_s": float(np.percentile(d, 99)),
                "step_time_max_s": float(d.max())}
