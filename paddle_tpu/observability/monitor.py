"""TrainingMonitor: per-step training telemetry into the shared registry.

One monitor instance watches one training loop (hybrid engine, static
Executor, or hapi `Model.fit`) and reports, per step:

  - wall time (`train/step_time_s` histogram) and a step counter;
  - tokens/sec and samples/sec when the caller supplies batch sizes;
  - MFU (`train/mfu`) from a supplied flops-per-token against the chip's
    peak bf16 FLOP/s (auto-detected on TPU; None on CPU disables MFU);
  - HBM high-water mark (`train/hbm_high_water_bytes` gauge — gauges track
    a running max, so this is the high-water across the run) via
    `paddle_tpu.device.max_memory_allocated` (PJRT peak_bytes_in_use);
  - trace-time compile counters (`train/compiles`): callers bump
    `record_compile` as a Python side effect inside their jitted step, so
    it counts XLA compilations exactly (the serving pattern);
  - a NaN/inf loss monitor with a configurable action — 'raise' fails
    loudly (NonFiniteLossError), 'warn' emits a RuntimeWarning and keeps
    counting `train/non_finite_loss`, 'none' skips the check AND the
    device sync it requires.

Host/device split: nothing here runs inside traced code. `end_step(loss=…)`
reads the loss back to host when nan_action != 'none' — that device sync
makes the recorded wall time the true step time; with 'none' the wall time
is dispatch-only (honest for pipelined loops that never sync).

Per-rank heartbeat-age gauges (`comm/heartbeat_age_s{rank=…}`) are fed into
the same registry by `distributed/comm_monitor.py`'s heartbeat thread;
`heartbeat_ages()` reads them back.
"""

from __future__ import annotations

import contextlib
import math
import time
import warnings

import numpy as np

from paddle_tpu.observability.registry import global_registry

__all__ = ["TrainingMonitor", "NonFiniteLossError"]


class NonFiniteLossError(FloatingPointError):
    """Raised by nan_action='raise' when a step's loss is NaN/inf."""


class TrainingMonitor:
    def __init__(self, registry=None, *, source="train", flops_per_token=None,
                 peak_flops="auto", nan_action="warn"):
        if nan_action not in ("raise", "warn", "none"):
            raise ValueError("nan_action must be 'raise', 'warn' or 'none'")
        self.registry = registry if registry is not None else global_registry()
        self.source = str(source)
        self.flops_per_token = flops_per_token
        self.peak_flops = peak_flops  # 'auto' resolved lazily on first use
        self.nan_action = nan_action
        self.steps = 0
        self.last = {}
        self._t0 = None

    def _labels(self):
        return {"source": self.source}

    def _resolve_peak(self):
        if self.peak_flops == "auto":
            from paddle_tpu.observability.hardware import detect_peak_flops

            self.peak_flops = detect_peak_flops()
        return self.peak_flops

    # -- compile counting (call at TRACE time inside the jitted step) -------
    def record_compile(self, kind="train_step"):
        self.registry.inc("train/compiles",
                          labels={"source": self.source, "kind": kind})

    # -- step bracketing ----------------------------------------------------
    def start_step(self):
        self._t0 = time.perf_counter()

    def end_step(self, loss=None, tokens=None, samples=None):
        if self._t0 is None:
            raise RuntimeError("end_step() without a matching start_step()")
        loss_value = None
        if loss is not None and self.nan_action != "none":
            # device->host readback: syncs, so the wall time below is the
            # true step time rather than async dispatch time
            loss_value = float(np.asarray(loss))
        wall = time.perf_counter() - self._t0
        self._t0 = None
        return self.record_step(wall, loss_value=loss_value, tokens=tokens,
                                samples=samples)

    @contextlib.contextmanager
    def step(self, tokens=None, samples=None):
        """Wall-time-only bracket for loops that don't surface a loss."""
        self.start_step()
        try:
            yield self
        finally:
            if self._t0 is not None:  # end_step not called inside the block
                self.end_step(tokens=tokens, samples=samples)

    def record_step(self, wall_s, loss_value=None, tokens=None, samples=None):
        r, lbl = self.registry, self._labels()
        self.steps += 1
        stats = {"step_time_s": wall_s}
        r.inc("train/steps", labels=lbl)
        r.observe("train/step_time_s", wall_s, labels=lbl)
        if tokens:
            tps = tokens / wall_s if wall_s > 0 else 0.0
            stats["tokens_per_sec"] = tps
            r.observe("train/tokens_per_sec", tps, labels=lbl)
            peak = self._resolve_peak()
            if self.flops_per_token and peak:
                mfu = tps * self.flops_per_token / peak
                stats["mfu"] = mfu
                r.observe("train/mfu", mfu, labels=lbl)
        if samples:
            sps = samples / wall_s if wall_s > 0 else 0.0
            stats["samples_per_sec"] = sps
            r.observe("train/samples_per_sec", sps, labels=lbl)
        try:
            from paddle_tpu import device as _dev

            hbm = _dev.max_memory_allocated()
        except Exception:
            hbm = 0
        stats["hbm_high_water_bytes"] = hbm
        r.set_gauge("train/hbm_high_water_bytes", hbm, labels=lbl)
        self.last = stats
        if loss_value is not None:
            stats["loss"] = loss_value
            if math.isfinite(loss_value):
                r.set_gauge("train/loss", loss_value, labels=lbl)
            elif self.nan_action != "none":
                # 'none' skips the check even when a caller hands the loss
                # in directly (hapi fit always has it on host)
                r.inc("train/non_finite_loss", labels=lbl)
                msg = (f"[telemetry] non-finite loss ({loss_value}) at "
                       f"monitored step {self.steps} (source="
                       f"{self.source!r})")
                if self.nan_action == "raise":
                    raise NonFiniteLossError(msg)
                warnings.warn(msg, RuntimeWarning, stacklevel=3)
        return stats

    # -- cross-subsystem reads ---------------------------------------------
    def heartbeat_ages(self):
        """{rank: age_seconds} from the comm-monitor's per-rank
        heartbeat-age gauges (empty when no CommMonitor is running)."""
        out = {}
        for lbl, v in self.registry.gauge_series(
                "comm/heartbeat_age_s").items():
            for part in lbl.split(","):
                if part.startswith("rank="):
                    out[int(part[len("rank="):])] = v
        return out
