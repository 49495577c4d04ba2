"""Metric loggers + profiling callbacks.

SURVEY.md §5 parity seats:

- tracing/profiling: the reference has none in-repo — PTL profiler flags
  pass through, and the only artifact is the sharded example's
  ``CUDACallback`` (epoch time / peak memory — our
  :class:`~ray_lightning_tpu.core.callbacks.EpochStatsCallback`).
  :class:`JaxProfilerCallback` is the TPU-native step up: it captures an XLA
  profiler trace (viewable in TensorBoard/Perfetto) for a window of steps.
- metrics/logging/observability: the reference transports
  ``callback_metrics`` rank-0 → driver; persistent logging is PTL's
  logger stack. :class:`CSVLogger` is the framework-owned equivalent —
  epoch-level metric rows on rank 0, resumable across restarts.
"""
from __future__ import annotations

import csv
import os
from typing import Any, Dict, Optional

import numpy as np

from ray_lightning_tpu.core.callbacks import Callback


class CSVLogger(Callback):
    """Append one metrics row per train epoch (+validation) to metrics.csv.

    Rank-0 only; the file lives under
    ``<default_root_dir>/<name>/version_<k>/metrics.csv`` like PTL's
    CSVLogger so downstream tooling works unchanged.
    """

    def __init__(self, save_dir: Optional[str] = None,
                 name: str = "tpu_logs", version: Optional[int] = None):
        self.save_dir = save_dir
        self.name = name
        self.version = version
        self._path: Optional[str] = None
        self._fieldnames: list = []

    @property
    def log_dir(self) -> Optional[str]:
        return os.path.dirname(self._path) if self._path else None

    def setup(self, trainer, pl_module, stage: str) -> None:
        if trainer.global_rank != 0 or self._path is not None:
            return
        root = self.save_dir or trainer.default_root_dir
        base = os.path.join(root, self.name)
        version = self.version
        if version is None:
            os.makedirs(base, exist_ok=True)
            existing = [
                int(d.split("_", 1)[1]) for d in os.listdir(base)
                if d.startswith("version_") and d.split("_", 1)[1].isdigit()
            ]
            version = max(existing) + 1 if existing else 0
        d = os.path.join(base, f"version_{version}")
        os.makedirs(d, exist_ok=True)
        self._path = os.path.join(d, "metrics.csv")

    def on_train_epoch_end(self, trainer, pl_module) -> None:
        if trainer.global_rank != 0 or self._path is None:
            return
        row: Dict[str, Any] = {
            "epoch": trainer.current_epoch,
            "step": trainer.global_step,
        }
        for k, v in trainer.callback_metrics.items():
            if hasattr(v, "__float__") or np.isscalar(v):
                # np.isscalar("abc") is True — a string metric (e.g. a
                # status tag) must be skipped, not crash the epoch
                try:
                    row[k] = float(v)
                except (TypeError, ValueError):
                    continue
        self._write(row)

    def _write(self, row: Dict[str, Any]) -> None:
        new_fields = [k for k in row if k not in self._fieldnames]
        if new_fields:
            self._fieldnames.extend(new_fields)
            # rewrite with the extended header (rows are few; epochs)
            rows = []
            if os.path.exists(self._path):
                with open(self._path) as f:
                    rows = list(csv.DictReader(f))
            with open(self._path, "w", newline="") as f:
                w = csv.DictWriter(f, fieldnames=self._fieldnames)
                w.writeheader()
                for r in rows:
                    w.writerow(r)
                w.writerow(row)
        else:
            with open(self._path, "a", newline="") as f:
                csv.DictWriter(f, fieldnames=self._fieldnames).writerow(row)

    def state_dict(self) -> Dict[str, Any]:
        return {"path": self._path, "fieldnames": self._fieldnames}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self._path = state.get("path")
        self._fieldnames = list(state.get("fieldnames", []))


class JaxProfilerCallback(Callback):
    """Capture an XLA profiler trace for a window of training steps.

    TPU-native tracing (SURVEY.md §5 "tracing/profiling: none in-repo"):
    starts ``jax.profiler`` at ``start_step`` and stops after
    ``num_steps``, writing a TensorBoard/Perfetto-compatible trace with
    device (MXU/HBM) timelines into ``<root>/profile``. Rank-0 only.
    With a wall-clock ``Trainer(telemetry=)`` the trainer's ``trainer.*``
    spans lie on the trace's host plane; ``python tools/trace_report.py
    --profile <root>/profile`` prints device time by named scope and the
    idle gaps by those spans (``docs/observability.md``).
    """

    def __init__(self, start_step: int = 5, num_steps: int = 3,
                 log_dir: Optional[str] = None):
        self.start_step = start_step
        self.num_steps = num_steps
        self.log_dir = log_dir
        self._active = False
        self._done = False          # one window per callback instance
        self._started_at: Optional[int] = None
        self.trace_dir: Optional[str] = None

    def on_train_batch_start(self, trainer, pl_module, batch,
                             batch_idx: int) -> None:
        if trainer.global_rank != 0 or self._active or self._done:
            return
        # >= (not ==): a run resumed PAST start_step must still profile —
        # with == the window is silently skipped forever. The window then
        # covers num_steps from wherever tracing actually started.
        if trainer.global_step >= self.start_step:
            import jax
            self.trace_dir = self.log_dir or os.path.join(
                trainer.default_root_dir, "profile")
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self._active = True
            self._started_at = trainer.global_step

    def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                           batch_idx: int) -> None:
        if not self._active:
            return
        if trainer.global_step >= self._started_at + self.num_steps:
            import jax
            trainer.block_until_ready()
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

    def teardown(self, trainer, pl_module, stage: str) -> None:
        if self._active:  # trace window larger than the run: close cleanly
            import jax
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
