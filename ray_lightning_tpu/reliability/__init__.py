"""Fault tolerance: deterministic fault injection, bounded retry, and
crash-recovering supervisors.

The reference's launcher detects a dead worker and fails fast
(``test_worker_exception_fails_fast``); this package owns everything a
production stack needs *between* "error raised" and "request failed":

- :mod:`~ray_lightning_tpu.reliability.faults` — a seedable
  :class:`FaultPlan` that injects failures (raise / NaN-poison / stall)
  at named sites by dispatch index, so chaos paths are exercised
  deterministically from tests. Zero overhead when no plan
  is armed.
- :mod:`~ray_lightning_tpu.reliability.retry` — :class:`RetryPolicy`
  (bounded attempts, exponential backoff, deterministic jitter, optional
  deadline) and :func:`call_with_retry`.
- :mod:`~ray_lightning_tpu.reliability.supervisor` —
  :class:`ServeSupervisor` (rebuilds a crashed
  :class:`~ray_lightning_tpu.serve.engine.ServeEngine` and re-admits
  every in-flight request by replaying its prompt + emitted tokens, so
  greedy outputs are token-identical with and without faults) and
  :class:`FitSupervisor` (re-runs ``Trainer.fit`` with
  ``ckpt_path="auto"`` under the policy).
- :mod:`~ray_lightning_tpu.reliability.guard` — the trainer's
  non-finite loss/gradient guard helpers.
- :mod:`~ray_lightning_tpu.reliability.gang` — gang supervision for
  *distributed* fits: per-rank worker heartbeats, driver-side hang/death
  detection with per-rank postmortems (:class:`GangMonitor` /
  :class:`GangFailure`), and :class:`GangSupervisor`, which restarts the
  full gang on a fresh rendezvous and resumes from the newest committed
  checkpoint — elastically, at the surviving worker count, when
  ``elastic=True`` and no warm standby covers the loss.
- :mod:`~ray_lightning_tpu.reliability.elastic` — the warm recovery
  tiers: :class:`StandbyPool` (pre-spawned, pre-warmed executor actors
  promoted into dead rank slots so restarts stop paying actor spawn)
  and :class:`MemoryCheckpointStore` (last-k train states in host RAM,
  ring-buddy replicated, consulted ahead of disk by ``resume="auto"``).

See ``docs/reliability.md`` for the full semantics (fault sites, retry
contract, the replay-exactness argument, and ``resume="auto"``).
"""
from __future__ import annotations

import logging

logger = logging.getLogger("ray_lightning_tpu.reliability")


def log_suppressed(site: str, exc: BaseException, detail: str = "") -> None:
    """Record a swallowed exception instead of silently dropping it.

    The package-wide lint (``tests/test_lint_exceptions.py``) rejects
    ``except Exception:`` blocks that neither re-raise nor call this —
    every broad catch must leave a trace an operator can find. With a
    :class:`~ray_lightning_tpu.obs.Telemetry` handle activated, every
    suppression additionally lands on the event bus (site
    ``log.suppressed``) so chaos runs are observable, not just survivable.
    """
    logger.warning("suppressed at %s: %s: %s%s", site,
                   type(exc).__name__, exc,
                   f" ({detail})" if detail else "")
    from ray_lightning_tpu.obs import emit_global, get_global
    emit_global("log.suppressed", site=site, exc=type(exc).__name__,
                detail=detail)
    tel = get_global()
    if tel is not None:
        tel.metrics.counter(
            "reliability_suppressed_total",
            help="exceptions swallowed via log_suppressed").inc()


from ray_lightning_tpu.reliability.faults import (  # noqa: E402
    FaultPlan, FaultSpec, InjectedFault, MODE_EXIT, MODE_NAN, MODE_RAISE,
    MODE_STALL, SITE_CKPT_SAVE, SITE_LOADER_NEXT, SITE_RENDEZVOUS_INIT,
    SITE_SERVE_DISPATCH, SITE_SERVE_REPLICA, SITE_TRAIN_STEP,
    SITE_WORKER_EXIT, SITE_WORKER_STALL, arm, disarm, ensure_armed, fire,
    get_armed)
from ray_lightning_tpu.reliability.guard import NonFiniteError  # noqa: E402
from ray_lightning_tpu.reliability.retry import (  # noqa: E402
    RetriesExhausted, RetryPolicy, call_with_retry)
from ray_lightning_tpu.reliability.supervisor import (  # noqa: E402
    FitSupervisor, ServeSupervisor, failed_completion)
from ray_lightning_tpu.reliability.gang import (  # noqa: E402
    GangConfig, GangFailure, GangMonitor, GangSupervisor, HeartbeatEmitter,
    RankPostmortem)
from ray_lightning_tpu.reliability.elastic import (  # noqa: E402
    MemoryCheckpointClient, MemoryCheckpointStore, StandbyPool,
    get_memory_store, install_memory_store, ring_buddy, standby_warmup)

__all__ = [
    "FaultPlan", "FaultSpec", "InjectedFault", "MODE_EXIT", "MODE_NAN",
    "MODE_RAISE", "MODE_STALL", "SITE_CKPT_SAVE", "SITE_LOADER_NEXT",
    "SITE_RENDEZVOUS_INIT", "SITE_SERVE_DISPATCH", "SITE_SERVE_REPLICA",
    "SITE_TRAIN_STEP", "SITE_WORKER_EXIT", "SITE_WORKER_STALL", "arm",
    "disarm", "ensure_armed", "fire", "get_armed",
    "NonFiniteError", "RetriesExhausted", "RetryPolicy", "call_with_retry",
    "FitSupervisor", "ServeSupervisor", "failed_completion",
    "GangConfig", "GangFailure", "GangMonitor", "GangSupervisor",
    "HeartbeatEmitter", "RankPostmortem",
    "MemoryCheckpointClient", "MemoryCheckpointStore", "StandbyPool",
    "get_memory_store", "install_memory_store", "ring_buddy",
    "standby_warmup",
    "logger", "log_suppressed",
]
