"""Request/Completion dataclasses for the continuous-batching engine.

A :class:`Request` is one user generation call: its prompt, a per-request
token budget, and per-request sampling params — each batch row of the
engine's step program carries its *own* temperature/top_k/eos/seed, so
heterogeneous requests share one compiled program. ``max_new_tokens`` is a
per-row countdown inside the engine step (not a static scan length like
one-shot :func:`~ray_lightning_tpu.models.generate.generate`): a row
retires the moment it hits eos or exhausts its budget, and its KV slot is
handed to the next queued request mid-flight.

A :class:`Completion` is the retired request: the generated tokens (eos
included when sampled), why it stopped, and the latency breakdown a
load harness aggregates into p50/p99.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

#: the tenant class a request belongs to when none is named — engines
#: without tenancy configured only ever see this class, and a
#: TenantScheduler holding only this class is behaviorally identical
#: to the FIFO scheduler (see ray_lightning_tpu/serve/tenancy.py)
DEFAULT_TENANT = "default"

FINISH_EOS = "eos"            # sampled its eos id
FINISH_LENGTH = "length"      # exhausted max_new_tokens
FINISH_TIMEOUT = "timeout"    # deadline expired (queued or mid-decode)
FINISH_REJECTED = "rejected"  # shed at admission (trace replay only)
FINISH_FAILED = "failed"      # engine crash recovery exhausted its retries


class OccupancyError(RuntimeError):
    """Base for admission-control errors carrying occupancy context.

    Keyword context renders as a ``[k=v, ...]`` suffix on the message
    (None values omitted) and every key becomes an attribute, so
    shed-load callers can log actionable rejections instead of a bare
    "full" (:class:`~ray_lightning_tpu.serve.pages.SlotPoolFull`,
    :class:`~ray_lightning_tpu.serve.scheduler.QueueFull`)."""

    def __init__(self, message: str, **ctx):
        shown = [f"{k}={v}" for k, v in ctx.items() if v is not None]
        super().__init__(
            message + (f" [{', '.join(shown)}]" if shown else ""))
        for k, v in ctx.items():
            setattr(self, k, v)


@dataclasses.dataclass
class Request:
    """One generation request.

    ``seed`` defaults to the request id: the engine derives every sample
    key as ``fold_in(fold_in(engine_base, seed), step)``, so a request's
    token stream with ``temperature > 0`` is a pure function of
    ``(engine seed, request seed, step)`` — reproducible across arrival
    orders, slot assignments, and batch compositions. Distinct co-resident
    seeds are asserted at slot assignment (no key reuse across slots).

    ``deadline``: optional absolute clock value (in the driving client's
    clock units) after which the request is abandoned — dropped from the
    queue, or cancelled mid-decode with the tokens produced so far.
    """
    id: int
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    eos_id: Optional[int] = None
    seed: Optional[int] = None
    deadline: Optional[float] = None
    # tenant class (multi-tenant scheduling, serve/tenancy.py): which
    # per-class queue/quota/fair-share bucket this request rides.
    # Scheduling is ordering-only — the tenant never changes the
    # request's tokens — and the class assignment rides the request
    # object through crash replay and fleet failover re-admission.
    tenant: str = DEFAULT_TENANT
    # timing bookkeeping, stamped by the driving client (clock units)
    arrival_time: Optional[float] = None
    first_token_time: Optional[float] = None
    # crash-recovery replay (set by ServeSupervisor, never by submit):
    # tokens this request had already emitted before its engine died.
    # Prefill re-feeds prompt + replay_tokens and resumes the sampling
    # key stream at step len(replay_tokens) — replay-exact, see
    # docs/reliability.md.
    replay_tokens: Optional[List[int]] = None
    # stamped by a paged engine at admission: how many prompt tokens'
    # KV was adopted from the shared-prefix cache instead of computed
    # (0 = no hit / dense engine); surfaced on the Completion
    prefix_hit_tokens: int = 0
    # failure-containment ledger (serve/containment.py): how many
    # replica deaths this request has been co-batched with. Incremented
    # by the fleet on every failover that displaces the request (and by
    # ServeSupervisor on engine-level recoveries) and, like
    # ``replay_tokens``, rides the request object through snapshot and
    # re-admission. At ``FleetConfig.max_request_failovers`` the request
    # retires ``failed`` with its partial tokens instead of consuming
    # another replica; a clean probation run resets it to 0. This is an
    # IMPLICATION count, not proof of guilt — innocents co-batched with
    # a poison request are implicated too, which is exactly what the
    # probation path exists to sort out (docs/reliability.md).
    crash_implications: int = 0
    # LoRA adapter name (multi-adapter serving, serve/adapters.py):
    # which resident adapter's (A, B) pair this request's batch rows
    # gather inside the shared programs. None = the base model
    # (bit-identical to an unadapted engine). Like ``tenant``, the
    # binding rides the request object through crash replay and fleet
    # failover re-admission; a TenantClass.adapter default is resolved
    # at engine admission, not here.
    adapter: Optional[str] = None

    def __post_init__(self):
        self.prompt = [int(t) for t in self.prompt]
        if not self.prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if not self.tenant or not isinstance(self.tenant, str):
            raise ValueError(
                f"tenant must be a non-empty string, got {self.tenant!r}")
        if self.adapter is not None and (
                not self.adapter or not isinstance(self.adapter, str)):
            raise ValueError(
                f"adapter must be a non-empty string or None, "
                f"got {self.adapter!r}")
        if self.seed is None:
            self.seed = self.id

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Completion:
    """A retired request: output tokens + stop reason + latency stamps."""
    request_id: int
    prompt: List[int]
    tokens: List[int]               # generated tokens, eos included
    finish_reason: str              # FINISH_EOS | FINISH_LENGTH | FINISH_TIMEOUT
    arrival_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # prompt tokens served from the shared-prefix KV cache (paged
    # engines with prefix_cache=True; 0 otherwise)
    prefix_hit_tokens: int = 0
    # the retiring request's tenant class (per-tenant obs
    # aggregation key; DEFAULT_TENANT without tenancy configured)
    tenant: str = DEFAULT_TENANT
    # the adapter this request actually decoded under (after any
    # TenantClass.adapter default resolution; None = base model)
    adapter: Optional[str] = None

    @property
    def latency(self) -> Optional[float]:
        """Arrival → completion, in the driving client's clock units."""
        if self.arrival_time is None or self.finish_time is None:
            return None
        return self.finish_time - self.arrival_time

    @property
    def time_to_first_token(self) -> Optional[float]:
        if self.arrival_time is None or self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time
