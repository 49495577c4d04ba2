"""Synchronous driver loop: engine + scheduler + clock.

:class:`ServeClient` is the single-threaded event loop the tests, the
example, and the bench all drive: submit requests (immediately or from an
arrival trace), then tick — each tick expires deadlines, asks the
scheduler for the next dispatch (prefill / step / idle), runs it, and
stamps completion timing.

Two clock modes:

- **tick clock** (default, ``clock=None``): time = number of engine
  dispatches so far. Fully deterministic — arrival traces expressed in
  ticks replay bit-identically, which is what the serving smoke tests
  pin ("request 3 arrives after the 5th engine dispatch, mid-flight").
- **wall clock** (``clock=time.perf_counter`` or any callable): real
  latencies for the bench; arrival times are seconds from ``run`` start.

**Async dispatch** (``async_dispatch=True``): a depth-2 pipelined tick
loop for the decode/verify hot path. When the scheduler's next action
is another STEP (or spec-round) dispatch over the same resident rows,
the tick ENQUEUES dispatch N+1 against the donated device carry first
and only then syncs dispatch N's report (its one host copy) — the
scheduler, retire loop, deadline sweep and telemetry all run on N's tokens while N+1
computes, taking the fixed host round-trip (not measured on the
current machine, ``docs/performance.md``) off the device's critical path.
Admission/prefill/chunk dispatches stay synchronous barriers (they
re-seat rows), so the pipeline drains naturally exactly where the loop
already pays compile-shape switches. Tokens are identical to the sync
driver (greedy AND sampled — the key stream is position-indexed), and
tick-clock semantics are pinned: one tick = one *enqueued* dispatch, so
deterministic traces replay byte-identically. Crash replay and fleet
failover snapshot the *synced* frontier: an in-flight dispatch is
discarded on rebuild and regenerated by replay, never committed twice.
See ``docs/serving.md#async-dispatch``.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_lightning_tpu.obs.spans import NULL_SPAN
from ray_lightning_tpu.reliability import faults
from ray_lightning_tpu.reliability.faults import SITE_SERVE_DRIVER
from ray_lightning_tpu.serve.engine import ServeEngine
from ray_lightning_tpu.serve.request import (Completion, DEFAULT_TENANT,
                                             FINISH_REJECTED,
                                             FINISH_TIMEOUT, Request)
from ray_lightning_tpu.serve.scheduler import (ACTION_CHUNK, ACTION_PREFILL,
                                               ACTION_STEP, FifoScheduler,
                                               QueueFull, SchedulerConfig)
from ray_lightning_tpu.serve.tenancy import TenantScheduler


class ServeClient:
    """Synchronous continuous-batching front-end.

    ``ServeClient(model, params, num_slots=8, prefill_len=64)`` builds the
    engine and a FIFO scheduler; ``submit()`` returns a request id,
    ``run_until_idle()`` drives everything to completion, and
    ``serve_trace([(t, {...}), ...])`` replays a scripted arrival trace
    (requests join mid-flight whenever ``t`` falls between dispatches).
    """

    def __init__(self, model, params, *, num_slots: int = 8,
                 prefill_batch: Optional[int] = None,
                 prefill_len: int = 64, steps_per_dispatch: int = 1,
                 scheduler_config: Optional[SchedulerConfig] = None,
                 seed: int = 0,
                 clock: Optional[Callable[[], float]] = None,
                 clock_epoch: Optional[float] = None,
                 retry_policy=None, telemetry=None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 kv_dtype: Optional[str] = None,
                 page_native: bool = False,
                 attention_kernel: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 weight_group_size: Optional[int] = None,
                 matmul_kernel: Optional[str] = None,
                 draft_model=None, draft_params=None,
                 spec_k: Optional[int] = None,
                 draft_weight_dtype: Optional[str] = None,
                 async_dispatch: bool = False,
                 tenant_classes=None,
                 adapters=None,
                 max_resident_adapters: Optional[int] = None,
                 lora_rank: Optional[int] = None,
                 journal=None):
        engine_kwargs = dict(
            num_slots=num_slots, prefill_batch=prefill_batch,
            prefill_len=prefill_len,
            steps_per_dispatch=steps_per_dispatch, seed=seed,
            telemetry=telemetry, page_size=page_size,
            num_pages=num_pages, prefill_chunk=prefill_chunk,
            prefix_cache=prefix_cache, kv_dtype=kv_dtype,
            page_native=page_native, attention_kernel=attention_kernel,
            weight_dtype=weight_dtype,
            weight_group_size=weight_group_size,
            matmul_kernel=matmul_kernel,
            draft_model=draft_model, draft_params=draft_params,
            spec_k=spec_k, draft_weight_dtype=draft_weight_dtype,
            tenant_classes=tenant_classes,
            adapters=adapters,
            max_resident_adapters=max_resident_adapters,
            lora_rank=lora_rank)
        if retry_policy is not None:
            # supervised engine: dispatch crashes rebuild + replay under
            # the policy instead of unwinding through the client loop;
            # exhausted requests retire as finish_reason="failed"
            from ray_lightning_tpu.reliability import ServeSupervisor
            self.engine = ServeSupervisor(model, params,
                                          policy=retry_policy,
                                          **engine_kwargs)
        else:
            self.engine = ServeEngine(model, params, **engine_kwargs)
        # tenancy armed: per-class queues + deficit-weighted fair share
        # (serve/tenancy.py) replace the single FIFO deque. A class list
        # holding only the default class is decision-for-decision the
        # FIFO scheduler, so arming tenancy never perturbs an existing
        # trace (A/B-pinned). The same list rode engine_kwargs above, so
        # supervisor rebuilds and fleet replicas keep the class map.
        if tenant_classes:
            self.scheduler: FifoScheduler = TenantScheduler(
                tenant_classes, scheduler_config)
            self._tenancy: Optional[TenantScheduler] = self.scheduler
        else:
            self.scheduler = FifoScheduler(scheduler_config)
            self._tenancy = None
        self._clock = clock
        # depth-2 pipelined dispatch (module docstring): the one
        # outstanding deferred-sync handle, reconciled one tick later.
        # Discarded (never synced) on engine rebuild and on shutdown —
        # the sync-frontier contract.
        self.async_dispatch = async_dispatch
        self._pending = None
        # the two-live-handles crash seat: when an unsupervised sync of
        # the OLDER handle fails while a newer enqueue is already live
        # (one retry seat, two handles), the older handle parks here so
        # a resumed loop re-syncs it in dispatch order instead of
        # wedging on the out-of-order guard. Same discard rules as
        # _pending (rebuilds, shutdown).
        self._pending_front = None
        # clock_epoch pins t=0 to an external origin instead of this
        # client's first now() call — how a ReplicaFleet keeps every
        # replica (including ones promoted mid-run) on ONE shared
        # timeline, so deadlines and TTFT stamps survive failover
        self._t0: Optional[float] = clock_epoch
        self._ops = 0  # engine dispatches so far = the tick clock
        self._next_id = 0
        self._seen_rebuilds = 0  # supervised: recovery TTFT sweep
        self.completions: Dict[int, Completion] = {}
        # telemetry is off by default: every armed emission below sits
        # behind `if tel is not None` — the disarmed loop pays one
        # attribute read + None check per tick, nothing else
        self._tel = telemetry
        # write-ahead request journal (serve/journal.py): admissions,
        # synced-frontier progress and retirements land on disk so a
        # driver death warm-restarts via ServeClient.restore. Same
        # zero-cost contract as telemetry: journal=None (the default)
        # costs one attribute read + None check per hook.
        self._journal = journal
        # the serve.driver fault site fires only on a DRIVER's own tick
        # loop — a ReplicaFleet member client or a spawned serve worker
        # is a replica turn (serve.replica territory) and pokes this off
        self._fire_driver_site = True
        # armed-only: request id -> enqueue->sync duration of the async
        # dispatch that retired it (client clock units), consumed by the
        # serve.retire event so request traces can split decode vs sync.
        # Never written while disarmed (the zero-surface pin).
        self._sync_durs: Dict[int, float] = {}
        self.num_slots = num_slots
        # name prefix for this client's occupancy gauges
        # (serve_queue_depth / serve_slot_occupancy / serve_pages_free /
        # serve_page_occupancy). "" for a standalone client keeps the
        # historical names; a ReplicaFleet stamps each replica's client
        # with a stable "replica<id>_" prefix so per-replica gauges
        # stop clobbering each other in the shared name-keyed registry
        # (docs/observability.md). Counters and histograms stay
        # unprefixed — they aggregate correctly across writers.
        self.gauge_prefix = ""

    # ------------------------------------------------------------ clock
    @property
    def ops(self) -> int:
        return self._ops

    def now(self) -> float:
        if self._clock is None:
            return float(self._ops)
        if self._t0 is None:
            self._t0 = self._clock()
            if self._tel is not None:
                # this client zeroes the request clock: tell the span
                # recorder where, so one export is one time axis
                self._tel.spans.set_origin(self._t0)
        return self._clock() - self._t0

    def _span(self, name: str, **args: Any):
        """A host span on the armed handle (tagged with the replica seat
        under a fleet), the shared no-op context when disarmed."""
        tel = self._tel
        if tel is None:
            return NULL_SPAN
        return tel.span(name, **args,
                        **getattr(self.engine, "_span_extra", {}))

    # ----------------------------------------------------------- submit
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               temperature: float = 0.0, top_k: Optional[int] = None,
               eos_id: Optional[int] = None, seed: Optional[int] = None,
               deadline: Optional[float] = None,
               tenant: Optional[str] = None,
               adapter: Optional[str] = None) -> int:
        """Validate + enqueue one request; returns its id. Raises
        ``ValueError`` for requests that can never fit the compiled
        shapes (or name an undeclared tenant, or an ``adapter`` that is
        not resident — :class:`~...adapters.UnknownAdapter`) and
        :class:`~...scheduler.QueueFull` at max queue depth — tenancy
        armed, a class at its own quota sheds
        :class:`~...tenancy.ClassQueueFull` instead. ``adapter=None``
        decodes under the tenant class's default adapter if its
        :class:`~...tenancy.TenantClass` declares one, else the base
        model."""
        req = Request(id=self._next_id, prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      temperature=temperature, top_k=top_k, eos_id=eos_id,
                      seed=seed, deadline=deadline,
                      tenant=tenant or DEFAULT_TENANT,
                      adapter=adapter)
        rid = self.submit_request(req)
        self._next_id += 1
        return rid

    def submit_request(self, req: Request) -> int:
        """Validate + enqueue an externally built :class:`Request` — the
        router seat: a :class:`~ray_lightning_tpu.serve.fleet.ReplicaFleet`
        owns request ids fleet-wide and re-admits a dead replica's
        requests here, so arrival/deadline/first-token stamps (and
        ``replay_tokens``) must ride the request object untouched:
        ``arrival_time`` is only stamped when the request has never been
        admitted anywhere."""
        if self._tel is None:
            return self._submit_request(req)
        with self._span("serve.submit", ids=[req.id]):
            return self._submit_request(req)

    def _submit_request(self, req: Request) -> int:
        self.engine.validate(req)
        now = self.now()
        tel = self._tel
        try:
            self.scheduler.submit(req, now)
        except QueueFull as exc:
            if tel is not None and self._tenancy is not None:
                # class-aware shed accounting (tenancy armed only — the
                # unarmed event stream stays byte-identical to HEAD).
                # Under a fleet this fires per REFUSING replica; the
                # fleet-wide verdict is fleet.shed / FleetSaturated.
                tel.event("engine.tenant_shed", id=req.id,
                          tenant=req.tenant, why=type(exc).__name__)
                tel.metrics.counter(
                    f"serve_tenant_shed_total_{req.tenant}",
                    help="admission refusals for this tenant class "
                    "(per-class quota and global-bound sheds)").inc()
            raise
        if req.arrival_time is None:
            req.arrival_time = now
        if self._journal is not None:
            self._journal.admit(req)
        if tel is not None:
            tel.event("serve.submit", id=req.id,
                      prompt_len=req.prompt_len,
                      max_new_tokens=req.max_new_tokens, t=now)
            tel.metrics.counter(
                "serve_requests_total",
                help="requests accepted by admission control").inc()
            tel.metrics.gauge(
                self.gauge_prefix + "serve_queue_depth",
                help="requests waiting in the scheduler queue"
            ).set(len(self.scheduler))
            if self._tenancy is not None:
                depth = self._tenancy.class_depths().get(req.tenant, 0)
                tel.event("engine.tenant_admitted", id=req.id,
                          tenant=req.tenant, class_depth=depth)
                tel.metrics.gauge(
                    self.gauge_prefix
                    + f"serve_tenant_queue_depth_{req.tenant}",
                    help="requests waiting in this tenant class's queue"
                ).set(depth)
        return req.id

    def _stamp_first_token(self, req: Request, t: float) -> None:
        """First-token bookkeeping shared by every stamping path
        (batched admit, final chunk, post-recovery sweep)."""
        req.first_token_time = t
        if self._tel is not None:
            self._tel.event("serve.first_token", id=req.id,
                            ttft=t - req.arrival_time, t=t)

    # ----------------------------------------------------- hot adapters
    def load_adapter(self, name: str, adapter) -> Optional[str]:
        """Hot-load a LoRA adapter into the engine's resident bank (a
        synced-frontier barrier, like admission). Returns the name the
        load evicted, if any. Forwarded through a supervising wrapper
        (which syncs its rebuild kwargs) and broadcast by a fleet."""
        self._drain_for_barrier()
        return self.engine.load_adapter(name, adapter)

    def unload_adapter(self, name: str) -> None:
        """Hot-unload a resident adapter (refuses while in-flight rows
        still pin it)."""
        self._drain_for_barrier()
        self.engine.unload_adapter(name)

    def _drain_for_barrier(self) -> None:
        """Sync any outstanding pipelined dispatch before an
        out-of-band barrier (adapter load/unload) — completions the
        drain retires are finalized, not dropped."""
        if self._pending is None and self._pending_front is None:
            return
        done: List[Completion] = []
        self._sync_pending(done)
        self._finalize(done)

    def shutdown(self) -> None:
        """Release the engine's KV pool/arena (and prefix-cache page
        refs) — a retired client stops pinning device memory. Forwarded
        through a supervising wrapper when ``retry_policy`` is set.
        Safe with a dispatch in flight: the outstanding handle is
        DISCARDED (the sync-frontier contract — its tokens were never
        committed; JAX retires the in-flight computation's buffers when
        their last reference drops), never synced into a dying engine."""
        self._pending = None
        self._pending_front = None
        journal = self._journal
        if journal is not None:
            self._journal = None
            journal.shutdown()
        self.engine.shutdown()

    # ----------------------------------------------------- warm restart
    @classmethod
    def restore(cls, journal_path: str, model, params, *,
                sync_every: int = 8, **kwargs) -> "ServeClient":
        """Rebuild a client from a dead driver's journal and re-admit
        every unretired request with ``replay_tokens`` set from the
        journaled frontier.

        ``kwargs`` are the same constructor arguments the dead client
        was built with — the engine must recompile the same fixed
        shapes (and re-declare the same ``tenant_classes``/``adapters``)
        for the re-admissions to validate. The journal at
        ``journal_path`` is REOPENED for appending with a bumped
        generation (the process-backend split-brain fence), so the
        restarted session keeps journaling into the same file and a
        second crash restores again. Token identity across the restart
        holds by the replica-failover argument: the sampling key stream
        is position-indexed (``fold_in(seed, step)``), so re-feeding
        ``prompt + frontier`` resumes it bit-identically, and requests
        whose retire record was durable are never re-emitted.
        """
        from ray_lightning_tpu.serve.journal import (
            COUNTER_JOURNAL_REPLAYED, EVENT_JOURNAL_RESTORED, Journal,
            read_journal)
        state = read_journal(journal_path)
        journal = Journal(journal_path, sync_every=sync_every,
                          generation=state.generation + 1,
                          telemetry=kwargs.get("telemetry"))
        client = cls(model, params, journal=journal, **kwargs)
        pending = state.pending()
        for req, toks in pending:
            req.replay_tokens = list(toks) if toks else None
            client.submit_request(req)
        client._next_id = max(client._next_id, state.next_request_id)
        tel = client._tel
        if tel is not None:
            tel.event(EVENT_JOURNAL_RESTORED, path=str(journal_path),
                      generation=journal.generation,
                      replayed=len(pending), retired=len(state.retired),
                      torn_tail=state.torn_tail)
            tel.metrics.counter(
                COUNTER_JOURNAL_REPLAYED,
                help="unretired requests re-admitted by warm restart"
            ).inc(len(pending))
        return client

    # ------------------------------------------------------------- loop
    def tick(self) -> List[Completion]:
        """One scheduling decision + engine dispatch. Returns completions
        retired by this tick (including deadline expirations). Armed, the
        tick is one root span (``serve.tick``, with the action it took)
        over a child span for every stretch of host work — the tables in
        ``docs/observability.md`` name them."""
        if self._tel is None:
            return self._tick(None)
        with self._span("serve.tick") as root:
            return self._tick(root)

    def _tick(self, root: Optional[Dict[str, Any]]) -> List[Completion]:
        if self._fire_driver_site:
            # the driver-death site (docs/reliability.md): raise mode
            # propagates out of tick() — the deterministic mid-decode
            # driver kill the warm-restart tests replay from a journal
            faults.fire(SITE_SERVE_DRIVER)
        now = self.now()
        done: List[Completion] = []
        with self._span("serve.sweep"):
            # queued requests past deadline never touch the accelerator — but
            # a failover re-admission waiting here already streamed tokens on
            # its dead replica (replay_tokens) and keeps them, plus its
            # original first-token stamp (the PR 3 partial-tokens contract)
            for req in self.scheduler.expire(now):
                done.append(Completion(
                    request_id=req.id, prompt=list(req.prompt),
                    tokens=list(req.replay_tokens or []),
                    finish_reason=FINISH_TIMEOUT,
                    arrival_time=req.arrival_time,
                    first_token_time=req.first_token_time,
                    prefix_hit_tokens=req.prefix_hit_tokens,
                    tenant=req.tenant, adapter=req.adapter))
            # sync driver: a prior tick's step crashed at its host copy and
            # parked the handle in the engine's retry seat — drain it before
            # anything that needs the synced frontier (deadline cancels,
            # admission), or the loop would wedge behind _require_synced
            if getattr(self.engine, "retry_pending", False):
                try:
                    done.extend(self.engine.step())  # retry-drain only:
                    #   the retry branch syncs the parked dispatch without
                    #   enqueueing a new one
                except Exception:
                    self._finalize(done)
                    raise
            # in-flight requests past deadline free their slot mid-decode.
            # With a dispatch in flight, sync it FIRST: the stale host view
            # can't tell a row the pending dispatch already retired
            # (eos/budget) from one that really ran out of time — cancelling
            # on the stale view would flip its finish_reason and drop synced
            # tokens (pinned: deadline expiry landing between enqueue and
            # sync, tests/test_async_dispatch.py)
            if ((self._pending is not None
                 or self._pending_front is not None) and any(
                    r.deadline is not None and now >= r.deadline
                    for r in self.engine.active_requests.values())):
                try:
                    self._sync_pending(done)
                except Exception:
                    self._finalize(done)
                    raise
            for req in list(self.engine.active_requests.values()):
                if req.deadline is not None and now >= req.deadline:
                    comp = self.engine.cancel(req.id)
                    if comp is not None:
                        done.append(comp)
        with self._span("scheduler.plan"):
            action, reqs = self.scheduler.next_action(self.engine)
        if root is not None:
            root["action"] = action
        if action == ACTION_PREFILL:
            # defer (don't crash on) requests whose seed collides with an
            # in-flight sample stream — the pool would refuse them at
            # acquire; they rejoin the queue head and clear once the
            # conflicting request retires. Intra-batch duplicates keep
            # their first arrival, so at least one request always admits.
            seen = {r.seed for r in self.engine.active_requests.values()}
            admit: List[Request] = []
            deferred: List[Request] = []
            for req in reqs:
                (deferred if req.seed in seen else admit).append(req)
                seen.add(req.seed)
            if deferred:
                self.scheduler.requeue_front(deferred)
            if admit:
                tel = self._tel
                if tel is not None:
                    for req in admit:
                        tel.event("serve.admit", id=req.id,
                                  queue_wait=now - req.arrival_time,
                                  t=now)
                try:
                    # admission re-seats rows: a synchronous barrier —
                    # the pipeline (if armed) drains here, which is
                    # exactly where the loop already pays compile-shape
                    # switches
                    self._sync_pending(done)
                    done.extend(self.engine.prefill(admit))
                except Exception:
                    # a crashed dispatch must not strand the popped
                    # batch: a crash in the ADMISSION loop rolled its
                    # slots back (atomic), leaving the batch in neither
                    # snapshot_in_flight() nor the queue — a
                    # whole-replica failover (ReplicaFleet) would
                    # silently lose it. A crash in the jitted dispatch
                    # AFTER admission leaves the batch in pool.active
                    # instead, where the snapshot covers it — requeuing
                    # those too would re-admit every request twice. The
                    # engine's admission atomicity makes active
                    # membership the exact discriminator. The
                    # expiry/cancel completions this tick already
                    # collected must also be committed before the
                    # unwind discards `done` (those requests left the
                    # scheduler AND the engine — nothing else can ever
                    # retire them). (Requeue may land ahead of
                    # seed-deferred batch siblings — those were
                    # colliding anyway.)
                    seated = {r.id
                              for r in self.engine.active_requests.values()}
                    self.scheduler.requeue_front(
                        [r for r in admit if r.id not in seated])
                    self._finalize(done)
                    raise
                self._ops += 1  # count the dispatch before stamping TTFT
                t_first = self.now()
                chunking = getattr(self.engine, "chunk_pending_ids",
                                   frozenset())
                with self._span("serve.stamp"):
                    for req in admit:
                        if req.id in chunking:
                            # chunk-routed: still prefilling, no first
                            # token yet — stamped by _dispatch_chunk on
                            # its final chunk
                            continue
                        if req.first_token_time is not None:
                            # failover re-admission of a request that had
                            # already streamed tokens on its dead
                            # replica: its first token happened THERE —
                            # re-stamping would corrupt TTFT across the
                            # fleet's shared clock
                            continue
                        self._stamp_first_token(req, t_first)
            else:
                # every popped request was seed-deferred: the tick must
                # still advance the engine — the conflicting request may
                # itself be chunk-prefilling (holding a slot with nothing
                # decoding: livelock otherwise) — but under the SAME
                # chunk/decode alternation bound as any other dispatch,
                # so a persistent deferral can't starve in-flight decode;
                # the substitute action falls through to the shared
                # dispatch chain below
                action = self.scheduler.drain_action(self.engine)
        try:
            if action == ACTION_CHUNK:
                # chunk dispatches re-seat rows too: barrier
                self._sync_pending(done)
                self._dispatch_chunk(done)
            elif action == ACTION_STEP:
                if self.async_dispatch:
                    self._step_pipelined(done)
                else:
                    done.extend(self.engine.step())
                    self._ops += 1
            elif action != ACTION_PREFILL:
                # idle: a trailing no-op dispatch may still be pending
                # (the last rows retired inside it) — drain, then
                # advance the tick clock so tick-mode traces progress
                self._sync_pending(done)
                self._ops += 1
        except Exception:
            # same contract as the prefill unwind above: completions
            # already collected this tick must not vanish with the crash
            self._finalize(done)
            raise
        rebuilds = getattr(self.engine, "rebuilds", 0)
        if rebuilds != self._seen_rebuilds:
            # a recovery may drain chunk prefills internally (prefix
            # replay waves): a request it ACTIVATED got its first token
            # inside the recovery, where _dispatch_chunk never saw the
            # chunk_activated handoff — stamp TTFT now, not at retire
            self._seen_rebuilds = rebuilds
            t = self.now()
            # skip requests the recovery RE-QUEUED mid-chunk (non-prefix
            # replay): they have no token yet — _dispatch_chunk stamps
            # them on their final chunk
            chunking = getattr(self.engine, "chunk_pending_ids",
                               frozenset())
            for req in self.engine.active_requests.values():
                if req.first_token_time is None and req.id not in chunking:
                    self._stamp_first_token(req, t)
        with (self._span("serve.finalize",
                         ids=[c.request_id for c in done])
              if self._tel is not None else NULL_SPAN):
            self._finalize(done)
            journal = self._journal
            if journal is not None:
                # journal the synced frontier: snapshot_in_flight only
                # ever reports tokens past step_sync (an in-flight
                # pipelined dispatch is invisible), so a restart replays
                # exactly what the driver had observed — never a
                # half-committed step
                for req, toks in self.engine.snapshot_in_flight():
                    journal.note_frontier(req.id, toks,
                                          req.first_token_time)
        return done

    def _finalize(self, done: List[Completion]) -> None:
        """Stamp finish times, record completions, and (armed) emit the
        retirement telemetry. Runs on the normal tick exit AND on a
        crashed dispatch's unwind: completions collected earlier in the
        tick (deadline expiries, mid-decode cancels) already left the
        scheduler and the engine, so discarding them with the stack
        would lose those requests forever — no failover can re-admit
        what neither the snapshot nor the queue contains."""
        t_done = self.now()
        for comp in done:
            comp.finish_time = t_done
            if comp.first_token_time is None and comp.tokens:
                # finished at its own prefill, before the post-dispatch
                # stamping loop ran for it
                comp.first_token_time = t_done
            self.completions[comp.request_id] = comp
        journal = self._journal
        if journal is not None:
            # the exactly-once commit point: a durable retire record is
            # what stops warm restart from re-emitting this request.
            # Runs on the crash unwind too — completions that left the
            # scheduler and the engine must reach the journal before the
            # driver dies for real.
            for comp in done:
                journal.retire(comp)
        tel = self._tel
        if tel is not None:
            self._record_retirements(tel, done)

    def _sync_handle(self, handle, done: List[Completion]) -> None:
        """``step_sync`` one handle into ``done``, plus (armed) record
        the enqueue->sync duration against each completion the dispatch
        retired — consumed by the ``serve.retire`` event's ``sync``
        stamp so request traces can split decode from reconciliation."""
        got = self.engine.step_sync(handle)
        if (got and self._tel is not None
                and handle.enqueued_tick is not None):
            dur = self.now() - handle.enqueued_tick
            if dur > 0:
                for comp in got:
                    self._sync_durs[comp.request_id] = dur
        done.extend(got)

    def _sync_front(self, done: List[Completion]) -> None:
        """Drain the parked older handle (two-live-handles crash seat),
        restoring it on failure so the resumed loop retries the same
        sync. A recovery inside this sync rebuilds the engine, so the
        younger outstanding handle is discarded (sync-frontier
        contract)."""
        if self._pending_front is None:
            return
        front, self._pending_front = self._pending_front, None
        before = getattr(self.engine, "rebuilds", 0)
        try:
            self._sync_handle(front, done)
        except Exception:
            self._pending_front = front
            raise
        if getattr(self.engine, "rebuilds", 0) != before:
            self._pending = None

    def _sync_pending(self, done: List[Completion]) -> None:
        """Drain the pipeline: sync the outstanding dispatch(es, oldest
        first) into ``done``. After this the engine is at its synced
        frontier and barrier dispatches (admission/chunk/cancel) may
        run."""
        self._sync_front(done)
        if self._pending is None:
            return
        pending, self._pending = self._pending, None
        try:
            self._sync_handle(pending, done)
        except Exception:
            # an unsupervised sync crash leaves the engine untouched
            # (step_sync materializes every host copy before its commit
            # point) — keep the handle so a resumed loop retries the
            # same sync instead of wedging every barrier behind
            # _require_synced with nothing left to drain
            self._pending = pending
            raise

    def _step_pipelined(self, done: List[Completion]) -> None:
        """One async STEP tick: enqueue dispatch N+1 against the device
        carry, THEN sync dispatch N — the retire loop, completion
        stamping and telemetry below all run while N+1 computes. A
        supervised engine may recover inside the enqueue (rebuild +
        replay of the synced frontier); the outstanding handle then
        belongs to the dead engine and is discarded un-synced — its
        tokens were regenerated by the replay."""
        eng = self.engine
        if self._pending_front is not None:
            # a prior tick's two-live-handles sync crash parked the
            # older handle; re-sync it (dispatch order) before enqueuing
            # a third dispatch — transient faults clear here, repeated
            # ones keep failing LOUD with the handle parked
            self._sync_front(done)
        if getattr(eng, "spec_needs_refill", False):
            # the refill ledger reads host-side token streams: force
            # the frontier current before the enqueue builds on it
            self._sync_pending(done)
        prev, self._pending = self._pending, None
        before = getattr(eng, "rebuilds", 0)
        try:
            self._pending = eng.step_enqueue()
        except Exception:
            # unsupervised enqueue crash: fault sites fire BEFORE the
            # program launches, so the engine state is untouched — put
            # the outstanding handle back so it can still be drained
            # (losing it would strand its tokens and wedge every later
            # barrier behind _require_synced)
            self._pending = prev
            raise
        self._ops += 1  # one tick = one enqueued dispatch (tick clock)
        if self._tel is not None and self._pending is not None:
            # client-clock enqueue stamp (the engine's enqueued_at is
            # wall perf_counter, useless under the tick clock): read by
            # _sync_handle to produce the serve.retire `sync` split
            self._pending.enqueued_tick = self.now()
        if getattr(eng, "rebuilds", 0) != before:
            prev = None  # dead engine's dispatch: discard, never sync
            if self._pending is not None:
                # a recovery inside the enqueue returned its sentinel:
                # surface the completions NOW — parking them a tick
                # would lose them if the replica dies or shuts down
                # before the next drain (failover sweeps completions,
                # not pending handles)
                self._sync_pending(done)
        if prev is not None:
            before = getattr(eng, "rebuilds", 0)
            # unsupervised failure here is the two-live-handles case
            # (one retry seat, two handles): the engine's commit-point
            # ordering left prev un-synced, so park it in the front
            # seat and fail LOUD — a resumed loop re-syncs prev in
            # dispatch order instead of wedging on the out-of-order
            # guard. (Supervised engines recover inside step_sync and
            # never reach the except.)
            try:
                self._sync_handle(prev, done)
            except Exception:
                self._pending_front = prev
                raise
            if getattr(eng, "rebuilds", 0) != before:
                # a recovery INSIDE the sync (device error at the host
                # copy) rebuilt the engine too — the handle enqueued
                # above belongs to the dead engine and must be
                # discarded un-synced, same contract as the enqueue
                # path (its rows were regenerated by the replay)
                self._pending = None

    @property
    def dispatch_in_flight(self) -> bool:
        """True while an enqueued dispatch awaits its sync (the fleet's
        runnable-replica probe counts reconciliation as work)."""
        return self._pending is not None or self._pending_front is not None

    def _dispatch_chunk(self, done: List[Completion]) -> None:
        """One chunk-prefill dispatch, plus TTFT stamping for the requests
        (if any) whose final chunk just activated their decode rows — the
        engine hands them over directly (``chunk_activated``), no scan of
        ``active_requests``."""
        done.extend(self.engine.prefill_chunk_step())
        self._ops += 1
        for req in self.engine.chunk_activated:
            if req.first_token_time is None:
                self._stamp_first_token(req, self.now())

    def _record_retirements(self, tel, done: List[Completion]) -> None:
        """Armed-path bookkeeping for one tick: retire events + the
        vLLM-style request lifecycle metrics (TTFT / TPOT / end-to-end
        latency histograms, queue-depth and slot-occupancy gauges). All
        times are in the client's clock units (ticks or seconds)."""
        m = tel.metrics
        for comp in done:
            # t/tenant/sync ride the retire event for the request-trace
            # assembler (obs/tracing.py): `sync` is the enqueue->sync
            # duration of the async dispatch that retired this request,
            # so the trace can split decode time from reconciliation
            retire: Dict[str, Any] = dict(
                id=comp.request_id, finish_reason=comp.finish_reason,
                tokens=len(comp.tokens), tenant=comp.tenant,
                t=comp.finish_time)
            sync_dur = self._sync_durs.pop(comp.request_id, None)
            if sync_dur:
                retire["sync"] = sync_dur
            tel.event("serve.retire", **retire)
            m.counter("serve_completions_total",
                      help="requests retired, any finish reason").inc()
            m.counter(f"serve_finish_{comp.finish_reason}_total",
                      help=f"requests retired with finish_reason="
                      f"{comp.finish_reason}").inc()
            m.counter("serve_tokens_total",
                      help="generated tokens across all requests"
                      ).inc(len(comp.tokens))
            if comp.latency is not None:
                m.histogram("serve_latency",
                            help="arrival -> completion (client clock "
                            "units)").observe(comp.latency)
            ttft = comp.time_to_first_token
            if self._tenancy is not None:
                if ttft is not None:
                    m.histogram(
                        f"serve_tenant_ttft_ms_{comp.tenant}",
                        help="arrival -> first token for this tenant "
                        "class (client clock units — ticks under the "
                        "tick clock)").observe(ttft)
                    slo = self._tenancy.classes[comp.tenant].ttft_slo
                    if slo is not None and ttft > slo:
                        m.counter(
                            f"serve_tenant_slo_miss_total_{comp.tenant}",
                            help="retirements whose TTFT exceeded this "
                            "tenant class's ttft_slo target").inc()
            if ttft is not None:
                m.histogram("serve_ttft",
                            help="arrival -> first token (client clock "
                            "units)").observe(ttft)
                if (len(comp.tokens) > 1
                        and comp.finish_time is not None):
                    m.histogram(
                        "serve_tpot",
                        help="per-token decode time after the first "
                        "(client clock units)").observe(
                        (comp.finish_time - comp.first_token_time)
                        / (len(comp.tokens) - 1))
        m.gauge(self.gauge_prefix + "serve_queue_depth",
                help="requests waiting in the scheduler queue"
                ).set(len(self.scheduler))
        if self._tenancy is not None:
            for name, depth in self._tenancy.class_depths().items():
                m.gauge(
                    self.gauge_prefix + f"serve_tenant_queue_depth_{name}",
                    help="requests waiting in this tenant class's queue"
                ).set(depth)
        m.gauge(self.gauge_prefix + "serve_slot_occupancy",
                help="fraction of KV slots holding an in-flight request"
                ).set(self.engine.active_count / self.num_slots)
        pages_free = getattr(self.engine, "free_pages", None)
        if pages_free is not None:
            num_pages = self.engine.pool.num_pages
            m.gauge(self.gauge_prefix + "serve_pages_free",
                    help="free KV pages in the paged arena"
                    ).set(pages_free)
            m.gauge(self.gauge_prefix + "serve_page_occupancy",
                    help="fraction of arena pages held (slots + prefix "
                    "cache)").set(1.0 - pages_free / num_pages)

    def _engine_busy(self) -> bool:
        """Decode rows active OR prompts still streaming chunk prefill
        OR a dispatch still awaiting its sync."""
        return bool(self.engine.active_count
                    or getattr(self.engine, "chunk_pending", 0)
                    or self._pending is not None
                    or self._pending_front is not None)

    @property
    def busy(self) -> bool:
        """True while a tick would do real work: requests queued, rows
        decoding/chunking, or an enqueued dispatch awaiting its sync.
        The drive-loop probe shared by :meth:`run_until_idle`, the
        fleet's replica seats, and the process-backend replica worker's
        own dispatch loop (``launchers/serve_worker.py``)."""
        return bool(len(self.scheduler) or self._engine_busy())

    def load_stats(self) -> Dict[str, Any]:
        """One host-side occupancy snapshot — every signal the fleet
        router scores (:class:`~ray_lightning_tpu.serve.fleet.Router`),
        in one dict. A process-backend replica ships this across the
        queue transport after every dispatch turn (and inside each
        submit verdict) so the driver's routing mirror reads the same
        numbers an in-process router would read off the live objects."""
        sched = self.scheduler
        stats: Dict[str, Any] = {
            "queue_depth": len(sched),
            "oldest_age": sched.oldest_age(self.now()),
            "dispatch_in_flight": self.dispatch_in_flight,
            "ops": self._ops,
        }
        stats.update(self.engine.occupancy())
        if self._tenancy is not None:
            stats["class_depths"] = self._tenancy.class_depths()
            stats["class_oldest"] = self._tenancy.class_oldest(self.now())
        return stats

    def run_until_idle(self, max_ticks: int = 100_000) \
            -> Dict[int, Completion]:
        """Tick until queue and slots drain; returns all completions."""
        ticks = 0
        while self.busy:
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(
                    f"serve loop did not drain in {max_ticks} ticks")
        return dict(self.completions)

    def serve_trace(self, trace: Sequence[Tuple[float, dict]],
                    max_ticks: int = 100_000) -> Dict[int, Completion]:
        """Replay a scripted arrival trace.

        ``trace`` is ``[(arrival_time, submit_kwargs), ...]`` in the
        client's clock units (ticks by default — deterministic; seconds
        under a wall clock). Requests are submitted the first tick at or
        after their arrival time, so later entries join mid-flight while
        earlier requests are still decoding. Returns ``{request_id:
        Completion}`` with ids assigned in trace order. An entry the
        admission layer refuses (queue at depth, prompt that can never
        fit) is SHED — recorded as a ``finish_reason="rejected"``
        completion — instead of aborting the replay and discarding every
        other request's work (overload sheds requests, not the server).
        """
        pending = sorted(trace, key=lambda item: item[0])
        idx = 0
        ticks = 0
        while (idx < len(pending) or len(self.scheduler)
               or self._engine_busy()):
            now = self.now()
            while idx < len(pending) and pending[idx][0] <= now:
                kwargs = pending[idx][1]
                try:
                    self.submit(**kwargs)
                except (QueueFull, ValueError) as exc:
                    rid = self._next_id
                    self._next_id += 1
                    self.completions[rid] = Completion(
                        request_id=rid,
                        prompt=[int(t) for t in kwargs.get("prompt", [])],
                        tokens=[], finish_reason=FINISH_REJECTED,
                        arrival_time=now, finish_time=now,
                        tenant=kwargs.get("tenant") or DEFAULT_TENANT,
                        adapter=kwargs.get("adapter"))
                    if self._tel is not None:
                        self._tel.event("serve.reject", id=rid,
                                        why=type(exc).__name__)
                        self._tel.metrics.counter(
                            "serve_rejected_total",
                            help="requests shed at admission").inc()
                idx += 1
            if (idx < len(pending) and not len(self.scheduler)
                    and not self._engine_busy()):
                # nothing in flight and the next arrival is in the
                # future: fast-forward the tick clock / yield the wall
                # clock instead of spinning
                if self._clock is None:
                    self._ops = max(self._ops,
                                    math.ceil(pending[idx][0]))
                else:
                    time.sleep(  # tl-lint: allow-sleep — wall-clock mode's idle yield; tick mode (clock=None) never sleeps
                        min(1e-3, max(0.0, pending[idx][0] - now)))
                continue
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(
                    f"serve trace did not drain in {max_ticks} ticks")
        return dict(self.completions)
