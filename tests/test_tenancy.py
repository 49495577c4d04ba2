"""Multi-tenant SLO-aware scheduling: tiers, fair share, quotas, obs.

The load-bearing assertions are the two ends of the tenancy contract:

- **Scheduling is ordering-only.** Whatever classes ride the queue, a
  request's tokens are identical to its solo run (the position-indexed
  key stream makes them a pure function of no scheduler state), and a
  configuration holding only the default class is decision-for-decision
  identical to the plain FIFO scheduler — same admission order, same
  tokens, same event stream (A/B-pinned below).
- **The policy invariants hold.** Weighted fair share converges to the
  weight ratios over a saturated synthetic trace, the lowest-weight
  batch class is never starved past its bound under interactive
  saturation, tie-breaks are deterministic (tick traces and their JSONL
  logs replay byte-identically), and class assignment survives crash
  replay and fleet failover.

Scheduler-policy tests drive a fake engine (no jax work); integration
tests reuse the session-scoped ``serve_nano_family`` pair at the
serve-suite pinned shapes (num_slots in {1,2,3}, prefill_len 8), so no
new compiled shapes land.
"""
import numpy as np
import pytest

from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
from ray_lightning_tpu.serve import (ClassQueueFull, DEFAULT_TENANT,
                                     FINISH_FAILED, FleetSaturated,
                                     QueueFull, ReplicaFleet, Request,
                                     SchedulerConfig, ServeClient,
                                     ServeEngine, SlotPoolFull,
                                     TenantClass, TenantScheduler)
from ray_lightning_tpu.serve.scheduler import ACTION_PREFILL, FifoScheduler

pytestmark = [pytest.mark.serve, pytest.mark.tenancy]


@pytest.fixture(scope="module")
def nano(serve_nano_family):
    return serve_nano_family[:2]


CLASSES = [
    TenantClass("fast", weight=4.0, tier="interactive", ttft_slo=6.0),
    TenantClass("bulk", weight=1.0, tier="batch"),
]


class FakeEngine:
    """Just enough engine surface for scheduler-policy tests: free
    slots, the batched-program width, and the active-request map the
    per-class slot quota reads."""

    def __init__(self, free_slots=4, prefill_batch=4, active=()):
        self.free_slots = free_slots
        self.prefill_batch = prefill_batch
        self.active_requests = {i: r for i, r in enumerate(active)}
        self.active_count = len(self.active_requests)
        self.chunk_pending = 0


def _req(rid, tenant=DEFAULT_TENANT, **kw):
    kw.setdefault("prompt", [1, 2])
    kw.setdefault("max_new_tokens", 4)
    return Request(id=rid, tenant=tenant, **kw)


def _drain_admissions(sched, n_pops, refill=None):
    """Pop one admission at a time (free_slots=1) and return the tenant
    sequence; ``refill(sched, i)`` keeps chosen queues saturated."""
    order = []
    eng = FakeEngine(free_slots=1, prefill_batch=1)
    for i in range(n_pops):
        if refill is not None:
            refill(sched, i)
        action, reqs = sched.next_action(eng)
        if action != ACTION_PREFILL:
            break
        order.extend(r.tenant for r in reqs)
    return order


# --------------------------------------------------------------------- #
# scheduler invariants (fake engine — pure policy)
# --------------------------------------------------------------------- #
def test_weighted_fair_share_converges_to_weight_ratio():
    """Two saturated batch classes at weights 3:1: admission counts over
    a long synthetic trace converge to the weight ratio."""
    sched = TenantScheduler([
        TenantClass("heavy", weight=3.0, tier="batch"),
        TenantClass("light", weight=1.0, tier="batch")])
    rid = [0]

    def refill(s, _i):
        # keep both queues deep: convergence is a saturation property
        while s.class_depths()["heavy"] < 4:
            s.submit(_req(rid[0], "heavy")); rid[0] += 1
        while s.class_depths()["light"] < 4:
            s.submit(_req(rid[0], "light")); rid[0] += 1

    order = _drain_admissions(sched, 80, refill)
    assert len(order) == 80
    counts = sched.admitted_counts()
    ratio = counts["heavy"] / counts["light"]
    assert 2.5 <= ratio <= 3.5, (counts, order[:16])


def test_interactive_tier_drains_before_batch():
    sched = TenantScheduler(CLASSES)
    for i in range(3):
        sched.submit(_req(i, "bulk"))
    for i in range(3, 6):
        sched.submit(_req(i, "fast"))
    order = _drain_admissions(sched, 6)
    assert order == ["fast"] * 3 + ["bulk"] * 3


def test_no_starvation_bound_under_interactive_saturation():
    """A weight-1 batch class under sustained interactive pressure is
    served at least once every ceil(threshold/weight)+1 admissions —
    the starvation-counter escape hatch."""
    sched = TenantScheduler(CLASSES, starvation_threshold=8.0)
    rid = [0]

    def refill(s, _i):
        while s.class_depths()["fast"] < 4:   # interactive never drains
            s.submit(_req(rid[0], "fast")); rid[0] += 1
        while s.class_depths()["bulk"] < 2:
            s.submit(_req(rid[0], "bulk")); rid[0] += 1

    order = _drain_admissions(sched, 60, refill)
    bulk_at = [i for i, t in enumerate(order) if t == "bulk"]
    assert bulk_at, "batch class fully starved"
    gaps = np.diff([-1] + bulk_at)
    assert gaps.max() <= 9, (gaps.max(), order)
    # and interactive still dominates: priority held between escapes
    assert order.count("fast") > order.count("bulk") * 4


def test_deterministic_tie_breaks_replay_identically():
    """Identical submissions → identical admission sequences, and equal
    weights arbitrate in declaration order — no hidden nondeterminism
    for tick-trace replay to trip on."""
    def run():
        sched = TenantScheduler([
            TenantClass("a", weight=1.0, tier="batch"),
            TenantClass("b", weight=1.0, tier="batch")])
        for i in range(12):
            sched.submit(_req(i, "a" if i % 2 else "b"))
        return _drain_admissions(sched, 12)

    first = run()
    assert first == run()
    # first pick goes to the first-declared class on an exact credit tie
    assert first[0] == "a"


def test_default_only_class_matches_fifo_decision_for_decision():
    """One-class tenancy IS the FIFO scheduler: same pops, same global
    QueueFull, same deadline stamping."""
    cfg = SchedulerConfig(max_queue_depth=4, default_deadline=7.0)
    fifo, ten = FifoScheduler(cfg), TenantScheduler(
        [TenantClass(DEFAULT_TENANT)], cfg)
    for s in (fifo, ten):
        for i in range(4):
            s.submit(_req(i), now=float(i))
        with pytest.raises(QueueFull):
            s.submit(_req(9), now=4.0)
    assert [r.id for r in fifo.waiting] == [r.id for r in ten.waiting]
    assert [r.deadline for r in fifo.waiting] \
        == [r.deadline for r in ten.waiting]
    eng = FakeEngine(free_slots=3, prefill_batch=2)
    assert fifo.next_action(eng) == ten.next_action(eng)
    assert fifo.expire(20.0) and ten.expire(20.0)
    assert len(fifo) == len(ten) == 0


def test_class_queue_quota_sheds_with_class_context():
    """A class at its own max_queue_depth sheds ClassQueueFull (carrying
    the saturated class's name/depth) while other classes still admit —
    class-aware admission control, not a global verdict."""
    sched = TenantScheduler([
        TenantClass("fast", tier="interactive"),
        TenantClass("bulk", tier="batch", max_queue_depth=2)])
    sched.submit(_req(0, "bulk"), now=0.0)
    sched.submit(_req(1, "bulk"), now=0.0)
    with pytest.raises(ClassQueueFull) as ei:
        sched.submit(_req(2, "bulk"), now=3.0)
    exc = ei.value
    assert exc.tenant == "bulk" and exc.class_queue_depth == 2
    assert exc.class_oldest_age == 3.0 and exc.queue_depth == 2
    assert isinstance(exc, QueueFull)  # existing shed paths handle it
    sched.submit(_req(3, "fast"))  # the other class is unaffected
    assert sched.class_depths() == {"fast": 1, "bulk": 2, "default": 0}
    assert sched.shed_counts()["bulk"] == 1


def test_global_queue_full_carries_class_breakdown():
    sched = TenantScheduler(CLASSES, SchedulerConfig(max_queue_depth=3))
    sched.submit(_req(0, "fast"), now=0.0)
    sched.submit(_req(1, "bulk"), now=1.0)
    sched.submit(_req(2, "bulk"), now=2.0)
    with pytest.raises(QueueFull) as ei:
        sched.submit(_req(3, "fast"), now=5.0)
    exc = ei.value
    assert exc.class_depths == {"fast": 1, "bulk": 2, "default": 0}
    assert exc.class_oldest == {"fast": 5.0, "bulk": 4.0}


def test_max_active_slots_quota_gates_selection():
    """A class at its slot quota contributes no admission candidates;
    the quota counts decoding AND chunk-prefilling holders (anything in
    the engine's active map)."""
    classes = [TenantClass("fast", tier="interactive"),
               TenantClass("bulk", tier="batch", max_active_slots=2)]
    sched = TenantScheduler(classes)
    for i in range(2):
        sched.submit(_req(i, "bulk"))
    sched.submit(_req(2, "fast"))
    eng = FakeEngine(free_slots=2, prefill_batch=2,
                     active=[_req(10, "bulk"), _req(11, "bulk")])
    action, reqs = sched.next_action(eng)
    assert action == ACTION_PREFILL
    assert [r.tenant for r in reqs] == ["fast"]  # bulk fenced at quota
    # slots retired: bulk is admissible again
    action, reqs = sched.next_action(FakeEngine(free_slots=2,
                                                prefill_batch=2))
    assert [r.tenant for r in reqs] == ["bulk", "bulk"]


def test_per_class_default_deadline_overrides_global():
    sched = TenantScheduler(
        [TenantClass("fast", tier="interactive", default_deadline=2.0),
         TenantClass("bulk", tier="batch")],
        SchedulerConfig(default_deadline=50.0))
    sched.submit(_req(0, "fast"), now=10.0)
    sched.submit(_req(1, "bulk"), now=10.0)
    sched.submit(_req(2, "fast", deadline=99.0), now=10.0)  # explicit wins
    deadlines = {r.id: r.deadline for r in sched.waiting}
    assert deadlines == {0: 12.0, 1: 60.0, 2: 99.0}
    assert [r.id for r in sched.expire(13.0)] == [0]


def test_unknown_tenant_and_bad_class_configs_are_loud(nano):
    dec, params = nano
    with pytest.raises(ValueError, match="unknown tenant"):
        TenantScheduler(CLASSES).submit(_req(0, "ghost"))
    with pytest.raises(ValueError):
        TenantClass("fast", weight=0.0)
    with pytest.raises(ValueError):
        TenantClass("fast", tier="express")
    with pytest.raises(ValueError):
        TenantScheduler([TenantClass("a"), TenantClass("a")])
    with pytest.raises(ValueError):
        TenantScheduler([])
    client = ServeClient(dec, params, num_slots=1, prefill_len=8)
    try:
        with pytest.raises(ValueError, match="no tenant classes"):
            client.submit([1, 2], max_new_tokens=2, tenant="fast")
    finally:
        client.shutdown()
    armed = ServeClient(dec, params, num_slots=1, prefill_len=8,
                        tenant_classes=CLASSES)
    try:
        with pytest.raises(ValueError, match="unknown tenant"):
            armed.submit([1, 2], max_new_tokens=2, tenant="ghost")
        # the auto-appended default class keeps untenanted submits valid
        armed.submit([1, 2], max_new_tokens=2)
    finally:
        armed.shutdown()


def test_engine_enforces_max_active_slots_for_direct_callers(nano):
    """The scheduler-driven path never trips the engine quota; a direct
    prefill() past it must refuse loudly with the tenant named, and the
    atomic-admission rollback must hold."""
    dec, params = nano
    classes = [TenantClass("bulk", tier="batch", max_active_slots=1)]
    eng = ServeEngine(dec, params, num_slots=3, prefill_len=8,
                      tenant_classes=classes)
    try:
        eng.prefill([_req(0, "bulk", max_new_tokens=6)])
        with pytest.raises(SlotPoolFull) as ei:
            eng.prefill([_req(1, "bulk", max_new_tokens=6)])
        assert ei.value.tenant == "bulk"
        assert eng.free_slots == 2  # rollback kept the refused slot free
    finally:
        eng.shutdown()


# --------------------------------------------------------------------- #
# end-to-end: ordering-only scheduling, determinism, recovery
# --------------------------------------------------------------------- #
MIXED_TRACE = [
    (0, dict(prompt=[11, 12], max_new_tokens=5, tenant="bulk")),
    (0, dict(prompt=[13, 14, 9], max_new_tokens=5, tenant="bulk")),
    (0, dict(prompt=[15], max_new_tokens=4, tenant="fast")),
    (1, dict(prompt=[16, 8], max_new_tokens=4, tenant="fast",
             temperature=0.8, top_k=12)),
    (2, dict(prompt=[4, 2, 6], max_new_tokens=4)),
    (4, dict(prompt=[7, 7], max_new_tokens=3, tenant="bulk")),
]


def _mixed_client(dec, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("tenant_classes", CLASSES)
    return ServeClient(dec, params, **kw)


def test_ab_default_class_is_behaviorally_identical_to_untenanted(nano):
    """THE acceptance A/B: arming tenancy with only the default class
    changes nothing — admission order, tokens, timing stamps and the
    event stream (modulo the additional engine.tenant_* events, which
    are the only new emissions) are identical to the untenanted
    client."""
    dec, params = nano
    trace = [(t, {k: v for k, v in kw.items() if k != "tenant"})
             for t, kw in MIXED_TRACE]

    def run(tenant_classes):
        tel = Telemetry()
        client = ServeClient(dec, params, num_slots=2, prefill_len=8,
                             telemetry=tel, tenant_classes=tenant_classes)
        try:
            out = client.serve_trace(list(trace))
        finally:
            client.shutdown()
        comps = {r: (c.tokens, c.finish_reason, c.arrival_time,
                     c.first_token_time, c.finish_time)
                 for r, c in out.items()}
        events = [(e.site, e.payload) for e in tel.events()]
        metrics = {k: v for k, v in tel.metrics.snapshot().items()
                   if "serve_tenant" not in k}
        return comps, events, metrics

    comps_a, events_a, metrics_a = run(None)
    comps_b, events_b, metrics_b = run([TenantClass(DEFAULT_TENANT)])
    assert comps_a == comps_b
    tenant_b = [e for e in events_b if e[0].startswith("engine.tenant")]
    assert tenant_b, "armed tenancy should emit its own events"
    assert [e for e in events_b
            if not e[0].startswith("engine.tenant")] == events_a
    assert metrics_a == metrics_b


def test_mixed_class_tokens_identical_to_solo_runs(nano):
    """Scheduling is ordering-only: every request in a contended
    mixed-class run (greedy AND sampled rows) emits exactly its solo
    tokens — the tenancy layer never touches a key stream."""
    dec, params = nano
    client = _mixed_client(dec, params)
    try:
        out = client.serve_trace(list(MIXED_TRACE))
    finally:
        client.shutdown()
    assert {r: c.tenant for r, c in out.items()} == {
        0: "bulk", 1: "bulk", 2: "fast", 3: "fast", 4: "default",
        5: "bulk"}
    for rid, (_t, kw) in enumerate(MIXED_TRACE):
        solo = _mixed_client(dec, params)
        try:
            sid = solo.submit(seed=rid, **kw)  # pin the mixed run's seed
            ref = solo.run_until_idle()[sid]
        finally:
            solo.shutdown()
        assert out[rid].tokens == ref.tokens, rid
        assert out[rid].finish_reason == ref.finish_reason


def test_tick_trace_jsonl_replays_byte_identically(tmp_path, nano):
    """Tenancy armed, tick clock: the same mixed-class trace writes a
    byte-identical JSONL event log every run — deterministic tie-breaks
    all the way down."""
    dec, params = nano

    def run(path):
        tel = Telemetry(jsonl_path=str(path))
        client = _mixed_client(dec, params, telemetry=tel)
        try:
            client.serve_trace(list(MIXED_TRACE))
        finally:
            client.shutdown()
        tel.flush()
        return path.read_bytes()

    first = run(tmp_path / "a.jsonl")
    assert first == run(tmp_path / "b.jsonl")
    assert b"engine.tenant_admitted" in first


def test_crash_replay_preserves_class_assignment_and_tokens(nano):
    """A supervised engine crash mid-mixed-trace rebuilds and replays:
    no request fails, every stream is token-identical to the unfaulted
    run, and every completion keeps its tenant class."""
    dec, params = nano

    def run(plan=None):
        client = _mixed_client(
            dec, params,
            retry_policy=RetryPolicy(max_attempts=3, base_delay=0.0))
        try:
            if plan is not None:
                with plan.armed():
                    out = client.serve_trace(list(MIXED_TRACE))
            else:
                out = client.serve_trace(list(MIXED_TRACE))
            return out, client.engine.rebuilds
        finally:
            client.shutdown()

    ref, _ = run()
    chaos, rebuilds = run(FaultPlan.at("serve.dispatch", [5]))
    assert rebuilds >= 1
    for rid, comp in ref.items():
        assert chaos[rid].finish_reason != FINISH_FAILED
        assert chaos[rid].tokens == comp.tokens, rid
        assert chaos[rid].tenant == comp.tenant


def test_fleet_failover_preserves_class_assignment_and_tokens(nano):
    """A replica killed mid-flight re-admits its mixed-class work to
    survivors through the replay path: class assignment rides the
    Request objects, tokens stay identical to the unfaulted fleet."""
    dec, params = nano

    def run(plan=None):
        fleet = ReplicaFleet(dec, params, num_replicas=3, num_standby=1,
                             num_slots=2, prefill_len=8,
                             tenant_classes=CLASSES)
        try:
            if plan is not None:
                with plan.armed():
                    out = fleet.serve_trace(list(MIXED_TRACE))
            else:
                out = fleet.serve_trace(list(MIXED_TRACE))
            return out, fleet.failovers
        finally:
            fleet.shutdown()

    ref, _ = run()
    chaos, failovers = run(FaultPlan.at("serve.replica", [4]))
    assert failovers >= 1
    for rid, comp in ref.items():
        assert chaos[rid].finish_reason != FINISH_FAILED
        assert chaos[rid].tokens == comp.tokens, rid
        assert chaos[rid].tenant == comp.tenant


def test_fleet_saturated_carries_aggregated_class_context(nano):
    """Every replica refusing a class-quota shed raises FleetSaturated
    with the per-class depth breakdown aggregated fleet-wide — shed
    logging names the saturated class."""
    dec, params = nano
    classes = [TenantClass("fast", tier="interactive"),
               TenantClass("bulk", tier="batch", max_queue_depth=1)]
    fleet = ReplicaFleet(dec, params, num_replicas=2, num_slots=1,
                         prefill_len=8, tenant_classes=classes)
    try:
        fleet.submit([1, 2], max_new_tokens=2, tenant="bulk")
        fleet.submit([3, 4], max_new_tokens=2, tenant="bulk")
        with pytest.raises(FleetSaturated) as ei:
            fleet.submit([5, 6], max_new_tokens=2, tenant="bulk")
        exc = ei.value
        assert exc.class_depths["bulk"] == 2
        assert exc.replicas == 2
        # the other class still has fleet-wide headroom
        fleet.submit([7, 8], max_new_tokens=2, tenant="fast")
        out = fleet.run_until_idle()
        assert all(c.finish_reason != FINISH_FAILED for c in out.values())
    finally:
        fleet.shutdown()


def test_tenant_obs_armed_and_disarmed(nano):
    """Armed: per-tenant admit/shed events and keyed metrics land on the
    handle (TTFT histogram per class, SLO-miss counter, shed counter).
    Disarmed (telemetry=None, the default): no handle reaches any layer
    — the zero-surface contract every obs site follows."""
    dec, params = nano
    tel = Telemetry()
    classes = [TenantClass("fast", tier="interactive", ttft_slo=0.5),
               TenantClass("bulk", tier="batch", max_queue_depth=1)]
    client = ServeClient(dec, params, num_slots=2, prefill_len=8,
                         telemetry=tel, tenant_classes=classes)
    try:
        client.submit([1, 2], max_new_tokens=3, tenant="fast")
        client.submit([3, 4], max_new_tokens=3, tenant="bulk")
        with pytest.raises(ClassQueueFull):
            client.submit([5, 6], max_new_tokens=3, tenant="bulk")
        client.run_until_idle()
    finally:
        client.shutdown()
    admitted = tel.events("engine.tenant_admitted")
    assert [e.payload["tenant"] for e in admitted] == ["fast", "bulk"]
    shed = tel.events("engine.tenant_shed")
    assert [e.payload["tenant"] for e in shed] == ["bulk"]
    snap = tel.metrics.snapshot()
    assert snap["serve_tenant_shed_total_bulk"] == 1
    assert snap["serve_tenant_ttft_ms_fast"]["count"] == 1
    assert snap["serve_tenant_ttft_ms_bulk"]["count"] == 1
    # every tick-clock TTFT (>= 1 tick) misses the rigged 0.5-tick SLO
    assert snap["serve_tenant_slo_miss_total_fast"] == 1
    assert "serve_tenant_slo_miss_total_bulk" not in snap  # no slo set

    disarmed = ServeClient(dec, params, num_slots=2, prefill_len=8,
                           tenant_classes=classes)
    try:
        assert disarmed._tel is None and disarmed.engine._tel is None
        disarmed.submit([1, 2], max_new_tokens=2, tenant="fast")
        disarmed.run_until_idle()
    finally:
        disarmed.shutdown()


def test_completion_tenant_rides_every_retirement_path(nano):
    """eos/length, queued-deadline expiry, mid-decode cancel and trace
    rejection completions all carry the class."""
    dec, params = nano
    classes = [TenantClass("fast", tier="interactive"),
               TenantClass("bulk", tier="batch", max_queue_depth=1)]
    client = ServeClient(dec, params, num_slots=1, prefill_len=8,
                         tenant_classes=classes)
    try:
        trace = [
            (0, dict(prompt=[1, 2], max_new_tokens=8, tenant="bulk")),
            # queued behind the 1-slot engine, expires waiting
            (1, dict(prompt=[3], max_new_tokens=2, tenant="fast",
                     deadline=3.0)),
            # bulk queue quota: shed as a rejected completion
            (1, dict(prompt=[4], max_new_tokens=2, tenant="bulk")),
            (1, dict(prompt=[9], max_new_tokens=2, tenant="bulk")),
        ]
        out = client.serve_trace(trace)
    finally:
        client.shutdown()
    reasons = {r: (c.finish_reason, c.tenant) for r, c in out.items()}
    assert reasons[0] == ("length", "bulk")
    assert reasons[1] == ("timeout", "fast")
    assert reasons[3] == ("rejected", "bulk")


def test_interactive_ttft_ticks_bounded_under_batch_flood(nano):
    """SLO isolation as dispatch counts (tick clock — no wall time): a
    saturating t=0 batch flood with interactive arrivals trickling in.
    The tiered scheduler holds the worst interactive TTFT within one
    in-flight bulk budget of its solo run (a fast arrival waits for a
    slot, never for the backlog: tiers jump the queue, they do not
    preempt), plain FIFO on the same trace is at least twice as late,
    and no bulk request starves."""
    dec, params = nano
    bulk_n, bulk_new, fast_new, slack = 10, 24, 8, 4.0
    rng = np.random.default_rng(7)
    flood = [(0, dict(prompt=rng.integers(0, 128, 8).tolist(),
                      max_new_tokens=bulk_new, tenant="bulk"))
             for _ in range(bulk_n)]
    fast = [(5 + 20 * i, dict(prompt=rng.integers(0, 128, 4).tolist(),
                              max_new_tokens=fast_new, tenant="fast"))
            for i in range(4)]
    fast_ids = range(bulk_n, bulk_n + len(fast))

    def run(trace, tenant_classes):
        client = ServeClient(dec, params, num_slots=2, prefill_len=8,
                             tenant_classes=tenant_classes)
        try:
            return client.serve_trace(list(trace))
        finally:
            client.shutdown()

    def worst_ttft(out, ids):
        return max(out[r].time_to_first_token for r in ids)

    tiered = run(flood + fast, CLASSES)
    fifo = run([(t, {k: v for k, v in kw.items() if k != "tenant"})
                for t, kw in flood + fast], None)
    solo = run(fast, CLASSES)
    assert worst_ttft(tiered, fast_ids) <= \
        worst_ttft(solo, range(len(fast))) + bulk_new + slack
    assert 2.0 * worst_ttft(tiered, fast_ids) <= worst_ttft(fifo, fast_ids)
    assert all(tiered[r].finish_reason != FINISH_FAILED
               and len(tiered[r].tokens) == bulk_new
               for r in range(bulk_n))
