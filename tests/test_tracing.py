"""End-to-end request tracing (PR 19): span trees, exact decomposition,
fleet-stitched Chrome export, SLO-miss attribution.

The load-bearing assertions (ISSUE 19 acceptance):

- **exact telescoping decomposition** — every assembled trace's
  queue/prefill/decode/sync/failover segments are contiguous (each
  starts where the previous ended) and their durations sum EXACTLY to
  end-to-end latency; under the tick clock these are exact integers;
- **failover is an annotated edge, not a new trace** — a mid-decode
  replica death re-admits the victim's requests onto the SAME trace id
  with a ``failover`` segment and a ``resubmit`` annotation; one trace
  per request, always;
- **byte-identical fleet export** — two identical tick-clock fleet
  runs produce byte-identical ``export_fleet_trace`` files (the same
  contract the JSONL event log pins);
- **cross-process stitching** (``test_fleet_process``-marked) — worker
  spans ship over ``MSG_SPAN`` onto the driver recorder tagged with
  their replica seat, and a kill -9 victim's last flushed spans
  survive into the stitched trace;
- **zero-cost when disarmed** — ``telemetry=None`` leaves every new
  call site inert: no sync-duration state, no span extras, empty
  ``metrics_snapshot()``/``request_traces()``, export refuses.
"""
import json
import math
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import TransformerLM, gpt2_config
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.obs.tracing import (SEGMENT_LABELS,
                                           assemble_request_traces,
                                           decomposition_rows,
                                           format_decomposition,
                                           format_slo_report,
                                           load_jsonl_events,
                                           slo_miss_attribution,
                                           tenant_rollup)
from ray_lightning_tpu.reliability import FaultPlan
from ray_lightning_tpu.serve import ReplicaFleet, ServeClient

pytestmark = [pytest.mark.serve]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def nano():
    mk = dict(vocab_size=128, max_seq_len=64, dtype=jnp.float32,
              scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(0), np.zeros((2, 4), np.int32))["params"]
    return dec, params


TRACE = [
    (0, dict(prompt=[5, 17, 3, 9], max_new_tokens=6)),
    (0, dict(prompt=[9, 2, 44], max_new_tokens=6)),
    (3, dict(prompt=[42, 7], max_new_tokens=5)),
    (5, dict(prompt=[1], max_new_tokens=6)),
]


def _assert_telescoping(tr, exact=True):
    """The decomposition contract: contiguous segments covering
    [arrival, retired] whose durations sum to the end-to-end latency."""
    assert tr.arrival is not None and tr.retired is not None, tr.id
    assert tr.segments, tr.id
    assert tr.segments[0].start == tr.arrival, tr.id
    assert tr.segments[-1].end == tr.retired, tr.id
    for a, b in zip(tr.segments, tr.segments[1:]):
        assert a.end == b.start, (tr.id, a, b)
    for seg in tr.segments:
        assert seg.label in SEGMENT_LABELS, seg
        assert seg.dur > 0, seg
    total = sum(seg.dur for seg in tr.segments)
    if exact:
        assert total == tr.total, tr.id
    else:  # wall clock: float summation, contiguity is still exact
        assert math.isclose(total, tr.total, rel_tol=1e-9), tr.id


# --------------------------------------------------------------------- #
# assembler unit tests (synthetic event dicts — the JSONL shape)
# --------------------------------------------------------------------- #
def _ev(site, **payload):
    return {"site": site, "t": payload.get("t", 0), "payload": payload}


def test_assembler_exact_decomposition_with_sync_split():
    events = [
        _ev("fleet.route", id=1, replica=2, load=0),
        _ev("serve.submit", id=1, prompt_len=4, max_new_tokens=8, t=0.0),
        _ev("engine.tenant_admitted", id=1, tenant="interactive"),
        _ev("serve.admit", id=1, queue_wait=2.0, t=2.0),
        _ev("engine.prefill", n=1, ids=[1], slots=[3]),
        _ev("serve.first_token", id=1, ttft=5.0, t=5.0),
        _ev("serve.retire", id=1, finish_reason="length", tokens=8,
            tenant="interactive", t=10.0, sync=1.0),
    ]
    traces = assemble_request_traces(events)
    assert list(traces) == [1]
    tr = traces[1]
    assert [s.label for s in tr.segments] == ["queue", "prefill",
                                              "decode", "sync"]
    assert [(s.start, s.end) for s in tr.segments] == [
        (0.0, 2.0), (2.0, 5.0), (5.0, 9.0), (9.0, 10.0)]
    _assert_telescoping(tr)
    assert tr.total == 10.0 and tr.ttft == 5.0
    assert tr.tenant == "interactive" and tr.tokens == 8
    assert tr.replicas == [2] and tr.slots == [3]
    # segments carry their fleet location (the Chrome pid/tid tracks)
    assert tr.segments[1].replica == 2 and tr.segments[1].slot == 3
    assert tr.breakdown() == {"queue": 2.0, "prefill": 3.0,
                              "decode": 4.0, "sync": 1.0, "failover": 0.0}


def test_assembler_failover_is_annotated_edge_not_new_trace():
    events = [
        _ev("serve.submit", id=7, prompt_len=2, t=0.0),
        _ev("serve.admit", id=7, queue_wait=1.0, t=1.0),
        _ev("serve.first_token", id=7, ttft=3.0, t=3.0),
        # replica dies; driver re-routes and the survivor re-admits
        _ev("fleet.route", id=7, replica=1, load=0),
        _ev("serve.submit", id=7, prompt_len=2, t=5.0),
        _ev("recovery.replay", id=7, replayed_tokens=4),
        _ev("serve.admit", id=7, queue_wait=0.5, t=6.0),
        _ev("serve.retire", id=7, finish_reason="length", tokens=8,
            t=9.0),
    ]
    traces = assemble_request_traces(events)
    assert list(traces) == [7]  # the id IS the trace id — never forks
    tr = traces[7]
    assert tr.resubmits == 1
    assert [s.label for s in tr.segments] == ["queue", "prefill",
                                              "failover", "decode"]
    assert (tr.segments[2].start, tr.segments[2].end) == (3.0, 6.0)
    _assert_telescoping(tr)
    edges = [a["edge"] for a in tr.annotations]
    assert edges == ["resubmit", "replay"]
    assert tr.annotations[1]["replayed_tokens"] == 4


def test_assembler_lost_first_admit_becomes_failover_edge():
    """kill -9 can eat the victim's ``serve.admit`` flush batch: the
    survivor's re-admission (after a duplicate submit) must still be a
    failover edge on the original arrival, never a fresh first
    admission that rewrites the trace's start."""
    events = [
        _ev("serve.submit", id=5, prompt_len=2, t=1.0),
        # victim dies; its admit/first_token never flushed
        _ev("serve.submit", id=5, prompt_len=2, t=6.0),
        _ev("serve.admit", id=5, queue_wait=5.5, t=6.5),
        _ev("serve.first_token", id=5, ttft=6.0, t=7.0),
        _ev("serve.retire", id=5, finish_reason="length", tokens=4,
            t=9.0),
    ]
    traces = assemble_request_traces(events)
    tr = traces[5]
    assert tr.arrival == 1.0  # the original submit stamp survives
    assert [s.label for s in tr.segments] == ["failover", "prefill",
                                              "decode"]
    assert (tr.segments[0].start, tr.segments[0].end) == (1.0, 6.5)
    _assert_telescoping(tr)
    assert tr.resubmits == 1


def test_assembler_tolerates_ring_truncation():
    # a request whose submit was evicted is skipped, not half-assembled
    events = [
        _ev("serve.admit", id=3, queue_wait=1.0, t=4.0),
        _ev("serve.retire", id=3, finish_reason="length", tokens=2,
            t=8.0),
        _ev("serve.submit", id=4, prompt_len=1, t=5.0),
        _ev("serve.admit", id=4, queue_wait=0.0, t=5.0),
        _ev("serve.retire", id=4, finish_reason="length", tokens=1,
            t=7.0),
    ]
    traces = assemble_request_traces(events)
    assert list(traces) == [4]
    _assert_telescoping(traces[4])


def test_slo_miss_attribution_fractions():
    mk = [  # two interactive requests: ttft 5 (miss at slo=4) and 2
        _ev("serve.submit", id=1, t=0.0),
        _ev("serve.admit", id=1, queue_wait=2.0, t=2.0),
        _ev("serve.first_token", id=1, ttft=5.0, t=5.0),
        _ev("serve.retire", id=1, finish_reason="length", tokens=4,
            tenant="interactive", t=8.0),
        _ev("serve.submit", id=2, t=1.0),
        _ev("serve.admit", id=2, queue_wait=0.5, t=1.5),
        _ev("serve.first_token", id=2, ttft=2.0, t=3.0),
        _ev("serve.retire", id=2, finish_reason="length", tokens=4,
            tenant="interactive", t=6.0),
    ]
    traces = assemble_request_traces(mk)
    rep = slo_miss_attribution(traces, {"interactive": 4.0})
    ia = rep["interactive"]
    assert (ia["count"], ia["misses"]) == (2, 1)
    # the missing request spent 2 queued + 3 prefilling before its
    # first token: 40% / 60%, summing to 1
    assert ia["attribution"] == {"queue": 0.4, "prefill": 0.6}
    assert math.isclose(sum(ia["attribution"].values()), 1.0)
    # report plumbing over the same traces
    assert "interactive: 1/2 TTFT misses" in format_slo_report(
        traces, {"interactive": 4.0})
    table = format_decomposition(traces)
    assert "queue" in table and "failover" in table
    rows = decomposition_rows(traces)
    assert [r["id"] for r in rows] == [1, 2]
    roll = tenant_rollup(traces)
    assert roll["interactive"]["count"] == 2


# --------------------------------------------------------------------- #
# live client: sync split + offline JSONL round-trip + CLI
# --------------------------------------------------------------------- #
def test_async_client_traces_split_sync_and_drain_state(nano, tmp_path):
    """Armed async-dispatch client: retire events carry the enqueue->
    sync reconciliation window, the assembled traces split it off the
    decode tail, sums stay exact under the tick clock — and the
    same traces assemble from the flushed JSONL log (the offline
    ``tools/trace_report.py`` path)."""
    dec, params = nano
    log = str(tmp_path / "serve.jsonl")
    tel = Telemetry(jsonl_path=log)
    client = ServeClient(dec, params, num_slots=2, prefill_len=16,
                         async_dispatch=True, telemetry=tel)
    out = client.serve_trace(TRACE)
    client.shutdown()
    tel.flush()
    traces = tel.request_traces()
    assert sorted(traces) == sorted(out)
    assert any(s.label == "sync" for tr in traces.values()
               for s in tr.segments)
    for rid, tr in traces.items():
        _assert_telescoping(tr)
        assert tr.tokens == len(out[rid].tokens)
        assert tr.ttft == out[rid].time_to_first_token
        assert tr.total == out[rid].latency
    # retired sync bookkeeping fully drained — no leak across requests
    assert client._sync_durs == {}
    # offline: the flushed log assembles to the SAME decomposition
    offline = assemble_request_traces(load_jsonl_events(log))
    assert {rid: [(s.label, s.start, s.end) for s in tr.segments]
            for rid, tr in offline.items()} == \
           {rid: [(s.label, s.start, s.end) for s in tr.segments]
            for rid, tr in traces.items()}


def test_trace_report_cli_over_flushed_log(nano, tmp_path):
    dec, params = nano
    log = str(tmp_path / "serve.jsonl")
    tel = Telemetry(jsonl_path=log)
    client = ServeClient(dec, params, num_slots=2, prefill_len=16,
                         telemetry=tel)
    client.serve_trace(TRACE)
    client.shutdown()
    tel.flush()
    trace_out = str(tmp_path / "trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         log, "--slo", "interactive=4.0", "--trace-out", trace_out,
         "--json"],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    assert len(doc["requests"]) == len(TRACE)
    assert "interactive" in doc["slo"]
    chrome = json.load(open(trace_out))
    assert {e["args"]["label"] for e in chrome["traceEvents"]} \
        <= set(SEGMENT_LABELS)


# --------------------------------------------------------------------- #
# in-process fleet: failover traces, byte-identical export, namespacing
# --------------------------------------------------------------------- #
def _fleet_run(dec, params, tel=None, export=None):
    fleet = ReplicaFleet(dec, params, num_replicas=3, num_standby=1,
                         num_slots=2, prefill_len=16, telemetry=tel)
    plan = FaultPlan.at("serve.replica", [7])
    with plan.armed():
        out = fleet.serve_trace(TRACE)
    traces = fleet.request_traces()
    if export is not None:
        fleet.export_fleet_trace(export)
    fleet.shutdown()
    return out, traces


@pytest.mark.fleet
def test_fleet_failover_traces_exact_tick_sums(nano):
    """A mid-decode replica kill under the tick clock: one trace per
    request, the victim's requests carry a ``failover`` segment on the
    SAME trace, and every decomposition sums to exact integers."""
    dec, params = nano
    tel = Telemetry()
    out, traces = _fleet_run(dec, params, tel)
    assert sorted(traces) == sorted(out)
    for rid, tr in traces.items():
        _assert_telescoping(tr)
        assert float(tr.total).is_integer(), rid  # tick clock
        assert tr.tokens == len(out[rid].tokens)
        assert tr.finish_reason == out[rid].finish_reason
    displaced = [tr for tr in traces.values() if tr.resubmits]
    assert displaced, "the kill displaced nobody — fault never fired"
    for tr in displaced:
        labels = [s.label for s in tr.segments]
        assert "failover" in labels
        assert "decode" in labels  # zero queue wait = no queue segment
        assert {a["edge"] for a in tr.annotations} >= {"resubmit"}
    # the fleet handle and the raw telemetry agree
    assert sorted(tel.request_traces()) == sorted(traces)


@pytest.mark.fleet
def test_fleet_trace_export_byte_identical_across_runs(nano, tmp_path):
    dec, params = nano
    paths = [str(tmp_path / f"fleet{i}.json") for i in (0, 1)]
    for p in paths:
        _fleet_run(dec, params, Telemetry(), export=p)
    b0, b1 = (open(p, "rb").read() for p in paths)
    assert b0 == b1
    doc = json.loads(b0)
    evs = doc["traceEvents"]
    assert evs
    # multi-track: engine spans landed on their replica seat's pid and
    # request segments on the replica/slot that served them
    assert {e["pid"] for e in evs} >= {0, 1}
    span_names = {e["name"] for e in evs if not e["name"].startswith("req")}
    assert any(n.startswith("engine.") for n in span_names)
    seg_labels = {e["args"]["label"] for e in evs
                  if e["name"].startswith("req")}
    # same-tick admits/prefills collapse to zero width; decode and the
    # injected failover always span ticks here
    assert {"decode", "failover"} <= seg_labels


@pytest.mark.fleet
def test_fleet_metrics_snapshot_namespaces_replica_gauges(nano):
    dec, params = nano
    tel = Telemetry()
    fleet = ReplicaFleet(dec, params, num_replicas=2, num_slots=2,
                         prefill_len=16, telemetry=tel)
    fleet.serve_trace(TRACE[:2])
    snap = fleet.metrics_snapshot()
    fleet.shutdown()
    assert "serve_queue_depth_r0" in snap
    assert "serve_queue_depth_r1" in snap
    assert "serve_slot_occupancy_r0" in snap
    # raw replica<N>_ spellings are rewritten, never passed through
    assert not any(k.startswith("replica") for k in snap)
    # fleet-level (and shared-counter) series pass through untouched
    assert snap["serve_fleet_replicas_live"] == 2
    assert snap["serve_requests_total"] == 2.0


@pytest.mark.fleet
def test_disarmed_tracing_surface_is_zero(nano):
    """telemetry=None: no tracing state anywhere — and the trace
    accessors say so instead of fabricating empties."""
    dec, params = nano
    client = ServeClient(dec, params, num_slots=2, prefill_len=16,
                         async_dispatch=True)
    client.serve_trace(TRACE[:2])
    assert client._sync_durs == {}
    assert client.engine._span_extra == {}
    client.shutdown()
    fleet = ReplicaFleet(dec, params, num_replicas=2, num_slots=2,
                         prefill_len=16)
    fleet.serve_trace(TRACE[:2])
    assert fleet.metrics_snapshot() == {}
    assert fleet.request_traces() == {}
    with pytest.raises(RuntimeError, match="telemetry"):
        fleet.export_fleet_trace("/tmp/never-written.json")
    fleet.shutdown()
    assert not os.path.exists("/tmp/never-written.json")


# --------------------------------------------------------------------- #
# process backend: MSG_SPAN forwarding + kill -9 stitching
# --------------------------------------------------------------------- #
WALL_TRACE = [
    (0.0, dict(prompt=[5, 17, 3, 9], max_new_tokens=6)),
    (0.0, dict(prompt=[9, 2, 44], max_new_tokens=6)),
    (0.2, dict(prompt=[42, 7], max_new_tokens=5)),
]


@pytest.mark.fleet_process
@pytest.mark.multiproc
def test_process_fleet_spans_forwarded_with_seat_tags(nano):
    """Armed process backend: worker-side engine spans ship over
    MSG_SPAN onto the driver recorder tagged with their replica seat,
    and the assembled traces telescope on the shared fleet timeline."""
    dec, params = nano
    tel = Telemetry()
    fleet = ReplicaFleet(dec, params, backend="process", num_replicas=2,
                         num_slots=4, prefill_len=16, telemetry=tel)
    try:
        out = fleet.serve_trace(WALL_TRACE)
        traces = fleet.request_traces()
    finally:
        fleet.shutdown()
    spans = tel.spans.spans()
    assert spans, "no worker spans arrived over MSG_SPAN"
    seats = {s.args.get("seat") for s in spans}
    assert seats >= {0, 1}  # both replicas' spans, stitched
    assert any(s.name == "engine.prefill.call" for s in spans)
    assert all(s.dur >= 0 for s in spans)
    assert sorted(traces) == sorted(out)
    for tr in traces.values():
        _assert_telescoping(tr, exact=False)


@pytest.mark.fleet_process
@pytest.mark.multiproc
@pytest.mark.slow
def test_process_fleet_kill9_traces_stitch_across_death(nano):
    """kill -9 a replica mid-decode: every request still assembles ONE
    trace; the victim's requests carry the failover edge on the shared
    fleet timeline with exact telescoping, and the victim's last
    flushed spans survive (they rode the death-surviving queue)."""
    dec, params = nano
    tel = Telemetry()
    reqs = [dict(prompt=[5, 17, 3, 9], max_new_tokens=20),
            dict(prompt=[9, 2, 44], max_new_tokens=20),
            dict(prompt=[42, 7], max_new_tokens=18),
            dict(prompt=[1, 33, 2], max_new_tokens=20)]
    fleet = ReplicaFleet(dec, params, backend="process", num_replicas=2,
                         num_standby=1, telemetry=tel, num_slots=2,
                         prefill_len=32, steps_per_dispatch=2)
    try:
        for kw in reqs:
            fleet.submit(**kw)
        victim = fleet._replicas[0]
        deadline = time.time() + 90.0
        while time.time() < deadline:
            fleet.tick()
            if any(t.replica == victim.id and t.tokens
                   for t in fleet._inflight.values()):
                break
            time.sleep(0.01)  # tl-lint: allow-sleep — wall-clock poll against real worker processes
        else:
            raise AssertionError("victim never flushed decode progress")
        os.kill(victim.actor._proc.pid, signal.SIGKILL)
        out = fleet.run_until_idle()
        traces = fleet.request_traces()
    finally:
        fleet.shutdown()
    assert fleet.failovers == 1
    assert sorted(traces) == sorted(out)          # one trace per request
    for rid, tr in traces.items():
        _assert_telescoping(tr, exact=False)
        assert tr.finish_reason == out[rid].finish_reason
        assert tr.tokens == len(out[rid].tokens)
    displaced = [tr for tr in traces.values() if tr.resubmits]
    assert displaced, "kill displaced nobody"
    for tr in displaced:
        assert "failover" in {s.label for s in tr.segments}
        assert {a["edge"] for a in tr.annotations} >= {"resubmit"}
    # replayed re-admissions annotate the trace they re-joined
    assert any(a["edge"] == "replay" for tr in displaced
               for a in tr.annotations)
    # the corpse's spans are on the driver recorder, seat-tagged
    assert {s.args.get("seat") for s in tel.spans.spans()} >= {victim.id}
