"""Offline reports: request traces from a JSONL event log, and device time
by scope + idle gaps by program span from a profile directory.

**Request traces** (``trace_report.py serve.jsonl``).

Runs the same assembly as ``Telemetry.request_traces()``
(``obs/tracing.py``) against a log file on disk — no live process
needed. Prints the per-request latency decomposition table (queue /
prefill / decode / sync / failover columns summing exactly to
end-to-end latency), the per-tenant-class rollup, and — given
``--slo`` targets — the SLO-miss attribution report ("interactive p99
TTFT miss = 78% class-queue wait"). Optionally exports the stitched
Chrome trace (request segments only: spans live in the recorder, not
the event log).

**Profiles** (``trace_report.py --profile <dir>``). ``<dir>`` is what
``JaxProfilerCallback`` or any ``jax.profiler.start_trace`` wrote (one
``.xplane.pb`` under ``plugins/profile/<time>/``). Prints, per device:
busy and idle time; device self time grouped by named scope (the
``op_name`` the program's ``jax.named_scope`` / flax module names gave
each op — ``docs/observability.md``, "Device scopes" — cut to its first
component and its last ``--depth`` ones: ``decode/…/attention/scores``);
and the idle gaps over ``--min-gap-ms``, each charged to the program
span (``serve.*`` / ``engine.*`` / ``scheduler.*`` / ``trainer.*``, on
the profile's host plane when the run armed a wall-clock ``Telemetry``)
that owns most of it — a gap's time inside a span and outside the span's
children is the span's own — with every span's share of the idle time. The reader ``JaxProfilerCallback`` lacked.

Usage:
    python tools/trace_report.py logs/serve.jsonl
    python tools/trace_report.py logs/serve.jsonl --slo interactive=4.0 \\
        --slo batch=50 --trace-out trace.json --json
    python tools/trace_report.py --profile runs/profile --depth 2 --top 15
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from ray_lightning_tpu.obs import tracing  # noqa: E402


def _parse_slo(pairs):
    slo = {}
    for pair in pairs or []:
        try:
            tenant, _, value = pair.partition("=")
            slo[tenant] = float(value)
        except ValueError:
            raise SystemExit(
                f"--slo expects class=target (e.g. interactive=4.0), "
                f"got {pair!r}")
    return slo


# ------------------------------------------------------------------ #
# --profile: device time by scope, idle gaps by program span
# ------------------------------------------------------------------ #
# the program's own spans nest (they close LIFO); a caller's annotations
# (the benchmark's ``harness.*``) need not, and are not the program's
PROGRAM_SPAN = re.compile(r"^(serve|engine|scheduler|trainer)\.")
_WRAPPER = re.compile(
    r"^(p?jit(\(.*\))?|while|body|cond|closed_call|checkpoint|"
    r"rematted_computation|remat\d*|branch_\d+_fun|core_call)$")
_VMAP = re.compile(r"vmap\(([^()]*)\)")


def scope_of(op_name: str, depth: int) -> str:
    """``jit(_engine_step_impl)/while/body/closed_call/decode/forward/
    TransformerLM/stack/block_3/attn/attention/scores/bqhd,bkhd->bhqk/
    dot_general`` -> ``decode/…/attention/scores``: compiler wrappers,
    ``vmap(...)`` shells, einsum specs and the primitive's own name
    dropped, layer indices folded (``block_*``), the first component and
    the last ``depth`` kept."""
    while True:
        bare = _VMAP.sub(r"\1", op_name)
        if bare == op_name:
            break
        op_name = bare
    parts = [re.sub(r"_\d+$", "_*", p) for p in op_name.split("/")[:-1]
             if p and "->" not in p and not _WRAPPER.match(p)]
    if not parts:
        return "(unscoped)"
    if len(parts) <= depth + 1:
        return "/".join(parts)
    return parts[0] + "/…/" + "/".join(parts[-depth:])


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` over one protobuf message's bytes:
    varints as ints, length-delimited fields as memoryviews (nothing is
    copied or descended into), fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} in an xplane")


def op_names_by_event(path: str) -> dict:
    """``{plane name: {event name: op_name}}`` from an ``.xplane.pb``.

    A TPU op event is named by its HLO instruction; the ``op_name`` the
    program gave it (named scopes, module names) sits in the event's
    *metadata* as the ``tf_op`` stat, which ``jax.profiler.ProfileData``
    does not hand out. This reads just that from the file's own bytes:
    ``XSpace.planes(1)`` -> ``XPlane.name(2)``, ``.event_metadata(4)``
    (map value ``XEventMetadata``: ``name(2)``, ``stats(5)``) and
    ``.stat_metadata(5)`` (``XStatMetadata``: ``id(1)``, ``name(2)``);
    an ``XStat`` is ``metadata_id(1)`` + ``str_value(5)``. The lines —
    nearly all of the file — are skipped, not parsed."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, events, tf_op_id = "", [], None
        for no, value in _fields(plane):
            if no == 2:
                name = bytes(value).decode()
            elif no == 4:
                events.extend(v for k, v in _fields(value) if k == 2)
            elif no == 5:
                for k, v in _fields(value):
                    if k == 2:
                        meta = dict(_fields(v))
                        if bytes(meta.get(2, b"")) == b"tf_op":
                            tf_op_id = meta.get(1)
        if tf_op_id is None:
            continue
        names = out.setdefault(name, {})
        for ev in events:
            ev_name, op_name = None, None
            for no, value in _fields(ev):
                if no == 2:
                    ev_name = bytes(value).decode(errors="replace")
                elif no == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) == tf_op_id and 5 in stat:
                        op_name = bytes(stat[5]).decode(errors="replace")
            if ev_name is not None and op_name:
                names[ev_name] = op_name
    return out


def load_profile(trace_dir: str):
    """``(devices, host)``: per device plane ``(name, [(Ev, op_name)])``
    from its "XLA Ops" line, and the program's spans on the host planes
    as ``Ev``s. A CPU profile has no device plane: XLA's CPU ops (host
    events with an ``hlo_module``) stand in as one pseudo-device, named
    by instruction — it walks the reader, it measures nothing."""
    from jax.profiler import ProfileData

    from benchmark import trace_reduce
    from benchmark.trace_reduce import Ev
    path = trace_reduce.find_xplane(trace_dir)
    op_names = op_names_by_event(path)
    data = ProfileData.from_file(path)
    devices, host, cpu_ops = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            scopes = op_names.get(plane.name, {})
            for ln in plane.lines:
                if ln.name != "XLA Ops":
                    continue
                ops = [(Ev(e.start_ns, e.start_ns + e.duration_ns,
                           trace_reduce.short_name(e.name)),
                        scopes.get(e.name, "")) for e in ln.events]
                if ops:
                    devices.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    ev = Ev(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if PROGRAM_SPAN.match(e.name):
                        host.append(ev)
                    elif e.duration_ns > 0 and "hlo_module" in dict(e.stats):
                        cpu_ops.append((ev, ""))
    if not devices and cpu_ops:
        devices.append(("/host:CPU (no device plane)", cpu_ops))
    return devices, host


def charge_gaps(gaps, host):
    """Disjoint idle gaps against the program's spans. A gap's time
    inside a span and outside that span's children is the span's *own*
    share of the gap. Returns ``(named, by_self)``: each gap with the
    span that owns most of it (``"(outside every program span)"`` when
    that is nobody), and for every span name the idle seconds it owns."""
    evs = sorted(host, key=lambda e: (e.start, -e.end))
    children = [[] for _ in evs]
    stack = []
    for i, ev in enumerate(evs):
        while stack and evs[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= evs[stack[-1]].end:
            children[stack[-1]].append(i)
        stack.append(i)
    by_self = {}
    named = []
    for s, e in gaps:
        over = {i: min(e, ev.end) - max(s, ev.start)
                for i, ev in enumerate(evs) if ev.end > s and ev.start < e}
        best, best_ns = "(outside every program span)", 0.0
        covered = 0.0
        for i, ns in over.items():
            own = ns - sum(over.get(c, 0.0) for c in children[i])
            name = evs[i].name
            by_self[name] = by_self.get(name, 0.0) + own / 1e9
            covered += own
            if own > best_ns:
                best, best_ns = name, own
        if (e - s) - covered > best_ns:
            best = "(outside every program span)"
        named.append((best, (e - s) / 1e9))
    return named, by_self


def profile_report(trace_dir: str, depth: int = 2, top: int = 15,
                   min_gap_ms: float = 1.0) -> dict:
    """The document ``--profile`` prints (``--json`` prints it whole)."""
    from benchmark import trace_reduce
    devices, host = load_profile(trace_dir)
    if not devices:
        raise SystemExit(f"no device ops in the profile under {trace_dir}")
    doc = {"profile": trace_dir, "host_spans": len(host),
           "host_span_names": sorted({h.name for h in host}),
           "devices": []}
    for name, ops in devices:
        evs = [ev for ev, _ in ops]
        scope = {id(ev): s for ev, s in ops}
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
        by_scope, by_op = {}, {}
        for ev, ns, _ in trace_reduce.self_times(evs):
            key = scope_of(scope[id(ev)], depth) if scope[id(ev)] \
                else "(no op_name) " + ev.name
            by_scope[key] = by_scope.get(key, 0.0) + ns / 1e9
            full = (scope[id(ev)] or "") + " :: " + ev.name
            by_op[full] = by_op.get(full, 0.0) + ns / 1e9
        busy = trace_reduce.merge((e.start, e.end)
                                  for e in trace_reduce.clip(evs, lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] >= min_gap_ms * 1e6]
        named, by_self = charge_gaps(gaps, host)
        by_name = {}
        for span, seconds in named:
            by_name.setdefault(span, []).append(seconds)
        window_s = (hi - lo) / 1e9
        busy_s = sum(e - s for s, e in busy) / 1e9
        doc["devices"].append({
            "device": name, "window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s else 0.0,
            "scoped_share": (sum(v for k, v in by_scope.items()
                                 if not k.startswith("(no op_name)"))
                             / max(sum(by_scope.values()), 1e-12)),
            "by_scope": sorted(by_scope.items(), key=lambda kv: -kv[1])[:top],
            "by_op": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
            "gaps": {"count": len(gaps), "min_gap_ms": min_gap_ms,
                     "total_s": sum(e - s for s, e in gaps) / 1e9,
                     "median_ms": (1e3 * statistics.median(
                         (e - s) / 1e9 for s, e in gaps) if gaps else None),
                     "by_owning_span": sorted(
                         ((span, len(v), sum(v), 1e3 * statistics.median(v))
                          for span, v in by_name.items()),
                         key=lambda r: -r[2]),
                     "idle_by_span_self_time": sorted(
                         ((k, v) for k, v in by_self.items() if v > 0),
                         key=lambda kv: -kv[1])}})
    return doc


def format_profile_report(doc: dict) -> str:
    lines = [f"profile {doc['profile']}: {doc['host_spans']} program "
             f"span events on the host plane "
             f"({', '.join(doc['host_span_names']) or 'none'})"]
    for d in doc["devices"]:
        lines += ["", f"{d['device']}: window {d['window_s']:.4f} s, busy "
                  f"{d['busy_s']:.4f} s, idle {100 * d['idle_share']:.2f} %"
                  f"; {100 * d['scoped_share']:.1f} % of op time carries "
                  "an op_name",
                  "  device self time by scope:"]
        for scope, s in d["by_scope"]:
            lines.append(f"    {s:10.6f} s  {100 * s / d['window_s']:6.2f} %"
                         f"  {scope}")
        lines.append("  ops with most self time (op_name :: instruction):")
        for op, s in d["by_op"]:
            lines.append(f"    {s:10.6f} s  {100 * s / d['window_s']:6.2f} %"
                         f"  {op}")
        g = d["gaps"]
        med = "-" if g["median_ms"] is None else f"{g['median_ms']:.3f}"
        lines.append(f"  idle gaps of {g['min_gap_ms']:g} ms or more: "
                     f"{g['count']}, {g['total_s']:.4f} s, median {med} ms;"
                     " by the program span that owns most of each:")
        for span, n, total, med_ms in g["by_owning_span"]:
            lines.append(f"    {total:10.6f} s  {n:5d} gaps  median "
                         f"{med_ms:8.3f} ms  {span}")
        lines.append("  the same idle time by the span that owns it "
                     "(inside it, outside its children):")
        for span, s in g["idle_by_span_self_time"]:
            lines.append(f"    {s:10.6f} s  {span}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-request latency decomposition + SLO-miss "
                    "attribution over a flushed obs JSONL log; or, with "
                    "--profile, device time by scope and idle gaps by "
                    "program span")
    ap.add_argument("jsonl", nargs="?",
                    help="event log written by "
                         "Telemetry(jsonl_path=...) + flush()")
    ap.add_argument("--profile", metavar="DIR",
                    help="a profile directory (JaxProfilerCallback's or "
                         "any jax.profiler.start_trace)")
    ap.add_argument("--depth", type=int, default=2,
                    help="--profile: trailing scope components kept")
    ap.add_argument("--top", type=int, default=15,
                    help="--profile: rows per table")
    ap.add_argument("--min-gap-ms", type=float, default=1.0,
                    help="--profile: shortest idle gap reported")
    ap.add_argument("--slo", action="append", metavar="CLASS=TARGET",
                    help="TTFT SLO target per tenant class (client "
                         "clock units); repeatable")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="also export the stitched Chrome trace "
                         "(request segments; load in Perfetto)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output: one JSON document "
                         "instead of tables")
    args = ap.parse_args(argv)

    if args.profile:
        doc = profile_report(args.profile, args.depth, args.top,
                             args.min_gap_ms)
        print(json.dumps(doc, sort_keys=True) if args.json
              else format_profile_report(doc))
        return 0
    if not args.jsonl:
        ap.error("give a JSONL event log, or --profile <dir>")

    events = tracing.load_jsonl_events(args.jsonl)
    traces = tracing.assemble_request_traces(events)
    slo = _parse_slo(args.slo)

    if args.trace_out:
        # offline stitching has no spans (they live in the recorder,
        # not the event log): a stand-in telemetry with an empty
        # recorder and the tick clock keeps the export pure-event
        from ray_lightning_tpu.obs.spans import SpanRecorder

        class _Offline:
            clock = None
            spans = SpanRecorder()

        tracing.export_fleet_chrome_trace(args.trace_out, _Offline(),
                                          traces)

    if args.json:
        doc = {
            "requests": tracing.decomposition_rows(traces),
            "tenants": tracing.tenant_rollup(traces),
        }
        if slo:
            doc["slo"] = tracing.slo_miss_attribution(traces, slo)
        print(json.dumps(doc, sort_keys=True, default=str))
        return 0

    if not traces:
        print(f"no request traces in {args.jsonl} "
              f"({len(events)} events)")
        return 0
    print(tracing.format_decomposition(traces))
    if slo:
        print()
        print("SLO-miss attribution (pre-first-token time of missed "
              "requests):")
        print(tracing.format_slo_report(traces, slo))
    if args.trace_out:
        print(f"\nChrome trace written to {args.trace_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
