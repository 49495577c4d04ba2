"""The program's own spans, cut to the measured window.

The serve kind arms a ``Telemetry`` handle in its traced run and keeps it
to itself; the metric files are handed ``facts`` only. The program keeps
the handle built last in the process (``obs.last_telemetry()``), so a
reader here finds it without an edit to the kind. On a program that has
no such function, or whose spans carry no ids, every reader below returns
``None`` and the result line leaves the metric out.

How the window is cut. The kind's set-up ends when the last request of the
first generation — request ids ``0 .. clients - 1``, submitted before
anything else — has retired: ``window_open()`` is called right after the
``tick()`` that returned it. The program's ``serve.finalize`` span carries
the ids a tick retired, so the window *opens at the end of the root*
``serve.tick`` *span that finalized the last of those ids*, and holds every
root tick that *starts* within ``wall_s`` of that point (the window closes
on the first tick that returns after ``--seconds``; ``wall_s`` is the
kind's own reading of it). Both ends lie on the recorder's clock, which
the kind sets to the harness's own (``time.perf_counter``): ``host_clock``
and the spans are one axis.

A tick's numbers: its duration, the action it took, the durations of its
descendants summed by span name, the self time of every span in its tree
(a span's duration minus what its children cover; the tree's self times
sum to the tick) and the counts its dispatch spans carry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from benchmark.harness import note

ROOT = "serve.tick"
FINALIZE = "serve.finalize"
#: the spans in which the host is blocked on the device
SYNC_SPANS = ("engine.step.sync", "engine.prefill.sync", "engine.chunk.sync")


@dataclasses.dataclass
class Tick:
    start: float                 # s, the recorder's clock
    dur: float                   # s
    action: Optional[str]        # "prefill" | "step" | "chunk" | "idle"
    by_name: Dict[str, float]    # s under each descendant span name
    self_by_name: Dict[str, float]   # self time, the root's under ROOT
    counts: Dict[str, Dict[str, Any]]  # span name -> its args


def handle():
    """The program's armed handle, or ``None``."""
    from ray_lightning_tpu import obs
    find = getattr(obs, "last_telemetry", None)
    return find() if find is not None else None


def _cut(spans: list, self_times: Dict[int, float], clients: int,
         wall_s: float) -> Optional[List[Tick]]:
    """``spans`` (closed, with ``id`` / ``parent`` / ``start`` / ``end``)
    -> the window's ticks in time order."""
    roots = {s.id: s for s in spans if s.name == ROOT}
    first = set(range(clients))
    openers = [s for s in spans if s.name == FINALIZE
               and s.parent in roots
               and first.intersection(s.args.get("ids") or ())]
    if not roots or not openers:
        return None
    opened = max(roots[s.parent].end for s in openers)
    inside = {rid for rid, r in roots.items()
              if opened <= r.start < opened + wall_s}
    ticks = {rid: Tick(roots[rid].start, roots[rid].dur,
                       roots[rid].args.get("action"), {}, {}, {})
             for rid in inside}
    # spans close children first, so walking the list backwards meets a
    # parent before its children: each span learns its root on the way
    root_of = {rid: rid for rid in inside}
    for s in reversed(spans):
        rid = root_of.get(s.id, root_of.get(s.parent))
        if rid is None:
            continue
        root_of[s.id] = rid
        t = ticks[rid]
        t.self_by_name[s.name] = (t.self_by_name.get(s.name, 0.0)
                                  + self_times[s.id])
        if s.id != rid:
            t.by_name[s.name] = t.by_name.get(s.name, 0.0) + s.dur
            t.counts[s.name] = s.args
    return sorted(ticks.values(), key=lambda t: t.start)


def window_ticks(run: dict) -> Optional[List[Tick]]:
    """The ticks of the measured window, computed once per run (kept in
    ``run``) and counted on an earlier line of the output."""
    if "_program_ticks" in run:
        return run["_program_ticks"]
    ticks = None
    tel = handle()
    wall_s, clients = run.get("wall_s"), (run.get("workload") or {}).get(
        "clients")
    if tel is not None and wall_s and clients \
            and hasattr(tel.spans, "self_times"):
        ticks = _cut(tel.spans.spans(), tel.spans.self_times(),
                     int(clients), float(wall_s))
    if ticks is not None:
        by_action: Dict[str, int] = {}
        for t in ticks:
            by_action[str(t.action)] = by_action.get(str(t.action), 0) + 1
        note(phase="program_spans", ticks=len(ticks), by_action=by_action,
             spans_dropped=tel.spans.dropped,
             covered_s=round(sum(t.dur for t in ticks), 4),
             wall_s=round(float(wall_s), 4))
    run["_program_ticks"] = ticks
    return ticks


def share_of_wall(run: dict, seconds_of) -> Optional[float]:
    """100 x (sum over the window's ticks of ``seconds_of(tick)``) over
    the window's wall time."""
    ticks = window_ticks(run)
    if not ticks:
        return None
    return 100.0 * sum(seconds_of(t) for t in ticks) / run["wall_s"]
