"""From a profiler trace to numbers: the one reducer every metric reads.

Four outputs, all from the device planes of an ``.xplane.pb`` read with
``jax.profiler.ProfileData`` (nothing but JAX):

1. busy / idle — the union of the intervals in which an op ran on a device,
   against the traced window;
2. device time by name — self time of every op (a ``while`` does not count
   its body twice) and time of every XLA module (program);
3. gaps — idle time between consecutive events of one program;
4. collective exposure — collective ops' time not covered by a compute op.

The interval functions work on plain ``Ev`` lists so the tests can hand
them a trace built by hand. Regular expressions that pick a program or a
collective live in the metric file that uses them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Ev:
    start: float   # ns
    end: float     # ns
    name: str

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Ev]
    modules: List[Ev]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Ev]                       # harness.* annotations
    window: Optional[Tuple[float, float]]  # ns, the traced window

    def bounds(self) -> Tuple[float, float]:
        if self.window is not None:
            return self.window
        evs = [e for d in self.devices for e in d.ops]
        return (min(e.start for e in evs), max(e.end for e in evs))


# ------------------------------------------------------------------ #
# interval arithmetic
# ------------------------------------------------------------------ #
def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Ev], lo: float, hi: float) -> List[Ev]:
    return [Ev(max(e.start, lo), min(e.end, hi), e.name) for e in events
            if e.end > lo and e.start < hi]


def busy_ns(events: Iterable[Ev]) -> float:
    """Length of the union of the events' intervals."""
    return sum(e - s for s, e in merge((ev.start, ev.end) for ev in events))


def covered_ns(intervals: Sequence[Tuple[float, float]],
               cover: Sequence[Tuple[float, float]]) -> float:
    """How much of the merged list ``intervals`` lies inside the merged
    list ``cover``: one sweep over both."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return total


def self_times(events: Iterable[Ev]) -> List[Tuple[Ev, float, bool]]:
    """``(event, self_ns, is_leaf)``: an event's time minus the events
    nested inside it (a ``while`` holds its body's ops on the same line)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out: List[List] = []
    stack: List[int] = []
    for ev in evs:
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack and ev.end <= out[stack[-1]][0].end:
            parent = out[stack[-1]]
            parent[1] -= ev.dur
            parent[2] = False
        out.append([ev, ev.dur, True])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, t), leaf) for ev, t, leaf in out]


def time_by_name(events: Iterable[Ev]) -> Dict[str, float]:
    """Seconds of self time under each event name."""
    out: Dict[str, float] = {}
    for ev, t, _ in self_times(events):
        out[ev.name] = out.get(ev.name, 0.0) + t / 1e9
    return out


def program_gaps(modules: Iterable[Ev], pattern: str) -> List[float]:
    """Seconds between the end of one event of a program matching
    ``pattern`` and the start of the next, for pairs with no other program
    between them: the device sat idle waiting for that dispatch."""
    rx = re.compile(pattern)
    evs = sorted(modules, key=lambda e: e.start)
    return [max(0.0, b.start - a.end) / 1e9 for a, b in zip(evs, evs[1:])
            if rx.search(a.name) and rx.search(b.name)]


def collective_exposed_ns(ops: Iterable[Ev], pattern: str) -> Tuple[float, float]:
    """``(exposed, total)`` ns of leaf ops matching ``pattern``: total is
    their union, exposed the part of it during which no other leaf op ran
    on the device."""
    rx = re.compile(pattern)
    leaves = [ev for ev, _, leaf in self_times(ops) if leaf]
    coll = merge((e.start, e.end) for e in leaves if rx.search(e.name))
    comp = merge((e.start, e.end) for e in leaves if not rx.search(e.name))
    total = sum(e - s for s, e in coll)
    return total - covered_ns(coll, comp), total


def idle_gaps(ops: Iterable[Ev], host: Sequence[Ev], lo: float, hi: float,
              top: int = 5) -> List[Tuple[str, float]]:
    """The longest idle gaps of one device inside [lo, hi], each named by
    the harness annotation that covers most of it on the host."""
    busy = merge((e.start, e.end) for e in clip(ops, lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])[:top]
    spans = sorted(host, key=lambda h: -h.dur)   # the innermost wins a tie
    out = []
    for s, e in gaps:
        best, best_ns = "unattributed", 0.0
        for h in spans:
            ns = min(e, h.end) - max(s, h.start)
            if ns > 0 and ns >= best_ns:
                best, best_ns = h.name, ns
        out.append((best, (e - s) / 1e9))
    return out


# ------------------------------------------------------------------ #
# summary over devices
# ------------------------------------------------------------------ #
def summarize(trace: Trace) -> dict:
    """busy_s (mean over devices), window_s, the worst device's idle share,
    the ten ops with most self time and the five longest idle gaps."""
    lo, hi = trace.bounds()
    window = (hi - lo) / 1e9
    busy = [busy_ns(clip(d.ops, lo, hi)) / 1e9 for d in trace.devices]
    ops: Dict[str, float] = {}
    for d in trace.devices:
        for name, s in time_by_name(clip(d.ops, lo, hi)).items():
            ops[name] = ops.get(name, 0.0) + s / len(trace.devices)
    worst = min(range(len(busy)), key=busy.__getitem__)
    return {
        "busy_s": sum(busy) / len(busy), "window_s": window,
        "idle_share_worst": 1.0 - min(busy) / window,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": idle_gaps(trace.devices[worst].ops, trace.host,
                               lo, hi),
    }


# ------------------------------------------------------------------ #
# reading the file
# ------------------------------------------------------------------ #
_HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])")


def short_name(text: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep the
    instruction's name and its (first) result type: ``fusion.372
    bf16[12,1024,1,16,64]``."""
    m = _HLO.match(text)
    if m is None:
        return text[:64]
    return f"{m.group('name')} {m.group('type').lstrip('(')}"[:64]


WINDOW_ANNOTATION = "harness.window"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str, host_as_device: bool = False) -> Trace:
    """Device planes (``/device:TPU:n``: lines "XLA Ops" and "XLA
    Modules") and the harness's own host annotations. ``host_as_device``
    is the CPU rehearsal's stand-in: XLA's CPU ops are host events, so
    they are gathered into one pseudo-device — it walks the reducer, it
    measures nothing."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    host: List[Ev] = []
    cpu_ops: List[Ev] = []
    cpu_modules: Dict[Tuple[str, int], List[float]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            def evs(line_name):
                ln = lines.get(line_name)
                return [] if ln is None else [
                    Ev(e.start_ns, e.start_ns + e.duration_ns,
                       short_name(e.name)) for e in ln.events]
            ops = evs("XLA Ops")
            if ops:
                devices.append(DeviceTrace(plane.name, ops,
                                           evs("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("harness."):
                        host.append(Ev(e.start_ns, e.start_ns + e.duration_ns,
                                       e.name))
                    elif host_as_device and e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            ev = Ev(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name)
                            cpu_ops.append(ev)
                            key = (stats["hlo_module"], stats.get("run_id", 0))
                            span = cpu_modules.setdefault(
                                key, [ev.start, ev.end])
                            span[0] = min(span[0], ev.start)
                            span[1] = max(span[1], ev.end)
    if host_as_device and cpu_ops:
        devices.append(DeviceTrace(
            "/host:CPU (rehearsal)", cpu_ops,
            [Ev(s, e, name) for (name, _), (s, e) in cpu_modules.items()]))
    window = None
    marks = [h for h in host if h.name == WINDOW_ANNOTATION]
    if marks:
        window = (min(h.start for h in marks), max(h.end for h in marks))
    host = [h for h in host if h.name != WINDOW_ANNOTATION]
    if not devices:
        raise RuntimeError(
            f"no device plane with XLA ops in {path}: planes "
            f"{[p.name for p in data.planes]}")
    return Trace(devices, host, window)

