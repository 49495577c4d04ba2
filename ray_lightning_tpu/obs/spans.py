"""Nestable host-side spans: the one span primitive of the package.

A span is one interval of host time with a name, the span that was open
when it began (its *parent*) and a small dict of arguments — counts
taken at the same boundary, and ``args["ids"]`` (request ids, which are
the trace ids of :mod:`~ray_lightning_tpu.obs.tracing`) where the work
belongs to requests. The serve tick, the trainer loop and the engine's
dispatches open them where the work happens (``docs/observability.md``,
"Spans"); :meth:`SpanRecorder.self_times` takes a span's duration minus
what its children cover.

Three clock modes:

- **tick** (``clock=None``): timestamps are a monotone enter/exit
  counter — deterministic nesting, no wall time, no jax import. A child
  span's ``[start, end]`` is always strictly inside its parent's.
- **wall** (``clock=time.perf_counter`` or any callable): the recorder
  keeps the **raw** reading of the injected clock, in the clock's own
  units. Nothing is zeroed until an export: with ``time.perf_counter`` a
  span is on the same axis as anything else that reads that clock (the
  benchmark's ``host_clock``).
- **inside a profile**: in wall mode every span also enters a
  ``jax.profiler.TraceAnnotation(name)`` for its lifetime. While a
  profile is being taken (``jax.profiler.start_trace``,
  ``JaxProfilerCallback``) the span therefore lies on the profile's host
  plane beside the device ops — one clock by construction; with no
  profile running the annotation costs a flag check.

Export uses the same tmp + ``os.replace`` publish as checkpoints and the
JSONL sink: the file on disk is always complete, valid JSON.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional

#: Reusable no-op context for disarmed call sites: ``with (tel.span(...)
#: if tel is not None else NULL_SPAN):`` keeps the hot loop allocation-free.
NULL_SPAN = contextlib.nullcontext()


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed span. ``start``/``end`` are raw readings of the
    recorder's clock (ticks under the tick clock); ``parent`` is the id
    of the span that was open when this one began, ``None`` at a root;
    ``depth`` is derived (the number of ancestors)."""
    id: int
    parent: Optional[int]
    name: str
    start: float
    end: float
    depth: int
    args: Dict[str, Any]

    @property
    def dur(self) -> float:
        return self.end - self.start


class SpanRecorder:
    """Record nested host spans; export Chrome trace-event JSON.

    Use as a context manager factory; the ``with`` target is the span's
    argument dict, so counts known only at the end can be added::

        rec = SpanRecorder()
        with rec.span("epoch", epoch=0):
            with rec.span("train_batch", idx=0) as args:
                args["rows"] = 12

    Spans close LIFO per recorder (host-side, single-threaded by design —
    the trainer loop and the serve loop are both synchronous drivers).
    The recorder keeps at most ``capacity`` *closed* spans, dropping the
    oldest (``dropped`` counts them; ``Telemetry`` mirrors the count in
    ``obs_spans_dropped_total``); the open stack is unbounded (its depth
    is the nesting depth).

    ``origin`` is the raw clock reading at which the *request* clock
    reads zero (``ServeClient.now``, the ``t`` stamps of the event
    stream): whoever zeroes that clock publishes it here
    (:meth:`set_origin`), and the Chrome exports subtract it, so spans
    and request segments of one export share one time axis.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 capacity: int = 65536):
        self._clock = clock
        self._seq = 0          # tick mode: advances at every enter/exit
        self._next_id = 0
        self._stack: List[list] = []   # [id, name, start, args, annotation]
        self._closed: List[Span] = []
        self._capacity = capacity
        self.dropped = 0
        self._drop_hook: Optional[Callable[[], None]] = None
        self.origin: Optional[float] = None
        self._annotation = None
        if clock is not None:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    # ------------------------------------------------------------ clock
    def _now(self) -> float:
        if self._clock is None:
            t = float(self._seq)
            self._seq += 1
            return t
        return self._clock()

    def set_origin(self, raw: float) -> None:
        """Publish the raw reading at which the request clock reads zero.
        The first caller wins: one recorder is one time axis."""
        if self.origin is None:
            self.origin = float(raw)

    # ------------------------------------------------------------ spans
    @contextlib.contextmanager
    def span(self, name: str, **args: Any):
        opened = self.begin(name, **args)
        try:
            yield opened
        finally:
            self.end()

    def begin(self, name: str, **args: Any) -> Dict[str, Any]:
        """Explicit begin (for code where a ``with`` block is awkward,
        e.g. spanning a loop iteration). Pair with :meth:`end` — spans
        close LIFO. Returns the span's (mutable) argument dict."""
        note = None
        if self._annotation is not None:
            note = self._annotation(name)
            note.__enter__()
        self._stack.append([self._next_id, name, self._now(), args, note])
        self._next_id += 1
        return args

    def end(self) -> None:
        if not self._stack:
            raise RuntimeError("SpanRecorder.end() with no open span")
        sid, name, start, args, note = self._stack.pop()
        end = self._now()
        if note is not None:
            note.__exit__(None, None, None)
        self._keep(Span(
            id=sid, parent=self._stack[-1][0] if self._stack else None,
            name=name, start=start, end=end, depth=len(self._stack),
            args=args))

    def _keep(self, span: Span) -> None:
        self._closed.append(span)
        if len(self._closed) > self._capacity:
            del self._closed[0]
            self.dropped += 1
            if self._drop_hook is not None:
                self._drop_hook()

    def record_closed(self, name: str, start: float, end: float,
                      depth: int = 0,
                      args: Optional[Dict[str, Any]] = None) -> None:
        """Import one already-closed span measured elsewhere — the
        process-backend ``MSG_SPAN`` leg lands here: a worker stamped
        raw ``[start, end]`` readings of the fleet's shared clock and
        shipped the closed span over the manager queue. An imported span
        gets an id of this recorder and is a parentless root (its
        ``depth`` is what the worker saw; the worker's seat rides
        ``args``); it respects the same capacity / ``dropped``
        accounting as locally recorded spans."""
        self._keep(Span(id=self._next_id, parent=None, name=name,
                        start=float(start), end=float(end),
                        depth=int(depth), args=dict(args or {})))
        self._next_id += 1

    @property
    def open_depth(self) -> int:
        return len(self._stack)

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """Closed spans in completion order (children before parents)."""
        if name is None:
            return list(self._closed)
        return [s for s in self._closed if s.name == name]

    def self_times(self) -> Dict[int, float]:
        """``{span id: self time}`` over the closed spans: a span's
        duration minus what its children cover (children of one span
        never overlap — spans close LIFO)."""
        out = {s.id: s.dur for s in self._closed}
        for s in self._closed:
            if s.parent in out:
                out[s.parent] -= s.dur
        return out

    # ------------------------------------------------------------ export
    def export_origin(self) -> float:
        """Where the Chrome exports put t=0: 0 under the tick clock, else
        the published ``origin``, else the earliest closed span."""
        if self._clock is None:
            return 0.0
        if self.origin is not None:
            return self.origin
        return min((s.start for s in self._closed), default=0.0)

    def chrome_events(self, origin: float,
                      tracks: bool = False) -> List[Dict[str, Any]]:
        """The closed spans as complete (``ph="X"``) events relative to
        ``origin``: µs in wall mode (Chrome's unit), ticks as they are.
        With ``tracks`` (the fleet export), ``pid`` = replica seat and
        ``tid`` = KV slot where the span's args name them; else one
        track (0, 0) — one host process, nesting kept."""
        scale = 1.0 if self._clock is None else 1e6
        return [{"name": s.name, "ph": "X",
                 "ts": (s.start - origin) * scale, "dur": s.dur * scale,
                 "pid": int(s.args.get("seat", 0) or 0) if tracks else 0,
                 "tid": int(s.args.get("slot", 0) or 0) if tracks else 0,
                 "args": s.args} for s in self._closed]

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event document of this recorder alone, sorted by
        start time so viewers rebuild the nesting directly; deterministic
        under the tick clock."""
        events = sorted(self.chrome_events(self.export_origin()),
                        key=lambda e: (e["ts"], -e["dur"]))
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        """Atomically publish the trace JSON (tmp + ``os.replace``);
        returns ``path``. Load it in Perfetto/``chrome://tracing``."""
        return publish_json(path, self.chrome_trace())


def publish_json(path: str, doc: Dict[str, Any]) -> str:
    """Key-sorted JSON through tmp + ``os.replace``: the file on disk is
    always complete. Returns ``path``."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, sort_keys=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return path
