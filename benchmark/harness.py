"""What every kind of cell shares: the files a cell is made of, the clock
that splits set-up from the window, the compile meter, the profiler slice,
and the result line. A kind (``benchmark/kinds/<kind>.py``) drives the
system under test through ``Ctx``; it never prints the result itself."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by path: names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def note(**fields) -> None:
    """An earlier line of standard output (the last line is the result)."""
    print(json.dumps(fields), flush=True)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits from jax's own
    monitoring events (copied from ``chip_smoke.py:85``): splits set-up
    into compile and the rest."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": round(self.compile_s, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class Slice:
    """A profiled slice of the steady window. ``start``/``stop`` are called
    by the kind at points where the device has been waited for; the trace
    goes to a directory under ``TMPDIR`` and is deleted once reduced."""

    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.dir: Optional[str] = None
        self.t0 = self.t1 = None
        self._mark = None

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.t1 is None

    @property
    def done(self) -> bool:
        return self.t1 is not None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events
        opts.host_tracer_level = 2       # TraceAnnotation spans
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        from benchmark.trace_reduce import WINDOW_ANNOTATION
        self._mark = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._mark.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def span(self, name: str):
        """A ``harness.<name>`` host span, recorded only while tracing."""
        if not self.running:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation("harness." + name):
            yield

    def reduce(self):
        """The trace as ``trace_reduce.Trace``; the files are removed."""
        from benchmark import trace_reduce
        try:
            return trace_reduce.load(trace_reduce.find_xplane(self.dir),
                                     host_as_device=self.rehearse)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Ctx:
    """One run of one cell."""

    def __init__(self, args, bench: dict, t_start: float):
        self.args = args
        self.bench = bench
        self.t_start = t_start
        self.cell = next(w for w in bench["workloads"]
                         if w["name"] == args.workload)
        self.config_entry = next(c for c in bench["configs"]
                                 if c["name"] == self.cell["config"])
        self.shape = load_json(self.config_entry["file"])
        self.workload = load_json("benchmark", "workloads",
                                  self.cell["name"] + ".json")
        self.chips = int(self.cell["chips"])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearse = bool(args.rehearse)
        if self.rehearse:
            # nano widths: the same files, every size overridden
            self.shape = {**self.shape, **self.workload["rehearse"]["shape"]}
            self.workload = {**self.workload,
                             **self.workload["rehearse"]["workload"]}
        self.meter: Optional[CompileMeter] = None
        self.devices: List[Any] = []
        self.slice = Slice(self.rehearse) if args.trace else None
        self.setup_s: Optional[float] = None

    def window_open(self) -> float:
        """Set-up ends here: process start to window open."""
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        note(phase="window_open", setup_s=round(self.setup_s, 3),
             **self.meter.snapshot())
        return now

    def span(self, name: str):
        """A ``harness.<name>`` host span while the slice is being traced."""
        if self.slice is None:
            return contextlib.nullcontext()
        return self.slice.span(name)

    def memory_peak_bytes(self) -> int:
        """Peak on the fullest chip: the peak of live buffers plus the peak
        the runtime reserved for programs' temporaries (the TPU backend
        counts them apart; together with what is free they make up the
        chip). The two peaks need not coincide, so this is an upper
        estimate — for a train step, whose state is live while it runs, it
        is the step's footprint."""
        peaks = []
        for d in self.devices[:self.chips]:
            stats = d.memory_stats() or {}
            peaks.append(stats.get("peak_bytes_in_use", 0)
                         + stats.get("peak_bytes_reserved", 0))
        return int(max(peaks))

    def reports(self, metric: dict) -> bool:
        """Does this cell report the metric (an entry of BENCHMARK.json)?"""
        if "workloads" in metric:
            return self.cell["name"] in metric["workloads"]
        if "moves" in metric:
            moved = next(m for m in self.bench["end_to_end"]
                         if m["name"] == metric["moves"])
            return self.reports(moved)
        return True


def compare(compared: Dict[str, Any]) -> bool:
    """``{name: [value, limit]}`` -> every value at or under its limit
    (a value that is not a number fails)."""
    return all(value == value and value <= limit
               for value, limit in compared.values())


def finish(ctx: Ctx, outcome: dict) -> int:
    """Read the memory peak, run the check, reduce the trace, print the
    result line (the last line of standard output)."""
    peak = ctx.memory_peak_bytes()
    device = {"platform": ctx.devices[0].platform,
              "kind": ctx.devices[0].device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": peak}
    facts = dict(outcome["facts"], memory_peak_bytes=peak, shape=ctx.shape,
                 chips=ctx.chips, device_kind=device["kind"],
                 workload=ctx.workload, rehearse=ctx.rehearse)
    t0 = time.perf_counter()
    compared = outcome["check"]()        # the program's state is freed first
    check_s = time.perf_counter() - t0
    correct = compare(compared) and outcome["failed"] == 0
    took = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
    e2e = {m["name"]: took[m["name"]] for m in ctx.bench["end_to_end"]
           if ctx.reports(m)}
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(outcome["attempted"]),
                            "failed": int(outcome["failed"])}
    if ctx.args.trace:
        from benchmark import trace_reduce
        trace = ctx.slice.reduce()
        summary = trace_reduce.summarize(trace)
        facts.update(trace=trace, trace_summary=summary)
        metrics = {}
        for m in ctx.bench["per_layer"]:
            if not ctx.reports(m):
                continue
            value = load_module("metrics", m["name"]).compute(facts)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary["device_ops"]],
            "idle_gaps": [[n, s] for n, s in summary["idle_gaps"]]}
        note(phase="end_to_end_of_traced_run", **e2e)
    else:
        units = {m["name"]: m["unit"] for m in ctx.bench["end_to_end"]}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in e2e.items()}
    note(phase="check", seconds=round(check_s, 3), **ctx.meter.snapshot(),
         memory_stats=ctx.devices[0].memory_stats())
    line["metrics"] = metrics
    line["device"] = device
    if ctx.rehearse:
        line["rehearsal"] = True
    line["compared"] = {k: [float(v), float(lim)]
                        for k, (v, lim) in compared.items()}
    print(json.dumps(line), flush=True)
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value:.6g} limit {limit:.6g} "
              f"{'ok' if value <= limit else 'FAIL'}", file=sys.stderr)
    print(f"correct={correct} failed={outcome['failed']}", file=sys.stderr,
          flush=True)
    return 0
