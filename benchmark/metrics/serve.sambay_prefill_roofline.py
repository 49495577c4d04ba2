"""Serve step programs: the roofline of the ``phi4flash`` family's
prefill program, which is compute bound — operations the valid prompt
tokens of a prefill require (``sambay_work.prefill_flops``: the
self-decoder over every token, the cross-decoder and the head over one
position a row; the mean over the slice's executions of that program)
over the bf16 peak, against the median device time of that program in
the trace. This is where the selective scan shows."""
import re
import statistics

from benchmark import peaks, sambay_work, trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
PREFILL_PROGRAM = r"_prefill_inject_impl"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not s.get("prefill_lengths") \
            or run["shape"].get("model_type") != "phi4flash":
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(PREFILL_PROGRAM)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].modules, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    flops = sum(sambay_work.prefill_flops(run["shape"], n)
                for n in s["prefill_lengths"]) / len(durs)
    floor_s = flops / peaks.peak_flops(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)
