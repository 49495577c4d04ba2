"""Serve step programs: the roofline of the ``afmoe`` family's
decode-step program, which is bandwidth bound — bytes one step must move
(``afmoe_work.decode_step_bytes``: the weights outside the routed
experts and the head's slice once, the matrices of the (layer, expert)
pairs that **took a row** — the program's ``moe_experts_hit`` on
``engine.step.call`` —, each decoding row's live K/V read once, ``min(
context, window)`` positions on a window layer and the context on a full
one; the mean over the slice's executions of that program) over the HBM
peak, against the median device time of that program in the trace. A
program that keeps no such counter gives nothing."""
import re
import statistics

from benchmark import afmoe_work, peaks, trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
DECODE_PROGRAM = r"_engine_step_impl"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not s.get("decode_contexts") \
            or run["shape"].get("model_type") != "afmoe":
        return None
    hits = [c["moe_experts_hit"]
            for c in afmoe_work.slice_step_calls(run) or ()
            if "moe_experts_hit" in c]
    if not hits:
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(DECODE_PROGRAM)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].modules, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    shape = run["shape"]
    fixed = afmoe_work.decode_step_bytes(shape, [], statistics.mean(hits))
    rows = sum(afmoe_work.live_kv_bytes(shape, c)
               for c in s["decode_contexts"])
    need = fixed + rows / len(durs)         # a mean step's rows
    floor_s = need / peaks.hbm_bandwidth(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)
