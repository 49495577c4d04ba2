"""Serve step programs: the roofline of the decode-step program, which is
bandwidth bound — bytes one step must read (every matmul weight in the
dtype held + the live KV of the occupied slots, mean over the slice's
executions of that program) over the HBM peak, against the median device
time of that program in the trace."""
import statistics

from benchmark import peaks, trace_reduce, work

LAYER = "Serve step programs"
SOURCE = "device_trace"
DECODE_PROGRAM = r"_engine_step_impl"


def compute(run):
    import re
    s = run["slice"]
    if run["rehearse"] or not s["decode_tokens"]:
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(DECODE_PROGRAM)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].modules, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    mean_live = s["decode_context_sum"] / len(durs)
    need = work.decode_step_bytes(run["shape"], [mean_live],
                                  run["weight_itemsize"], run["kv_itemsize"])
    floor_s = need / peaks.hbm_bandwidth(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)
