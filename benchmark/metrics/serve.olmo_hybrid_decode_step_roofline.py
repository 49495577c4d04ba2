"""Serve step programs: the roofline of the ``olmo_hybrid`` family's
decode-step program, which is bandwidth bound — bytes one step must move
(``olmo_hybrid_work.decode_step_bytes``: every bf16 matmul weight once,
each decoding row's matrix state and conv tail read and written, its
live K/V read once; the mean over the slice's executions of that
program) over the HBM peak, against the median device time of that
program in the trace."""
import re
import statistics

from benchmark import olmo_hybrid_work, peaks, trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
DECODE_PROGRAM = r"_engine_step_impl"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not s.get("decode_contexts") \
            or run["shape"].get("model_type") != "olmo_hybrid":
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(DECODE_PROGRAM)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].modules, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    shape = run["shape"]
    weights = olmo_hybrid_work.decode_step_bytes(shape, [])
    rows = sum(olmo_hybrid_work.decode_step_bytes(shape, [c]) - weights
               for c in s["decode_contexts"])
    need = weights + rows / len(durs)       # a mean step's rows
    floor_s = need / peaks.hbm_bandwidth(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)
