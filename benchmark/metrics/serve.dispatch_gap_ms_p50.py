"""ServeEngine: median idle time on the device between two consecutive
decode-step programs (no other program between them): what one host round
trip per token costs the chip."""
import statistics

from benchmark import trace_reduce

LAYER = "ServeEngine"
SOURCE = "device_trace"
DECODE_PROGRAM = r"_engine_step_impl"


def compute(run):
    trace = run["trace"]
    lo, hi = trace.bounds()
    gaps = trace_reduce.program_gaps(
        trace_reduce.clip(trace.devices[0].modules, lo, hi), DECODE_PROGRAM)
    return 1e3 * statistics.median(gaps) if gaps else None
