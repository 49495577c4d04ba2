"""Serve step programs: the roofline of the dense-slot chunk program
under the ``afmoe`` family, counted as compute bound — operations the
valid prompt tokens of a mean chunk dispatch require
(``afmoe_work.piece_flops`` of each row's offset and length, from the
program's own ``engine.chunk.call`` spans inside the traced slice; routed
experts at their expected share, a window layer's keys at ``min(p + 1,
window)``) over the bf16 peak, against the median device time of that
program in the trace. This is where the held experts' matrices streamed
for a few dozen rows each, the ragged products' tiles, the rows taken
out of the pool and put back and the padding of a ragged last piece
show."""
import re
import statistics

from benchmark import afmoe_work, peaks, trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
CHUNK_PROGRAM = r"_chunk_prefill_dense_impl"


def compute(run):
    if run["rehearse"] or run["shape"].get("model_type") != "afmoe":
        return None
    dispatches = afmoe_work.slice_pieces(run)
    if not dispatches:
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(CHUNK_PROGRAM)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].modules, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    flops = sum(afmoe_work.piece_flops(run["shape"], off, n)
                for pieces in dispatches for off, n in pieces)
    floor_s = flops / len(dispatches) / peaks.peak_flops(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)
