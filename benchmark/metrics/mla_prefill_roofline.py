"""Serve step programs: the roofline of ``ops/mla_prefill.py``'s kernel
(the expanded latent attention of a chunk dispatch's pieces, one call a
layer) — the least time a mean call can take, the larger of its
operations over the bf16 peak and its bytes over the HBM peak
(``deepseek_v2_work.mla_prefill_flops`` / ``mla_prefill_bytes`` of each
row's offset and valid tokens, from the program's own
``engine.chunk.call`` spans inside the traced slice: the ``offset + n``
positions expanded once, the piece's keys scored and summed, causal),
against the median device time of the kernel's events (``mla_prefill.*``
in the trace's op line). Every dispatch calls the kernel once a layer. A
program without the kernel — off a TPU, or before it existed — gives
nothing."""
import re
import statistics

from benchmark import deepseek_v2_work, peaks, trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
KERNEL = r"^mla_prefill\b"


def compute(run):
    if run["rehearse"] or run["shape"].get("model_type") != "deepseek_v2":
        return None
    dispatches = deepseek_v2_work.slice_pieces(run)
    if not dispatches:
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(KERNEL)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].ops, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    shape, kind = run["shape"], run["device_kind"]
    need_flops = sum(deepseek_v2_work.mla_prefill_flops(shape, pieces)
                     for pieces in dispatches) / len(dispatches)
    need_bytes = sum(deepseek_v2_work.mla_prefill_bytes(shape, pieces)
                     for pieces in dispatches) / len(dispatches)
    floor_s = max(need_bytes / peaks.hbm_bandwidth(kind),
                  need_flops / peaks.peak_flops(kind))
    return 100.0 * floor_s / statistics.median(durs)
