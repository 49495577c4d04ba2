"""Serve step programs: the roofline of ``ops/mla_decode.py``'s kernel
(the absorbed latent attention of a decode step, one call a layer) — the
least time a mean call can take, the larger of its bytes over the HBM
peak and its operations over the bf16 peak
(``deepseek_v2_work.mla_decode_bytes`` / ``mla_decode_flops`` over the
decoding rows of the slice: each row's live latent and rotary key, its
queries and outputs; a position's 1088 operations a head against its
1152 bytes put the call at the ridge), against the median device time of
the kernel's events (``mla_decode.*`` in the trace's op line). The mean
call is the slice's decoded tokens over the kernel's executions divided
by the layers, since every call of a step reads every decoding row for
one layer. A program without the kernel — off a TPU, or before it
existed — gives nothing."""
import re
import statistics

from benchmark import deepseek_v2_work, deepseek_v2_weights, peaks
from benchmark import trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
KERNEL = r"^mla_decode\b"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not s.get("decode_contexts") \
            or run["shape"].get("model_type") != "deepseek_v2":
        return None
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(KERNEL)
    durs = [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].ops, lo, hi) if rx.search(e.name)]
    if not durs:
        return None
    shape, kind = run["shape"], run["device_kind"]
    contexts = s["decode_contexts"]
    steps = len(durs) / deepseek_v2_weights.sizes(shape)["n"]
    need_bytes = deepseek_v2_work.mla_decode_bytes(shape, contexts) / steps
    need_flops = deepseek_v2_work.mla_decode_flops(shape, contexts) / steps
    floor_s = max(need_bytes / peaks.hbm_bandwidth(kind),
                  need_flops / peaks.peak_flops(kind))
    return 100.0 * floor_s / statistics.median(durs)
