"""Serve step programs: the roofline of the ``deepseek_v2`` family's
decode-step program — the least time a mean step can take, the larger of
its bytes over the HBM peak and its operations over the bf16 peak (the
absorbed read of the latent sits near the ridge: 1088 operations a
position a head against 1152 bytes a position), against the median
device time of ``_engine_step_impl`` in the trace. Bytes
(``deepseek_v2_work.decode_step_bytes``): the weights outside the routed
experts and the head's slice once, the matrices of the (layer, expert)
pairs that **took a row** — the program's ``moe_experts_hit`` on
``engine.step.call``, the mean over the slice —, each decoding row's
live latent and rotary key read once. Operations
(``deepseek_v2_work.decode_flops``): every decoded token of the slice,
over the program's executions. A program that keeps no such counter
gives nothing."""
import re
import statistics

from benchmark import deepseek_v2_work, peaks, trace_reduce

LAYER = "Serve step programs"
SOURCE = "device_trace"
DECODE_PROGRAM = r"_engine_step_impl"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not s.get("decode_contexts") \
            or run["shape"].get("model_type") != "deepseek_v2":
        return None
    hits = [c["moe_experts_hit"]
            for c in deepseek_v2_work.slice_step_calls(run) or ()
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
    shape, kind = run["shape"], run["device_kind"]
    contexts = s["decode_contexts"]
    fixed = deepseek_v2_work.decode_step_bytes(shape, [],
                                               statistics.mean(hits))
    rows = sum(deepseek_v2_work.live_cache_bytes(shape, c)
               for c in contexts)
    need_bytes = fixed + rows / len(durs)       # a mean step's rows
    need_flops = sum(deepseek_v2_work.decode_flops(shape, c)
                     for c in contexts) / len(durs)
    floor_s = max(need_bytes / peaks.hbm_bandwidth(kind),
                  need_flops / peaks.peak_flops(kind))
    return 100.0 * floor_s / statistics.median(durs)
