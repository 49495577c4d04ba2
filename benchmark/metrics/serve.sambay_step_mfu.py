"""Serve step programs: the share of the whole step for the ``phi4flash``
family — matmul, attention and scan operations that the prompt tokens
prefilled and the tokens decoded inside the traced slice require
(``sambay_work.prefill_flops`` / ``decode_flops`` summed over the
harness's own lengths; padded prefill rows and idle slots are not
counted) over slice seconds x the bf16 peak."""
from benchmark import peaks, sambay_work

LAYER = "Serve step programs"
SOURCE = "host_clock"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not run.get("slice_s") \
            or "decode_contexts" not in s \
            or run["shape"].get("model_type") != "phi4flash":
        return None
    shape = run["shape"]
    flops = (sum(sambay_work.prefill_flops(shape, n)
                 for n in s["prefill_lengths"])
             + sum(sambay_work.decode_flops(shape, c)
                   for c in s["decode_contexts"]))
    if not flops:
        return None
    return 100.0 * flops / (run["slice_s"]
                            * peaks.peak_flops(run["device_kind"]))
