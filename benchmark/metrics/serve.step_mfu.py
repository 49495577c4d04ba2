"""Serve step programs: operations the prompt tokens prefilled and the
tokens decoded inside the traced slice require (``work.prefill_flops`` /
``work.decode_flops`` summed from the harness's own counts; padded prefill
rows and idle slots are not counted) over slice seconds x the bf16 peak."""
from benchmark import peaks, work

LAYER = "Serve step programs"
SOURCE = "host_clock"


def compute(run):
    s = run["slice"]
    if run["rehearse"] or not run.get("slice_s") or not (
            s["decode_tokens"] or s["prefill_tokens"]):
        return None
    shape = run["shape"]
    d, n_layer = shape["n_embd"], shape["n_layer"]
    flops = (2.0 * work.matmul_params(shape, with_head=False)
             * s["prefill_tokens"]
             + 2.0 * shape["vocab_size"] * d * s["prefills"]
             + 2.0 * n_layer * d * s["prefill_sq"]
             + 2.0 * work.matmul_params(shape) * s["decode_tokens"]
             + 4.0 * n_layer * d * s["decode_context_sum"])
    return 100.0 * flops / (run["slice_s"]
                            * peaks.peak_flops(run["device_kind"]))
