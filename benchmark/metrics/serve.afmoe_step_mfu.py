"""Serve step programs: the share of the whole step for the ``afmoe``
family — matmul and attention operations that the prompt tokens fed and
the tokens decoded inside the traced slice require, over slice seconds x
the bf16 peak. Routed work is counted at the expected ``k held / E``
experts a token of the share this chip holds
(``afmoe_work.experts_per_token``); a window layer's keys at ``min(
context, window)``. Whole prompts prefilled and tokens decoded are
counted from the harness's own lengths (``afmoe_work.prefill_flops`` /
``decode_flops``). A prompt longer than the engine's ``prefill_chunk``
enters in pieces, which the harness's frontier does not see: those
tokens are counted from the program's own ``engine.chunk.call`` spans
inside the slice (``afmoe_work.slice_pieces``, ``piece_flops`` of each
row's offset and length; the head of a prompt's last piece is left out)
and such prompts are left out of the prefill count. Padded rows and idle
slots are not counted."""
from benchmark import afmoe_work, peaks

LAYER = "Serve step programs"
SOURCE = "host_clock"


def compute(run):
    s = run.get("slice") or {}
    if run["rehearse"] or not run.get("slice_s") \
            or "decode_contexts" not in s \
            or run["shape"].get("model_type") != "afmoe":
        return None
    shape = run["shape"]
    chunk = run["workload"]["engine"].get("prefill_chunk")
    flops = (sum(afmoe_work.prefill_flops(shape, n)
                 for n in s["prefill_lengths"] if not chunk or n <= chunk)
             + sum(afmoe_work.decode_flops(shape, c)
                   for c in s["decode_contexts"])
             + sum(afmoe_work.piece_flops(shape, off, n)
                   for pieces in afmoe_work.slice_pieces(run) or ()
                   for off, n in pieces))
    if not flops:
        return None
    return 100.0 * flops / (run["slice_s"]
                            * peaks.peak_flops(run["device_kind"]))
