"""Serve step programs: the roofline of the ``deepseek_v2`` family's
dense-slot chunk program, counted as compute bound — operations the valid
prompt tokens of a mean chunk dispatch require
(``deepseek_v2_work.piece_flops`` of each row's offset and length, from
the program's own ``engine.chunk.call`` spans inside the traced slice:
the expanded form of latent attention, routed experts at their expected
share) over the bf16 peak, against the median device time of
``_chunk_prefill_dense_impl`` in the trace
(``serve_family_metrics.chunk_roofline``). This is where the float32
scores of every head that the expanded form passes through memory a
block of latents at a time, the held experts' matrices streamed for a
few hundred rows each and the padding of a ragged last piece show."""
from benchmark import deepseek_v2_work, serve_family_metrics

LAYER = "Serve step programs"
SOURCE = "device_trace"


def compute(run):
    return serve_family_metrics.chunk_roofline(run, deepseek_v2_work,
                                               "deepseek_v2")
