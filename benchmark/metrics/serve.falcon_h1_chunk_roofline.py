"""Serve step programs: the roofline of the ``falcon_h1`` family's
dense-slot chunk program, counted as compute bound — operations the valid
prompt tokens of a mean chunk dispatch require
(``falcon_h1_work.piece_flops`` of each row's offset and length, from the
program's own ``engine.chunk.call`` spans inside the traced slice) over
the bf16 peak, against the median device time of
``_chunk_prefill_dense_impl`` in the trace
(``serve_family_metrics.chunk_roofline``). This is where the SSD's
float32 products at ``Precision.HIGHEST``, the rows taken out of the pool
and put back and the padding of a ragged last piece show."""
from benchmark import falcon_h1_work, serve_family_metrics

LAYER = "Serve step programs"
SOURCE = "device_trace"


def compute(run):
    return serve_family_metrics.chunk_roofline(run, falcon_h1_work,
                                               "falcon_h1")
