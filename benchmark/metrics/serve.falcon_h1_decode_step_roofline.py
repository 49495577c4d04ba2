"""Serve step programs: the roofline of the ``falcon_h1`` family's
decode-step program, which is bandwidth bound — bytes one step must move
(``falcon_h1_work.decode_step_bytes``: every bf16 matmul weight once, the
head included; each decoding row's SSM state and conv tail read and
written, 25.5 MB a row at the published widths; its live K/V read once;
the mean over the slice's executions of that program) over the HBM peak,
against the median device time of ``_engine_step_impl`` in the trace
(``serve_family_metrics.decode_step_roofline``)."""
from benchmark import falcon_h1_work, serve_family_metrics

LAYER = "Serve step programs"
SOURCE = "device_trace"


def compute(run):
    return serve_family_metrics.decode_step_roofline(run, falcon_h1_work,
                                                     "falcon_h1")
