"""Serve step programs: the share of the whole step for the
``falcon_h1`` family — matmul, attention and SSD operations that the
prompt tokens fed and the tokens decoded inside the traced slice require
(``falcon_h1_work``), over slice seconds x the bf16 peak
(``serve_family_metrics.step_mfu``: prompts prefilled whole and tokens
decoded from the harness's lengths, chunk pieces from the program's own
``engine.chunk.call`` spans)."""
from benchmark import falcon_h1_work, serve_family_metrics

LAYER = "Serve step programs"
SOURCE = "host_clock"


def compute(run):
    return serve_family_metrics.step_mfu(run, falcon_h1_work, "falcon_h1")
