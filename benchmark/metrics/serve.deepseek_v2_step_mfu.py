"""Serve step programs: the share of the whole step for the
``deepseek_v2`` family — matmul and latent attention operations that the
prompt tokens fed and the tokens decoded inside the traced slice require
(``deepseek_v2_work``: a decode step's absorbed form at ``2 x 128 x (576
+ 512)`` a live position and layer, a piece's expanded form — its
positions expanded once, the 192-wide score and the 128-wide value,
causal within the piece —, routed experts at their expected share), over
slice seconds x the bf16 peak (``serve_family_metrics.step_mfu``: prompts
prefilled whole and tokens decoded from the harness's lengths, chunk
pieces from the program's own ``engine.chunk.call`` spans)."""
from benchmark import deepseek_v2_work, serve_family_metrics

LAYER = "Serve step programs"
SOURCE = "host_clock"


def compute(run):
    return serve_family_metrics.step_mfu(run, deepseek_v2_work,
                                         "deepseek_v2")
