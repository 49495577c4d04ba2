"""ServeEngine: share of the window's wall time inside chunk ticks (the
root ``serve.tick`` spans whose action was ``chunk``): what streaming
long prompts in, a piece a dispatch, costs the decode loop that
alternates with it. ``serve.prefill_share`` counts ``prefill`` ticks
only and cannot see them."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_span"


def compute(run):
    return program_spans.share_of_wall(
        run, lambda t: t.dur if t.action == "chunk" else 0.0)
