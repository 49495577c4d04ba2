"""ServeEngine: share of the window's wall time inside prefill ticks (the
root ``serve.tick`` spans whose action was ``prefill``): what admissions
cost the decode loop that waits behind them."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_span"


def compute(run):
    return program_spans.share_of_wall(
        run, lambda t: t.dur if t.action == "prefill" else 0.0)
