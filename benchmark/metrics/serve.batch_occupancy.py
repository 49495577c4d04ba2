"""ServeEngine: active rows over ``num_slots``, mean over the window's
step dispatches — the counts the engine takes where it dispatches (args of
``engine.step.call``). Below 100 %, the step program runs over rows that
emit nothing."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_counter"


def compute(run):
    calls = [t.counts["engine.step.call"]
             for t in program_spans.window_ticks(run) or ()
             if "engine.step.call" in t.counts]
    if not calls:
        return None
    return 100.0 * sum(c["active"] / c["slots"] for c in calls) / len(calls)
