"""ServeEngine: valid prompt tokens over the tokens the prefill program was
run over (``prefill_batch x prefill_len`` a dispatch), summed over the
window's prefill dispatches — the counts the engine takes where it
dispatches (args of ``engine.prefill.call``)."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_counter"


def compute(run):
    calls = [t.counts["engine.prefill.call"]
             for t in program_spans.window_ticks(run) or ()
             if "engine.prefill.call" in t.counts]
    ran = sum(c["program_tokens"] for c in calls)
    return 100.0 * sum(c["tokens"] for c in calls) / ran if ran else None
