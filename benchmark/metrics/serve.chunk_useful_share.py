"""ServeEngine: valid prompt tokens over the tokens the chunk program was
run over (``prefill_batch x prefill_chunk`` a dispatch on dense slots),
summed over the window's chunk dispatches — the counts the engine takes
where it dispatches (args of ``engine.chunk.call``). It falls with ragged
last pieces and with dispatches that found a single prompt to feed."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_counter"


def compute(run):
    calls = [t.counts["engine.chunk.call"]
             for t in program_spans.window_ticks(run) or ()
             if "engine.chunk.call" in t.counts]
    ran = sum(c["program_tokens"] for c in calls)
    return 100.0 * sum(c["tokens"] for c in calls) / ran if ran else None
