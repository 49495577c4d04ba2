"""ServeClient / scheduler: median over the window's step ticks of the
tick's time outside the blocking host copies (``serve.tick`` minus its
``engine.step.sync`` spans, ``benchmark/program_spans.py``): what the host
adds per token — plan, operand build, the jitted call's enqueue, the retire
loop, stamping."""
import statistics

from benchmark import program_spans

LAYER = "ServeClient / scheduler"
SOURCE = "program_span"


def compute(run):
    ticks = program_spans.window_ticks(run)
    host = [t.dur - t.by_name.get("engine.step.sync", 0.0)
            for t in ticks or () if t.action == "step"]
    return 1e3 * statistics.median(host) if host else None
