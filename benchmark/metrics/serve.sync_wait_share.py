"""ServeEngine: share of the window's wall time the host spent blocked on
the device — the program's sync spans (``engine.step.sync``,
``engine.prefill.sync``, ``engine.chunk.sync``) over ``wall_s``. The rest
is host work the device may be waiting for."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_span"


def compute(run):
    return program_spans.share_of_wall(
        run, lambda t: sum(t.by_name.get(n, 0.0)
                           for n in program_spans.SYNC_SPANS))
