"""ServeEngine: at-rest cache bytes an active row keeps live, by the
engine's own counters — ``recurrent_bytes + window_bytes + global_bytes``
(args of ``engine.step.call``: from the shapes of the cache leaves, what
each leaf declares itself to be, and the synced frontier) summed over the
window's step dispatches, over the active rows summed likewise: the
recurrent state and the rings as held (whole), the full-length K/V up to
each row's context. It moves when a ring is held at the slot's full
length, or a state in a wider dtype. ``None``
on a program whose step spans carry no such counts."""
from benchmark import program_spans

LAYER = "ServeEngine"
SOURCE = "program_counter"
KINDS = ("recurrent_bytes", "window_bytes", "global_bytes")


def compute(run):
    calls = [t.counts["engine.step.call"]
             for t in program_spans.window_ticks(run) or ()
             if "engine.step.call" in t.counts]
    calls = [c for c in calls if all(k in c for k in KINDS)]
    rows = sum(c["active"] for c in calls)
    if not rows:
        return None
    return sum(c[k] for c in calls for k in KINDS) / rows / 1e6
