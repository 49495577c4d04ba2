"""Trainer loop: median host-clock time of a step when every step's loss is
waited for (the traced run does so for a few steps after the profiled
slice; the untraced window never does)."""
import statistics

LAYER = "Trainer loop"
SOURCE = "host_clock"


def compute(run):
    steps = run.get("step_ms") or []
    return statistics.median(steps) if steps else None
