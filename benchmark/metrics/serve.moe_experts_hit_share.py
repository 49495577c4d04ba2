"""Serve step programs: the share of the held (layer, expert) pairs that
took at least one row in a decode step (``moe_experts_hit /
moe_experts``), the mean over the window's step dispatches — the counts
the expert layer leaves in the cache (args of ``engine.step.call``; every
slot's row is computed and counted, decoding or not). Every pair that is
hit streams its three matrices whole, so this is the share of the routed
experts' bytes a step reads. A program that keeps no such counter gives
nothing."""
import statistics

from benchmark import afmoe_work

LAYER = "Serve step programs"
SOURCE = "program_counter"


def compute(run):
    calls = [c for c in afmoe_work.window_step_calls(run) or ()
             if c.get("moe_experts")]
    if not calls:
        return None
    return 100.0 * statistics.mean(
        c["moe_experts_hit"] / c["moe_experts"] for c in calls)
