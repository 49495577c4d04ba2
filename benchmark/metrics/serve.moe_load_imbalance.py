"""Serve step programs: how unevenly a decode step's local assignments
fall on the held experts — the fullest (layer, expert) pair's rows
(``moe_load_max``) over the mean rows a pair takes (``moe_assignments /
moe_experts``), the mean over the window's step dispatches; the counts
the expert layer leaves in the cache and the engine reads where it syncs
(args of ``engine.step.call``). 1 is an even load; the ragged product's
longest group, and in the deployment the slowest chip of the exchange,
follow the fullest expert. A program that keeps no such counter gives
nothing."""
import statistics

from benchmark import afmoe_work

LAYER = "Serve step programs"
SOURCE = "program_counter"


def compute(run):
    calls = [c for c in afmoe_work.window_step_calls(run) or ()
             if c.get("moe_assignments")]
    if not calls:
        return None
    return statistics.mean(
        c["moe_load_max"] * c["moe_experts"] / c["moe_assignments"]
        for c in calls)
