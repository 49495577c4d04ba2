"""Strategy / mesh: the share of the traced window in which a collective op
ran on a device and no compute op did — the worst device. Async pairs
(``-start`` / ``-done``) count as the op events the trace holds: a transfer
that overlaps compute shows as a short ``-done``."""
from benchmark import trace_reduce

LAYER = "Strategy / mesh"
SOURCE = "device_trace"
COLLECTIVE = (r"all-gather|all-reduce|reduce-scatter|collective-permute|"
              r"all-to-all|collective-broadcast")


def compute(run):
    trace = run["trace"]
    lo, hi = trace.bounds()
    shares = []
    for dev in trace.devices:
        exposed, total = trace_reduce.collective_exposed_ns(
            trace_reduce.clip(dev.ops, lo, hi), COLLECTIVE)
        if total > 0:
            shares.append(100.0 * exposed / (hi - lo))
    return max(shares) if shares else None
