"""ServeEngine: 95th percentile, over the requests the window completed,
of (completion - first token) / (tokens - 1) on the harness's clock — the
time per output token a caller sees, prefills of other requests that land
in a request's life included."""
import numpy as np

LAYER = "ServeEngine"
SOURCE = "host_clock"


def compute(run):
    tpot = run.get("tpot_ms")
    return float(np.percentile(tpot, 95)) if tpot else None
