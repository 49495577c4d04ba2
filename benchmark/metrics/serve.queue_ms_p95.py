"""ServeClient / scheduler: 95th percentile of the ``queue`` segment
(submit -> admission) of the program's own request traces
(``obs/tracing.py``, armed by ``telemetry=`` in the traced run), over the
requests the window completed."""
import numpy as np

LAYER = "ServeClient / scheduler"
SOURCE = "program_span"


def compute(run):
    queue = run.get("queue_ms")
    return float(np.percentile(queue, 95)) if queue else None
