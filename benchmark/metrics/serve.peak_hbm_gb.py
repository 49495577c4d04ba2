"""Device: the fullest device's peak, as ``harness.Ctx.memory_peak_bytes``
reads it from ``memory_stats()`` after the window: live buffers plus what the
runtime reserved for programs' temporaries."""
LAYER = "Device"
SOURCE = "program_counter"


def compute(run):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
