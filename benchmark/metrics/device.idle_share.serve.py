"""Device: 1 - (union of op intervals / traced window) on the device that
was busy least."""
LAYER = "Device"
SOURCE = "device_trace"


def compute(run):
    return 100.0 * run["trace_summary"]["idle_share_worst"]
