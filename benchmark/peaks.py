"""Published per-chip peaks, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page —
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip (the table
copied from ``bench.py:46-111``; that original is listed in PERF.md for a
later ``simplicity`` PR to delete). A device that is not in the table is an
error, never a default: a share of an assumed peak is a made-up number.
"""
from __future__ import annotations

#: device_kind substring -> (bf16 FLOP/s, HBM bytes/s)
PEAKS = {
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
}


class UnknownDevice(RuntimeError):
    pass


def _lookup(device_kind: str):
    kind = device_kind.lower()
    for key, val in PEAKS.items():
        if key in kind:
            return val
    raise UnknownDevice(
        f"no published peak for device_kind {device_kind!r}; known: "
        f"{sorted(PEAKS)}")


def peak_flops(device_kind: str) -> float:
    return _lookup(device_kind)[0]


def hbm_bandwidth(device_kind: str) -> float:
    return _lookup(device_kind)[1]
