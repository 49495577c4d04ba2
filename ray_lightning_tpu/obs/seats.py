"""Trace-time decisions, counted on the span that traced them.

A seat that picks its program while it is traced (the default attention
seat of ``models/transformer.py``: blockwise kernel or dense path) calls
:func:`note`; whoever builds the program opens :func:`tally` around the
call that traces it and finds the counts on that span's arguments. With no
tally open a note goes nowhere, so a bare ``model.apply`` keeps no record.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional

_OPEN = threading.local()


def note(kernel: bool, how: str) -> None:
    """One attention seat traced: it took the kernel (``how``: ``local`` /
    ``sharded``) or fell back to the dense path (``how``: the reason)."""
    seen = getattr(_OPEN, "seen", None)
    if seen is not None:
        seen.append((kernel, how))


@contextlib.contextmanager
def tally(args: Optional[Dict[str, Any]]):
    """Count the seats traced inside the block onto ``args`` (a span's
    argument dict, ``None`` when telemetry is disarmed): ``attn_kernel``,
    ``attn_dense`` and, when a seat fell back, ``attn_dense_reason`` (the
    first one's). A call that traces nothing — every step but the first —
    adds nothing."""
    if args is None:
        yield
        return
    prev, _OPEN.seen = getattr(_OPEN, "seen", None), []
    try:
        yield
    finally:
        seen, _OPEN.seen = _OPEN.seen, prev
        if seen:
            dense = [how for kernel, how in seen if not kernel]
            args["attn_kernel"] = len(seen) - len(dense)
            args["attn_dense"] = len(dense)
            if dense:
                args["attn_dense_reason"] = dense[0]
