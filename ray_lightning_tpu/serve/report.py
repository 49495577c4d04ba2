"""The one buffer a decode dispatch hands the host.

Every step / spec-round program ends by packing what
:meth:`ServeEngine.step_sync` reads into ONE flat ``int32`` array, inside
the same jitted program (:func:`pack_report`), so a dispatch costs the
host one device-to-host transfer instead of one round trip an array.
Layout, ``B`` slots and ``rounds`` scanned sub-steps::

    cur (B) | pos (B) | active (B) | remaining (B) | stepno (B)
    | emitted (rounds x B [x k+1]) | finished (rounds x B)
    [| accepted (rounds x B) | rejected (rounds x B)]      spec only

Booleans ride as 0 / 1. The device carry the next enqueue chains on is
returned beside the report and never leaves the device.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["StepReport", "pack_report", "unpack_report"]


class StepReport(NamedTuple):
    """:func:`unpack_report`'s host arrays, each writable and owned by
    the engine (the next prefill writes the carry rows in place)."""
    cur: np.ndarray                  # (B, 1) int32
    pos: np.ndarray                  # (B, 1) int32
    active: np.ndarray               # (B,) bool
    remaining: np.ndarray            # (B,) int32
    stepno: np.ndarray               # (B,) int32
    emitted: np.ndarray              # (rounds, B[, k+1]) int32, −1 = parked
    finished: np.ndarray             # (rounds, B) bool
    accepted: Optional[np.ndarray] = None   # spec: (rounds, B) int32
    rejected: Optional[np.ndarray] = None   # spec: (rounds, B) int32


def pack_report(cur, pos, active, remaining, stepno, emitted, finished,
                accepted=None, rejected=None) -> jax.Array:
    """The report of one dispatch (traced: a concatenate at the end of
    the step program, never a program of its own)."""
    parts = [cur, pos, active, remaining, stepno, emitted, finished]
    if accepted is not None:
        parts += [accepted, rejected]
    with jax.named_scope("decode/report"):
        return jnp.concatenate(
            [jnp.ravel(p).astype(jnp.int32) for p in parts])


def unpack_report(buf: np.ndarray, slots: int, rounds: int,
                  spec_width: Optional[int] = None) -> StepReport:
    """Slice a fetched report (a writable host copy) back into the
    arrays :func:`pack_report` was given. ``spec_width`` is ``k + 1`` for
    a speculative dispatch (``emitted`` is then ``(rounds, B, k+1)`` and
    the accept ledgers follow), ``None`` for a plain step."""
    B, width = slots, spec_width or 1
    sizes = [B] * 5 + [rounds * B * width, rounds * B]
    if spec_width is not None:
        sizes += [rounds * B] * 2
    if buf.shape != (sum(sizes),) or buf.dtype != np.int32:
        raise ValueError(
            f"step report of {buf.dtype}{list(buf.shape)} does not hold "
            f"{slots} slots x {rounds} rounds (width {spec_width}): "
            f"int32[{sum(sizes)}] expected")
    cur, pos, active, remaining, stepno, emitted, finished, *ledgers = \
        np.split(buf, np.cumsum(sizes)[:-1])
    emitted = emitted.reshape(
        (rounds, B) if spec_width is None else (rounds, B, width))
    return StepReport(
        cur.reshape(B, 1), pos.reshape(B, 1), active.astype(bool),
        remaining, stepno, emitted,
        finished.reshape(rounds, B).astype(bool),
        *(x.reshape(rounds, B) for x in ledgers))
