"""The three whole-program shares a family-built serve cell reports, from
the family's *work module* (``<family>_work.py``: ``prefill_flops``,
``decode_flops``, ``piece_flops``, ``decode_step_bytes`` and
``slice_pieces``) — one definition each, so that a family's metric file
is a line that names its work module and its ``model_type``:

- :func:`step_mfu` — operations of the prompts prefilled, the chunk
  pieces fed and the tokens decoded inside the traced slice, over slice
  seconds x the bf16 peak: the share of the whole step;
- :func:`decode_step_roofline` — the bytes a decode step must move over
  the HBM peak, against the median device time of ``_engine_step_impl``;
- :func:`chunk_roofline` — the operations the valid tokens of a mean
  chunk dispatch require over the bf16 peak, against the median device
  time of ``_chunk_prefill_dense_impl``.

Each gives ``None`` for a rehearsal, for another ``model_type`` and
where the run kept nothing to read (no slice, no spans, no such program
in the trace). The rules are those of ``metrics/serve.olmo_hybrid_*``:
whole prompts prefilled and tokens decoded come from the harness's own
lengths; a prompt longer than the engine's ``prefill_chunk`` enters in
pieces, which the harness's frontier does not see, so its tokens come
from the program's own ``engine.chunk.call`` spans inside the slice (the
head of a prompt's last piece is left out) and it is left out of the
prefill count. Padded rows and idle slots are never counted."""
from __future__ import annotations

import re
import statistics

from benchmark import peaks, trace_reduce

STEP_PROGRAM = r"_engine_step_impl"
CHUNK_PROGRAM = r"_chunk_prefill_dense_impl"


def _durations(run: dict, program: str) -> list:
    """Device seconds of each execution of ``program`` in the trace."""
    trace = run["trace"]
    lo, hi = trace.bounds()
    rx = re.compile(program)
    return [e.dur / 1e9 for e in trace_reduce.clip(
        trace.devices[0].modules, lo, hi) if rx.search(e.name)]


def _mine(run: dict, model_type: str) -> bool:
    return not run["rehearse"] \
        and run["shape"].get("model_type") == model_type


def step_mfu(run: dict, work, model_type: str):
    s = run.get("slice") or {}
    if not _mine(run, model_type) or not run.get("slice_s") \
            or "decode_contexts" not in s:
        return None
    shape = run["shape"]
    chunk = run["workload"]["engine"].get("prefill_chunk")
    flops = (sum(work.prefill_flops(shape, n)
                 for n in s["prefill_lengths"] if not chunk or n <= chunk)
             + sum(work.decode_flops(shape, c)
                   for c in s["decode_contexts"])
             + sum(work.piece_flops(shape, off, n)
                   for pieces in work.slice_pieces(run) or ()
                   for off, n in pieces))
    if not flops:
        return None
    return 100.0 * flops / (run["slice_s"]
                            * peaks.peak_flops(run["device_kind"]))


def decode_step_roofline(run: dict, work, model_type: str):
    s = run.get("slice") or {}
    if not _mine(run, model_type) or not s.get("decode_contexts"):
        return None
    durs = _durations(run, STEP_PROGRAM)
    if not durs:
        return None
    shape = run["shape"]
    weights = work.decode_step_bytes(shape, [])
    rows = sum(work.decode_step_bytes(shape, [c]) - weights
               for c in s["decode_contexts"])
    need = weights + rows / len(durs)       # a mean step's rows
    floor_s = need / peaks.hbm_bandwidth(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)


def chunk_roofline(run: dict, work, model_type: str):
    if not _mine(run, model_type):
        return None
    dispatches = work.slice_pieces(run)
    if not dispatches:
        return None
    durs = _durations(run, CHUNK_PROGRAM)
    if not durs:
        return None
    flops = sum(work.piece_flops(run["shape"], off, n)
                for pieces in dispatches for off, n in pieces)
    floor_s = flops / len(dispatches) / peaks.peak_flops(run["device_kind"])
    return 100.0 * floor_s / statistics.median(durs)
