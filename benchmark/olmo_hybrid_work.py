"""Operations and bytes Olmo-Hybrid *requires*, from shapes alone — the
sibling of ``work.py`` and ``sambay_work.py`` for the ``olmo_hybrid``
family. Padded rows of a prefill or a piece, idle slots and cache
positions past a row's context are never counted, so a share built on
these cannot pass 100 % unless the time leaves work out.

``shape`` is the configuration file's dict. Layer kinds and sizes are
``olmo_hybrid_weights``'s (``layer_kind``, ``sizes``).

Counting rules. A matmul weight costs 2 operations a token (the
embedding is a lookup and costs none; the head is not tied and counts).
Softmax attention over S keys costs, a token and a full layer, 4 d S (2
for the scores, 2 for the values; ``heads x head_dim = d``). The delta
rule costs, a token and a head:

- one position at a time (a decode step): the decay (``dv dk``), the
  read ``S k``, the rank-one write and the read-out ``S q`` (``2 dv dk``
  each) — ``7 dv dk``;
- chunkwise, in blocks of ``C`` = 64 (a prefill or a piece): three
  products against the state (``w S``, ``q S``, ``u^T k``: ``6 dv dk``)
  and, inside the block, the lower triangles of ``K K^T`` and ``Q K^T``
  (``C dk`` each), of the solve against ``[beta V | beta g K]``
  (``C (dk + dv)``) and of ``(Q K^T) U`` (``C dv``) —
  ``6 dv dk + C (3 dk + 2 dv)``. The program computes the squares, not
  the triangles; the triangles are what the form needs.

Either way the conv costs ``2 K`` a token and a channel of q, k and v.
"""
from __future__ import annotations

from typing import Dict, Iterable

from benchmark import olmo_hybrid_weights as ow
from benchmark import program_spans

#: the block of the chunkwise rule (``ops/gated_delta.py::BLOCK``)
BLOCK = 64


def mixer_params(shape: dict) -> Dict[str, int]:
    """Matmul weights of one layer's mixer, by kind."""
    z = ow.sizes(shape)
    d, H, dv = z["d"], z["H"], z["dv"]
    return {ow.LINEAR: d * z["cw"] + d * H * dv + d * 2 * H + H * dv * d,
            ow.FULL: 4 * d * d}


def _count(shape: dict, kind: str) -> int:
    return sum(k == kind for k in shape["layer_types"])


def matmul_params(shape: dict, with_head: bool = True) -> int:
    z = ow.sizes(shape)
    mixer = mixer_params(shape)
    n = sum(mixer[k] + 3 * z["d"] * z["ff"] for k in shape["layer_types"])
    return n + z["V"] * z["d"] if with_head else n


def rule_flops_per_token(shape: dict, chunkwise: bool) -> int:
    """The delta rule and the conv of every linear layer, one token."""
    z = ow.sizes(shape)
    H, dk, dv = z["H"], z["dk"], z["dv"]
    rule = 6 * dv * dk + BLOCK * (3 * dk + 2 * dv) if chunkwise \
        else 7 * dv * dk
    return _count(shape, ow.LINEAR) * (H * rule + 2 * z["K"] * z["cw"])


def decode_flops(shape: dict, context_len: int) -> float:
    """One decoded token whose context (itself included) is
    ``context_len``: every matmul weight, the one-position rule, full
    attention over the context."""
    z = ow.sizes(shape)
    return (2.0 * matmul_params(shape) + rule_flops_per_token(shape, False)
            + 4.0 * z["d"] * _count(shape, ow.FULL) * context_len)


def piece_flops(shape: dict, offset: int, n: int,
                with_head: bool = False) -> float:
    """``n`` prompt tokens fed at absolute ``offset`` (a piece of a
    chunked prefill): every layer over every token, token ``i`` of the
    prompt attending ``i + 1`` keys. The head runs on a prompt's last
    token only, so a piece counts it only ``with_head``."""
    z = ow.sizes(shape)
    keys = n * offset + n * (n + 1) // 2        # sum of offset + 1 .. + n
    return (2.0 * matmul_params(shape, with_head=False) * n
            + rule_flops_per_token(shape, True) * n
            + 4.0 * z["d"] * _count(shape, ow.FULL) * keys
            + (2.0 * z["V"] * z["d"] if with_head else 0.0))


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """A whole prompt in one program: the piece from 0, and the head."""
    return piece_flops(shape, 0, prompt_len, with_head=True)


def cache_bytes_per_row(shape: dict, context_len: int,
                        kv_itemsize: int = 2,
                        state_itemsize: int = 4) -> Dict[str, int]:
    """At-rest bytes one row of ``context_len`` positions keeps live, by
    kind: the recurrent state (the matrix state and the conv tail of
    every linear layer) and the full layers' K/V."""
    z = ow.sizes(shape)
    return {
        "recurrent": _count(shape, ow.LINEAR) * state_itemsize * (
            z["H"] * z["dv"] * z["dk"] + (z["K"] - 1) * z["cw"]),
        "window": 0,
        "global": _count(shape, ow.FULL) * 2 * z["d"] * kv_itemsize
        * context_len}


def decode_step_bytes(shape: dict, contexts: Iterable[int],
                      weight_itemsize: int = 2, kv_itemsize: int = 2,
                      state_itemsize: int = 4) -> float:
    """Bytes one decode step over rows at ``contexts`` must move: every
    matmul weight once (the head included; a token's embedding row is
    not counted), each row's recurrent state read and written, each
    row's live K/V read once."""
    total = float(matmul_params(shape) * weight_itemsize)
    for c in contexts:
        row = cache_bytes_per_row(shape, int(c), kv_itemsize,
                                  state_itemsize)
        total += 2 * row["recurrent"] + row["global"]
    return total


def slice_pieces(run: dict):
    """The pieces of prompts that the program's chunk dispatches fed
    inside the traced slice, as ``[[(offset, tokens), ...] a dispatch]``
    — or ``None`` where the program kept no such spans. The harness's
    frontier cannot count them (it does not see a prompt that is half
    in), so they are read from the args of the program's own
    ``engine.chunk.call`` spans (``off`` and ``lens``, an entry a row).
    The slice is the last ``slice_s`` seconds of the window: the ticks
    that start inside it (the profiler is started between two ticks and
    stopped a few milliseconds after the last one, so the cut is good to
    a tick)."""
    ticks = program_spans.window_ticks(run)
    if not ticks or not run.get("slice_s"):
        return None
    hi = ticks[-1].start + ticks[-1].dur
    calls = [t.counts["engine.chunk.call"] for t in ticks
             if t.start >= hi - run["slice_s"]
             and "engine.chunk.call" in t.counts]
    return [list(zip(c["off"], c["lens"])) for c in calls if "lens" in c]
