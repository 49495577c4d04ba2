"""Operations and bytes SambaY (Phi-4-mini-flash-reasoning) *requires*,
from shapes alone — the sibling of ``work.py`` for the ``phi4flash``
family. Padded prefill rows, idle slots and cache positions past a row's
context are never counted, so a share built on these cannot pass 100 %
unless the time leaves work out.

``shape`` is the configuration file's dict. Layer kinds and sizes are
``sambay_weights``'s (``layer_kind``, ``sizes``).

Counting rules. A matmul weight costs 2 operations a token. Differential
attention over S keys costs, a token and a layer, 2 d S for the scores
(``Hq`` heads of ``D``) and 4 d S for the values (each head reads a value
head of ``2 D``): 6 d S. The selective scan costs, a token and a Mamba
layer, 6 di N for the state (decay, input, accumulate, read-out) and
2 K di for the conv. At prefill the cross-decoder (every layer past the
full-attention one) runs on one position a row — the architecture's own
saving — and the head once.
"""
from __future__ import annotations

from typing import Dict, Iterable

from benchmark import sambay_weights as sw


def mixer_params(shape: dict) -> Dict[str, int]:
    """Matmul weights of one layer's mixer, by kind."""
    z = sw.sizes(shape)
    d, di, N, R = z["d"], z["di"], z["N"], z["R"]
    attn = d * (z["Hq"] + 2 * z["Hkv"]) * z["D"] + d * d
    return {sw.MAMBA: d * 2 * di + di * (R + 2 * N) + R * di + di * d,
            sw.SWA: attn, sw.FULL: attn, sw.CROSS: 2 * d * d,
            sw.GMU: 2 * d * di}


def _layers(shape: dict, decoder: str):
    """Layer indices of the self-decoder (through the full-attention
    layer), the cross-decoder (past it) or both."""
    n = shape["num_hidden_layers"]
    split = n // 2 + 2
    return {"self": range(split), "cross": range(split, n),
            "all": range(n)}[decoder]


def matmul_params(shape: dict, decoder: str = "all",
                  with_head: bool = True) -> int:
    z = sw.sizes(shape)
    mixer = mixer_params(shape)
    n = sum(mixer[sw.layer_kind(shape, l)] + 3 * z["d"] * z["ff"]
            for l in _layers(shape, decoder))
    return n + z["V"] * z["d"] if with_head else n


def _count(shape: dict, decoder: str, *kinds: str) -> int:
    return sum(sw.layer_kind(shape, l) in kinds
               for l in _layers(shape, decoder))


def scan_flops_per_token(shape: dict) -> int:
    z = sw.sizes(shape)
    return _count(shape, "all", sw.MAMBA) * (
        6 * z["di"] * z["N"] + 2 * z["K"] * z["di"])


def decode_flops(shape: dict, context_len: int) -> float:
    """One decoded token whose context (itself included) is
    ``context_len``: every matmul weight, the scan, windowed attention
    over ``min(context, W)`` keys, and the full layer's K/V attended by
    itself and by every cross layer."""
    z = sw.sizes(shape)
    windowed = _count(shape, "all", sw.SWA)
    global_ = _count(shape, "all", sw.FULL, sw.CROSS)
    return (2.0 * matmul_params(shape) + scan_flops_per_token(shape)
            + 6.0 * z["d"] * (windowed * min(context_len, z["W"])
                              + global_ * context_len))


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens: the self-decoder over every
    token (windowed attention sum_i min(i, W), full attention
    sum_i i), the cross-decoder and the head over the last one."""
    z = sw.sizes(shape)
    L, W, d = prompt_len, z["W"], z["d"]
    full_sq = L * (L + 1) // 2
    win_sq = full_sq if L <= W else W * (W + 1) // 2 + (L - W) * W
    return (2.0 * matmul_params(shape, "self", with_head=False) * L
            + scan_flops_per_token(shape) * L
            + 6.0 * d * (_count(shape, "self", sw.SWA) * win_sq
                         + _count(shape, "self", sw.FULL) * full_sq)
            + 2.0 * matmul_params(shape, "cross", with_head=True)
            + 6.0 * d * _count(shape, "cross", sw.CROSS) * L)


def cache_bytes_per_row(shape: dict, context_len: int,
                        kv_itemsize: int = 2,
                        state_itemsize: int = 4) -> Dict[str, int]:
    """At-rest bytes one row of ``context_len`` positions keeps live, by
    kind: the recurrent state (scan state and conv tail of every Mamba
    layer), the rings up to the window, the one full-length K/V."""
    z = sw.sizes(shape)
    per_pos = 2 * z["Hkv"] * z["D"] * kv_itemsize
    return {
        "recurrent": _count(shape, "all", sw.MAMBA) * state_itemsize * (
            z["N"] * z["di"] + (z["K"] - 1) * z["di"]),
        "window": _count(shape, "all", sw.SWA) * per_pos
        * min(context_len, z["W"]),
        "global": _count(shape, "all", sw.FULL) * per_pos * context_len}


def decode_step_bytes(shape: dict, contexts: Iterable[int],
                      weight_itemsize: int = 2, kv_itemsize: int = 2,
                      state_itemsize: int = 4) -> float:
    """Bytes one decode step over rows at ``contexts`` must move: every
    matmul weight once (the tied head's table included), each row's
    recurrent state read and written, each ring up to ``min(context,
    W)``, and the full layer's live K/V once for each layer that reads
    it (itself and the cross layers)."""
    readers = _count(shape, "all", sw.FULL, sw.CROSS)
    total = float(matmul_params(shape) * weight_itemsize)
    for c in contexts:
        row = cache_bytes_per_row(shape, int(c), kv_itemsize,
                                  state_itemsize)
        total += 2 * row["recurrent"] + row["window"] \
            + readers * row["global"]
    return total
