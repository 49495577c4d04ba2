"""Operations and bytes Falcon-H1 *requires*, from shapes alone — the
sibling of ``olmo_hybrid_work.py`` and ``afmoe_work.py`` for the
``falcon_h1`` family, in the form ``serve_family_metrics.py`` reads.
Padded rows of a prefill or a piece, idle slots and cache positions past
a row's context are never counted, so a share built on these cannot pass
100 % unless the time leaves work out.

``shape`` is the configuration file's dict; sizes are
``falcon_h1_weights.sizes``'. Every layer is the same: attention and a
Mamba-2 mixer side by side, then the MLP.

Counting rules. A matmul weight costs 2 operations a token (the
embedding is a lookup and costs none; the head is not tied and counts).
Softmax attention over S keys costs, a token and a layer, ``4 Hq D S``
(2 for the scores, 2 for the values). The SSD costs, a token, with ``H``
heads of a ``(P, N)`` state and ``G`` groups of B and C:

- one position at a time (a decode step): the decay (``P N``), the
  write ``dt x B^T`` (``2 P N``) and the read ``S C`` (``2 P N``) a head
  — ``5 H P N``;
- chunkwise, in blocks of ``c`` = ``mamba_chunk_size`` (a prefill or a
  piece): the two products against the state (``C S_0^T`` and the
  write, ``4 P N`` a head) and, inside the block, the lower triangles of
  ``C B^T`` (``c N`` a group) and of its product against ``dt X`` (``c
  P`` a head) — ``H (4 P N + c P) + G c N``. The program computes the
  squares, not the triangles; the triangles are what the form needs.

Either way the conv costs ``2 K`` a token and a channel of x, B and C.
"""
from __future__ import annotations

from typing import Dict, Iterable

from benchmark import falcon_h1_weights as fw
from benchmark.olmo_hybrid_work import slice_pieces  # noqa: F401  (generic)


def attention_params(shape: dict) -> int:
    z = fw.sizes(shape)
    return z["d"] * (z["Hq"] + 2 * z["Hkv"]) * z["D"] \
        + z["Hq"] * z["D"] * z["d"]


def ssm_params(shape: dict) -> int:
    """The mixer's two matrices (the conv, A, D, dt_bias and the norm are
    vectors, counted in :func:`layer_params` only)."""
    z = fw.sizes(shape)
    width = sum(w for _, w in fw.in_proj_parts(z))
    return z["d"] * width + z["di"] * z["d"]


def mlp_params(shape: dict) -> int:
    z = fw.sizes(shape)
    return 3 * z["d"] * z["ff"]


def layer_params(shape: dict) -> int:
    """Every parameter of one layer, vectors included."""
    z = fw.sizes(shape)
    vectors = (2 * z["d"]                          # the two RMSNorm gains
               + z["K"] * z["cw"] + z["cw"]        # conv taps and bias
               + 3 * z["H"] + z["di"])             # A_log D dt_bias, norm
    return attention_params(shape) + ssm_params(shape) + mlp_params(shape) \
        + vectors


def total_params(shape: dict) -> int:
    """The cut as the chip holds it: the layers, the embedding, the head
    and the final norm."""
    z = fw.sizes(shape)
    return z["n"] * layer_params(shape) + 2 * z["V"] * z["d"] + z["d"]


def matmul_params(shape: dict, with_head: bool = True) -> int:
    z = fw.sizes(shape)
    n = z["n"] * (attention_params(shape) + ssm_params(shape)
                  + mlp_params(shape))
    return n + z["V"] * z["d"] if with_head else n


def ssd_flops_per_token(shape: dict, chunkwise: bool) -> int:
    """The SSD and the conv of every layer, one token."""
    z = fw.sizes(shape)
    H, P, N, G, c = z["H"], z["P"], z["N"], z["G"], z["chunk"]
    rule = H * (4 * P * N + c * P) + G * c * N if chunkwise \
        else 5 * H * P * N
    return z["n"] * (rule + 2 * z["K"] * z["cw"])


def per_key_flops(shape: dict) -> int:
    """Attention over one key, a token, all layers."""
    z = fw.sizes(shape)
    return 4 * z["Hq"] * z["D"] * z["n"]


def decode_flops(shape: dict, context_len: int) -> float:
    """One decoded token whose context (itself included) is
    ``context_len``: every matmul weight, the one-position SSD, attention
    over the context."""
    return (2.0 * matmul_params(shape) + ssd_flops_per_token(shape, False)
            + per_key_flops(shape) * context_len)


def piece_flops(shape: dict, offset: int, n: int,
                with_head: bool = False) -> float:
    """``n`` prompt tokens fed at absolute ``offset`` (a piece of a
    chunked prefill): every layer over every token, token ``i`` of the
    prompt attending ``i + 1`` keys. The head runs on a prompt's last
    token only, so a piece counts it only ``with_head``."""
    z = fw.sizes(shape)
    keys = n * offset + n * (n + 1) // 2        # sum of offset + 1 .. + n
    return (2.0 * matmul_params(shape, with_head=False) * n
            + ssd_flops_per_token(shape, True) * n
            + per_key_flops(shape) * keys
            + (2.0 * z["V"] * z["d"] if with_head else 0.0))


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """A whole prompt in one program: the piece from 0, and the head."""
    return piece_flops(shape, 0, prompt_len, with_head=True)


def cache_bytes_per_row(shape: dict, context_len: int,
                        kv_itemsize: int = 2,
                        state_itemsize: int = 4) -> Dict[str, int]:
    """At-rest bytes one row of ``context_len`` positions keeps live, by
    kind: every layer's recurrent state (the SSM state and the conv tail)
    and every layer's K/V."""
    z = fw.sizes(shape)
    return {
        "recurrent": z["n"] * state_itemsize * (
            z["H"] * z["P"] * z["N"] + (z["K"] - 1) * z["cw"]),
        "window": 0,
        "global": z["n"] * 2 * z["Hkv"] * z["D"] * kv_itemsize
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
