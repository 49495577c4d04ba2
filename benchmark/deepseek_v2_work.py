"""Operations and bytes DeepSeek-V2 *requires*, from shapes alone — the
sibling of ``afmoe_work.py`` for the ``deepseek_v2`` family, in the form
``serve_family_metrics.py`` reads. Padded rows of a prefill or a piece,
idle slots and cache positions past a row's context are never counted,
so a share built on these cannot pass 100 % unless the time leaves work
out.

``shape`` is the configuration file's dict (``deepseek_v2_weights.sizes``
reads it): ``n_routed_experts`` is the experts **held** (20),
``published.n_routed_experts`` the router's outputs (160).

Counting rules. A matmul weight costs 2 operations a token (the
embedding is a lookup and costs none; the head is not tied and counts,
over the slice of the vocabulary held).

- *Attention's projections*, a token and a layer: ``W_dq``, ``W_uq``,
  ``W_dkv`` and ``W_o``.
- *Latent attention* takes one of two forms, and each is counted as the
  form it is:

  - **absorbed** (a decode step): ``W_uk`` folded into the query and
    ``W_uv`` applied to the weighted latent, ``2 H C (Dn + Dv)`` a token;
    then, a live position, the score against the latent and the rotary
    key, ``2 H (C + R)``, and the weighted sum of latents, ``2 H C`` —
    ``2 x 128 x (576 + 512)`` at the published widths;
  - **expanded** (a piece, a prefill): every position the piece's queries
    read is expanded once a piece, ``2 C H (Dn + Dv)`` a position; then a
    key and query, ``2 H (Dn + R)`` for the score and ``2 H Dv`` for the
    value, causal within the piece: the token at position ``p`` reads
    ``p + 1`` keys.

- *A dense layer's MLP*: three ``d x F`` matrices.
- *An expert layer outside its routed experts*: the router (all ``E``
  outputs) and the shared experts' three ``d x fs`` matrices.
- *Routed experts*: a token takes ``k`` experts of ``E``, of which
  ``held / E`` live here: ``k held / E`` experts a token **in
  expectation** (0.75 at the published sizes: the group limit chooses
  among groups, and by symmetry each group is as likely), three matrices
  each. What the tokens really drew is in the program's counter
  (``moe_assignments``); the operation count keeps to the expectation,
  so that it is a function of shapes.

Bytes of a decode step: every weight outside the routed experts and the
head's slice once (``W_uk`` and ``W_uv`` among them), the three matrices
of every (layer, expert) pair that **took a row** (the program's
``moe_experts_hit``), and each decoding row's live latent and rotary key
read once, ``C + R`` values a position and layer.

The two kernels are counted alone too, a call being one layer.
``ops/mla_decode.py`` (a decode step): for each decoding row, the live
latent and rotary key read and the query and output of its heads; the
score and the weighted sum of every live position. ``ops/mla_prefill.py``
(a chunk dispatch): for each row's piece, the ``offset + n`` positions
expanded and the piece's keys scored and summed, causal; the latent and
rotary key of those positions read once, the queries and outputs of its
heads, and ``W_uk`` / ``W_uv`` once a call.
"""
from __future__ import annotations

from typing import Iterable

from benchmark import deepseek_v2_weights as dw
from benchmark.afmoe_work import slice_step_calls  # noqa: F401  (generic)
from benchmark.olmo_hybrid_work import slice_pieces  # noqa: F401  (generic)


def projection_params(shape: dict) -> int:
    """``W_dq``, ``W_uq``, ``W_dkv`` and ``W_o``: what a token passes
    through in either form."""
    z = dw.sizes(shape)
    return (z["d"] * z["qr"] + z["qr"] * z["H"] * (z["Dn"] + z["R"])
            + z["d"] * (z["C"] + z["R"]) + z["H"] * z["Dv"] * z["d"])


def up_params(shape: dict) -> int:
    """``W_uk`` and ``W_uv`` (the published ``kv_b_proj``)."""
    z = dw.sizes(shape)
    return z["C"] * z["H"] * (z["Dn"] + z["Dv"])


def attention_params(shape: dict) -> int:
    return projection_params(shape) + up_params(shape)


def dense_mlp_params(shape: dict) -> int:
    z = dw.sizes(shape)
    return 3 * z["d"] * z["F"]


def expert_params(shape: dict) -> int:
    """One routed expert's three matrices."""
    z = dw.sizes(shape)
    return 3 * z["d"] * z["f"]


def shared_params(shape: dict) -> int:
    z = dw.sizes(shape)
    return 3 * z["d"] * z["fs"]


def router_params(shape: dict) -> int:
    z = dw.sizes(shape)
    return z["d"] * z["E"]


def experts_per_token(shape: dict) -> float:
    """Routed experts a token meets here, in expectation."""
    z = dw.sizes(shape)
    return z["k"] * z["held"] / z["E"]


def fixed_params(shape: dict) -> int:
    """Matmul weights outside the routed experts, all layers, the head's
    slice included."""
    z = dw.sizes(shape)
    n_moe = z["n"] - z["n_dense"]
    return (z["n"] * attention_params(shape)
            + z["n_dense"] * dense_mlp_params(shape)
            + n_moe * (router_params(shape) + shared_params(shape))
            + z["d"] * z["V"])


def total_params(shape: dict) -> int:
    """The cut as the chip holds it: every matrix, the held experts, the
    embedding, and the norms' gains."""
    z = dw.sizes(shape)
    n_moe = z["n"] - z["n_dense"]
    gains = z["n"] * (2 * z["d"] + z["qr"] + z["C"]) + z["d"]
    return (fixed_params(shape) + n_moe * z["held"] * expert_params(shape)
            + z["V"] * z["d"] + gains)


def token_flops(shape: dict, with_head: bool = True) -> float:
    """One token through every layer's matmuls outside ``W_uk`` / ``W_uv``
    and attention over positions; routed experts at their expected
    share."""
    z = dw.sizes(shape)
    n_moe = z["n"] - z["n_dense"]
    params = (fixed_params(shape) - z["n"] * up_params(shape)
              - (0 if with_head else z["d"] * z["V"])
              + n_moe * experts_per_token(shape) * expert_params(shape))
    return 2.0 * params


def absorbed_flops_per_position(shape: dict) -> int:
    """A live position in a decode step, one layer: the score against the
    latent and the rotary key, the weighted sum of latents."""
    z = dw.sizes(shape)
    return 2 * z["H"] * (z["C"] + z["R"]) + 2 * z["H"] * z["C"]


def expanded_flops_per_key(shape: dict) -> int:
    """A key and query of a piece, one layer: score and value."""
    z = dw.sizes(shape)
    return 2 * z["H"] * (z["Dn"] + z["R"]) + 2 * z["H"] * z["Dv"]


def decode_flops(shape: dict, context_len: int) -> float:
    """One decoded token whose context (itself included) is
    ``context_len``: the absorbed form."""
    z = dw.sizes(shape)
    return (token_flops(shape) + 2.0 * z["n"] * up_params(shape)
            + z["n"] * absorbed_flops_per_position(shape) * context_len)


def piece_flops(shape: dict, offset: int, n: int,
                with_head: bool = False) -> float:
    """``n`` prompt tokens fed at absolute ``offset`` (a piece of a
    chunked prefill): the expanded form — the ``offset + n`` positions
    read expanded once, token ``i`` of the piece attending ``offset + i
    + 1`` keys. The head runs on a prompt's last token only, so a piece
    counts it only ``with_head``."""
    z = dw.sizes(shape)
    keys = n * offset + n * (n + 1) // 2        # sum of offset + 1 .. + n
    return (n * token_flops(shape, with_head=False)
            + z["n"] * (2.0 * up_params(shape) * (offset + n)
                        + expanded_flops_per_key(shape) * keys)
            + (2.0 * z["d"] * z["V"] if with_head else 0.0))


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """A whole prompt in one program: the piece from 0, and the head."""
    return piece_flops(shape, 0, prompt_len, with_head=True)


def cache_bytes_per_position(shape: dict, itemsize: int = 2) -> int:
    """At-rest bytes one position of one row keeps in ONE layer: the
    latent and the rotary key."""
    z = dw.sizes(shape)
    return (z["C"] + z["R"]) * itemsize


def live_cache_bytes(shape: dict, context_len: int,
                     itemsize: int = 2) -> int:
    """Latent bytes a row at ``context_len`` keeps live, all layers."""
    z = dw.sizes(shape)
    return z["n"] * cache_bytes_per_position(shape, itemsize) * context_len


def decode_step_bytes(shape: dict, contexts: Iterable[int],
                      experts_hit: float, weight_itemsize: int = 2,
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step over rows at ``contexts`` must move when
    ``experts_hit`` (layer, expert) pairs took a row."""
    total = weight_itemsize * (fixed_params(shape)
                               + experts_hit * expert_params(shape))
    return float(total) + sum(live_cache_bytes(shape, int(c), kv_itemsize)
                              for c in contexts)


def mla_decode_flops(shape: dict, contexts: Iterable[int]) -> float:
    """Operations of one ``ops/mla_decode.py`` call (one layer) over
    decoding rows at ``contexts``."""
    return float(absorbed_flops_per_position(shape)
                 * sum(int(c) for c in contexts))


def mla_decode_bytes(shape: dict, contexts: Iterable[int],
                     itemsize: int = 2) -> float:
    """Bytes of one such call: each row's live latent and rotary key,
    its heads' queries (``C + R`` wide) and float32 outputs (``C``)."""
    z = dw.sizes(shape)
    contexts = [int(c) for c in contexts]
    per_row = z["H"] * ((z["C"] + z["R"]) * itemsize + 4 * z["C"])
    return float(cache_bytes_per_position(shape, itemsize) * sum(contexts)
                 + per_row * len(contexts))


def mla_prefill_flops(shape: dict, pieces: Iterable) -> float:
    """Operations of one ``ops/mla_prefill.py`` call (one layer) over a
    chunk dispatch's rows, ``(offset, tokens)`` each."""
    total = 0.0
    for off, n in pieces:
        keys = n * off + n * (n + 1) // 2
        total += 2.0 * up_params(shape) * (off + n) \
            + expanded_flops_per_key(shape) * keys
    return total


def mla_prefill_bytes(shape: dict, pieces: Iterable,
                      itemsize: int = 2) -> float:
    """Bytes of one such call."""
    z = dw.sizes(shape)
    total = float(up_params(shape) * itemsize)
    for off, n in pieces:
        total += cache_bytes_per_position(shape, itemsize) * (off + n) \
            + n * z["H"] * ((z["Dn"] + z["R"]) * itemsize + 4 * z["Dv"])
    return total
