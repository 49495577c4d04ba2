"""Operations and bytes Trinity / AFMoE *requires*, from shapes alone —
the sibling of ``work.py``, ``sambay_work.py`` and
``olmo_hybrid_work.py`` for the ``afmoe`` family. Padded rows of a
prefill or a piece, idle slots and cache positions past a row's context
or outside a window are never counted, so a share built on these cannot
pass 100 % unless the time leaves work out.

``shape`` is the configuration file's dict (``afmoe_weights.sizes``
reads it): ``num_experts`` is the experts **held** (32),
``published.num_experts`` the router's outputs (256).

Counting rules. A matmul weight costs 2 operations a token (the
embedding is a lookup and costs none; the head is not tied and counts,
over the slice of the vocabulary held).

- *Attention's projections*, a token and a layer: ``W_qkvg`` (query,
  key, value and gate from one product) and ``W_o``.
- *A dense layer's MLP*: three ``d x F`` matrices.
- *An expert layer outside its routed experts*: the router (all ``E``
  outputs) and the shared expert's three ``d x f`` matrices.
- *Routed experts*: a token takes ``k`` experts of ``E``, of which
  ``held / E`` live here: ``k held / E`` experts a token **in
  expectation** (0.5 at the published sizes), three matrices each. What
  the tokens really drew is in the program's counter
  (``moe_assignments``), which the hit-share and imbalance metrics read;
  the operation count keeps to the expectation, so that it is a function
  of shapes.
- *Attention over keys*: ``2 Hq D`` for the score and ``2 Hq D`` for the
  value, a key and query. The token at position ``p`` reads ``p + 1``
  keys on a full layer and ``min(p + 1, window)`` on a window layer.

Bytes of a decode step: every weight outside the routed experts and the
head's slice once, the three matrices of every (layer, expert) pair that
**took a row** (the program's ``moe_experts_hit``), and each decoding
row's live K/V read once: ``min(context, window)`` positions on a window
layer, the context on a full one, ``2 Hkv D`` values a position.
"""
from __future__ import annotations

from typing import Iterable

from benchmark import afmoe_weights as aw
from benchmark import program_spans
from benchmark.olmo_hybrid_work import slice_pieces  # noqa: F401  (generic)


def attention_params(shape: dict) -> int:
    z = aw.sizes(shape)
    return z["d"] * 2 * (z["Hq"] + z["K"]) * z["D"] + z["Hq"] * z["D"] * z["d"]


def dense_mlp_params(shape: dict) -> int:
    z = aw.sizes(shape)
    return 3 * z["d"] * z["F"]


def expert_params(shape: dict) -> int:
    """One expert's three matrices (routed or shared)."""
    z = aw.sizes(shape)
    return 3 * z["d"] * z["f"]


def router_params(shape: dict) -> int:
    z = aw.sizes(shape)
    return z["d"] * z["E"]


def experts_per_token(shape: dict) -> float:
    """Routed experts a token meets here, in expectation."""
    z = aw.sizes(shape)
    return z["k"] * z["held"] / z["E"]


def fixed_params(shape: dict) -> int:
    """Matmul weights every token passes, all layers, the head's slice
    included: everything but the routed experts."""
    z = aw.sizes(shape)
    n_moe = z["n"] - z["n_dense"]
    return (z["n"] * attention_params(shape)
            + z["n_dense"] * dense_mlp_params(shape)
            + n_moe * (router_params(shape) + expert_params(shape))
            + z["d"] * z["V"])


def token_flops(shape: dict, with_head: bool = True) -> float:
    """One token through every layer's matmuls, attention over keys left
    out; routed experts at their expected share."""
    z = aw.sizes(shape)
    n_moe = z["n"] - z["n_dense"]
    params = fixed_params(shape) - (0 if with_head else z["d"] * z["V"]) \
        + n_moe * experts_per_token(shape) * expert_params(shape)
    return 2.0 * params


def keys_read(shape: dict, first: int, n: int) -> int:
    """Keys the tokens at positions ``first .. first + n - 1`` read, all
    layers: position ``p`` reads ``p + 1`` on a full layer and ``min(p +
    1, window)`` on a window layer."""
    z = aw.sizes(shape)

    def upto(m, cap=None):      # sum over p < m of min(p + 1, cap)
        if cap is None or m <= cap:
            return m * (m + 1) // 2
        return cap * (cap + 1) // 2 + (m - cap) * cap

    full = upto(first + n) - upto(first)
    window = upto(first + n, z["W"]) - upto(first, z["W"])
    n_window = sum(t == aw.SLIDING for t in z["types"])
    return n_window * window + (z["n"] - n_window) * full


def per_key_flops(shape: dict) -> float:
    z = aw.sizes(shape)
    return 4.0 * z["Hq"] * z["D"]


def decode_flops(shape: dict, context_len: int) -> float:
    """One decoded token whose context (itself included) is
    ``context_len``."""
    return token_flops(shape) + per_key_flops(shape) * keys_read(
        shape, context_len - 1, 1)


def piece_flops(shape: dict, offset: int, n: int,
                with_head: bool = False) -> float:
    """``n`` prompt tokens fed at absolute ``offset`` (a piece of a
    chunked prefill). The head runs on a prompt's last token only, so a
    piece counts it only ``with_head``."""
    z = aw.sizes(shape)
    return n * token_flops(shape, with_head=False) \
        + per_key_flops(shape) * keys_read(shape, offset, n) \
        + (2.0 * z["d"] * z["V"] if with_head else 0.0)


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """A whole prompt in one program: the piece from 0, and the head."""
    return piece_flops(shape, 0, prompt_len, with_head=True)


def kv_bytes_per_position(shape: dict, itemsize: int = 2) -> int:
    """At-rest bytes one position of one row keeps in ONE layer (K and
    V, as published: no tiling)."""
    z = aw.sizes(shape)
    return 2 * z["K"] * z["D"] * itemsize


def live_kv_bytes(shape: dict, context_len: int, itemsize: int = 2) -> int:
    """K/V bytes a row at ``context_len`` keeps live, all layers."""
    return kv_bytes_per_position(shape, itemsize) * keys_read(
        shape, context_len - 1, 1)


def decode_step_bytes(shape: dict, contexts: Iterable[int],
                      experts_hit: float, weight_itemsize: int = 2,
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step over rows at ``contexts`` must move when
    ``experts_hit`` (layer, expert) pairs took a row."""
    total = weight_itemsize * (fixed_params(shape)
                               + experts_hit * expert_params(shape))
    return float(total) + sum(live_kv_bytes(shape, int(c), kv_itemsize)
                              for c in contexts)


def slice_step_calls(run: dict):
    """The args of the program's ``engine.step.call`` spans inside the
    traced slice (``moe_assignments``, ``moe_experts_hit``,
    ``moe_load_max``, ``moe_experts`` among them), or ``None`` where the
    program kept no such spans; cut as
    ``olmo_hybrid_work.slice_pieces`` cuts the chunk calls."""
    if not run.get("slice_s"):
        return None
    return window_step_calls(run, last_s=run["slice_s"])


def window_step_calls(run: dict, last_s: float = float("inf")):
    """The same over the whole window (or its last ``last_s`` seconds)."""
    ticks = program_spans.window_ticks(run)
    if not ticks:
        return None
    hi = ticks[-1].start + ticks[-1].dur
    return [t.counts["engine.step.call"] for t in ticks
            if t.start >= hi - last_s and "engine.step.call" in t.counts]
