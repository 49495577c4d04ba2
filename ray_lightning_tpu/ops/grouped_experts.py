"""A dropless expert layer for the experts *held here*: route over every
expert the router knows, compute the terms of the experts this chip
holds, sorted and grouped (``models/afmoe.py`` is its user; the
train-only capacity router of ``models/moe.py`` drops tokens and builds
dense ``(N, E, C)`` dispatch tensors, which a served token cannot take).

The share of an expert-parallel group (the ``model-configs`` guide,
section 4): the layer is told ``held`` experts from ``offset`` on. It
takes the top ``k`` of **all** the router's outputs, normalises the
``k`` weights wherever their experts live, and adds only the terms of
its own experts. A token none of whose ``k`` experts live here gets
nothing from this function (its caller adds the shared expert). No token
is dropped, no capacity exists, and nothing stands in for the absent
chips or their exchange.

Two routers (:func:`route`). The sigmoid one (DeepSeek-V3's, which
``afmoe`` shares): ``s = sigmoid(logits)``; the ``k`` experts with the
largest ``s + b`` (``b``: a per-expert bias that balances the load and
enters the *choice* only); weights ``s[e_k]`` without ``b``, divided by
their sum (``route_norm``) and multiplied by ``route_scale``. The softmax
one (DeepSeek-V2's ``group_limited_greedy``, ``models/deepseek_v2.py``):
``s = softmax(logits)`` over every expert; the experts are ``n_group``
groups of consecutive numbers, each scored by its best ``s``; the
``topk_group`` best groups are kept and the ``k`` best experts among them
taken; weights ``s[e_k]`` times ``routed_scaling_factor`` where they are
not normalised. A tie goes to the lower number, of a group and of an
expert (``jax.lax.top_k``'s order).

The product: the ``N k`` assignments are ordered by local expert (those
that live elsewhere, and the pad tokens of a ragged piece, sort behind
every group and belong to none), and each projection is **one ragged
product** over the groups — ``jax.lax.ragged_dot``, which the TPU
compiler runs tile by tile over the groups' rows; off the TPU it is
XLA's masked expansion, which the CPU tests run at a small size.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def route(logits, bias, top_k: int, normalise: bool = True,
          scale: float = 1.0, scoring: str = "sigmoid", n_group: int = 1,
          topk_group: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Router logits ``(N, E)`` float32 over **all** experts and the
    choice bias ``(E,)`` (``None``: none) -> the chosen experts ``(N,
    k)`` int32 and their weights ``(N, k)`` float32: ``scoring`` scores
    (``"sigmoid"`` or ``"softmax"``), the top ``k`` of ``s + b`` — among
    the ``topk_group`` best of ``n_group`` groups where ``n_group > 1``
    (:func:`group_limit`) —, the weights ``s`` alone, divided by their
    sum + 1e-20 (``route_norm``), times ``route_scale``."""
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        choice = group_limit(choice, n_group, topk_group)
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts.astype(jnp.int32), weights * scale


def group_limit(choice, n_group: int, topk_group: int):
    """``choice (N, E)`` with every expert outside the ``topk_group``
    best of ``n_group`` groups of ``E / n_group`` consecutive experts set
    to ``-inf``; a group is scored by its best expert (DeepSeek-V2's
    ``group_limited_greedy``)."""
    N, E = choice.shape
    if E % n_group:
        raise ValueError(f"{E} experts do not make {n_group} groups")
    groups = choice.reshape(N, n_group, E // n_group)
    _, kept = jax.lax.top_k(jnp.max(groups, axis=-1), topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                   # (N, n_group)
    return jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(N, E)


def group(experts, held: int, offset: int = 0, valid=None):
    """The ``N k`` assignments ordered by local expert.

    Returns ``order (N k,)`` — assignment ``order[i]`` (token
    ``order[i] // k``) is row ``i`` of the grouped product —, ``sizes
    (held,)`` int32 — the rows of each held expert, in order — and
    ``here (N, k)`` bool — does the assignment live here. ``valid (N,)``
    marks the tokens that count (``None`` = all): an assignment of a pad
    token belongs to no group."""
    local = experts - offset
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    flat = jnp.where(here, local, held).reshape(-1)    # elsewhere: last
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
    return order, sizes, here


def grouped_swiglu(x, order, sizes, w_gate_up, w_down, top_k: int, dtype):
    """``E_e(x) = W_down_e(silu(W_gate_e x) * (W_up_e x))`` for every
    grouped row. ``x (N, d)`` float32, ``w_gate_up (held, d, 2 f)``
    (gate then up), ``w_down (held, f, d)`` -> ``(N k, d)`` float32 in
    the *grouped* order (rows past the groups hold nothing to be read)."""
    f = w_down.shape[1]
    rows = jnp.take(x.astype(dtype), order // top_k, axis=0)
    gu = jax.lax.ragged_dot(rows, w_gate_up.astype(dtype), sizes,
                            preferred_element_type=jnp.float32)
    h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    return jax.lax.ragged_dot(h.astype(dtype), w_down.astype(dtype), sizes,
                              preferred_element_type=jnp.float32)


def combine(grouped, order, weights, here):
    """Grouped rows back to their tokens: ``y_n = sum_k w_nk E_{e_nk}(x_n)``
    over the assignments that live here. ``grouped (N k, d)`` ->
    ``(N, d)`` float32."""
    N, k = weights.shape
    back = jnp.argsort(order).astype(jnp.int32)     # the inverse permutation
    terms = jnp.take(grouped, back, axis=0).reshape(N, k, -1)
    # a row past the groups holds whatever the product left there
    return jnp.sum(jnp.where(here[..., None], weights[..., None] * terms,
                             0.0), axis=1)


def held_experts(x, logits, bias, w_gate_up, w_down, *, top_k: int,
                 offset: int = 0, normalise: bool = True,
                 scale: float = 1.0, valid: Optional[jax.Array] = None,
                 dtype=jnp.bfloat16, scoring: str = "sigmoid",
                 n_group: int = 1, topk_group: int = 1):
    """The routed part of the layer that the held experts give, and the
    load: ``(y (N, d) float32, sizes (held,) int32)``. Scopes
    ``moe/dispatch`` (scores, top-k, sort, gather), ``moe/experts`` (the
    two ragged products), ``moe/combine``."""
    held = w_down.shape[0]
    with jax.named_scope("moe/dispatch"):
        # the sigmoid router with no group limit is called as it always
        # was: its program, and whatever stands in for it, stay the same
        limits = {} if (scoring, n_group, topk_group) == ("sigmoid", 1, 1) \
            else dict(scoring=scoring, n_group=n_group,
                      topk_group=topk_group)
        experts, weights = route(logits, bias, top_k, normalise, scale,
                                 **limits)
        order, sizes, here = group(experts, held, offset, valid)
    with jax.named_scope("moe/experts"):
        grouped = grouped_swiglu(x, order, sizes, w_gate_up, w_down, top_k,
                                 dtype)
    with jax.named_scope("moe/combine"):
        return combine(grouped, order, weights, here), sizes
