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

The router is the sigmoid one (DeepSeek-V3's, which ``afmoe`` shares):
``s = sigmoid(logits)``; the ``k`` experts with the largest ``s + b``
(``b``: a per-expert bias that balances the load and enters the *choice*
only); weights ``s[e_k]`` without ``b``, divided by their sum
(``route_norm``) and multiplied by ``route_scale``.

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
          scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """Router logits ``(N, E)`` float32 over **all** experts and the
    choice bias ``(E,)`` -> the chosen experts ``(N, k)`` int32 and
    their weights ``(N, k)`` float32: sigmoid scores, the top ``k`` of
    ``s + b``, the weights ``s`` alone, divided by their sum + 1e-20
    (``route_norm``), times ``route_scale``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts.astype(jnp.int32), weights * scale


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
                 dtype=jnp.bfloat16):
    """The routed part of the layer that the held experts give, and the
    load: ``(y (N, d) float32, sizes (held,) int32)``. Scopes
    ``moe/dispatch`` (scores, top-k, sort, gather), ``moe/experts`` (the
    two ragged products), ``moe/combine``."""
    held = w_down.shape[0]
    with jax.named_scope("moe/dispatch"):
        experts, weights = route(logits, bias, top_k, normalise, scale)
        order, sizes, here = group(experts, held, offset, valid)
    with jax.named_scope("moe/experts"):
        grouped = grouped_swiglu(x, order, sizes, w_gate_up, w_down, top_k,
                                 dtype)
    with jax.named_scope("moe/combine"):
        return combine(grouped, order, weights, here), sizes
