"""The plain reference for Trinity / AFMoE: the full forward of one
sequence in ``jax.numpy`` and float32 — no cache, no batch, **no ring**
(a banded causal mask over every position), **no grouped heads** (each
key-value head is repeated for its query heads), **no grouping** of the
experts (every held expert is applied to every token, under the mask of
its assignments), float32 matmuls at ``Precision.HIGHEST``. Nothing here
imports the program under test; the weights are
``afmoe_weights.make_canonical``'s (bf16-rounded numbers, read as
float32) and the layer equations are written out below, after the
configuration's keys.

What ``config.json`` leaves open is listed under ``assumed`` in the
configuration file (the family's published ``afmoe`` modelling code):
the embedding times ``sqrt(d)``; sandwich norms; QK-norm per head; rotary
positions on the window layers only (half-split pairs, all ``D`` dims);
the gate ``sigmoid(W_g x)`` on the attention values before ``W_o``;
sigmoid router scores, the choice by ``s + b``, the weights by ``s``.

**The share.** The reference is given the same share as the program:
the router scores all ``E`` experts, the top ``k`` are normalised
wherever they live, and only the terms of the experts held (the
configuration's ``num_experts`` from ``deployment.expert_offset``) and
the shared expert are added. ``tests/test_afmoe.py`` adds the eight
shares up against this same reference uncut.

**Blocked to fit.** One jitted function a stage (attention; the dense
MLP; the router and the shared expert; a block of ``EXPERT_BLOCK`` held
experts) is called layer by layer and expert block by expert block from
Python: a stage's weights are widened to float32 only while it runs —
906 MB for a block of 8 experts at the published widths — so the cut
model fits beside its 8.64 GB of bf16 weights. Attention runs a block of
``QUERY_BLOCK`` queries at a time.

``mode`` is ``reference.py``'s: the precision of every matmul operand
(``"f32"`` the reference, ``"bf16"`` a witness, ``"fp8"`` the control);
the router's product keeps float32 operands in every mode but under
``router_dtype`` (the bf16-router witness: the one product the
configuration states in float32). The function returned keeps
``last_margins`` ``(expert layers, rows)``: for the rows of its last
call and each expert layer, how far the selection score ``s + b`` of a
*held* expert lies from the boundary between the last expert taken and
the first left out — the smallest move that would take one of them
across it, which is what a near tie is counted from
(``families/afmoe.py``). Only held experts count: a swap between two
experts that live on other chips moves nothing this chip adds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import afmoe_weights as aw
from benchmark.reference import HIGHEST
from benchmark.sambay_reference import _held_in, _mm, _round_operand

F32 = jnp.float32
QUERY_BLOCK = 512
EXPERT_BLOCK = 8
PAD_MULTIPLE = 1024


def _mmw(x, w, mode):
    return _mm(x, w.astype(F32), mode)


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (T, H, D): the pair (i, i + D / 2) turns by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=F32) / x.shape[-1])
    angle = pos.astype(F32)[:, None, None] * inv
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def _attention(x, w, *, windowed, z, mode):
    """x (T, d) -> x + RMSNorm(Attn(RMSNorm(x)))."""
    T = x.shape[0]
    Hq, K, D, W = z["Hq"], z["K"], z["D"], z["W"]
    pos = jnp.arange(T)
    h = _rms_norm(x, w["attn_in_g"], z["eps"])
    qkvg = _mmw(h, w["w_qkvg"], mode)
    q = _rms_norm(qkvg[:, :Hq * D].reshape(T, Hq, D), w["q_norm_g"],
                  z["eps"])
    k = _rms_norm(qkvg[:, Hq * D:(Hq + K) * D].reshape(T, K, D),
                  w["k_norm_g"], z["eps"])
    v = qkvg[:, (Hq + K) * D:(Hq + 2 * K) * D].reshape(T, K, D)
    gate = qkvg[:, (Hq + 2 * K) * D:]
    if windowed:
        q, k = _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"])
    # query head h reads key-value head h // (Hq / K)
    k = _round_operand(jnp.repeat(k, Hq // K, axis=1), mode)
    v = _round_operand(jnp.repeat(v, Hq // K, axis=1), mode)
    blk = min(QUERY_BLOCK, T)

    def block(q_b, first):          # q_b (blk, Hq, D) at first ..
        s = jnp.einsum("thd,shd->hts", _round_operand(q_b, mode), k,
                       precision=HIGHEST) / np.sqrt(D)
        t = (first + jnp.arange(blk))[:, None]
        allowed = pos[None, :] <= t
        if windowed:
            allowed = allowed & (t - pos[None, :] < W)
        p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", _round_operand(p, mode), v,
                          precision=HIGHEST)

    out = jax.lax.map(lambda xs: block(*xs),
                      (q.reshape(T // blk, blk, Hq, D),
                       jnp.arange(0, T, blk)))
    out = jax.nn.sigmoid(gate) * out.reshape(T, Hq * D)
    return x + _rms_norm(_mmw(out, w["w_o"], mode), w["attn_out_g"],
                         z["eps"])


def _dense_mlp(x, w, *, z, mode):
    """x (T, d) -> x + RMSNorm(SwiGLU(RMSNorm(x)))."""
    h = _rms_norm(x, w["mlp_in_g"], z["eps"])
    gu = _mmw(h, w["w_gate_up"], mode)
    y = _mmw(jax.nn.silu(gu[:, :z["F"]]) * gu[:, z["F"]:], w["w_down"],
             mode)
    return x + _rms_norm(y, w["mlp_out_g"], z["eps"])


def _route(x, w, *, z, mode, router_dtype):
    """x (T, d) -> the normed input, the chosen experts (T, k), their
    weights, a held expert's room to the boundary of the top ``k`` (in
    ``s + b``), and the shared expert's term."""
    k = z["k"]
    h = _rms_norm(x, w["mlp_in_g"], z["eps"])
    logit = jnp.matmul(
        _held_in(h, router_dtype),
        _held_in(w["w_router"].astype(F32), router_dtype),
        precision=HIGHEST)
    s = jax.nn.sigmoid(logit)
    pick = s + w["router_bias"]
    edge, experts = jax.lax.top_k(pick, k + 1)
    experts = experts[:, :k]
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if z["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    weights = weights * z["route_scale"]
    # the one boundary a rounding can move lies between the last expert
    # taken and the first left out. How far from it is the nearest
    # expert *held here*: one taken above the first left out, one left
    # out below the last taken
    last_in, first_out = edge[:, k - 1:k], edge[:, k:]
    room = jnp.where(pick >= last_in, pick - first_out, last_in - pick)
    local = jnp.arange(pick.shape[-1]) - z["offset"]
    margin = jnp.min(jnp.where((local >= 0) & (local < z["held"]), room,
                               jnp.inf), axis=-1)
    gu = _mmw(h, w["ws_gate_up"], mode)
    shared = _mmw(jax.nn.silu(gu[:, :z["f"]]) * gu[:, z["f"]:],
                  w["ws_down"], mode)
    return h, experts, weights, margin, shared


def _expert_block(h, experts, weights, w_gate_up, w_down, first, *, z,
                  mode):
    """The terms of the held experts ``first .. first + len - 1`` (global
    numbers): each applied to every token, under its mask."""
    f = z["f"]
    y = jnp.zeros(h.shape, F32)
    for i in range(w_gate_up.shape[0]):
        gu = _mmw(h, w_gate_up[i], mode)
        out = _mmw(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_down[i], mode)
        mine = jnp.sum(jnp.where(experts == first + i, weights, 0.0), -1)
        y = y + mine[:, None] * out
    return y


def make_logits_fn(shape: dict, mode: str = "f32", router_dtype=F32,
                   pad_multiple: int = PAD_MULTIPLE):
    """``f(params, tokens (T,), rows) -> (len(rows), V)`` float32
    next-token logits of one sequence at the positions ``rows``."""
    z = aw.sizes(shape)
    attention = {
        windowed: jax.jit(functools.partial(
            _attention, windowed=windowed, z=z, mode=mode))
        for windowed in (True, False)}
    dense_mlp = jax.jit(functools.partial(_dense_mlp, z=z, mode=mode))
    route = jax.jit(functools.partial(_route, z=z, mode=mode,
                                      router_dtype=router_dtype))
    expert_block = jax.jit(functools.partial(_expert_block, z=z, mode=mode))

    @jax.jit
    def close(x, y, g):
        return x + _rms_norm(y, g, z["eps"])

    @jax.jit
    def head(x, rows, normf_g, w_head):
        return _mmw(_rms_norm(x[rows], normf_g, z["eps"]), w_head, mode)

    def round_up(n, multiple):
        return -(-n // multiple) * multiple

    def logits(params, tokens, rows):
        # every layer is causal and routes a token by itself, so a zero
        # tail changes no row asked for: lengths are rounded up so that
        # requests share compiled programs
        n_rows = len(rows)
        pad_to = round_up(len(tokens), pad_multiple)
        if pad_to > QUERY_BLOCK:    # whole blocks of queries
            pad_to = round_up(pad_to, QUERY_BLOCK)
        tokens = np.pad(np.asarray(tokens, np.int32),
                        (0, pad_to - len(tokens)))
        rows = np.asarray(rows, np.int32)
        asked = jnp.asarray(np.pad(rows, (0, round_up(n_rows, 64) - n_rows),
                                   mode="edge"))
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        if z["mup"]:
            x = x * np.float32(np.sqrt(z["d"]))
        margins = []
        for l, w in enumerate(params["layers"]):
            x = attention[z["types"][l] == aw.SLIDING](x, w)
            if l < z["n_dense"]:
                x = dense_mlp(x, w)
                continue
            h, experts, weights, margin, y = route(x, w)
            for b in range(0, z["held"], EXPERT_BLOCK):
                y = y + expert_block(
                    h, experts, weights, w["w_gate_up"][b:b + EXPERT_BLOCK],
                    w["w_down"][b:b + EXPERT_BLOCK], z["offset"] + b)
            x = close(x, y, w["mlp_out_g"])
            margins.append(margin[asked])
        logits.last_margins = np.asarray(jnp.stack(margins))[:, :n_rows] \
            if margins else np.full((0, n_rows), np.inf)
        return head(x, asked, params["normf_g"], params["head"])[:n_rows]

    logits.last_margins = None
    return logits
