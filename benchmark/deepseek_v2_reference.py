"""The plain reference for DeepSeek-V2: the full forward of one sequence
in ``jax.numpy`` and float32 — no cache, no batch, **no absorption**
(multi-head latent attention in its published, *expanded* form: every
head's ``k_nope`` and ``v`` made from the latents, every position's
query against every earlier position's key), **no grouping** of the
experts (every held expert is applied to every token, under the mask of
its assignments), float32 matmuls at ``Precision.HIGHEST``. Nothing here
imports the program under test; the weights are
``deepseek_v2_weights.make_canonical``'s (bf16-rounded numbers, read as
float32) and the layer equations are written out below, after the
configuration's keys, the DeepSeek-V2 paper (arXiv:2405.04434) and the
family's published ``deepseek_v2`` modelling code::

    c_q = RMSNorm(h W_dq);  [q_nope | q_pe] = c_q W_uq          a head
    [c | k_pe] = h W_dkv;   c = RMSNorm(c)
    k_nope = c W_uk,  v = c W_uv                                a head
    q_pe, k_pe rotated (YaRN, the pair (2 i, 2 i + 1))
    s = (q_nope . k_nope + q_pe . k_pe) * scale,  causal softmax
    x <- x + W_o concat_h(sum_j p v_j)
    x <- x + FFN(RMSNorm(x))

The score is the published one's ``concat(q_nope, q_pe) . concat(k_nope,
k_pe)`` written as its two sums (no ``(T, H, 192)`` key is built).
``FFN`` is the dense SwiGLU on the first ``first_k_dense_replace``
layers; after them the expert layer: router logits over all ``E``
experts, softmax, each of ``n_group`` groups scored by its best expert,
the ``topk_group`` best groups kept, the top ``k`` experts among them,
weights ``s * routed_scaling_factor``, plus the shared experts.

**The share.** The reference is given the same share as the program:
the router scores all ``E`` experts and chooses among them wherever they
live, and only the terms of the experts held (the configuration's
``n_routed_experts`` from ``deployment.expert_offset``) and the shared
experts are added. ``tests/test_deepseek_v2.py`` adds the eight shares up
against this same reference uncut.

**Blocked to fit.** One jitted function a stage (attention; the dense
MLP; the router and the shared experts; a block of ``EXPERT_BLOCK`` held
experts) is called layer by layer and expert block by expert block from
Python, so only one stage's weights are ever widened to float32; the
attention runs a block of ``QUERY_BLOCK`` queries at a time.

``mode`` is ``reference.py``'s: the precision of every matmul operand
(``"f32"`` the reference, ``"bf16"`` a witness, ``"fp8"`` the control);
the router's product keeps float32 operands in every mode. The function
returned keeps ``last_margins`` ``(expert layers, rows)``: for the rows
of its last call and each expert layer, how far (in router logits, which
order the softmax scores and the group scores as the scores do) the
choice of a *held* expert lies from flipping — the room of a held expert
to the boundary of the top ``k`` among the groups kept, and the room
between the last group kept and the first left out wherever swapping
those two groups would change the held experts taken. That is what a
near tie is counted from (``families/deepseek_v2.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import deepseek_v2_weights as dw
from benchmark.reference import HIGHEST
from benchmark.sambay_reference import _mm, _round_operand

F32 = jnp.float32
QUERY_BLOCK = 64
EXPERT_BLOCK = 10
PAD_MULTIPLE = 2048


def _mmw(x, w, mode):
    return _mm(x, w.astype(F32), mode)


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def inv_freq(z) -> np.ndarray:
    """YaRN's frequencies of the ``R`` rotary dims (the published
    ``DeepseekV2YarnRotaryEmbedding``; its cos / sin factor is
    ``mscale / mscale_all_dim`` = 1 here)."""
    R, base = z["R"], z["theta"]
    extra = base ** (-np.arange(0, R, 2, dtype=np.float64) / R)
    if z["factor"] <= 1:
        return extra.astype(np.float32)

    def dim_of(rotations):
        return R * math.log(z["original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(z["beta_fast"])), 0)
    high = min(math.ceil(dim_of(z["beta_slow"])), R - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(R // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp          # the published inv_freq_mask
    return (extra / z["factor"] * (1 - mask) + extra * mask).astype(
        np.float32)


def softmax_scale(z) -> float:
    m = 0.1 * z["mscale_all_dim"] * math.log(z["factor"]) + 1.0 \
        if z["factor"] > 1 else 1.0
    return (z["Dn"] + z["R"]) ** -0.5 * m * m


def _rope(x, pos, freq):
    """x (T, ..., R): the published order — the dims de-interleaved (the
    pair (2 i, 2 i + 1) to (i, i + R / 2)), then rotate-half."""
    angle = pos.astype(F32)[:, None] * freq
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(x, w, *, z, mode):
    """x (T, d) -> x + MLA(RMSNorm(x)), expanded."""
    T = x.shape[0]
    H, Dn, R, C, Dv = z["H"], z["Dn"], z["R"], z["C"], z["Dv"]
    pos = jnp.arange(T)
    freq = jnp.asarray(inv_freq(z))
    h = _rms_norm(x, w["attn_norm_g"], z["eps"])
    c_q = _rms_norm(_mmw(h, w["w_dq"], mode), w["q_norm_g"], z["eps"])
    q = _mmw(c_q, w["w_uq"], mode).reshape(T, H, Dn + R)
    q_nope, q_pe = q[..., :Dn], _rope(q[..., Dn:], pos, freq)
    ckr = _mmw(h, w["w_dkv"], mode)
    c = _rms_norm(ckr[:, :C], w["kv_norm_g"], z["eps"])
    k_pe = _rope(ckr[:, C:], pos, freq)                     # (T, R)
    # kv_b_proj: a head's key columns, then its value columns
    w_uk = jnp.transpose(w["w_uk"].astype(F32), (2, 0, 1)).reshape(C, H * Dn)
    w_uv = jnp.transpose(w["w_uv"].astype(F32), (1, 0, 2)).reshape(C, H * Dv)
    k_nope = _round_operand(_mm(c, w_uk, mode).reshape(T, H, Dn), mode)
    v = _round_operand(_mm(c, w_uv, mode).reshape(T, H, Dv), mode)
    k_pe = _round_operand(k_pe, mode)
    scale = softmax_scale(z)
    blk = min(QUERY_BLOCK, T)

    def block(qn, qp, first):       # queries first .. first + blk - 1
        s = (jnp.einsum("thd,shd->hts", _round_operand(qn, mode), k_nope,
                        precision=HIGHEST)
             + jnp.einsum("thr,sr->hts", _round_operand(qp, mode), k_pe,
                          precision=HIGHEST)) * scale
        allowed = pos[None, :] <= (first + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,she->the", _round_operand(p, mode), v,
                          precision=HIGHEST)

    out = jax.lax.map(lambda xs: block(*xs),
                      (q_nope.reshape(T // blk, blk, H, Dn),
                       q_pe.reshape(T // blk, blk, H, R),
                       jnp.arange(0, T, blk)))
    return x + _mmw(out.reshape(T, H * Dv), w["w_o"], mode)


def _dense_mlp(x, w, *, z, mode):
    """x (T, d) -> x + SwiGLU(RMSNorm(x))."""
    h = _rms_norm(x, w["mlp_norm_g"], z["eps"])
    gu = _mmw(h, w["w_gate_up"], mode)
    return x + _mmw(jax.nn.silu(gu[:, :z["F"]]) * gu[:, z["F"]:],
                    w["w_down"], mode)


def _top(values, k):
    """The ``k`` largest of each row, ties to the lower index."""
    return jax.lax.top_k(values, k)


def choose(logit, z):
    """Router logits (T, E) -> the chosen experts (T, k) and their
    weights: softmax, groups scored by their best expert, the
    ``topk_group`` best kept (ties to the lower group), the top ``k``
    experts among them (ties to the lower expert), ``s *
    routed_scaling_factor`` (normalised first where ``norm_topk_prob``)."""
    T, E = logit.shape
    G, kg, k = z["n_group"], z["topk_group"], z["k"]
    s = jax.nn.softmax(logit, axis=-1)
    best = jnp.max(s.reshape(T, G, E // G), axis=-1)
    _, groups = _top(best, kg)
    keep = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], groups].set(
        True)
    allowed = jnp.repeat(keep, E // G, axis=1)
    _, experts = _top(jnp.where(allowed, s, -1.0), k)
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if z["norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts, weights * z["scale"], groups, allowed


def _held_taken(logit, allowed, z):
    """Which held experts the top ``k`` of ``logit`` among ``allowed``
    takes: (T, held) bool."""
    k, held, off = z["k"], z["held"], z["offset"]
    _, experts = _top(jnp.where(allowed, logit, -jnp.inf), k)
    local = experts - off
    mine = (local >= 0) & (local < held)
    return jnp.any(mine[:, :, None]
                   & (local[:, :, None] == jnp.arange(held)), axis=1)


def margins(logit, z):
    """How far the held experts' choice lies from a flip, in logits (the
    module docstring); (T,)."""
    T, E = logit.shape
    G, kg, k = z["n_group"], z["topk_group"], z["k"]
    held, off = z["held"], z["offset"]
    best = jnp.max(logit.reshape(T, G, E // G), axis=-1)
    g_edge, g_order = _top(best, G)
    rows = jnp.arange(T)[:, None]

    def allowed_of(groups):
        keep = jnp.zeros((T, G), bool).at[rows, groups].set(True)
        return jnp.repeat(keep, E // G, axis=1)

    allowed = allowed_of(g_order[:, :kg])
    pick = jnp.where(allowed, logit, -jnp.inf)
    edge, _ = _top(pick, k + 1)
    last_in, first_out = edge[:, k - 1:k], edge[:, k:]
    room = jnp.where(pick >= last_in, pick - first_out, last_in - pick)
    local = jnp.arange(E) - off
    is_held = (local >= 0) & (local < held)
    expert_margin = jnp.min(jnp.where(is_held & allowed, room, jnp.inf),
                            axis=-1)
    if kg == G:
        return expert_margin
    # the last group kept swapped for the first left out
    swapped = jnp.concatenate([g_order[:, :kg - 1], g_order[:, kg:kg + 1]],
                              axis=1)
    changes = jnp.any(_held_taken(logit, allowed, z)
                      != _held_taken(logit, allowed_of(swapped), z), axis=1)
    group_room = g_edge[:, kg - 1] - g_edge[:, kg]
    return jnp.minimum(expert_margin,
                       jnp.where(changes, group_room, jnp.inf))


def _route(x, w, *, z, mode):
    """x (T, d) -> the normed input, the chosen experts (T, k), their
    weights, the held choice's room to a flip, and the shared experts'
    term."""
    h = _rms_norm(x, w["mlp_norm_g"], z["eps"])
    logit = jnp.matmul(h, w["w_router"].astype(F32), precision=HIGHEST)
    experts, weights, _, _ = choose(logit, z)
    gu = _mmw(h, w["ws_gate_up"], mode)
    shared = _mmw(jax.nn.silu(gu[:, :z["fs"]]) * gu[:, z["fs"]:],
                  w["ws_down"], mode)
    return h, experts, weights, margins(logit, z), shared


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


def make_logits_fn(shape: dict, mode: str = "f32",
                   pad_multiple: int = PAD_MULTIPLE):
    """``f(params, tokens (T,), rows) -> (len(rows), V)`` float32
    next-token logits of one sequence at the positions ``rows``."""
    z = dw.sizes(shape)
    attention = jax.jit(functools.partial(_attention, z=z, mode=mode))
    dense_mlp = jax.jit(functools.partial(_dense_mlp, z=z, mode=mode))
    route = jax.jit(functools.partial(_route, z=z, mode=mode))
    expert_block = jax.jit(functools.partial(_expert_block, z=z, mode=mode))

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
        # (a short sequence — a rehearsal's — pads to whole query blocks)
        multiple = pad_multiple if len(tokens) > pad_multiple // 8 \
            else QUERY_BLOCK
        pad_to = round_up(round_up(len(tokens), multiple), QUERY_BLOCK)
        tokens = np.pad(np.asarray(tokens, np.int32),
                        (0, pad_to - len(tokens)))
        rows = np.asarray(rows, np.int32)
        asked = jnp.asarray(np.pad(rows, (0, round_up(n_rows, 64) - n_rows),
                                   mode="edge"))
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        found = []
        for l, w in enumerate(params["layers"]):
            x = attention(x, w)
            if l < z["n_dense"]:
                x = dense_mlp(x, w)
                continue
            h, experts, weights, margin, y = route(x, w)
            for b in range(0, z["held"], EXPERT_BLOCK):
                y = y + expert_block(
                    h, experts, weights, w["w_gate_up"][b:b + EXPERT_BLOCK],
                    w["w_down"][b:b + EXPERT_BLOCK], z["offset"] + b)
            x = x + y
            found.append(margin[asked])
        logits.last_margins = np.asarray(jnp.stack(found))[:, :n_rows] \
            if found else np.full((0, n_rows), np.inf)
        return head(x, asked, params["normf_g"], params["head"])[:n_rows]

    logits.last_margins = None
    return logits
