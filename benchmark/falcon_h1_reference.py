"""The plain reference for Falcon-H1: the full forward of one sequence in
``jax.numpy`` and float32 — no cache, no batch, the Mamba-2 mixer as the
**token-by-token recurrence** under ``lax.scan`` (not the chunkwise SSD
the program runs), float32 matmuls at ``Precision.HIGHEST``. Nothing
here imports the program under test or ``ops/ssd.py``; the weights are
``falcon_h1_weights.make_canonical``'s (bf16-rounded numbers, read as
float32) and the layer equations are written out below, after the
configuration's keys and the family's published ``falcon_h1`` modelling
code, as the configuration file records them (``assumed``)::

    x_0 = E[t] * embedding_multiplier
    u = RMSNorm_in(x)
    x <- x + Attn(u * attention_in) * attention_out
           + SSM(u * ssm_in) * ssm_out
    x <- x + W_down(silu(W_gate h * m_0) * W_up h) * m_1,  h = RMSNorm_ff(x)
    logits = W_head RMSNorm_f(x) * lm_head_multiplier

Attention repeats each key-value head for its query heads (no grouped
read) and runs a block of ``QUERY_BLOCK`` queries at a time. The SSM
takes head ``h``'s B and C from group ``h // (H / G)`` by repeating each
group for its heads.

One jitted function a layer is called layer by layer from Python: a
layer's weights are widened to float32 only while it runs, so the cut
model fits beside its bf16 weights; the head is read ``VOCAB_BLOCKS``
column blocks at a time for the same reason (widened whole it is 5.3 GB).

``mode`` is ``reference.py``'s: the precision of every matmul operand
(``"f32"`` the reference, ``"bf16"`` a witness, ``"fp8"`` the control).
``state_dtype`` is the dtype the SSM state is held in between positions:
float32 in the reference, ``bfloat16`` for the bf16-state witness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import falcon_h1_weights as fw
from benchmark.reference import HIGHEST
from benchmark.sambay_reference import _held_in, _mm, _round_operand

F32 = jnp.float32
QUERY_BLOCK = 512
#: column blocks of the head (at most; a count that divides the vocabulary)
VOCAB_BLOCKS = 16


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


def _attention(u, w, z, mode):
    """u (T, d), already times ``attention_in`` -> (T, Hq D)."""
    T = u.shape[0]
    Hq, K, D = z["Hq"], z["Hkv"], z["D"]
    pos = jnp.arange(T)
    qkv = _mmw(u, w["w_qkv"], mode)
    q = _rope(qkv[:, :Hq * D].reshape(T, Hq, D), pos, z["theta"])
    k = _rope(qkv[:, Hq * D:(Hq + K) * D].reshape(T, K, D) * z["key_m"],
              pos, z["theta"])
    v = qkv[:, (Hq + K) * D:].reshape(T, K, D)
    # query head h reads key-value head h // (Hq / K)
    k = _round_operand(jnp.repeat(k, Hq // K, axis=1), mode)
    v = _round_operand(jnp.repeat(v, Hq // K, axis=1), mode)
    blk = min(QUERY_BLOCK, T)

    def block(q_b, first):          # q_b (blk, Hq, D) at first ..
        s = jnp.einsum("thd,shd->hts", _round_operand(q_b, mode), k,
                       precision=HIGHEST) / np.sqrt(D)
        allowed = pos[None, :] <= (first + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", _round_operand(p, mode), v,
                          precision=HIGHEST)

    out = jax.lax.map(lambda xs: block(*xs),
                      (q.reshape(T // blk, blk, Hq, D),
                       jnp.arange(0, T, blk)))
    return out.reshape(T, Hq * D)


def _mamba2(u, w, z, mode, state_dtype):
    """u (T, d), already times ``ssm_in`` -> (T, di): Mamba-2, one
    position at a time."""
    T = u.shape[0]
    H, P, N, G, K, di = z["H"], z["P"], z["N"], z["G"], z["K"], z["di"]
    mu = jnp.concatenate([jnp.full((n,), m, F32) for (_, n), m
                          in zip(fw.in_proj_parts(z), z["mu"])])
    zxbcdt = _mmw(u, w["w_in"], mode) * mu
    gate, xbc = zxbcdt[:, :di], zxbcdt[:, di:di + z["cw"]]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][i] * padded[i:i + T]
                                        for i in range(K)))
    x = xbc[:, :di].reshape(T, H, P)
    # head h reads group h // (H / G)
    b = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(zxbcdt[:, di + z["cw"]:] + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def step(s, xs):                # s (H, P, N)
        x_t, b_t, c_t, dt_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        s = _held_in(s, state_dtype)
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, b, c, dt))
    y = (y + w["D"][:, None] * x).reshape(T, di) * jax.nn.silu(gate)
    y = _rms_norm(y.reshape(T, G, di // G), 1.0, z["eps"]).reshape(T, di)
    return y * w["ssm_norm_g"]


def _layer(x, w, *, z, mode, state_dtype):
    """One whole layer: the two mixers on one normed input, summed into
    one residual add, then the MLP."""
    u = _rms_norm(x, w["in_norm_g"], z["eps"])
    a = _mmw(_attention(u * z["attn_in"], w, z, mode), w["w_o"], mode)
    m = _mmw(_mamba2(u * z["ssm_in"], w, z, mode, state_dtype),
             w["w_out"], mode)
    x = x + a * z["attn_out"] + m * z["ssm_out"]
    h = _rms_norm(x, w["ff_norm_g"], z["eps"])
    gu = _mmw(h, w["w_gate_up"], mode)
    g, up = gu[:, :z["ff"]] * z["mlp"][0], gu[:, z["ff"]:]
    return x + _mmw(jax.nn.silu(g) * up, w["w_down"], mode) * z["mlp"][1]


def make_logits_fn(shape: dict, mode: str = "f32", state_dtype=F32,
                   pad_multiple: int = QUERY_BLOCK):
    """``f(params, tokens (T,), rows) -> (len(rows), V)`` float32
    next-token logits of one sequence at the positions ``rows``."""
    z = fw.sizes(shape)
    layer = jax.jit(functools.partial(_layer, z=z, mode=mode,
                                      state_dtype=state_dtype))
    V = z["V"]
    nb = next(b for b in (VOCAB_BLOCKS, 8, 4, 2, 1) if V % b == 0)

    @jax.jit
    def head(x, rows, normf_g, w_head):
        h = _rms_norm(x[rows], normf_g, z["eps"])

        def block(i, out):
            cols = jax.lax.dynamic_slice_in_dim(w_head, i * (V // nb),
                                                V // nb, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, _mmw(h, cols, mode), i * (V // nb), axis=1)

        out = jax.lax.fori_loop(0, nb, block,
                                jnp.zeros((h.shape[0], V), F32))
        return out * z["head_m"]

    def round_up(n, multiple):
        return -(-n // multiple) * multiple

    def logits(params, tokens, rows):
        # every layer is causal, so a zero tail changes no row asked for:
        # lengths are rounded up so that requests share compiled programs
        n_rows = len(rows)
        tokens = np.pad(np.asarray(tokens, np.int32),
                        (0, round_up(len(tokens), pad_multiple)
                         - len(tokens)))
        rows = np.pad(np.asarray(rows, np.int32),
                      (0, round_up(n_rows, 64) - n_rows), mode="edge")
        x = params["embed"][jnp.asarray(tokens)].astype(F32) * z["emb"]
        for w in params["layers"]:
            x = layer(x, w)
        return head(x, jnp.asarray(rows), params["normf_g"],
                    params["head"])[:n_rows]

    return logits
