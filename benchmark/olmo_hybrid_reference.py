"""The plain reference for Olmo-Hybrid: the full forward of one sequence
in ``jax.numpy`` and float32 — no cache, no batch, the delta rule as the
**token-by-token recurrence** under ``lax.scan`` (not the chunkwise form
the program runs), float32 matmuls at ``Precision.HIGHEST``. Nothing
here imports the program under test; the weights are
``olmo_hybrid_weights.make_canonical``'s (bf16-rounded numbers, read as
float32) and the layer equations are written out below, after the
configuration's keys (Gated DeltaNet, arXiv:2412.06464, as in
flash-linear-attention's ``GatedDeltaNet``) as the configuration file
records them.

Departures from the published description, each forced by what
``config.json`` leaves open and listed under ``assumed`` in the
configuration file: the norm sits on each sub-layer's output (the Olmo
2 / 3 convention); no rotary embedding (``rope_theta`` is null); q, k
and v each go through their own depthwise causal convolution and
``silu``; the gated output norm is ``RMSNorm_{d_v}(o) * silu(W_g x)``.

One jitted function *per layer kind* is called layer by layer from
Python: a layer's weights are widened to float32 only while it runs, so
the cut model fits beside its bf16 weights. Attention runs a block of
``QUERY_BLOCK`` queries at a time, so a sequence of 4608 positions never
holds more than one block of scores.

``mode`` is ``reference.py``'s: the precision of every matmul operand
(``"f32"`` the reference, ``"bf16"`` a witness, ``"fp8"`` the control).
``state_dtype`` is the dtype the delta state is held in between
positions: float32 in the reference, ``bfloat16`` for the bf16-state
witness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import olmo_hybrid_weights as ow
from benchmark.reference import HIGHEST
from benchmark.sambay_reference import _held_in, _mm, _round_operand

F32 = jnp.float32
QUERY_BLOCK = 512


def _mmw(x, w, mode):
    return _mm(x, w.astype(F32), mode)


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _delta_net(x, w, z, mode, state_dtype):
    """x (T, d) -> (T, d): Gated DeltaNet, one position at a time."""
    T = x.shape[0]
    H, dk, dv, K = z["H"], z["dk"], z["dv"], z["K"]
    kw = H * dk
    qkv = _mmw(x, w["w_qkv"], mode)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w["conv_w"][i] * padded[i:i + T]
                          for i in range(K)))
    q = qkv[:, :kw].reshape(T, H, dk)
    k = qkv[:, kw:2 * kw].reshape(T, H, dk)
    v = qkv[:, 2 * kw:].reshape(T, H, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12) \
        / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12)
    ab = _mmw(x, w["w_ab"], mode)
    beta = jax.nn.sigmoid(ab[:, H:]) * (2.0 if z["neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(w["A_log"])
                    * jax.nn.softplus(ab[:, :H] + w["dt_bias"]))

    def step(s, xs):                # s (H, dv, dk)
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, None, None] * s
        read = jnp.einsum("hvk,hk->hv", s, k_t, precision=HIGHEST)
        s = s + (b_t[:, None] * (v_t - read))[:, :, None] * k_t[:, None, :]
        s = _held_in(s, state_dtype)
        return s, jnp.einsum("hvk,hk->hv", s, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, dv, dk), F32),
                        (q, k, v, alpha, beta))
    o = _rms_norm(o, w["o_norm_g"], z["eps"]).reshape(T, H * dv)
    return _mmw(o * jax.nn.silu(_mmw(x, w["w_gate"], mode)), w["w_o"], mode)


def _attention(x, w, z, mode):
    """x (T, d) -> (T, d): QK-normed causal attention; ``T`` is a
    multiple of ``QUERY_BLOCK`` or below it."""
    T, d = x.shape
    heads, D = z["heads"], z["D"]
    qkv = _mmw(x, w["w_qkv"], mode)
    q = _rms_norm(qkv[:, :d], w["q_norm_g"], z["eps"]).reshape(T, heads, D)
    k = _rms_norm(qkv[:, d:2 * d], w["k_norm_g"],
                  z["eps"]).reshape(T, heads, D)
    v = qkv[:, 2 * d:].reshape(T, heads, D)
    k, v = _round_operand(k, mode), _round_operand(v, mode)
    blk = min(QUERY_BLOCK, T)

    def block(q_b, first):          # q_b (blk, heads, D) at first ..
        scores = jnp.einsum("thd,shd->hts", _round_operand(q_b, mode), k,
                            precision=HIGHEST) / np.sqrt(D)
        allowed = jnp.arange(T)[None, :] \
            <= (first + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hts,shd->thd", _round_operand(probs, mode), v,
                          precision=HIGHEST)

    out = jax.lax.map(lambda xs: block(*xs),
                      (q.reshape(T // blk, blk, heads, D),
                       jnp.arange(0, T, blk)))
    return _mmw(out.reshape(T, d), w["w_o"], mode)


def _layer(x, w, *, kind, z, mode, state_dtype):
    """One whole layer: mixer and gated MLP, each normed on its way
    into the residual stream."""
    out = _delta_net(x, w, z, mode, state_dtype) if kind == ow.LINEAR \
        else _attention(x, w, z, mode)
    x = x + _rms_norm(out, w["mixer_norm_g"], z["eps"])
    gu = _mmw(x, w["w_gate_up"], mode)
    g, up = gu[:, :z["ff"]], gu[:, z["ff"]:]
    return x + _rms_norm(_mmw(jax.nn.silu(g) * up, w["w_down"], mode),
                         w["mlp_norm_g"], z["eps"])


def make_logits_fn(shape: dict, mode: str = "f32", state_dtype=F32,
                   pad_multiple: int = QUERY_BLOCK):
    """``f(params, tokens (T,), rows) -> (len(rows), V)`` float32
    next-token logits of one sequence at the positions ``rows``."""
    z = ow.sizes(shape)

    @functools.lru_cache(maxsize=None)
    def layer_fn(kind):
        return jax.jit(functools.partial(
            _layer, kind=kind, z=z, mode=mode, state_dtype=state_dtype))

    @jax.jit
    def head(x, rows, normf_g, w_head):
        return _mmw(_rms_norm(x[rows], normf_g, z["eps"]), w_head, mode)

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
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for l, w in enumerate(params["layers"]):
            x = layer_fn(ow.layer_kind(shape, l))(x, w)
        return head(x, jnp.asarray(rows), params["normf_g"],
                    params["head"])[:n_rows]

    return logits
