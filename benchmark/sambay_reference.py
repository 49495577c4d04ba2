"""The plain reference for SambaY (Phi-4-mini-flash-reasoning): the full
forward of one sequence in ``jax.numpy`` and float32 — no cache, no
batch, a sequential recurrence, float32 matmuls at
``Precision.HIGHEST``. Nothing here imports the program under test; the
weights are ``sambay_weights.make_canonical``'s (bf16-rounded numbers,
read as float32) and the layer equations are written out below, after
arXiv:2507.06607 and the released ``modeling_phi4flash.py`` as the
configuration file records them.

One jitted function *per layer kind* is called layer by layer from
Python ("in blocks"): a layer's weights are widened to float32 only
while it runs, so the whole model at its published size fits beside its
bf16 weights.

``mode`` is ``reference.py``'s: the precision of every matmul operand
(``"f32"`` the reference, ``"bf16"`` a witness, ``"fp8"`` the control).
``state_dtype`` is the dtype the recurrent state ``h`` is held in
between positions: float32 in the reference, ``bfloat16`` for the
bf16-state witness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import sambay_weights as sw
from benchmark.reference import HIGHEST, _layer_norm

F32 = jnp.float32


def _held_in(x, dtype):
    """``x`` (float32) with the precision of ``dtype`` and float32's
    type. ``reduce_precision``, not a cast there and back: on a TPU XLA
    may elide a float32 -> bfloat16 -> float32 round trip (it allows
    excess precision), and a witness built on the cast reads exactly what
    the float32 reference reads (it did, on the chip: PERF.md section 6,
    PR 28)."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _round_operand(x, mode):
    if mode == "bf16":
        return _held_in(x, jnp.bfloat16)
    return reference._round_operand(x, mode)    # f32: x; fp8: scaled e4m3


def _mm(x, w, mode):
    if mode == "fp8":
        return reference._mm(x, w, mode)
    return jnp.matmul(_round_operand(x, mode), _round_operand(w, mode),
                      precision=HIGHEST)


def _mmw(x, w, mode):
    return _mm(x, w.astype(F32), mode)


def _mamba(x, w, z, mode, state_dtype):
    """x (T, d) -> (out (T, d), y (T, di)); ``y`` (before the gate) is
    the memory when this is layer n/2."""
    T = x.shape[0]
    di, N, K, R = z["di"], z["N"], z["K"], z["R"]
    xz = _mmw(x, w["w_in"], mode)
    xs, gate = xz[:, :di], xz[:, di:]
    padded = jnp.pad(xs, ((K - 1, 0), (0, 0)))
    xc = w["conv_b"] + sum(w["conv_w"][i] * padded[i:i + T]
                           for i in range(K))
    xc = jax.nn.silu(xc)
    dbc = _mmw(xc, w["w_x"], mode)
    dt = jax.nn.softplus(_mmw(dbc[:, :R], w["w_dt"], mode) + w["dt_b"])
    b, c = dbc[:, R:R + N], dbc[:, R + N:]
    a = -jnp.exp(w["A_log"])                              # (di, N)

    def step(h, xs_t):
        dt_t, x_t, b_t, c_t = xs_t
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        h = _held_in(h, state_dtype)
        return h, jnp.sum(h * c_t[None, :], axis=1)

    _, y = jax.lax.scan(step, jnp.zeros((di, N), F32), (dt, xc, b, c))
    y = y + w["D"] * xc
    return _mmw(y * jax.nn.silu(gate), w["w_out"], mode), y


def _diff_attention(q, k, v, allowed, w, lam0, z, mode):
    """q (T, Hq, D), k / v (S, Hkv, D), allowed (T, S) bool. Query heads
    2i, 2i+1 are pair i; pair i reads key pair i // g (its two heads, one
    each) and that pair's two value heads side by side."""
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    g = Hq // Hkv
    heads = np.arange(Hq)
    k_of = 2 * ((heads // 2) // g) + heads % 2
    v_of = (heads // 2) // g
    kh = k[:, k_of]                                       # (S, Hq, D)
    vh = v.reshape(v.shape[0], Hkv // 2, 2 * D)[:, v_of]  # (S, Hq, 2D)
    scores = jnp.einsum("thd,shd->hts", _round_operand(q, mode),
                        _round_operand(kh, mode),
                        precision=HIGHEST) / np.sqrt(D)
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("hts,she->the", _round_operand(probs, mode),
                   _round_operand(vh, mode), precision=HIGHEST)
    a = a.reshape(a.shape[0], Hq // 2, 2, 2 * D)
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam0)
    out = a[:, :, 0] - lam * a[:, :, 1]
    out = out / jnp.sqrt(jnp.mean(out * out, axis=-1, keepdims=True)
                         + z["eps"])
    out = out * w["subln"] * (1.0 - lam0)
    return out.reshape(out.shape[0], Hq * D)


def _causal(T, window=None):
    t, s = np.arange(T)[:, None], np.arange(T)[None, :]
    ok = s <= t
    if window is not None:
        ok &= (t - s) < window
    return jnp.asarray(ok)


def _self_attention(x, w, lam0, window, z, mode):
    T = x.shape[0]
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    qkv = _mmw(x, w["w_qkv"], mode) + w["b_qkv"]
    q = qkv[:, :Hq * D].reshape(T, Hq, D)
    k = qkv[:, Hq * D:(Hq + Hkv) * D].reshape(T, Hkv, D)
    v = qkv[:, (Hq + Hkv) * D:].reshape(T, Hkv, D)
    out = _diff_attention(q, k, v, _causal(T, window), w, lam0, z, mode)
    return _mmw(out, w["w_o"], mode) + w["b_o"], (k, v)


def _cross_attention(x, kv, w, lam0, z, mode):
    T = x.shape[0]
    q = (_mmw(x, w["w_q"], mode) + w["b_q"]).reshape(T, z["Hq"], z["D"])
    out = _diff_attention(q, kv[0], kv[1], _causal(T), w, lam0, z, mode)
    return _mmw(out, w["w_o"], mode) + w["b_o"]


def _layer(x, memory, kv, w, lam0, *, kind, z, mode, state_dtype):
    """One whole layer: mixer and gated MLP, each on a residual."""
    u = _layer_norm(x, w["ln1_g"], w["ln1_b"], z["eps"])
    if kind == sw.MAMBA:
        out, memory = _mamba(u, w, z, mode, state_dtype)
    elif kind == sw.SWA:
        out, _ = _self_attention(u, w, lam0, z["W"], z, mode)
    elif kind == sw.FULL:
        out, kv = _self_attention(u, w, lam0, None, z, mode)
    elif kind == sw.GMU:
        out = _mmw(memory * jax.nn.silu(_mmw(u, w["w_in"], mode)),
                   w["w_out"], mode)
    else:
        out = _cross_attention(u, kv, w, lam0, z, mode)
    x = x + out
    u = _layer_norm(x, w["ln2_g"], w["ln2_b"], z["eps"])
    gu = _mmw(u, w["w_gate_up"], mode)
    g, up = gu[:, :z["ff"]], gu[:, z["ff"]:]
    return x + _mmw(jax.nn.silu(g) * up, w["w_down"], mode), memory, kv


def make_logits_fn(shape: dict, mode: str = "f32", state_dtype=F32,
                   pad_multiple: int = 256):
    """``f(params, tokens (T,), rows) -> (len(rows), V)`` float32
    next-token logits of one sequence at the positions ``rows``."""
    z = sw.sizes(shape)
    n = z["n"]

    @functools.lru_cache(maxsize=None)
    def layer_fn(kind):
        return jax.jit(functools.partial(
            _layer, kind=kind, z=z, mode=mode, state_dtype=state_dtype))

    @jax.jit
    def head(x, rows, lnf_g, lnf_b, embed):
        h = _layer_norm(x[rows], lnf_g, lnf_b, z["eps"])
        return _mm(h, embed.astype(F32).T, mode)

    def round_up(n):
        return -(-n // pad_multiple) * pad_multiple

    def logits(params, tokens, rows):
        # every layer is causal, so a zero tail changes no row asked for:
        # lengths are rounded up so that requests share compiled programs
        n_rows = len(rows)
        tokens = np.pad(np.asarray(tokens, np.int32),
                        (0, round_up(len(tokens)) - len(tokens)))
        rows = np.pad(np.asarray(rows, np.int32),
                      (0, round_up(n_rows) - n_rows), mode="edge")
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(F32)
        # the memory and the full layer's K/V exist from layer n/2 on;
        # placeholders of their shapes keep each kind one program
        memory = jnp.zeros((tokens.shape[0], z["di"]), F32)
        kv = (jnp.zeros((tokens.shape[0], z["Hkv"], z["D"]), F32),) * 2
        for l in range(n):
            kind = sw.layer_kind(shape, l)
            lam0 = sw.lambda_init(l) if kind in (
                sw.SWA, sw.FULL, sw.CROSS) else 0.0
            x, memory, kv = layer_fn(kind)(x, memory, kv,
                                           params["layers"][l], lam0)
        return head(x, jnp.asarray(rows, jnp.int32), params["lnf_g"],
                    params["lnf_b"], params["embed"])[:n_rows]

    return logits
