"""Falcon-H1 weights from ``--seed``.

The canonical layout (what the plain reference reads)::

    embed (V, d) bf16   head (d, V) bf16   normf_g (d,) f32
    layers[l]: in_norm_g ff_norm_g (d,) f32
               w_qkv (d, (Hq + 2 Hkv) D)  w_o (Hq D, d) bf16
               w_in (d, 2 di + 2 G N + H)  w_out (di, d) bf16
               conv_w (K, di + 2 G N)  conv_b (di + 2 G N,) f32
               A_log dt_bias D (H,)  ssm_norm_g (di,) f32
               w_gate_up (d, 2 ff)  w_down (ff, d) bf16

``w_qkv`` holds W_q, W_k and W_v side by side (the columns of q, then k,
then v), ``w_in`` the in-projection's z, x, B, C and dt columns in that
order, ``w_gate_up`` W_gate then W_up: the published matrices, laid
beside each other so that program and reference read the same arrays
without a copy. Every matrix is *held* in bfloat16 — float32 weights of
the cut (21 GB) fit no chip — so program and reference read the same
bf16-rounded numbers, the reference as float32. Vectors and the conv
stay float32.

Init (``assumed`` in the configuration file). Every matrix is drawn
normal with the scale that makes its product **with the multipliers on
its path** a unit-variance map: ``std = 1 / (m sqrt(fan_in))``, ``m``
the product of those multipliers (:func:`matrix_std`) — the embedding
``1 / embedding_multiplier``, W_k ``1 / (attention_in x key sqrt(d))``,
each column part of W_in ``1 / (ssm_in x mu_part sqrt(d))``, the head
``1 / (lm_head sqrt(d))``. The multipliers are µP's and were set for
the *trained* weights; under one std for every matrix (0.02) the
keys' 0.011 leaves attention uniform, the MLP adds under 1 % of the
stream and the logits spread by 0.011, so nothing but the embedding
reaches the head. Drawn so, every branch adds O(1) to the stream,
attention scores spread by about 1 and the logits by about 1 — and a
multiplier left out moves its path by ``1 / m``. Mamba-2's own draws
for the rest: ``A = uniform(1, 16)`` a head (``A_log = log A``), ``dt``
log-uniform in [1e-3, 1e-1] (floored at 1e-4) and ``dt_bias`` its
inverse softplus, ``D = 1``; the conv's taps and bias uniform(+-K^-0.5),
as a depthwise ``Conv1d``'s; RMSNorm gains 1.

One jitted call a layer makes it (the key is an argument, so one compile
serves every layer and every seed); the embedding and the head are drawn
a block of rows at a time, so nothing is ever held in float32 at full
size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (re-exported)

#: blocks of rows the embedding and the head are drawn in (at most)
TABLE_BLOCKS = 16


def sizes(shape: dict) -> dict:
    H, P = shape["mamba_n_heads"], shape["mamba_d_head"]
    G, N = shape["mamba_n_groups"], shape["mamba_d_state"]
    di = shape["mamba_d_ssm"]
    return dict(
        d=shape["hidden_size"], n=shape["num_hidden_layers"],
        ff=shape["intermediate_size"], V=shape["vocab_size"],
        Hq=shape["num_attention_heads"], Hkv=shape["num_key_value_heads"],
        D=shape["head_dim"], theta=float(shape["rope_theta"]),
        H=H, P=P, G=G, N=N, di=di, K=shape["mamba_d_conv"],
        cw=di + 2 * G * N, chunk=shape["mamba_chunk_size"],
        eps=float(shape["rms_norm_eps"]),
        emb=float(shape["embedding_multiplier"]),
        head_m=float(shape["lm_head_multiplier"]),
        attn_in=float(shape["attention_in_multiplier"]),
        attn_out=float(shape["attention_out_multiplier"]),
        key_m=float(shape["key_multiplier"]),
        ssm_in=float(shape["ssm_in_multiplier"]),
        ssm_out=float(shape["ssm_out_multiplier"]),
        mu=tuple(float(m) for m in shape["ssm_multipliers"]),
        mlp=tuple(float(m) for m in shape["mlp_multipliers"]))


def in_proj_parts(z: dict) -> tuple:
    """The in-projection's column parts, in order: (name, width)."""
    gn = z["G"] * z["N"]
    return (("z", z["di"]), ("x", z["di"]), ("B", gn), ("C", gn),
            ("dt", z["H"]))


def matrix_std(z: dict) -> dict:
    """Each matrix's draw (see the module docstring); a matrix whose
    column parts sit behind different multipliers gives a tuple, a part
    each."""
    d = z["d"]
    unit = d ** -0.5
    return {
        "embed": 1.0 / z["emb"],
        "head": unit / z["head_m"],
        "w_qkv": (unit / z["attn_in"], unit / (z["attn_in"] * z["key_m"]),
                  unit / z["attn_in"]),
        "w_o": (z["Hq"] * z["D"]) ** -0.5 / z["attn_out"],
        "w_in": tuple(unit / (z["ssm_in"] * m) for m in z["mu"]),
        "w_out": z["di"] ** -0.5 / z["ssm_out"],
        "w_gate_up": (unit / z["mlp"][0], unit),
        "w_down": z["ff"] ** -0.5 / z["mlp"][1]}


def _make_layer(key, z: dict) -> dict:
    d, ff, H, K, cw = z["d"], z["ff"], z["H"], z["K"], z["cw"]
    std = matrix_std(z)
    keys = iter(jax.random.split(key, 17))      # one a column part

    def normal(rows, widths, stds):
        cols = [s * jax.random.normal(next(keys), (rows, w), jnp.float32)
                for w, s in zip(widths, stds)]
        return jnp.concatenate(cols, axis=1).astype(jnp.bfloat16)

    ones = lambda n: jnp.ones((n,), jnp.float32)        # noqa: E731
    qkv = (z["Hq"] * z["D"], z["Hkv"] * z["D"], z["Hkv"] * z["D"])
    dt = jnp.exp(jax.random.uniform(next(keys), (H,)) * (
        math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    bound = K ** -0.5
    return {
        "in_norm_g": ones(d), "ff_norm_g": ones(d),
        "w_qkv": normal(d, qkv, std["w_qkv"]),
        "w_o": normal(z["Hq"] * z["D"], (d,), (std["w_o"],)),
        "w_in": normal(d, [w for _, w in in_proj_parts(z)], std["w_in"]),
        "w_out": normal(z["di"], (d,), (std["w_out"],)),
        "conv_w": jax.random.uniform(next(keys), (K, cw), jnp.float32,
                                     -bound, bound),
        "conv_b": jax.random.uniform(next(keys), (cw,), jnp.float32,
                                     -bound, bound),
        "A_log": jnp.log(jax.random.uniform(next(keys), (H,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)), "D": ones(H),
        "ssm_norm_g": ones(z["di"]),
        "w_gate_up": normal(d, (ff, ff), std["w_gate_up"]),
        "w_down": normal(ff, (d,), (std["w_down"],))}


@functools.lru_cache(maxsize=None)
def _layer_maker(frozen_sizes: tuple):
    z = dict(frozen_sizes)
    return jax.jit(lambda key: _make_layer(key, z))


def _table(key, rows: int, cols: int, std: float):
    """A ``(rows, cols)`` bf16 matrix of normal(0, std), a block of rows
    at a time."""
    nb = next(b for b in (TABLE_BLOCKS, 8, 4, 2, 1) if rows % b == 0)

    def make(key):
        blocks = jax.lax.map(
            lambda k: (std * jax.random.normal(k, (rows // nb, cols),
                                               jnp.float32)
                       ).astype(jnp.bfloat16),
            jax.random.split(key, nb))
        return blocks.reshape(rows, cols)

    return jax.jit(make)(key)


def make_canonical(key, shape: dict) -> dict:
    """The whole weight set from one key (not traceable as a whole: one
    jitted call a layer keeps the float32 draws layer-sized)."""
    z = sizes(shape)
    std = matrix_std(z)
    make = _layer_maker(tuple(sorted(z.items())))
    layers = [make(jax.random.fold_in(key, l + 2)) for l in range(z["n"])]
    return {"embed": _table(jax.random.fold_in(key, 0), z["V"], z["d"],
                            std["embed"]),
            "head": _table(jax.random.fold_in(key, 1), z["d"], z["V"],
                           std["head"]),
            "normf_g": jnp.ones((z["d"],), jnp.float32), "layers": layers}
