"""Olmo-Hybrid weights from ``--seed``.

The canonical layout (what the plain reference reads)::

    embed (V, d) bf16   head (d, V) bf16   normf_g (d,) f32
    layers[l]: mixer_norm_g mlp_norm_g (d,) f32
               w_gate_up (d, 2 ff) bf16   w_down (ff, d) bf16
      linear_attention:
               w_qkv (d, 2 H dk + H dv)  w_gate (d, H dv)  w_ab (d, 2 H)
               w_o (H dv, d) bf16; conv_w (K, 2 H dk + H dv) f32
               A_log dt_bias (H,)  o_norm_g (dv,) f32
      full_attention:
               w_qkv (d, 3 d)  w_o (d, d) bf16; q_norm_g k_norm_g (d,) f32

``w_qkv`` holds W_q, W_k and W_v side by side (the columns of q, then k,
then v), ``w_gate_up`` W_gate then W_up, ``w_ab`` W_a then W_b: the
published matrices, laid beside each other so that program and reference
read the same arrays without a copy. Every matrix is *held* in bfloat16
— float32 weights of the cut (16.4 GB) fit no chip — so program and
reference read the same bf16-rounded numbers, the reference as float32.
Vectors stay float32.

Init (``assumed`` in the configuration file): normal(0, std) for the
embedding, the head and every matrix (``std`` = ``initializer_range``,
0.02). No 1 / sqrt(2 n_layer) on the residual projections: every
sub-layer's output goes through an RMSNorm before it is added. ``w_ab``
is drawn ``AB_GAIN`` times narrower, see there. RMSNorm gains 1. The
delta rule's decays: ``A_log = 0`` and ``dt_bias`` the inverse softplus
of a log-uniform dt in [1e-3, 1e-1], so that ``alpha = exp(-dt)`` spans
0.905 .. 0.999 before the data moves it — a state that remembers tens to
a thousand tokens, O(1) beside the current token's term. The conv
uniform(+-K^-0.5), as Mamba's.

One jitted call *per layer kind* makes a layer (the key is an argument,
so one compile serves every layer of the kind and every seed); nothing
is ever held in float32 at full size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (re-exported)

LINEAR, FULL = "linear_attention", "full_attention"
#: ``w_ab`` (which makes the decay's and the write strength's logits) is
#: drawn this much narrower than the other matrices. Its input is the
#: residual stream itself (no norm before a mixer), whose RMS grows to
#: ~6 over 16 layers: at 0.02 the logits would spread by +-7, ``beta``
#: would sit at 0 or 2 and ``alpha`` would fall to 0.5 on many tokens —
#: a state wiped every few positions. An eighth as wide they spread by
#: about +-1: ``beta`` over (0.5, 1.5), ``alpha`` inside 0.78 .. 0.9996.
AB_GAIN = 0.125


def sizes(shape: dict) -> dict:
    d, heads = shape["hidden_size"], shape["num_attention_heads"]
    H = shape["linear_num_key_heads"]
    dk, dv = shape["linear_key_head_dim"], shape["linear_value_head_dim"]
    return dict(
        d=d, n=shape["num_hidden_layers"], ff=shape["intermediate_size"],
        heads=heads, D=d // heads, V=shape["vocab_size"], H=H, dk=dk, dv=dv,
        K=shape["linear_conv_kernel_dim"], cw=2 * H * dk + H * dv,
        eps=float(shape["rms_norm_eps"]),
        neg_eigval=bool(shape["linear_allow_neg_eigval"]),
        std=float(shape.get("initializer_range", 0.02)))


def layer_kind(shape: dict, layer: int) -> str:
    return shape["layer_types"][layer]


def _make_layer(key, kind: str, z: dict) -> dict:
    d, ff, H, dv, K, cw = z["d"], z["ff"], z["H"], z["dv"], z["K"], z["cw"]
    std = z["std"]
    keys = iter(jax.random.split(key, 12))

    def normal(shape, scale=std):
        return (scale * jax.random.normal(next(keys), shape,
                                          jnp.float32)).astype(jnp.bfloat16)

    ones = lambda n: jnp.ones((n,), jnp.float32)        # noqa: E731
    out = {"mixer_norm_g": ones(d), "mlp_norm_g": ones(d),
           "w_gate_up": normal((d, 2 * ff)), "w_down": normal((ff, d))}
    if kind == LINEAR:
        dt = jnp.exp(jax.random.uniform(next(keys), (H,)) * (
            math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        out.update(
            w_qkv=normal((d, cw)), w_gate=normal((d, H * dv)),
            w_ab=normal((d, 2 * H), AB_GAIN * std),
            w_o=normal((H * dv, d)),
            conv_w=jax.random.uniform(next(keys), (K, cw), jnp.float32,
                                      -K ** -0.5, K ** -0.5),
            A_log=jnp.zeros((H,), jnp.float32),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)), o_norm_g=ones(dv))
    else:
        out.update(w_qkv=normal((d, 3 * d)), w_o=normal((d, d)),
                   q_norm_g=ones(d), k_norm_g=ones(d))
    return out


@functools.lru_cache(maxsize=None)
def _layer_maker(kind: str, frozen_sizes: tuple):
    z = dict(frozen_sizes)
    return jax.jit(lambda key: _make_layer(key, kind, z))


def make_canonical(key, shape: dict) -> dict:
    """The whole weight set from one key (not traceable as a whole: one
    jitted call a layer keeps the float32 draws layer-sized)."""
    z = sizes(shape)
    frozen = tuple(sorted(z.items()))

    def table(k, rows, cols):
        return jax.jit(lambda k: (z["std"] * jax.random.normal(
            k, (rows, cols), jnp.float32)).astype(jnp.bfloat16))(k)

    layers = [_layer_maker(layer_kind(shape, l), frozen)(
        jax.random.fold_in(key, l + 2)) for l in range(z["n"])]
    return {"embed": table(jax.random.fold_in(key, 0), z["V"], z["d"]),
            "head": table(jax.random.fold_in(key, 1), z["d"], z["V"]),
            "normf_g": jnp.ones((z["d"],), jnp.float32), "layers": layers}
