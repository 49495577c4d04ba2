"""SambaY (Phi-4-mini-flash-reasoning) weights from ``--seed``.

The canonical layout (what the plain reference reads)::

    embed (V, d) bf16      lnf_g lnf_b (d,) f32
    layers[l]: ln1_g ln1_b ln2_g ln2_b (d,) f32
               w_gate_up (d, 2 ff) bf16   w_down (ff, d) bf16
      mamba:   w_in (d, 2 di)  w_x (di, R + 2 N)  w_dt (R, di)
               w_out (di, d) bf16; conv_w (K, di) conv_b dt_b D (di,)
               A_log (di, N) f32
      swa / full: w_qkv (d, (Hq + 2 Hkv) D)  w_o (d, d) bf16; b_qkv b_o
               lq1 lk1 lq2 lk2 (D,) subln (2 D,) f32
      cross:   w_q (d, d)  w_o (d, d) bf16; b_q b_o, the five as above
      gmu:     w_in (d, di)  w_out (di, d) bf16

Every matrix is *held* in bfloat16 — float32 weights of the published
size (15.4 GB) fit no chip — so program and reference read the same
bf16-rounded numbers, the reference as float32. Vectors stay float32.

Init (``assumed`` in the configuration file): normal(0, std) for the
embedding and every matrix (``std`` = ``initializer_range``, 0.02;
``w_x`` three times wider, see ``X_PROJ_GAIN``), the residual projections (``w_out``, ``w_o``,
``w_down``) scaled by 1 / sqrt(2 n_layer) as GPT-2's are; LayerNorm 1 / 0;
attention biases 0. Mamba's own (``mamba_ssm``): ``A_log = log(1 .. N)``,
``D = 1``, ``w_dt`` uniform(+-R^-0.5), ``dt_b`` the inverse softplus of a
log-uniform dt in [1e-3, 1e-1], the conv uniform(+-K^-0.5). Differential
attention's (Diff Transformer): the four lambda vectors normal(0, 0.1),
``subln`` 1.

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

MAMBA, SWA, FULL, GMU, CROSS = "mamba", "swa", "full", "gmu", "cross"
#: ``w_x`` (which makes dt, B and C) is drawn this much wider than the
#: other matrices: at 0.02 the selective scan's B and C are ~0.1, the
#: state's share of the scan output ``h C + D x`` is a thousandth, and no
#: comparison of logits could see the recurrent state at all. Three times
#: wider they are O(1), as in a trained model, and the state carries
#: about as much as the skip.
X_PROJ_GAIN = 3.0
#: sizes config.json does not carry (the released model's defaults)
DEFAULTS = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2}


def sizes(shape: dict) -> dict:
    d = shape["hidden_size"]
    get = lambda k: shape.get(k, DEFAULTS[k])           # noqa: E731
    return dict(
        d=d, n=shape["num_hidden_layers"], ff=shape["intermediate_size"],
        Hq=shape["num_attention_heads"], Hkv=shape["num_key_value_heads"],
        D=d // shape["num_attention_heads"], V=shape["vocab_size"],
        W=shape["sliding_window"], N=get("mamba_d_state"),
        K=get("mamba_d_conv"), di=get("mamba_expand") * d,
        R=shape.get("mamba_dt_rank") or math.ceil(d / 16),
        eps=float(shape["layer_norm_eps"]),
        std=float(shape.get("initializer_range", 0.02)))


def layer_kind(shape: dict, layer: int) -> str:
    """Layers 0 .. n/2: Mamba on even, windowed attention on odd (n/2
    itself is the Mamba that leaves the memory); n/2 + 1: full attention;
    then gated memory units on even, cross attention on odd."""
    half = shape["num_hidden_layers"] // 2
    if layer <= half:
        return MAMBA if layer % 2 == 0 else SWA
    if layer == half + 1:
        return FULL
    return GMU if layer % 2 == 0 else CROSS


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _make_layer(key, kind: str, z: dict) -> dict:
    d, ff, di, N, K, R, D = (z["d"], z["ff"], z["di"], z["N"], z["K"],
                             z["R"], z["D"])
    std = z["std"]
    resid = std / math.sqrt(2 * z["n"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale=std, dtype=jnp.bfloat16):
        return (scale * jax.random.normal(next(keys), shape,
                                          jnp.float32)).astype(dtype)

    def uniform(shape, bound, dtype=jnp.float32):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)        # noqa: E731
    zeros = lambda n: jnp.zeros((n,), jnp.float32)      # noqa: E731
    out = {"ln1_g": ones(d), "ln1_b": zeros(d), "ln2_g": ones(d),
           "ln2_b": zeros(d), "w_gate_up": normal((d, 2 * ff)),
           "w_down": normal((ff, d), resid)}
    diff = lambda: {n: normal((D,), 0.1, jnp.float32)   # noqa: E731
                    for n in ("lq1", "lk1", "lq2", "lk2")} | {
                        "subln": ones(2 * D)}
    if kind == MAMBA:
        dt = jnp.exp(jax.random.uniform(next(keys), (di,)) * (
            math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        out.update(
            w_in=normal((d, 2 * di)), conv_w=uniform((K, di), K ** -0.5),
            conv_b=uniform((di,), K ** -0.5),
            w_x=normal((di, R + 2 * N), X_PROJ_GAIN * std),
            w_dt=uniform((R, di), R ** -0.5, jnp.bfloat16),
            dt_b=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32)), (di, N)),
            D=ones(di), w_out=normal((di, d), resid))
    elif kind in (SWA, FULL):
        width = (z["Hq"] + 2 * z["Hkv"]) * D
        out.update(w_qkv=normal((d, width)), b_qkv=zeros(width),
                   w_o=normal((d, d), resid), b_o=zeros(d), **diff())
    elif kind == CROSS:
        out.update(w_q=normal((d, d)), b_q=zeros(d),
                   w_o=normal((d, d), resid), b_o=zeros(d), **diff())
    else:
        out.update(w_in=normal((d, di)), w_out=normal((di, d), resid))
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
    embed = jax.jit(lambda k: (z["std"] * jax.random.normal(
        k, (z["V"], z["d"]), jnp.float32)).astype(jnp.bfloat16))(
            jax.random.fold_in(key, 0))
    layers = [_layer_maker(layer_kind(shape, l), frozen)(
        jax.random.fold_in(key, l + 1)) for l in range(z["n"])]
    return {"embed": embed, "lnf_g": jnp.ones((z["d"],), jnp.float32),
            "lnf_b": jnp.zeros((z["d"],), jnp.float32), "layers": layers}
