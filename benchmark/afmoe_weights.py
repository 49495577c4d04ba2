"""Trinity / AFMoE weights from ``--seed``.

The canonical layout (what the plain reference reads)::

    embed (V, d) bf16   head (d, V) bf16   normf_g (d,) f32
    layers[l]: attn_in_g attn_out_g mlp_in_g mlp_out_g (d,) f32
      attention: w_qkvg (d, (2 Hq + 2 Hkv) D)  q_norm_g k_norm_g (D,)
                 w_o (Hq D, d)
      a dense layer:  w_gate_up (d, 2 F)  w_down (F, d)
      an expert layer:
        w_router (d, E)  router_bias (E,) f32   — all E router outputs
        w_gate_up (held, d, 2 f)  w_down (held, f, d) — the held experts
        ws_gate_up (d, 2 f)  ws_down (f, d)           — the shared expert

``w_qkvg`` holds the query's columns (head after head), then the key's,
the value's and the gate's; ``w_gate_up`` W_gate then W_up: the published
matrices laid beside each other so that program and reference read the
same arrays without a copy. Every matrix is *held* in bfloat16 — program
and reference read the same bf16-rounded numbers, the reference as
float32. Vectors stay float32.

**The share.** ``num_experts`` in the configuration file is the experts
*held* (32), ``published.num_experts`` the router's outputs (256),
``deployment.expert_offset`` the first held expert. An expert's weights
are drawn from the layer's key and the expert's **global** number, so
the eight shares of a layer (offsets 0, 32, .. 224) hold eight disjoint
eighths of one layer of 256 experts and the same router: the test that
adds the shares up rests on it.

Init (``assumed`` in the configuration file): normal(0, std) for the
embedding, the head and every matrix (``std`` = ``initializer_range``,
0.02); RMSNorm gains 1; the router's choice bias normal(0,
``ROUTER_BIAS_STD``), see there.

One jitted call makes a layer (the key is an argument: one compile
serves every layer of a kind and every seed); its experts are drawn one
at a time under ``lax.map``, so nothing is ever held in float32 beyond
one expert's matrices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (re-exported)

SLIDING, FULL = "sliding_attention", "full_attention"

#: the router's input is an RMS-normed row of ``d`` values, so its
#: logits spread by ``std * sqrt(d)`` — 1.11 at the published width: of
#: 256 sigmoid scores the top four lie at 0.91-0.97, their normalised
#: weights at 0.24-0.26 (the sigmoid router's top scores saturate; what
#: follows the token is the *choice*). The choice bias ``b`` (the load
#: balancer's, "SMEBU") is drawn this wide: the gap between the 4th and
#: the 5th ``s`` is 0.006 in the median, and a bias of 0.001 moves the
#: choice of 6 % of the tokens (``benchmark/tests/test_bench_afmoe_work
#: .py`` holds both numbers at the published width; a nano shape, whose
#: 16 scores lie further apart, states its own ``router_bias_std``)
ROUTER_BIAS_STD = 1e-3


def sizes(shape: dict) -> dict:
    pub = shape.get("published", {})
    dep = shape.get("deployment", {})
    held = shape["num_experts"]
    types = tuple(shape["layer_types"])
    return dict(
        d=shape["hidden_size"], n=shape["num_hidden_layers"],
        n_dense=shape["num_dense_layers"], types=types,
        V=shape["vocab_size"], Hq=shape["num_attention_heads"],
        K=shape["num_key_value_heads"], D=shape["head_dim"],
        F=shape["intermediate_size"], f=shape["moe_intermediate_size"],
        W=int(shape["sliding_window"]),
        E=int(pub.get("num_experts", held)), held=held,
        offset=int(dep.get("expert_offset", 0)),
        k=shape["num_experts_per_tok"],
        route_norm=bool(shape.get("route_norm", True)),
        route_scale=float(shape.get("route_scale", 1.0)),
        mup=bool(shape.get("mup_enabled", True)),
        eps=float(shape["rms_norm_eps"]),
        theta=float(shape["rope_theta"]),
        std=float(shape.get("initializer_range", 0.02)),
        bias_std=float(shape.get("router_bias_std", ROUTER_BIAS_STD)))


def _make_layer(key, z: dict, dense: bool) -> dict:
    d, f, std = z["d"], z["f"], z["std"]
    keys = iter(jax.random.split(key, 12))

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    ones = lambda n: jnp.ones((n,), jnp.float32)        # noqa: E731
    out = {
        "attn_in_g": ones(d), "attn_out_g": ones(d), "mlp_in_g": ones(d),
        "mlp_out_g": ones(d),
        "w_qkvg": normal(next(keys),
                         (d, 2 * (z["Hq"] + z["K"]) * z["D"])),
        "q_norm_g": ones(z["D"]), "k_norm_g": ones(z["D"]),
        "w_o": normal(next(keys), (z["Hq"] * z["D"], d)),
    }
    if dense:
        out["w_gate_up"] = normal(next(keys), (d, 2 * z["F"]))
        out["w_down"] = normal(next(keys), (z["F"], d))
        return out
    out["w_router"] = normal(next(keys), (d, z["E"]))
    out["router_bias"] = z["bias_std"] * jax.random.normal(
        next(keys), (z["E"],), jnp.float32)
    out["ws_gate_up"] = normal(next(keys), (d, 2 * f))
    out["ws_down"] = normal(next(keys), (f, d))
    # an expert's draws depend on its global number only
    k_experts = next(keys)
    experts = jax.vmap(lambda e: jax.random.fold_in(k_experts, e))(
        z["offset"] + jnp.arange(z["held"]))

    def expert(k):
        k_gu, k_d = jax.random.split(k)
        return normal(k_gu, (d, 2 * f)), normal(k_d, (f, d))

    out["w_gate_up"], out["w_down"] = jax.lax.map(expert, experts)
    return out


@functools.lru_cache(maxsize=None)
def _layer_maker(frozen_sizes: tuple, dense: bool):
    z = dict(frozen_sizes)
    return jax.jit(lambda key: _make_layer(key, z, dense))


def make_canonical(key, shape: dict) -> dict:
    """The whole weight set from one key (one jitted call a layer keeps
    the float32 draws expert-sized)."""
    z = sizes(shape)
    frozen = tuple(sorted(z.items()))

    def table(k, rows, cols):
        return jax.jit(lambda k: (z["std"] * jax.random.normal(
            k, (rows, cols), jnp.float32)).astype(jnp.bfloat16))(k)

    layers = [_layer_maker(frozen, l < z["n_dense"])(
        jax.random.fold_in(key, l + 2)) for l in range(z["n"])]
    return {"embed": table(jax.random.fold_in(key, 0), z["V"], z["d"]),
            "head": table(jax.random.fold_in(key, 1), z["d"], z["V"]),
            "normf_g": jnp.ones((z["d"],), jnp.float32), "layers": layers}
