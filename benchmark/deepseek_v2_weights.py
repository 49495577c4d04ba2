"""DeepSeek-V2 weights from ``--seed``.

The canonical layout (what the plain reference reads)::

    embed (V, d) bf16   head (d, V) bf16   normf_g (d,) f32
    layers[l]: attn_norm_g mlp_norm_g (d,) f32
      attention: w_dq (d, qr)  q_norm_g (qr,)  w_uq (qr, H (Dn + R))
                 w_dkv (d, C + R)  kv_norm_g (C,)
                 w_uk (H, Dn, C)  w_uv (H, C, Dv)  w_o (H Dv, d)
      a dense layer:  w_gate_up (d, 2 F)  w_down (F, d)
      an expert layer:
        w_router (d, E)                               — all E router outputs
        w_gate_up (held, d, 2 f)  w_down (held, f, d) — the held experts
        ws_gate_up (d, 2 fs)  ws_down (fs, d)         — the shared experts

``w_uq``'s columns are, a head, ``q_nope`` then ``q_pe``; ``w_dkv``'s the
latent then the rotary key; ``w_uk`` / ``w_uv`` are the key and the value
columns of the published ``kv_b_proj`` (``W_ukv``), a head, laid out as
the absorbed product reads them; ``w_gate_up`` W_gate then W_up. The
shared experts (``n_shared_experts`` of width ``f``) are one SwiGLU of
``fs = n_shared_experts x f``, as the published code builds them. Every
matrix is *held* in bfloat16 — program and reference read the same
bf16-rounded numbers, the reference as float32. Vectors stay float32.

**The share.** ``n_routed_experts`` in the configuration file is the
experts *held* (one routing group, 20), ``published.n_routed_experts``
the router's outputs (160), ``deployment.expert_offset`` the first held
expert. An expert's weights are drawn from the layer's key and the
expert's **global** number, so the eight shares of a layer (offsets 0,
20, .. 140) hold eight disjoint eighths of one layer of 160 experts and
the same router: the test that adds the shares up rests on it.

Init (``assumed`` in the configuration file): normal(0, std) for the
embedding, the head and every matrix (``std`` = ``initializer_range``,
0.02); RMSNorm gains 1.

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


def sizes(shape: dict) -> dict:
    pub = shape.get("published", {})
    dep = shape.get("deployment", {})
    rs = shape.get("rope_scaling") or {}
    held = int(shape["n_routed_experts"])
    return dict(
        d=shape["hidden_size"], n=shape["num_hidden_layers"],
        n_dense=shape["first_k_dense_replace"], V=shape["vocab_size"],
        H=shape["num_attention_heads"], qr=shape["q_lora_rank"],
        C=shape["kv_lora_rank"], Dn=shape["qk_nope_head_dim"],
        R=shape["qk_rope_head_dim"], Dv=shape["v_head_dim"],
        F=shape["intermediate_size"], f=shape["moe_intermediate_size"],
        fs=shape["moe_intermediate_size"] * shape["n_shared_experts"],
        E=int(pub.get("n_routed_experts", held)), held=held,
        offset=int(dep.get("expert_offset", 0)),
        k=shape["num_experts_per_tok"], n_group=shape["n_group"],
        topk_group=shape["topk_group"],
        norm=bool(shape["norm_topk_prob"]),
        scale=float(shape["routed_scaling_factor"]),
        eps=float(shape["rms_norm_eps"]),
        theta=float(shape["rope_theta"]),
        factor=float(rs.get("factor", 1.0)),
        original=int(rs.get("original_max_position_embeddings", 4096)),
        beta_fast=float(rs.get("beta_fast", 32)),
        beta_slow=float(rs.get("beta_slow", 1)),
        mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        std=float(shape.get("initializer_range", 0.02)))


def _make_layer(key, offset, z: dict, dense: bool) -> dict:
    d, f, std, H = z["d"], z["f"], z["std"], z["H"]
    keys = iter(jax.random.split(key, 12))

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    ones = lambda n: jnp.ones((n,), jnp.float32)        # noqa: E731
    out = {
        "attn_norm_g": ones(d), "mlp_norm_g": ones(d),
        "w_dq": normal(next(keys), (d, z["qr"])), "q_norm_g": ones(z["qr"]),
        "w_uq": normal(next(keys), (z["qr"], H * (z["Dn"] + z["R"]))),
        "w_dkv": normal(next(keys), (d, z["C"] + z["R"])),
        "kv_norm_g": ones(z["C"]),
        "w_uk": normal(next(keys), (H, z["Dn"], z["C"])),
        "w_uv": normal(next(keys), (H, z["C"], z["Dv"])),
        "w_o": normal(next(keys), (H * z["Dv"], d)),
    }
    if dense:
        out["w_gate_up"] = normal(next(keys), (d, 2 * z["F"]))
        out["w_down"] = normal(next(keys), (z["F"], d))
        return out
    out["w_router"] = normal(next(keys), (d, z["E"]))
    out["ws_gate_up"] = normal(next(keys), (d, 2 * z["fs"]))
    out["ws_down"] = normal(next(keys), (z["fs"], d))
    # an expert's draws depend on its global number only
    k_experts = next(keys)
    experts = jax.vmap(lambda e: jax.random.fold_in(k_experts, e))(
        offset + jnp.arange(z["held"]))

    def expert(k):
        k_gu, k_d = jax.random.split(k)
        return normal(k_gu, (d, 2 * f)), normal(k_d, (f, d))

    out["w_gate_up"], out["w_down"] = jax.lax.map(expert, experts)
    return out


@functools.lru_cache(maxsize=None)
def _layer_maker(frozen_sizes: tuple, dense: bool):
    z = dict(frozen_sizes)
    return jax.jit(lambda key, offset: _make_layer(key, offset, z, dense))


def make_layer(key, shape: dict, l: int) -> dict:
    """Layer ``l`` of the weight set of ``key`` (the share's experts:
    one compile serves every offset)."""
    z = sizes(shape)
    frozen = tuple(sorted((k, v) for k, v in z.items() if k != "offset"))
    return _layer_maker(frozen, l < z["n_dense"])(
        jax.random.fold_in(key, l + 2), jnp.int32(z["offset"]))


def make_canonical(key, shape: dict) -> dict:
    """The whole weight set from one key (one jitted call a layer keeps
    the float32 draws expert-sized)."""
    z = sizes(shape)

    def table(k, rows, cols):
        return jax.jit(lambda k: (z["std"] * jax.random.normal(
            k, (rows, cols), jnp.float32)).astype(jnp.bfloat16))(k)

    layers = [make_layer(key, shape, l) for l in range(z["n"])]
    return {"embed": table(jax.random.fold_in(key, 0), z["V"], z["d"]),
            "head": table(jax.random.fold_in(key, 1), z["d"], z["V"]),
            "normf_g": jnp.ones((z["d"],), jnp.float32), "layers": layers}
