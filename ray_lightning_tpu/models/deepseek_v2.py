"""DeepSeek-V2 LM (``model_type: deepseek_v2``): multi-head latent
attention over a compressed K/V cache, then a dense SwiGLU in the first
``first_k_dense_replace`` layers and softmax-routed experts with a group
limit and shared experts after them, on the serving protocol
``serve/engine.py`` drives (the one ``models/afmoe.py`` and
``models/falcon_h1.py`` are on).

Every layer is pre-norm: ``h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h))``,
then a final RMSNorm and an **untied** head. No bias anywhere; the
embedding is not scaled.

**Attention (MLA)**, ``H`` heads, from the configuration's widths (the
DeepSeek-V2 paper, arXiv:2405.04434, and the family's published
``deepseek_v2`` modelling code):

- ``c_q = RMSNorm(x W_dq)`` (``q_lora_rank``); ``q = c_q W_uq``: a head
  ``q_nope`` (``qk_nope_head_dim``) then ``q_pe`` (``qk_rope_head_dim``);
- ``[c, k_pe] = x W_dkv``; ``c = RMSNorm(c)`` (``kv_lora_rank``); ``k_pe``
  is one key shared by every head;
- ``q_pe`` and ``k_pe`` are rotated (:func:`rope`: YaRN's frequencies,
  the pair ``(2 i, 2 i + 1)``); ``k_nope = c W_uk`` and ``v = c W_uv`` a
  head (``W_uk`` / ``W_uv``: the key and value columns of ``W_ukv``);
- ``s = (q_nope . k_nope + q_pe . k_pe) * scale``, ``scale = (nope +
  rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
  causal softmax in float32; ``o = W_o concat_h(sum_j p v_j)``.

**The cache** holds what the compression gives, each leaf declared
(:meth:`DeepseekV2LM.cache_leaf`, both ``global``): ``latent`` ``(B, L,
kv_lora_rank)`` — ``c`` after its norm, a position a row of 512 lanes —
and ``rope_key`` ``(B, qk_rope_head_dim, L)`` — ``k_pe`` rotated, with
positions minor-most: 64 values a position would fill half a lane row,
which the TPU pads to 128 and a kernel's copy cannot slice (the
compiler's verdict for a v5e: ``tests/test_chip_compile.py``); a step
writes its one position through ``ops/cache_write.py``'s kernel, which
takes such a leaf as it lies. Nothing per head is ever stored. Two forms
read the cache:

- **expanded** (a piece, a prefill, the full forward): ``k_nope`` and
  ``v`` of every head are made from a block of cached latents at a time
  and fed to a running softmax (scope ``mla/expand``) — on a TPU inside
  ``ops/mla_prefill.py``'s kernel, a head and a block of 256 at a time,
  so no score or expanded key leaves VMEM; elsewhere in XLA, every head
  and a block of :data:`KEY_BLOCK` at a time. No program holds a score
  over the whole context;
- **absorbed** (a decode step): ``W_uk`` is folded into the query, ``q_c
  = q_nope W_uk^T`` (``kv_lora_rank`` wide a head), which scores the
  latent directly (the rotary part against ``rope_key``); the weighted
  sum of latents is mapped by ``W_uv`` after (scope ``mla/absorbed``).
  Each row reads its own live positions only, through
  ``ops/mla_decode.py``'s kernel on a TPU; elsewhere the same arithmetic
  over the whole slot under a mask. K/V are never expanded in a step.

**Expert layer.** Router logits ``W_r x`` in float32 over **all**
``n_routed_experts`` (the router's outputs); ``ops/grouped_experts.py``
takes it from there: softmax scores, the ``topk_group`` best of
``n_group`` groups (a group scored by its best expert), the top
``num_experts_per_tok`` among them, weights ``s`` times
``routed_scaling_factor`` (``norm_topk_prob`` false). ``y = E_shared(x)
+ sum_k w_k E_{e_k}(x)``; the ``n_shared_experts`` shared experts are one
SwiGLU of ``n_shared_experts x moe_intermediate_size``. The layer is
**told which experts it holds** (``cfg.experts_held`` from
``cfg.expert_offset``: one chip's routing group) and adds only their
terms and the shared expert's, dropless; it leaves the rows each held
expert took in a ``"counter"`` leaf (``expert_load``), as
``models/afmoe.py`` does.

Call modes (``cfg.decode`` selects the cached ones), as
``models/afmoe.py`` has them: the full forward (``decode=False``, every
position's logits, no cache); **continue** (``kv_positions=None``: a
``(B, C)`` piece at absolute ``offset`` with ``lengths`` valid tokens,
written into the cache and attending the row's cached positions; the
last valid token's logits back — prefill is continue from 0,
:attr:`DeepseekV2LM.continues_prefill`); a decode step (``kv_positions``
(B, 1)).

Matmul operands are ``cfg.dtype`` (bfloat16) with float32 accumulation;
the residual stream, norms, rotation, softmax, router logits (float32
operands at ``Precision.HIGHEST``) and top-k are float32; the cache is
held in ``cfg.dtype``, weights in ``cfg.param_dtype``; logits float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.afmoe import (GatedMLP, _softmax_finish,
                                            _softmax_init, _softmax_update)
from ray_lightning_tpu.models.generate import CacheLeaf
from ray_lightning_tpu.models.olmo_hybrid import _put_rows, _RMSNorm
from ray_lightning_tpu.models.sambay import _gather_rows, _Linear
from ray_lightning_tpu.ops import mla_decode, mla_prefill
from ray_lightning_tpu.ops.cache_write import write_rows
from ray_lightning_tpu.ops.grouped_experts import held_experts

#: cached latents a piece's attention expands and reads at a time
KEY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    # the published keys (config.json of DeepSeek-V2)
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288       # the dense layers' width
    moe_intermediate_size: int = 1536    # an expert's width
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160          # the router's outputs
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    scoring_func: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    rope_theta: float = 10000.0
    #: YaRN (``rope_scaling`` of the config)
    rope_factor: float = 40.0
    rope_original_max_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.707
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # the share of an expert-parallel group this chip holds
    experts_held: Optional[int] = None   # None: every expert
    expert_offset: int = 0
    # how it is run
    max_seq_len: int = 4096          # positions one slot holds
    decode: bool = False
    dtype: Any = jnp.bfloat16        # matmul operands, cache at rest
    param_dtype: Any = jnp.bfloat16  # weights at rest

    def __post_init__(self):
        if self.tie_word_embeddings or self.attention_bias:
            raise ValueError("written for an untied head and no biases")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has a key and a value a "
                             "query head")
        if self.moe_layer_freq != 1:
            raise ValueError("written for an expert layer in every layer "
                             "after the dense ones")
        if self.scoring_func != "softmax" \
                or self.topk_method != "group_limited_greedy":
            raise ValueError("written for group-limited softmax routing")
        if self.n_routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError("n_group must divide the experts and "
                             "topk_group lie in 1 .. n_group")
        if self.num_experts_per_tok > self.topk_group * (
                self.n_routed_experts // self.n_group):
            raise ValueError("more experts a token than the groups kept "
                             "hold")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotation pairs dims: qk_rope_head_dim "
                             "is even")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace is a count of layers")
        held = self.held
        if held < 1 or self.expert_offset < 0 \
                or self.expert_offset + held > self.n_routed_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + held}"
                f" are not among the router's {self.n_routed_experts}")
        if self.max_seq_len > self.max_position_embeddings:
            raise ValueError("max_seq_len exceeds the declared positions")

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) \
            + 1.0 if self.rope_factor > 1 else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m


# ------------------------------------------------------------ pieces
def _linear(cfg: DeepseekV2Config, features: int, name: str) -> _Linear:
    return _Linear(features, False, cfg.dtype, cfg.param_dtype, name=name)


def yarn_inv_freq(cfg: DeepseekV2Config):
    """YaRN's rotary frequencies for the ``qk_rope_head_dim`` dims: the
    fast-turning pairs keep ``theta^(-2 i / dim)``, the slow ones are
    divided by ``factor``, a linear ramp between (the correction range of
    ``beta_fast`` / ``beta_slow`` rotations over the original context).
    YaRN's factor on cos and sin, ``mscale / mscale_all_dim``, is 1 in
    the published configuration (both 0.707) and is not applied."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.rope_factor <= 1:
        return extra

    def correction(rotations):
        return dim * math.log(cfg.rope_original_max_positions
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def rope(x, positions, inv_freq):
    """Rotate ``x (B, T, ..., R)`` by ``positions (B, T)``: the pair
    ``(2 i, 2 i + 1)`` turns by ``pos * inv_freq[i]``. The result lists
    the pairs' first members, then their second (the published code's
    order; both sides of a product use it). float32 in and out."""
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3)
                          + angle.shape[-1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1)


def _expand(latent, rope_key, w_uk, w_uv, dtype):
    """A block of cached positions -> every head's key ``(B, H, S, Dn +
    R)`` (``k_nope = c W_uk`` beside the one rotary key) and value ``(B,
    H, S, Dv)`` (``c W_uv``), float32 from operands in ``dtype``: what
    ``models/afmoe.py``'s running softmax reads. ``latent (B, S, C)``,
    ``rope_key (B, R, S)``, ``w_uk (H, Dn, C)``, ``w_uv (H, C, Dv)``."""
    f32 = jnp.float32
    lat = latent.astype(dtype)
    k_nope = jnp.einsum("bsc,hdc->bhsd", lat, w_uk.astype(dtype),
                        preferred_element_type=f32)
    v = jnp.einsum("bsc,hce->bhse", lat, w_uv.astype(dtype),
                   preferred_element_type=f32)
    k_pe = jnp.broadcast_to(jnp.swapaxes(rope_key, 1, 2)[:, None],
                            k_nope.shape[:3] + rope_key.shape[1:2])
    return jnp.concatenate([k_nope, k_pe.astype(f32)], axis=-1), v


def _read_latents(carry, q, latent, rope_key, w_uk, w_uv, live, at, dtype):
    """The cache's first ``live`` positions (a traced count: only what
    some row still reads is visited) into the running softmax, a block of
    :data:`KEY_BLOCK` at a time, each block expanded (:func:`_expand`);
    query ``t`` of row ``b`` reads the positions up to ``at[b, t]``."""
    S = latent.shape[1]
    blk = min(KEY_BLOCK, S)
    n_blocks = (jnp.minimum(live, S) + blk - 1) // blk

    def body(i, carry):
        # the last block of a length that is no multiple of the block
        # starts early; the positions it repeats are masked out
        start = jnp.minimum(i * blk, S - blk)
        index = start + jnp.arange(blk)
        k, v = _expand(
            jax.lax.dynamic_slice_in_dim(latent, start, blk, axis=1),
            jax.lax.dynamic_slice_in_dim(rope_key, start, blk, axis=2),
            w_uk, w_uv, dtype)
        ok = (index[None, None, :] <= at[:, :, None]) \
            & (index >= i * blk)[None, None, :]
        return _softmax_update(carry, q, k, v, ok, dtype)

    return jax.lax.fori_loop(0, n_blocks, body, carry)


def _kernel_seat(kernel, max_len: int) -> bool:
    """Does a call read the cache through ``kernel`` (``ops/mla_decode``
    for a decode step, ``ops/mla_prefill`` for a piece): on a TPU, for a
    slot of whole blocks."""
    return jax.default_backend() == "tpu" and max_len % kernel.BLOCK == 0


class LatentAttention(nn.Module):
    """Multi-head latent attention and its cache (the module docstring)."""
    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, x, offset, lengths, kv_positions):
        cfg = self.cfg
        B, T, d = x.shape
        H, Dn, R = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                    cfg.qk_rope_head_dim)
        C, Dv, dtype = cfg.kv_lora_rank, cfg.v_head_dim, cfg.dtype
        scale = cfg.softmax_scale
        init = nn.initializers.normal(0.02)
        w_uk = self.param("w_uk", init, (H, Dn, C), cfg.param_dtype)
        w_uv = self.param("w_uv", init, (H, C, Dv), cfg.param_dtype)
        step = kv_positions is not None
        at = kv_positions.astype(jnp.int32) if step \
            else offset[:, None] + jnp.arange(T)[None, :]       # (B, T)
        inv_freq = yarn_inv_freq(cfg)
        with jax.named_scope("mla/q"):
            c_q = _RMSNorm(cfg.rms_norm_eps, name="q_norm")(
                _linear(cfg, cfg.q_lora_rank, "q_down")(x))
            q = _linear(cfg, H * (Dn + R), "q_up")(c_q).reshape(
                B, T, H, Dn + R)
            q_nope = q[..., :Dn]
            q_pe = rope(q[..., Dn:], at, inv_freq)
        with jax.named_scope("mla/kv_compress"):
            ckr = _linear(cfg, C + R, "kv_down")(x)
            latent = _RMSNorm(cfg.rms_norm_eps, name="kv_norm")(
                ckr[..., :C])
            rope_key = jnp.swapaxes(rope(ckr[..., C:], at, inv_freq), 1,
                                    2)                      # (B, R, T)
        q_nope = jnp.swapaxes(q_nope, 1, 2)                 # (B, H, T, Dn)
        q_pe = jnp.swapaxes(q_pe, 1, 2)
        # the expanded form's query in XLA: every head one group of one
        # (afmoe's (B, K, G, T, D)), scaled
        q_xla = jnp.concatenate([q_nope, q_pe], axis=-1)[:, :, None] * scale
        carry = _softmax_init(B, H, 1, T, Dv)

        if not cfg.decode:
            with jax.named_scope("mla/expand"):
                causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
                k, v = _expand(latent, rope_key, w_uk, w_uv, dtype)
                out = _softmax_finish(_softmax_update(
                    carry, q_xla, k, v, causal[None], dtype))
        else:
            is_init = not self.has_variable("cache", "latent")
            lat_c = self.variable("cache", "latent", jnp.zeros,
                                  (B, cfg.max_seq_len, C), dtype)
            rk_c = self.variable("cache", "rope_key", jnp.zeros,
                                 (B, R, cfg.max_seq_len), dtype)
            if is_init:
                out = jnp.zeros((B, T, H * Dv), jnp.float32)
            elif step:
                pos = at[:, 0]
                with jax.named_scope("mla/kv_compress"):
                    lat_c.value = _put_rows(lat_c.value, latent, pos)
                    # (B, R, L) is write_rows' (B, H, D, L) view of a
                    # (B, L, 1, R) leaf: the axis moves are bitcasts
                    rk_c.value = jnp.moveaxis(write_rows(
                        jnp.moveaxis(rk_c.value[:, None], 3, 1),
                        jnp.moveaxis(rope_key[:, None], 3, 1), pos),
                        1, 3)[:, 0]
                with jax.named_scope("mla/absorbed"):
                    q_c = jnp.einsum(
                        "bhd,hdc->bhc", q_nope[:, :, 0].astype(dtype),
                        w_uk.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype)
                    q_r = q_pe[:, :, 0].astype(dtype)
                    live = pos + 1
                    if _kernel_seat(mla_decode, cfg.max_seq_len):
                        o = mla_decode.mla_decode(
                            q_c, q_r, lat_c.value, rk_c.value, live,
                            scale=scale)
                    else:
                        o = mla_decode.mla_decode_dense(
                            q_c, q_r, lat_c.value, rk_c.value, live,
                            scale=scale)
                    out = jnp.einsum("bhc,hce->bhe", o.astype(dtype),
                                     w_uv.astype(dtype),
                                     preferred_element_type=jnp.float32
                                     ).reshape(B, 1, H * Dv)
            else:
                with jax.named_scope("mla/kv_compress"):
                    lat_c.value = _put_rows(lat_c.value, latent, offset)
                    rk_c.value = jax.vmap(
                        lambda row, new, at:
                        jax.lax.dynamic_update_slice_in_dim(
                            row, new, at, axis=1))(
                        rk_c.value, rope_key.astype(dtype), offset)
                with jax.named_scope("mla/expand"):
                    if _kernel_seat(mla_prefill, cfg.max_seq_len):
                        out = jnp.moveaxis(mla_prefill.mla_prefill(
                            q_nope, q_pe, w_uk, w_uv, lat_c.value,
                            rk_c.value, offset, scale=scale), 1, 2
                        ).reshape(B, T, H * Dv)
                    else:
                        out = _softmax_finish(_read_latents(
                            carry, q_xla, lat_c.value, rk_c.value, w_uk,
                            w_uv, jnp.max(offset) + T, at, dtype))
        with jax.named_scope("mla/out"):
            return _linear(cfg, d, "out")(out)


class ExpertLayer(nn.Module):
    """The routed experts held here and the shared experts."""
    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, x, valid):
        cfg = self.cfg
        B, T, d = x.shape
        f, held = cfg.moe_intermediate_size, cfg.held
        fs = f * cfg.n_shared_experts
        init = nn.initializers.normal(0.02)
        w_router = self.param("router", init, (d, cfg.n_routed_experts),
                              cfg.param_dtype)
        w_gate_up = self.param("experts_gate_up", init, (held, d, 2 * f),
                               cfg.param_dtype)
        w_down = self.param("experts_down", init, (held, f, d),
                            cfg.param_dtype)
        flat = x.reshape(B * T, d)
        # the shape-building init pass of a cache computes nothing of the
        # experts: its zero cache must not wait for weights to be drawn
        is_init = cfg.decode and not self.has_variable("cache",
                                                       "expert_load")
        if is_init:
            routed = jnp.zeros((B * T, d), jnp.float32)
            load = jnp.zeros((held,), jnp.int32)
        else:
            with jax.named_scope("moe/route"):
                logits = jnp.dot(flat.astype(jnp.float32),
                                 w_router.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
            routed, load = held_experts(
                flat, logits, None, w_gate_up, w_down,
                top_k=cfg.num_experts_per_tok, offset=cfg.expert_offset,
                normalise=cfg.norm_topk_prob,
                scale=cfg.routed_scaling_factor,
                valid=None if valid is None else valid.reshape(B * T),
                dtype=cfg.dtype, scoring=cfg.scoring_func,
                n_group=cfg.n_group, topk_group=cfg.topk_group)
        if cfg.decode:
            # the rows each held expert took in this call (the leaf of a
            # "counter": no slot owns it, the engine hands it on)
            count = self.variable("cache", "expert_load", jnp.zeros,
                                  (held,), jnp.int32)
            count.value = load
        with jax.named_scope("moe/shared"):
            gu = _linear(cfg, 2 * fs, "shared_gate_up")(x)
            shared = _linear(cfg, d, "shared_down")(
                jax.nn.silu(gu[..., :fs]) * gu[..., fs:])
        return routed.reshape(B, T, d) + shared


class DeepseekV2LM(nn.Module):
    """See the module docstring. ``positions`` is accepted and unused
    (a decode step's positions are ``kv_positions``, a piece's are
    ``offset + arange``); ``lengths`` (B,) is the valid tokens of a
    continue call's piece (``None`` = all of it), ``offset`` (B,) the
    absolute position of its first token (``None`` = 0)."""
    cfg: DeepseekV2Config

    #: the prefill is a *continue* from the cache it is given:
    #: ``ServeEngine(prefill_chunk=)`` may feed a prompt into a dense
    #: slot in pieces. A latent row is no K/V row of heads, which pages,
    #: int8 storage, the prefix cache, draft verification and the LoRA
    #: bank do not know: ``ServeEngine.__init__`` refuses them by name
    continues_prefill = True

    def cache_leaf(self, names: Tuple[str, ...]) -> CacheLeaf:
        """What one leaf of the ``cache`` collection is (by its path)."""
        return {
            "latent": CacheLeaf(0, "global", seq_axis=1),
            "rope_key": CacheLeaf(0, "global", seq_axis=2),
            "expert_load": CacheLeaf(None, "counter"),
        }[names[-1]]

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, positions=None,
                 kv_positions=None, lengths=None, offset=None):
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.rms_norm_eps
        proceed = cfg.decode and kv_positions is None
        valid = None
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        else:
            lengths = jnp.asarray(lengths, jnp.int32)
            valid = jnp.arange(T)[None, :] < lengths[:, None]
        offset = jnp.zeros((B,), jnp.int32) if offset is None \
            else jnp.asarray(offset, jnp.int32)
        embedding = self.param("embedding", nn.initializers.normal(0.02),
                               (cfg.vocab_size, cfg.hidden_size),
                               cfg.param_dtype)
        x = jnp.take(embedding, tokens, axis=0).astype(jnp.float32)
        for layer in range(cfg.num_hidden_layers):
            scope = f"layer_{layer}"
            x = x + LatentAttention(cfg, name=scope + "_attn")(
                _RMSNorm(eps, name=scope + "_attn_norm")(x), offset, lengths,
                kv_positions)
            h = _RMSNorm(eps, name=scope + "_mlp_norm")(x)
            if layer < cfg.first_k_dense_replace:
                x = x + GatedMLP(cfg, name=scope + "_mlp")(h)
            else:
                x = x + ExpertLayer(cfg, name=scope + "_moe")(h, valid)
        if proceed:
            # the head over each row's last valid token only
            x = _gather_rows(x, jnp.maximum(lengths - 1, 0))
        x = _RMSNorm(eps, name="norm_f")(x)
        return _linear(cfg, cfg.vocab_size, "lm_head")(x)
