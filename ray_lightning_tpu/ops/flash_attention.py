"""Blockwise (flash) attention: O(block²) VMEM instead of O(T²) HBM.

Online-softmax formulation over KV blocks — the memory-efficient attention
the reference never needed (its largest axis was parameter memory, SURVEY.md
§5 "long-context: entirely absent") but a TPU-native framework must own for
long sequences. This module is ``attention_impl="flash"``: on a TPU the
hand-tiled pallas kernels (``ops/pallas_flash.py`` — the same kernels the
default seat of ``models/transformer.py`` picks by itself for causal
self-attention, ``PERF.md`` section 6, PR 33), elsewhere the XLA
implementation below (a python loop over query blocks, ``lax.scan`` over
KV blocks), which ring attention shares one step of.

Falls back to :func:`dot_product_attention` for arbitrary additive masks or
attention dropout (neither fits the blockwise accumulator cheaply).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.ops.attention import dot_product_attention

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_update(carry: Tuple[jax.Array, jax.Array, jax.Array],
                  qi: jax.Array, kj: jax.Array, vj: jax.Array,
                  qpos: jax.Array, kpos: jax.Array,
                  causal: bool, kv_len: int, scale: float):
    """One online-softmax accumulation step.

    carry: m (B,H,bq) running max, l (B,H,bq) running denom,
           acc (B,bq,H,D) running numerator (f32).
    qi: (B,bq,H,D); kj/vj: (B,bk,H,D); qpos (bq,), kpos (bk,) global
    positions (kpos may exceed kv_len for padding — masked out).
    Shared by the flash kernel and ring attention (one step per ring hop).
    """
    m, l, acc = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                   preferred_element_type=jnp.float32) * scale
    allow = (kpos < kv_len)[None, :]
    if causal:
        allow = allow & (kpos[None, :] <= qpos[:, None])
    s = jnp.where(allow[None, None], s, _BIG_NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(allow[None, None], p, 0.0)
    alpha = jnp.exp(m - m_new)  # (B,H,bq)
    l_new = l * alpha + p.sum(axis=-1)
    alpha_t = jnp.transpose(alpha, (0, 2, 1))[..., None]  # (B,bq,H,1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vj.dtype), vj,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha_t + pv
    return m_new, l_new, acc_new


def _finalize(l: jax.Array, acc: jax.Array, dtype) -> jax.Array:
    l_t = jnp.transpose(l, (0, 2, 1))[..., None]  # (B,bq,H,1)
    return jnp.where(l_t > 0, acc / jnp.maximum(l_t, 1e-30), 0.0).astype(
        dtype)


def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    *,
                    causal: bool = False,
                    mask: Optional[jax.Array] = None,
                    dropout_rate: float = 0.0,
                    dropout_rng: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    softmax_dtype=jnp.float32,
                    use_pallas: Optional[bool] = None) -> jax.Array:
    """Blockwise attention; signature-compatible with
    :func:`dot_product_attention`. Shapes (B, T, H, D)."""
    del softmax_dtype  # always f32 in the accumulator
    if mask is not None or (dropout_rate > 0.0 and dropout_rng is not None):
        return dot_product_attention(
            q, k, v, causal=causal, mask=mask, dropout_rate=dropout_rate,
            dropout_rng=dropout_rng)

    if use_pallas is None:
        # trace-safe platform probe (tracers have no .devices())
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention
        return pallas_flash_attention(q, k, v, causal=causal,
                                      block_q=block_q, block_k=block_k)

    B, T, H, D = q.shape
    S = k.shape[1]
    bq, bk = min(block_q or 512, T), min(block_k or 1024, S)
    n_q, n_k = -(-T // bq), -(-S // bk)
    Tp, Sp = n_q * bq, n_k * bk
    scale = D ** -0.5

    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))

    q_blocks = jnp.moveaxis(qp.reshape(B, n_q, bq, H, D), 1, 0)
    k_blocks = jnp.moveaxis(kp.reshape(B, n_k, bk, H, D), 1, 0)
    v_blocks = jnp.moveaxis(vp.reshape(B, n_k, bk, H, D), 1, 0)

    # causal offset aligns the *ends* of q and kv (standard for S != T)
    pos_shift = S - T

    # python loop over q blocks: the block index stays *static*, so the
    # causal KV-block skip is a static slice and the inner scan remains
    # reverse-differentiable (a dynamic fori_loop bound would not be)
    out_blocks = []
    for ib in range(n_q):
        off = ib * bq
        qi = q_blocks[ib]
        qpos = off + jnp.arange(bq) + pos_shift
        if causal:
            # last key this q block may attend to is off + bq - 1 + pos_shift
            n_needed = max(0, min(n_k,
                                  (off + bq + pos_shift + bk - 1) // bk))
        else:
            n_needed = n_k

        def inner(carry, kv, qi=qi, qpos=qpos):
            kj, vj, koff = kv
            kpos = koff + jnp.arange(bk)
            return _block_update(carry, qi, kj, vj, qpos, kpos, causal, S,
                                 scale), None

        init = (jnp.full((B, H, bq), _BIG_NEG, jnp.float32),
                jnp.zeros((B, H, bq), jnp.float32),
                jnp.zeros((B, bq, H, D), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            inner, init,
            (k_blocks[:n_needed], v_blocks[:n_needed],
             jnp.arange(n_needed) * bk))
        out_blocks.append(_finalize(l, acc, q.dtype))

    out = jnp.stack(out_blocks, axis=1).reshape(B, Tp, H, D)
    return out[:, :T]
