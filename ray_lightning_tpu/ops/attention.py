"""Attention ops: XLA-fused reference path + pallas flash-attention hook.

The reference framework has no kernels of its own (its hot loop is torch
DDP); a TPU-native framework owns its attention math. Two tiers:

- :func:`dot_product_attention` — plain jnp einsum formulation: the
  correctness baseline, the CPU/test path, and what every masked, cached,
  non-causal, short or dropout-carrying call runs. It materialises the
  ``(B, H, T, S)`` scores.
- :mod:`ray_lightning_tpu.ops.flash_attention` — blockwise online-softmax
  attention (XLA loop), with the hand-tiled pallas kernels in
  ``ops/pallas_flash.py``; asked for by ``attention_impl="flash"``, and
  picked by the default seat itself (``models/transformer.py::
  attention_seat``) for causal self-attention on a TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def causal_mask(q_len: int, kv_len: int, dtype=jnp.float32) -> jax.Array:
    """Additive causal mask of shape (1, 1, q_len, kv_len)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
    offset = kv_len - q_len
    allow = j <= i + offset
    mask = jnp.where(allow, 0.0, jnp.finfo(dtype).min).astype(dtype)
    return mask[None, None, :, :]


def dot_product_attention(q: jax.Array,
                          k: jax.Array,
                          v: jax.Array,
                          *,
                          causal: bool = False,
                          mask: Optional[jax.Array] = None,
                          dropout_rate: float = 0.0,
                          dropout_rng: Optional[jax.Array] = None,
                          softmax_dtype=jnp.float32) -> jax.Array:
    """Multi-head attention core. Shapes: (B, T, H, D) for q/k/v.

    Softmax runs in ``softmax_dtype`` (f32) regardless of input dtype —
    the standard bf16-safe formulation for the MXU.
    """
    *_, num_heads, head_dim = q.shape
    del num_heads
    scale = head_dim ** -0.5
    # the three scopes name the ops of this path in a device profile
    # (docs/observability.md, "Device scopes"); they add no op
    with jax.named_scope("attention/scores"):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=softmax_dtype) * scale
        if causal:
            logits = logits + causal_mask(q.shape[1], k.shape[1],
                                          dtype=softmax_dtype)
        if mask is not None:
            logits = logits + mask.astype(softmax_dtype)
    with jax.named_scope("attention/softmax"):
        weights = jax.nn.softmax(logits.astype(softmax_dtype), axis=-1)
        if (causal and q.shape[1] > k.shape[1]) or mask is not None:
            # Fully-masked rows (end-aligned causal with q_len > kv_len,
            # or a user mask): softmax of all -inf is uniform garbage;
            # emit exactly 0 instead — the same convention as the flash
            # kernels, so impls are swappable. Statically impossible when
            # q_len <= kv_len and no mask is given, so the hot path skips
            # the reduction at trace time.
            all_masked = jnp.all(
                logits <= jnp.finfo(softmax_dtype).min * 0.5,
                axis=-1, keepdims=True)
            weights = jnp.where(all_masked, 0.0, weights)
        weights = weights.astype(q.dtype)
        if dropout_rate > 0.0 and dropout_rng is not None:
            keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                        weights.shape)
            weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)
    with jax.named_scope("attention/context"):
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v,
                          preferred_element_type=q.dtype)
