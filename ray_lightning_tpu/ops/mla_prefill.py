"""Multi-head latent attention of a prompt piece in its *expanded* form
(``models/deepseek_v2.py``): each head's keys and values are made from
the cached latents a block at a time, inside the kernel, and never
leave it.

A piece of ``T`` queries at a row's offset attends every cached position
up to its own. Done in XLA a block of latents at a time, every head's
float32 scores — ``(T, block)`` a head and row — go through HBM and back
several times a block; here one grid step holds one head of one row: its
``T`` queries (``q_nope`` and ``q_pe``) and that head's ``W_uk`` / ``W_uv``
stay in VMEM, and a loop over the row's live blocks of latents copies
each ``(block, C)`` latent and ``(R, block)`` rotary key HBM to VMEM by
a double-buffered DMA, expands ``k = lat W_uk_h^T`` and ``v = lat
W_uv_h``, scores ``q_nope k^T + q_pe k_r`` and folds them into an online
softmax. Blocks past the row's ``offset + T`` are never fetched; the
causal mask covers the diagonal's blocks. The cache is as the decode
kernel (``ops/mla_decode.py``) reads it: ``(B, L, C)`` and ``(B, R, L)``.

Arithmetic: the XLA form's roundings (``models/deepseek_v2.py::_expand``
and ``models/afmoe.py``'s running softmax): latents and weights in the
compute dtype into the expansion with float32 accumulation, the keys and
values rounded to the compute dtype, float32 scores and softmax, the
weights rounded to the compute dtype before they meet the values; the
running max, denominator and accumulator in float32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
#: cached positions a block carries
BLOCK = 256
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def blocks_read(offset, width: int, block: int = BLOCK):
    """Blocks the kernel copies for a row whose piece of ``width`` starts
    at ``offset``."""
    return -(-(offset + width) // block)


def _kernel(off_ref, qn_ref, qr_ref, uk_ref, uv_ref, c_hbm, r_hbm, o_ref,
            cbuf, rbuf, sem, acc_ref, *, block, n_max, scale):
    b = pl.program_id(0)
    f32 = jnp.float32
    T = qn_ref.shape[2]
    offset = off_ref[b]
    n = jnp.clip(blocks_read(offset, T, block), 1, n_max)

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(c_hbm.at[b, at], cbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(r_hbm.at[b, :, at], rbuf.at[slot],
                                      sem.at[1, slot]))

    for c in copies(0, 0):
        c.start()
    qn = qn_ref[0, 0]                                   # (T, Dn)
    qr = qr_ref[0, 0]                                   # (T, R)
    uk = uk_ref[0]                                      # (Dn, C)
    uv = uv_ref[0]                                      # (C, Dv)
    dtype = uk.dtype
    at = offset + jax.lax.broadcasted_iota(jnp.int32, (T, block), 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(j, carry):
        m, l = carry
        slot = j % 2

        @pl.when(j + 1 < n)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        lat = cbuf[slot]                                # (block, C)
        k = jax.lax.dot_general(lat, uk, _NT, preferred_element_type=f32)
        v = jnp.dot(lat, uv, preferred_element_type=f32)
        s = (jax.lax.dot_general(qn, k.astype(dtype), _NT,
                                 preferred_element_type=f32)
             + jnp.dot(qr, rbuf[slot], preferred_element_type=f32)) * scale
        key = j * block + jax.lax.broadcasted_iota(jnp.int32, (T, block), 1)
        s = jnp.where(key <= at, s, _BIG_NEG)           # (T, block)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(dtype), v.astype(dtype), preferred_element_type=f32)
        return m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)

    init = (jnp.full((T, 1), _BIG_NEG, f32), jnp.zeros((T, 1), f32))
    _, l = jax.lax.fori_loop(0, n, step, init)
    o_ref[0, 0] = acc_ref[...] / l


def mla_prefill(q_nope: jax.Array, q_pe: jax.Array, w_uk: jax.Array,
                w_uv: jax.Array, latent: jax.Array, rope_key: jax.Array,
                offset: jax.Array, *, scale: float,
                block: Optional[int] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Expanded attention of each row's piece over its cached positions.

    ``q_nope`` (B, H, T, Dn) and ``q_pe`` (B, H, T, R) in the cache's
    dtype, query ``t`` of row ``b`` at absolute position ``offset[b] +
    t``; ``w_uk`` (H, Dn, C) and ``w_uv`` (H, C, Dv); ``latent`` (B, L,
    C) and ``rope_key`` (B, R, L), the piece's own positions written;
    ``offset`` (B,) int32. Returns (B, H, T, Dv) float32. ``block``
    defaults to :data:`BLOCK` and must divide ``L``; ``interpret``
    defaults to off on a TPU and on elsewhere.
    """
    B, H, T, Dn = q_nope.shape
    R, L, C = q_pe.shape[-1], latent.shape[1], latent.shape[2]
    Dv = w_uv.shape[-1]
    block = block or BLOCK
    if L % block:
        raise ValueError(f"mla_prefill reads whole blocks of {block} "
                         f"positions: a slot of {L} is none")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = latent.dtype
    kernel = functools.partial(_kernel, block=block, n_max=L // block,
                               scale=scale)

    def head(width):
        return pl.BlockSpec((1, 1, T, width), lambda b, h, off: (b, h, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H),
            in_specs=[head(Dn), head(R),
                      pl.BlockSpec((1, Dn, C), lambda b, h, off: (h, 0, 0)),
                      pl.BlockSpec((1, C, Dv), lambda b, h, off: (h, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=head(Dv),
            scratch_shapes=[
                pltpu.VMEM((2, block, C), dtype),
                pltpu.VMEM((2, R, block), rope_key.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((T, Dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_prefill",
    )(offset.astype(jnp.int32), q_nope.astype(dtype), q_pe.astype(dtype),
      w_uk.astype(dtype), w_uv.astype(dtype), latent, rope_key)
