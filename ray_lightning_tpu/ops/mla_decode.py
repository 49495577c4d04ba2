"""Per-row decode attention over a latent cache (multi-head latent
attention in its *absorbed* form, ``models/deepseek_v2.py``): each row
reads its cached latent and rotary key only as far as its own length.

A latent cache holds, a position, one ``C``-wide latent ``c`` (the keys'
and values' common compression, RMS-normed) and one ``R``-wide rotary key
``k_r`` shared by every head. With ``W_uk`` folded into the query (``q_c
= q_nope W_uk^T``, ``C`` wide a head), head ``h``'s score against
position ``j`` is ``(q_c[h] . c[j] + q_r[h] . k_r[j]) * scale`` and its
output ``sum_j p[h, j] c[j]`` (``W_uv`` is applied after, by the caller).
So the latent is both the key and the value, and all heads read the same
bytes: a row's ``H`` heads are the rows of one ``(H, C) @ (C, block)``
product and one ``(H, block) @ (block, C)``, whatever the number of
heads — a whole MXU tile at the published 128.

The kernel is one ``pallas_call`` a layer whose grid walks the rows, as
``ops/pallas_decode.py``'s: a row copies its live blocks of ``block``
positions, ``ceil(length / block)`` of them, HBM to VMEM by its own
double-buffered DMA, and the last block of a row starts the copy of the
next row's first. Blocks past a row's length are never fetched. The
cache is ``(B, L, C)``, positions on sublanes, and ``(B, R, L)``,
positions on lanes (``R`` = 64 values would fill half a lane row, which
the TPU pads and a copy cannot slice): a block is ``(block, C)`` and
``(R, block)``, and nothing is transposed.

Arithmetic: the dense path's roundings (:func:`mla_decode_dense`, the
XLA form off the TPU and the tests' witness): query and cache in the
compute dtype into the products with float32 accumulation, float32
scores and softmax, weights rounded to the compute dtype before they meet
the latent. The online softmax keeps the running max, denominator and
accumulator in float32; it differs from the dense path only by summation
order and the rescaling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
#: positions a DMA block carries
BLOCK = 512
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def blocks_read(lengths, block: int = BLOCK):
    """Blocks the kernel copies for rows holding ``lengths``."""
    return -(-lengths // block)


def _kernel(len_ref, qc_ref, qr_ref, c_hbm, r_hbm, o_ref, cbuf, rbuf, sem,
            acc_ref, slot_ref, *, block, n_max, scale):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    f32 = jnp.float32

    def copies(row, j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(c_hbm.at[row, at], cbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(r_hbm.at[row, :, at], rbuf.at[slot],
                                      sem.at[1, slot]))

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    length = len_ref[b]
    n = jnp.clip(blocks_read(length, block), 1, n_max)
    base = slot_ref[0]
    qc = qc_ref[0]                                      # (H, C)
    qr = qr_ref[0]                                      # (H, R)
    H = qc.shape[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(j, carry):
        m, l = carry
        slot = (base + j) % 2

        @pl.when(j + 1 < n)
        def _():
            for c in copies(b, j + 1, 1 - slot):
                c.start()

        @pl.when((j + 1 == n) & (b + 1 < rows))
        def _():
            for c in copies(b + 1, 0, 1 - slot):
                c.start()

        for c in copies(b, j, slot):
            c.wait()
        lat = cbuf[slot]                                # (block, C)
        s = (jax.lax.dot_general(qc, lat, _NT, preferred_element_type=f32)
             + jnp.dot(qr, rbuf[slot], preferred_element_type=f32)) * scale
        key = j * block + jax.lax.broadcasted_iota(jnp.int32, (H, block), 1)
        s = jnp.where(key < length, s, _BIG_NEG)        # (H, block)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(lat.dtype), lat, preferred_element_type=f32)
        return m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)

    init = (jnp.full((H, 1), _BIG_NEG, f32), jnp.zeros((H, 1), f32))
    _, l = jax.lax.fori_loop(0, n, step, init)
    slot_ref[0] = (base + n) % 2
    o_ref[0] = acc_ref[...] / l


def mla_decode(q_c: jax.Array, q_r: jax.Array, latent: jax.Array,
               rope_key: jax.Array, lengths: jax.Array, *, scale: float,
               block: Optional[int] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """Absorbed attention of one query a row over that row's first
    ``lengths[b]`` cached positions.

    ``q_c`` (B, H, C) and ``q_r`` (B, H, R) in the cache's dtype;
    ``latent`` (B, L, C) and ``rope_key`` (B, R, L), the just written
    position included; ``lengths`` (B,) int32, each at least 1 (a length
    past ``L`` reads the whole slot). Returns (B, H, C) float32: each
    head's softmax-weighted sum of latents. ``block`` defaults to
    :data:`BLOCK` and must divide ``L``; ``interpret`` defaults to off on
    a TPU and on elsewhere.
    """
    B, H, C = q_c.shape
    L, R = latent.shape[1], rope_key.shape[1]
    block = block or BLOCK
    if L % block:
        raise ValueError(f"mla_decode reads whole blocks of {block} "
                         f"positions: a slot of {L} is none")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(_kernel, block=block, n_max=L // block,
                               scale=scale)

    def row(width):
        return pl.BlockSpec((1, H, width), lambda b, lens: (b, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[row(C), row(R), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(C),
            scratch_shapes=[
                pltpu.VMEM((2, block, C), latent.dtype),
                pltpu.VMEM((2, R, block), rope_key.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, C), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, C), jnp.float32),
        # the next row's first block is copied during this row's last:
        # the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(lengths.astype(jnp.int32), q_c, q_r.astype(rope_key.dtype), latent,
      rope_key)


def mla_decode_dense(q_c, q_r, latent, rope_key, lengths, *, scale):
    """The same in XLA, every row over the whole slot under a mask."""
    f32 = jnp.float32
    dtype = latent.dtype
    s = (jnp.einsum("bhc,blc->bhl", q_c.astype(dtype), latent,
                    preferred_element_type=f32)
         + jnp.einsum("bhr,brl->bhl", q_r.astype(dtype), rope_key,
                      preferred_element_type=f32)) * scale
    live = jnp.arange(latent.shape[1])[None, None, :] < lengths[:, None,
                                                                 None]
    p = jax.nn.softmax(jnp.where(live, s, _BIG_NEG), axis=-1)
    return jnp.einsum("bhl,blc->bhc", p.astype(dtype), latent,
                      preferred_element_type=f32)
