"""Per-row decode attention: each row reads its cached K/V only as far as
its own length.

A dense serve slot holds ``L`` positions a row, but a row at step ``t`` of
its request has only its prompt and ``t`` tokens behind it. The masked
read (``ops/attention.py::dot_product_attention`` under the per-row
``key <= position`` mask) streams all ``L`` positions of every row and
throws most of them away: ``gpt2-large.serve.closed40`` holds contexts of
about 260 in slots of 1024 (``PERF.md`` section 5). This kernel is one
``pallas_call`` a layer whose grid walks the rows. A row copies its live
key blocks, ``ceil(length / block)`` of them, HBM to VMEM by its own
double-buffered DMA, and the last block of a row starts the copy of the
next row's first, so the stream does not stop between rows. Blocks past a
row's length are never fetched.

Layout. The TPU holds a ``(B, L, H, D)`` cache leaf with positions
minor-most (``ops/cache_write.py`` says why), so the kernel sees it as
``(B, H·D, L)``: a bitcast, and a block of keys is ``(H·D, block)`` with
heads and their dims on sublanes and positions on lanes. The query row is
spread over a block-diagonal ``(Hp, H·D)`` (head ``h``'s dims in row
``h``, zeros elsewhere; ``Hp`` is ``H`` rounded up to a sublane tile), so
the scores of all heads are one product ``(Hp, H·D) @ (H·D, block)`` and
the context one ``(Hp, block) @ (block, H·D)``; each head keeps its own
lanes of the context row at the end.

Arithmetic. The dense path's roundings: bf16 query and K/V into the
products with f32 accumulation, f32 scores, f32 softmax, weights rounded
to the compute dtype before they meet V. The online softmax keeps the
running max, denominator and accumulator in f32 across a row's blocks;
the dense path differs from it only by summation order and by the
rescaling. Positions of the last block past the length are masked.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
#: positions a DMA block carries: one lane row of the cache's minor axis
BLOCK = 128
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def decode_block(max_len: int) -> Optional[int]:
    """The key block for slots of ``max_len`` positions, or ``None`` where
    the slot is no whole number of blocks."""
    return BLOCK if max_len % BLOCK == 0 else None


def blocks_read(lengths, block: int = BLOCK):
    """Key blocks the kernel copies for rows holding ``lengths``."""
    return -(-lengths // block)


def _kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, acc_ref,
            slot_ref, *, block, n_max, head_dim, scale):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    Hp, HD = acc_ref.shape
    f32 = jnp.float32

    def copies(row, j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[row, :, at], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[row, :, at], vbuf.at[slot],
                                      sem.at[1, slot]))

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    length = len_ref[b]
    n = jnp.clip(blocks_read(length, block), 1, n_max)
    base = slot_ref[0]
    # rows of the block-diagonal query: head h's dims in row h
    row = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 1)
    own = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)
    q = q_ref[0].astype(f32)                            # (1, H·D)
    qbd = jnp.where(own, q, 0.0).astype(kbuf.dtype)     # (Hp, H·D)
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
        s = jnp.dot(qbd, kbuf[slot], preferred_element_type=f32) * scale
        key = j * block + jax.lax.broadcasted_iota(jnp.int32, (Hp, block),
                                                   1)
        s = jnp.where(key < length, s, _BIG_NEG)        # (Hp, block)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot], _NT,
            preferred_element_type=f32)                 # (Hp, H·D)
        return m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)

    init = (jnp.full((Hp, 1), _BIG_NEG, f32), jnp.zeros((Hp, 1), f32))
    _, l = jax.lax.fori_loop(0, n, step, init)
    slot_ref[0] = (base + n) % 2
    out = jnp.where(own, acc_ref[...] / l, 0.0)
    o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, block: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Attention of one query a row over that row's first ``lengths[b]``
    cached positions.

    ``q`` (B, 1, H, D); ``k_cache`` / ``v_cache`` (B, L, H, D), the just
    written position included; ``lengths`` (B,) int32, each at least 1 (a
    length past ``L`` reads the whole slot). Returns (B, 1, H, D) in
    ``q.dtype``. ``block`` defaults to :data:`BLOCK` and must divide
    ``L``; ``interpret`` defaults to off on a TPU and on elsewhere.
    """
    B, T, H, D = q.shape
    L = k_cache.shape[1]
    block = block or BLOCK
    if T != 1 or L % block:
        raise ValueError(
            f"decode_attention takes one query a row over whole blocks of "
            f"{block} positions: got T={T}, L={L}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    HD = H * D
    Hp = -(-H // 16) * 16

    def view(cache):          # positions minor-most: a bitcast on the TPU
        return jnp.moveaxis(cache, 1, -1).reshape(B, HD, L)

    kernel = functools.partial(_kernel, block=block, n_max=L // block,
                               head_dim=D, scale=D ** -0.5)
    row = pl.BlockSpec((1, 1, HD), lambda b, lens: (b, 0, 0))
    with jax.named_scope("attention/decode_rows"):
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B,),
                in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=row,
                scratch_shapes=[
                    pltpu.VMEM((2, HD, block), k_cache.dtype),
                    pltpu.VMEM((2, HD, block), v_cache.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((Hp, HD), jnp.float32),
                    pltpu.SMEM((1,), jnp.int32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, 1, HD), q.dtype),
            # the next row's first block is copied during this row's
            # last: the rows run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="decode_attention",
        )(lengths.astype(jnp.int32), q.reshape(B, 1, HD), view(k_cache),
          view(v_cache))
    return out.reshape(B, 1, H, D)
