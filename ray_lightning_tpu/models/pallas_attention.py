"""Pallas paged-attention kernel: fused page gather + in-kernel int8
dequant + tiled softmax on the decode/verify hot path.

This is the hand-tiled half of the paged serving story
(``docs/serving.md``): PR 11's page-native attention already reads and
writes K/V through the page table in pure XLA, but that path still
materializes page-sized score/output temporaries between HLO ops, and
int8 KV codes are dequantized into compute-dtype blocks the compiler
schedules as ordinary tensors. This kernel does the whole read side of
cached attention in ONE ``pallas_call`` per layer, in the mold of
PagedAttention (Kwon et al. 2023) with FlashAttention-style tiling
(Dao et al. 2022):

- **page-table-indexed block loads** — the page table is a
  scalar-prefetch operand (``PrefetchScalarGridSpec``), so each grid
  step's ``BlockSpec`` index map picks the ARENA page to stream into
  VMEM directly from the table (unmapped −1 entries clamp to page 0,
  the same finite-junk-the-mask-never-admits argument as the XLA
  paths). Only occupied pages are ever touched; nothing shaped like
  ``num_slots x max_seq_len`` exists anywhere.
- **in-kernel int8 dequant** — int8 arenas stream CODES (int8) and
  per-page-per-head scales (f32) through the block pipeline; the
  ``codes x scales`` multiply happens on the (page_size, H, D) VMEM
  block right before the dot. No dense dequantized K/V arena is ever
  materialized — the only full-precision K/V in existence is one
  page's worth of VMEM scratch per grid step.
- **tiled softmax, f32 accumulators** — scores are computed blockwise
  per page column into a VMEM-resident ``(T, max_seq_len, Hp)`` f32
  logits tile (key positions on sublanes, heads on lanes) with the
  per-row block-causal mask (``key_pos <= kv_positions[row, q]``) fused
  into the same step; the softmax then runs ONCE, exactly, over the
  completed tile (grid phase 2), and the output accumulates blockwise
  over V page columns in f32. Exact softmax — not the online
  approximation — is deliberate: operand roundings, mask and softmax
  are the XLA page-native path's, so the two differ only by f32
  summation order (``docs/serving.md`` says what that buys on the CPU
  tier and on the chip).
- **heads on the lane axis** — what makes it lower. Mosaic refuses a
  ``(1, T)`` or ``(1, 1, H, 1)`` block and any dot that batches over a
  middle axis, so the wrapper views ``q``/arena as ``(..., H*D)`` (free
  reshapes), positions ride in SMEM beside the page table (scalar
  prefetch), per-page scales arrive as one lane-padded ``(1, Hp)`` row,
  and every in-kernel value is 2-D: per-head ``q.k`` is a lane-wise
  product summed by an f32 one-hot selector dot ``(H*D, Hp)``, and the
  per-head weight is broadcast back over its lanes by the transposed
  selector before meeting V. Compiled for a v5e at GPT-2-small shapes by
  ``tests/test_chip_compile.py``; run on the chip by ``chip_smoke.py``.

Grid: ``(B, 2 * pages_per_slot)`` with the page axis innermost and
sequential — steps ``0..pp-1`` score K pages, steps ``pp..2pp-1``
accumulate V pages (the softmax fires on the first output step). The
logits tile and the ``(T, H*D)`` accumulator live in VMEM scratch and
persist across the inner grid, exactly the scheme
``ops/pallas_flash.py`` uses. VMEM cost per slot is
``T * max_seq_len * Hp`` f32 for the tile (0.5 MiB per query row at
GPT-2-small serving shapes) — under the ~16 MB budget for decode and
``spec_k + 1`` verify blocks.

On a backend without a TPU the kernel runs under **pallas interpret
mode** (the same kernel body, executed by XLA CPU), which is how the
CPU tier-1 suite pins it; wall-clock there is honestly worse than the
XLA path (interpretation tax). On a TPU backend it is compiled, always:
a kernel that cannot lower raises the compiler's message — it never
runs interpreted there and never gives way to the XLA path.

Engines select this path with ``ServeEngine(...,
attention_kernel="pallas")`` on top of ``page_native=True`` — see
``MultiHeadAttention._page_native_attention`` for the call site (the
write half stays in XLA: T tokens' K/V land in their owning pages
through the page table before the kernel reads).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "interpret_default"]

_BIG_NEG = float(jnp.finfo(jnp.float32).min)


def interpret_default() -> bool:
    """Interpret mode off-TPU (the CPU tier-1 correctness path); on a TPU
    backend always False — the kernel compiles or the program raises."""
    return jax.default_backend() != "tpu"


def _head_selectors(H: int, D: int):
    """One-hot maps between the ``H * D`` lane axis (head ``h`` owns
    lanes ``h*D .. h*D+D-1``) and a head axis padded to a lane multiple:
    ``sel`` (H*D, Hp) sums each head's lanes into its column, ``sel.T``
    (Hp, H*D) broadcasts a per-head value back over its lanes. Both are
    exact under an f32 dot (one non-zero term per output element)."""
    hp = -(-H // 128) * 128
    sel = (np.arange(H * D)[:, None] // D
           == np.arange(hp)[None, :]).astype(np.float32)
    return jnp.asarray(sel), jnp.asarray(sel.T)


def _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, sel_ref, selt_ref,
            ks_ref, vs_ref, o_ref, logits_ref, acc_ref, *,
            page_size: int, pages_per_slot: int, scale: float,
            compute_dtype):
    """One grid step; see the module docstring for the two-phase plan.

    Heads ride the LANE axis (``H * D`` wide) so every value in the
    kernel is 2-D and (8, 128)-tileable — Mosaic lowers no dot that
    batches over a middle axis. Per-head reductions and broadcasts go
    through the one-hot selectors (:func:`_head_selectors`).
    ``ks_ref``/``vs_ref`` are None on full-precision arenas (the plain
    wrapper below drops them from the signature — pallas passes refs
    positionally).
    """
    del pt_ref  # consumed by the index maps
    b = pl.program_id(0)
    j = pl.program_id(1)
    pp = pages_per_slot
    ps = page_size
    T = q_ref.shape[1]
    hp = sel_ref.shape[1]
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST

    def load(ref, sref):
        blk = ref[0]                                     # (ps, H*D)
        if sref is None:
            return blk.astype(f32)
        # kv_dequantize, blockwise: codes (int8) x per-page-per-head
        # f32 scales -> compute dtype, on VMEM scratch only. The (1, Hp)
        # scale row is broadcast over its head's lanes by the selector
        # (8 identical rows keep the dot sublane-aligned).
        s_lane = jnp.dot(jnp.broadcast_to(sref[0], (8, hp)), selt_ref[...],
                         preferred_element_type=f32, precision=hi)[:1]
        return (blk.astype(f32) * s_lane).astype(compute_dtype).astype(f32)

    @pl.when(j < pp)
    def _scores():
        kb = load(k_ref, ks_ref)                         # (ps, H*D) f32
        qb = q_ref[0].astype(f32)                        # (T, H*D)
        # page j covers absolute positions j*ps .. j*ps+ps-1
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, hp), 0)
        col = pl.multiple_of(j * ps, ps)
        for t in range(T):
            # per-head q.k: lane-wise products (exact in f32 for bf16
            # operands), summed per head by the selector dot
            s = jnp.dot(kb * qb[t:t + 1], sel_ref[...],
                        preferred_element_type=f32, precision=hi)
            # per-row block-causal mask fused into the score step
            bias = jnp.where(kpos <= pos_ref[b, t], 0.0, _BIG_NEG)
            logits_ref[t, pl.ds(col, ps), :] = s * scale + bias

    @pl.when(j == pp)
    def _softmax():
        # the tile is complete: ONE exact f32 softmax over every key
        # position, the XLA page-native path's jax.nn.softmax — weights
        # overwrite the tile in place (key axis = sublanes here)
        for t in range(T):
            lg = logits_ref[t]                           # (S, Hp)
            m = jnp.max(lg, axis=0, keepdims=True)
            e = jnp.exp(lg - m)
            w = e / jnp.sum(e, axis=0, keepdims=True)
            logits_ref[t] = jnp.where(m <= _BIG_NEG * 0.5, 0.0, w)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j >= pp)
    def _accumulate():
        vb = load(v_ref, vs_ref)                         # (ps, H*D) f32
        col = pl.multiple_of((j - pp) * ps, ps)
        for t in range(T):
            wb = logits_ref[t, pl.ds(col, ps), :]        # (ps, Hp) f32
            # weights round to compute dtype before meeting V, as the
            # XLA path's ``weights.astype(q.dtype)`` does
            wl = jnp.dot(wb.astype(compute_dtype).astype(f32),
                         selt_ref[...], preferred_element_type=f32,
                         precision=hi)                   # (ps, H*D)
            acc_ref[t:t + 1, :] += jnp.sum(wl * vb, axis=0, keepdims=True)

    @pl.when(j == 2 * pp - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _kernel_plain(pt_ref, pos_ref, q_ref, k_ref, v_ref, sel_ref, selt_ref,
                  o_ref, logits_ref, acc_ref, **kw):
    _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, sel_ref, selt_ref, None,
            None, o_ref, logits_ref, acc_ref, **kw)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    k_scales: Optional[jax.Array],
                    v_scales: Optional[jax.Array],
                    kv_positions: jax.Array, page_table: jax.Array, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Cached paged attention for one layer's decode/verify read side.

    - ``q`` (B, T, H, D) — T = 1 (decode step) or k+1 (spec verify).
    - ``k_pages``/``v_pages`` (num_pages, page_size, H, D) — the arena
      leaves (int8 codes when quantized; the block's own T tokens must
      already be written — the caller's write half runs first).
    - ``k_scales``/``v_scales`` (num_pages, 1, H, 1) f32 per-page
      absmax scales, or None for full-precision arenas.
    - ``kv_positions`` (B, T) — each row's absolute positions (the mask
      admits ``key <= kv_positions[row, t]``, block-causal).
    - ``page_table`` (B, pages_per_slot) int32, −1 = unmapped (reads
      clamp to page 0; the mask never admits a position without a
      mapped page on any row whose output is consumed).

    Returns (B, T, H, D) in ``q.dtype``: the XLA page-native path's
    output up to f32 summation order inside each score and each V
    accumulation (same operand roundings, same exact softmax).
    """
    B, T, H, D = q.shape
    P, ps = k_pages.shape[0], k_pages.shape[1]
    pp = page_table.shape[1]
    HD = H * D
    quantized = k_scales is not None
    if interpret is None:
        interpret = interpret_default()

    page_table = page_table.astype(jnp.int32)
    kv_positions = kv_positions.astype(jnp.int32)
    sel, selt = _head_selectors(H, D)
    hp = sel.shape[1]

    def row_map(b, j, pt, pos):
        return (b, 0, 0)

    def const_map(b, j, pt, pos):
        return (0, 0)

    # K streams pages during the score phase and parks on its last page
    # through the output phase (an unchanged block index is not
    # re-fetched); V parks on the first output page through the score
    # phase — each occupied page crosses HBM→VMEM once per pass.
    def k_map(b, j, pt, pos):
        col = jnp.minimum(j, pp - 1)
        return (jnp.maximum(pt[b, col], 0), 0, 0)

    def v_map(b, j, pt, pos):
        col = jnp.maximum(j - pp, 0)
        return (jnp.maximum(pt[b, col], 0), 0, 0)

    # free reshapes: (H, D) are the arena's contiguous minor axes
    in_specs = [
        pl.BlockSpec((1, T, HD), row_map),
        pl.BlockSpec((1, ps, HD), k_map),
        pl.BlockSpec((1, ps, HD), v_map),
        pl.BlockSpec((HD, hp), const_map),
        pl.BlockSpec((hp, HD), const_map),
    ]
    operands = [q.reshape(B, T, HD), k_pages.reshape(P, ps, HD),
                v_pages.reshape(P, ps, HD), sel, selt]
    if quantized:
        # per-page head scales as one lane-padded row each (P*Hp f32 —
        # small next to the arena's P*ps*H*D codes)
        def scale_rows(s):
            return jnp.pad(s.reshape(P, 1, H), ((0, 0), (0, 0),
                                                (0, hp - H)))
        in_specs += [pl.BlockSpec((1, 1, hp), k_map),
                     pl.BlockSpec((1, 1, hp), v_map)]
        operands += [scale_rows(k_scales), scale_rows(v_scales)]
        kernel = _kernel
    else:
        kernel = _kernel_plain
    kernel = functools.partial(
        kernel, page_size=ps, pages_per_slot=pp, scale=D ** -0.5,
        compute_dtype=q.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, 2 * pp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T, HD), row_map),
        scratch_shapes=[
            pltpu.VMEM((T, pp * ps, hp), jnp.float32),  # logits tile
            pltpu.VMEM((T, HD), jnp.float32),           # f32 accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, HD), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table, kv_positions, *operands)
    return out.reshape(B, T, H, D)
