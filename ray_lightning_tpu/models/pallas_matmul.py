"""Pallas fused dequant-matmul: stream int8/int4 weight CODES into the
matmul kernel and kill the per-dispatch dequant pass.

PR 11's weight-only quantization cut at-rest param bytes to
0.25x/0.14x, but ``dequantize_params`` still materialized a
full-precision parameter tree at every program entry — so the
per-dispatch HBM byte stream, the thing decode is bound by, never
shrank (quantized decode honestly LOSES wall-clock on hosts without
convert-into-GEMM fusion; ``docs/performance.md`` round 11). This
kernel is the weight-side sibling of ``models/pallas_attention.py``
(which closed the same gap for KV codes): the projection matmuls
consume the quantized codes DIRECTLY —

- **codes+scales in, no dense weight anywhere** — the weight operand
  of each grid step is a ``(tile_k, tile_n)`` block of int8 codes (int4:
  nibble-packed ``(tile_k, tile_n/2)``) plus its scale block, streamed
  HBM→VMEM by the BlockSpec pipeline. Unpacking and the ``codes x
  scales`` multiply happen on the VMEM block right before the dot; the
  only full-precision weight in existence is one tile of VMEM scratch
  per grid step. The per-dispatch param byte stream drops to the
  codes+scales floor ``models/quant.py param_bytes`` already accounts.
- **in-kernel int4 nibble unpack** — arithmetic-shift sign extension on
  int32 views (:func:`_nibbles`; :func:`unpack_int4_block` pins it
  value-for-value against ``quant.unpack_int4`` over all 16 codes), low
  nibble first, exactly the ``pack_int4`` layout. The two nibbles are
  never re-interleaved in the kernel (no lane interleave on the chip):
  the even and odd halves of the packed axis stay apart
  (:func:`_kernel_int4`).
- **per-output-channel / per-group scales on the block** — int8 scales
  broadcast along the tile's contraction rows; int4 group scales are
  widened to the half-width packed axis by an f32 one-hot selector dot
  (exact). Scales are never folded into the activations: the
  dequantized block is the same element-wise ``codes x scale`` product
  the XLA path computes.
- **both weight orientations** — ``transpose=False`` contracts the
  stored leaf's axis 0 (every Dense/DenseGeneral kernel: qkv, out,
  mlp up/down, the untied lm_head); ``transpose=True`` contracts the
  stored last axis (the tied LM head, ``wte.attend``'s ``x @ E.T`` —
  the same codes the embedding LOOKUP gathers row-wise).

Contract (``docs/serving.md`` has the chip's verdict): the kernel's
dot is the dequantize-then-XLA-matmul path's — same ``codes x scales``
products, same promoted operands — accumulated in f32 and rounded once
to the output dtype, the only accumulator the chip's matmul unit has
(Mosaic refuses a dot without it, so the old bitwise-vs-XLA form could
never lower). It equals the XLA path up to accumulation order: a few
ulps (``tests/test_pallas_matmul.py``), which keeps greedy token
identity on the pinned CPU configs and gives teacher-forced agreement
within bf16 rounding on the chip. ``tile_k < K`` splits the
contraction into f32-accumulated partial dots in VMEM scratch.

Both kernels compile for the chip at GPT-2-small shapes, dense and
tied head, padded or unpadded vocab (``tests/test_chip_compile.py``).
Interpret mode is chosen from the backend and never on a TPU one: a
kernel that cannot lower raises the compiler's message there — it
never runs interpreted on a chip and never gives way to the XLA path.

Engines select this path with ``ServeEngine/ServeClient(...,
matmul_kernel="pallas")`` (requires ``weight_dtype=``; the cfg field
``TransformerConfig.matmul_kernel`` is the source of truth the layers
dispatch on, so supervisor rebuilds and fleet replicas re-select
identical programs). ``quant.materialize_for_program`` then skips the
program-entry dequant and the codes flow through jit as pytree leaves.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.models.pallas_attention import interpret_default
from ray_lightning_tpu.models.quant import QTensor, matmul_view

__all__ = ["quantized_matmul", "unpack_int4_block", "kernel_calls"]

#: default caps for the derived output tile (largest divisor of the
#: axis at or under the cap) — one (tile_m, tile_n) f32 out block plus
#: the K-long code and x panels stay far under the ~16 MB VMEM budget.
#: tile_m needs the cap too: M is the FLATTENED token count, and a
#: prefill/verify dispatch's (M, K) x panel would otherwise ride into
#: one grid step whole (decode steps sit far below it either way).
#: Output tiling never touches an element's reduction order.
DEFAULT_TILE_N = 512
DEFAULT_TILE_M = 256

#: trace-time counter of kernel instantiations — the tests' witness
#: that a "fused" engine actually armed the kernel (a cached program does
#: not retrace, so snapshot it before the engine's first compile)
_KERNEL_CALLS = 0


def kernel_calls() -> int:
    """How many times :func:`quantized_matmul` has traced a kernel this
    process (compile-time count, not per-dispatch)."""
    return _KERNEL_CALLS


def _nibbles(packed: jax.Array):
    """Sign-extended ``(low, high)`` nibbles of each packed byte, as
    int32 (int8 shifts are a Mosaic lowering gap; interpret mode
    computes the same values either way). ``low`` holds the EVEN
    positions of the unpacked last axis, ``high`` the odd ones —
    ``quant.pack_int4``'s layout."""
    p = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)  # arithmetic
    hi = jnp.right_shift(p, 4)   # p is sign-extended: == int8 >> 4
    return lo, hi


def unpack_int4_block(packed: jax.Array) -> jax.Array:
    """Sibling of ``quant.unpack_int4`` over the kernel's nibble math
    (:func:`_nibbles`): sign-extend both nibbles of each byte and
    re-interleave to the doubled last axis — value-for-value identical
    (pinned over all 16 codes). The kernel itself never interleaves
    (Mosaic has no lane interleave): it keeps the even and odd halves
    apart, see :func:`_kernel_int4`."""
    out = jnp.stack(_nibbles(packed), axis=-1)
    return out.reshape(*packed.shape[:-1], 2 * packed.shape[-1])


def _emit(parts, o_refs, acc_refs, nk: int) -> None:
    """Write one grid step's f32 partial products: straight to the
    outputs when the contraction is one tile (``nk == 1``, rounded once
    to the output dtype, no scratch), else accumulated in f32 VMEM
    scratch over the innermost grid axis and emitted on its last step."""
    if nk == 1:
        for o_ref, part in zip(o_refs, parts):
            o_ref[...] = part.astype(o_ref.dtype)
        return
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    for acc_ref, part in zip(acc_refs, parts):
        acc_ref[...] += part

    @pl.when(kk == nk - 1)
    def _write():
        for o_ref, acc_ref in zip(o_refs, acc_refs):
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_int8(x_ref, q_ref, s_ref, o_ref, *acc, dims, nk, param_dtype,
                 compute_dtype):
    """One (m, n, k) grid step, int8 codes. The weight tile is the exact
    element-wise product chain of ``QTensor.dequantize`` followed by
    flax's promote-to-compute-dtype, so the dot meets the
    dequantize-then-XLA path's operands exactly; it accumulates in f32 —
    the only accumulator the chip's matmul unit has (Mosaic refuses a
    bf16-accumulated ``tpu.matmul``)."""
    w = (q_ref[...].astype(jnp.float32) * s_ref[...]     # s (1, cols)
         ).astype(param_dtype).astype(compute_dtype)
    part = jax.lax.dot_general(x_ref[...], w, dims,
                               preferred_element_type=jnp.float32)
    _emit((part,), (o_ref,), acc, nk)


def _kernel_int4(*refs, transpose: bool, nk: int, param_dtype,
                 compute_dtype):
    """One (m, n, k) grid step, nibble-packed int4 codes.

    Mosaic has no lane interleave and no lane-splitting reshape, so the
    even and odd halves of the packed axis never meet in the kernel and
    group scales are widened by an f32 one-hot selector dot
    (``scales (rows, groups) @ e (groups, half)`` — exact: one non-zero
    term per element) instead of a grouped reshape:

    - dense (``refs = x, q, s, e, out_even, out_odd, *acc``): the packed
      axis is the OUTPUT axis; the low nibbles give the even output
      columns and the high nibbles the odd ones, as two outputs the
      wrapper interleaves.
    - transpose (``refs = x_even, x_odd, q, s, e, out, *acc``): the
      packed axis is the CONTRACTION; the wrapper hands the even and
      odd activation columns apart and the two partial dots add in f32.

    Either way each weight half is ``codes x scale`` in f32 -> param
    dtype -> compute dtype, ``QTensor.dequantize``'s chain exactly.
    """
    n_in = 5 if transpose else 4
    n_out = 1 if transpose else 2
    q_ref, s_ref, e_ref = refs[n_in - 3:n_in]
    o_refs, acc = refs[n_in:n_in + n_out], refs[n_in + n_out:]
    lo, hi = _nibbles(q_ref[...])
    wide = jnp.dot(s_ref[...], e_ref[...],
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)

    def half(codes):
        return (codes.astype(jnp.float32) * wide).astype(
            param_dtype).astype(compute_dtype)

    f32 = jnp.float32
    if transpose:
        # operands go to the dots as f32: every value is exactly its
        # compute-dtype rounding, so the products are unchanged — and
        # XLA:CPU, which interpret mode runs on, has no bf16 x bf16 ->
        # f32 kernel for this pair of transposed dots
        nt = (((1,), (1,)), ((), ()))
        parts = (jax.lax.dot_general(refs[0][...].astype(f32),
                                     half(lo).astype(f32), nt,
                                     preferred_element_type=f32)
                 + jax.lax.dot_general(refs[1][...].astype(f32),
                                       half(hi).astype(f32), nt,
                                       preferred_element_type=f32),)
    else:
        x = refs[0][...]
        parts = (jnp.dot(x, half(lo), preferred_element_type=f32),
                 jnp.dot(x, half(hi), preferred_element_type=f32))
    _emit(parts, o_refs, acc, nk)


def _group_selector(groups: int, half: int, per_group: int) -> jax.Array:
    """One-hot ``(groups, half)``: column ``i`` of the half-width packed
    axis belongs to group ``i // per_group``."""
    return jnp.asarray(
        (np.arange(half)[None, :] // per_group
         == np.arange(groups)[:, None]).astype(np.float32))


def _auto_tile(n: int, cap: int, align: int) -> int:
    """Derived tile for an OUTPUT axis of length ``n``: the whole axis
    when it fits under ``cap`` (a block equal to its array dim is always
    legal on the chip); else the largest divisor of ``n`` that is a
    multiple of ``align`` (lane/sublane tiling, and the int4 group) and
    <= cap; else ``cap`` itself with a ragged final tile — pallas pads
    the final block's reads and drops its out-of-range writes, and an
    output row/column never mixes with another, so the padding cannot
    reach a kept element. ``cap`` must be a multiple of ``align``."""
    if n <= cap:
        return n
    for d in range(cap - cap % align, 0, -align):
        if n % d == 0:
            return d
    return cap


def quantized_matmul(x: jax.Array, qt: QTensor, *,
                     transpose: bool = False,
                     tile_m: Optional[int] = None,
                     tile_n: Optional[int] = None,
                     tile_k: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """``x (..., K) @ dequantize(qt) -> (..., N)`` with the dequant
    fused into the matmul kernel — no dense weight materializes.

    ``transpose=False`` contracts ``qt``'s stored axis 0 and flattens
    the remaining axes to ``N`` (the caller reshapes to its feature
    dims); ``transpose=True`` contracts the stored LAST axis (the tied
    LM head's ``x @ E.T``). Output dtype is ``x.dtype`` — callers
    promote to compute dtype first, exactly like flax's Dense.

    Tiling: ``tile_k`` defaults to the full contraction (one dot per
    output tile); ``tile_m``/``tile_n`` default to :func:`_auto_tile`
    under :data:`DEFAULT_TILE_M` / :data:`DEFAULT_TILE_N` — the whole
    axis, a lane-aligned divisor, or (divisor-poor axes: an unpadded
    50257-class vocab on the tied head) a lane-aligned tile with a
    ragged, masked final block. The same derivation runs on the chip
    and under interpret mode. An EXPLICIT tile must divide its axis
    exactly — a ragged one raises — and ``tile_k`` always must (padding
    inside a contraction would reach kept elements). int4 group
    boundaries must not split across tiles: ``group_size`` must divide
    ``tile_n`` (dense orientation) or ``tile_k`` (transpose
    orientation, where the groups ride the contraction axis).
    """
    codes, scales, K, N = matmul_view(qt, transpose)
    if x.shape[-1] != K:
        raise ValueError(
            f"quantized_matmul contraction mismatch: x has "
            f"{x.shape[-1]} features, the quantized leaf contracts "
            f"over {K}")
    lead = x.shape[:-1]
    x2d = x.reshape(-1, K)
    M = x2d.shape[0]
    gs = qt.group_size if qt.bits == 4 else 1
    for name, tile, dim in (("tile_m", tile_m, M), ("tile_n", tile_n, N),
                            ("tile_k", tile_k, K)):
        if tile is not None and (tile < 1 or dim % tile):
            raise ValueError(
                f"{name}={tile} does not divide its axis ({dim}): the "
                "kernel's fixed-shape grid would leave a ragged final "
                "tile — pick a tile that divides the axis exactly")
    if tile_m is None:
        # 32 = the int8 sublane tile, a multiple of bf16's 16 and f32's 8
        tile_m = _auto_tile(M, DEFAULT_TILE_M, 32)
    tile_k = K if tile_k is None else tile_k
    if tile_n is None:
        # lanes are 128 wide; the dense int4 codes block is tile_n/2
        # packed bytes wide and must stay group-aligned
        align = 128 if transpose or qt.bits == 8 else math.lcm(256, gs)
        tile_n = _auto_tile(
            N, max(DEFAULT_TILE_N, align) // align * align, align)
    if qt.bits == 4:
        group_axis, tile_g = (("tile_k", tile_k) if transpose
                              else ("tile_n", tile_n))
        if tile_g % qt.group_size:
            raise ValueError(
                f"group_size ({qt.group_size}) must divide {group_axis} "
                f"({tile_g}): int4 scale groups ride the "
                f"{'contraction' if transpose else 'output'} axis and "
                "a tile boundary must not split a group")
    if interpret is None:
        interpret = interpret_default()

    nm, nn, nk = pl.cdiv(M, tile_m), pl.cdiv(N, tile_n), K // tile_k
    global _KERNEL_CALLS
    _KERNEL_CALLS += 1

    def call(kernel, in_specs, operands, out_tile, out_cols, n_out=1):
        # f32 partial-dot accumulators — only the nk > 1 tiling has them
        out_spec = pl.BlockSpec(out_tile, lambda i, j, kk: (i, j))
        return pl.pallas_call(
            kernel,
            grid=(nm, nn, nk),
            in_specs=in_specs,
            out_specs=[out_spec] * n_out,
            out_shape=[jax.ShapeDtypeStruct((M, out_cols), x.dtype)] * n_out,
            scratch_shapes=([pltpu.VMEM(out_tile, jnp.float32)] * n_out
                            if nk > 1 else []),
            interpret=interpret,
            name="quantized_matmul",
        )(*operands)

    common = dict(nk=nk, param_dtype=qt.dtype, compute_dtype=x.dtype)
    if qt.bits == 8:
        if transpose:
            # codes (N, K): rows = output tile, cols = contraction
            q_spec = pl.BlockSpec((tile_n, tile_k), lambda i, j, kk: (j, kk))
            s_spec = pl.BlockSpec((1, tile_k), lambda i, j, kk: (0, kk))
            dims = (((1,), (1,)), ((), ()))
        else:
            # codes (K, N): rows = contraction, cols = output tile
            q_spec = pl.BlockSpec((tile_k, tile_n), lambda i, j, kk: (kk, j))
            s_spec = pl.BlockSpec((1, tile_n), lambda i, j, kk: (0, j))
            dims = (((1,), (0,)), ((), ()))
        x_spec = pl.BlockSpec((tile_m, tile_k), lambda i, j, kk: (i, kk))
        (out,) = call(functools.partial(_kernel_int8, dims=dims, **common),
                      [x_spec, q_spec, s_spec], (x2d, codes, scales),
                      (tile_m, tile_n), N)
    elif transpose:
        # codes (N, K/2): the packed axis is the contraction — even and
        # odd activation columns meet the low and high nibbles apart
        half_k, groups = tile_k // 2, K // gs
        xh_spec = pl.BlockSpec((tile_m, half_k), lambda i, j, kk: (i, kk))
        (out,) = call(
            functools.partial(_kernel_int4, transpose=True, **common),
            [xh_spec, xh_spec,
             pl.BlockSpec((tile_n, half_k), lambda i, j, kk: (j, kk)),
             pl.BlockSpec((tile_n, groups), lambda i, j, kk: (j, 0)),
             pl.BlockSpec((groups, half_k), lambda i, j, kk: (0, kk))],
            (x2d[:, 0::2], x2d[:, 1::2], codes, scales,
             _group_selector(groups, K // 2, gs // 2)),
            (tile_m, tile_n), N)
    else:
        # codes (K, N/2): the packed axis is the output — low nibbles
        # are the even output columns, high nibbles the odd ones
        half_n, groups = tile_n // 2, N // gs
        even, odd = call(
            functools.partial(_kernel_int4, transpose=False, **common),
            [pl.BlockSpec((tile_m, tile_k), lambda i, j, kk: (i, kk)),
             pl.BlockSpec((tile_k, half_n), lambda i, j, kk: (kk, j)),
             pl.BlockSpec((tile_k, groups), lambda i, j, kk: (kk, 0)),
             pl.BlockSpec((groups, half_n), lambda i, j, kk: (0, j))],
            (x2d, codes, scales, _group_selector(groups, N // 2, gs // 2)),
            (tile_m, half_n), N // 2, n_out=2)
        out = jnp.stack([even, odd], axis=-1).reshape(M, N)
    return out.reshape(*lead, N)
