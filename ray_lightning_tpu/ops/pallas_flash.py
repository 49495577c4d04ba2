"""Hand-tiled pallas flash-attention kernels for TPU — forward AND backward.

Forward: grid ``(B, H, n_q, n_k)`` with the KV dimension innermost: for each
query block the kernel streams KV blocks through VMEM, maintaining the
online softmax state (running max ``m``, denominator ``l``, f32
accumulator) in scratch across grid steps, and writes the normalized output
plus the logsumexp on the last KV block. Matmuls hit the MXU at the input
dtype with f32 accumulation (``preferred_element_type``), per the TPU
kernel guide.

Backward (FlashAttention-2 scheme, the recompute form): probabilities are
rebuilt blockwise from the saved logsumexp instead of storing the (T, S)
matrix, so training memory stays O(T·D):

- ``delta = rowsum(dO ⊙ O)`` — cheap elementwise jnp precompute;
- dk/dv kernel, grid ``(B, H, n_k, n_q)`` (q innermost): for KV block j,
  accumulate ``dv += pᵀ dO`` and ``dk += dsᵀ q`` over the q blocks, where
  ``p = exp(q kᵀ·scale − lse)`` and ``ds = p ⊙ (dO vᵀ − delta)``;
- dq kernel, grid ``(B, H, n_q, n_k)`` (kv innermost): ``dq += ds k``.

The public entry is wrapped in ``jax.custom_vjp`` so ``attention_impl=
"flash"`` trains on TPU (round-2 find: differentiating through a bare
``pallas_call`` has no JVP rule and crashes every training step). Causal
runs skip fully-masked blocks in all three kernels (~2x on the causal path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
# lse value for padded query rows: exp(s - big) == 0 for any finite s, so
# padding contributes exactly nothing to dk/dv.
_PAD_LSE = 1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, kv_len: int,
                  q_len: int, block_q: int, block_k: int):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block (innermost, sequential)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _BIG_NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        qi = q_ref[0, 0]  # (bq, D)
        kj = k_ref[0, 0]  # (bk, D)
        vj = v_ref[0, 0]

        s = jax.lax.dot_general(
            qi, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        allow = kpos < kv_len
        if causal:
            # align ends when q_len != kv_len (standard decode convention)
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + (kv_len - q_len)
            allow = allow & (kpos <= qpos)
        s = jnp.where(allow, s, _BIG_NEG)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(allow, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l_new

    if causal:
        # predicate away KV blocks entirely above the diagonal (~2x FLOPs
        # saved on the causal hot path; init/emit still run every step)
        first_key = j * block_k
        last_q = i * block_q + block_q - 1 + (kv_len - q_len)
        pl.when(first_key <= last_q)(_compute)
    else:
        _compute()

    @pl.when(j == n_k - 1)
    def _emit():
        l = l_ref[:, 0]
        safe_l = jnp.maximum(l, 1e-30)
        out = jnp.where(l[:, None] > 0, acc_ref[:] / safe_l[:, None], 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        lse_ref[0, 0, :, 0] = jnp.where(
            l > 0, m_ref[:, 0] + jnp.log(safe_l), _PAD_LSE)


def _recomputed_p_ds(qi, kj, vj, doi, lse, delta, *, scale, causal, i, j,
                     kv_len, q_len, block_q, block_k):
    """Shared backward block math: rebuild p from lse, form ds.

    Returns (p, ds) as f32 ``(bq, bk)``; masked positions are exactly 0 in
    both, so padded/causal-forbidden entries contribute nothing to any of
    dq/dk/dv.
    """
    s = jax.lax.dot_general(
        qi, kj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    allow = kpos < kv_len
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + (kv_len - q_len)
        allow = allow & (kpos <= qpos)
    p = jnp.where(allow, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(
        doi, vj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bq, bk)
    ds = p * (dp - delta[:, None])
    return p, ds


def _flash_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                      causal: bool, kv_len: int, q_len: int, block_q: int,
                      block_k: int):
    j = pl.program_id(2)   # kv block
    i = pl.program_id(3)   # q block (innermost, sequential)
    n_i = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        qi = q_ref[0, 0]
        doi = do_ref[0, 0]
        kj = k_ref[0, 0]
        vj = v_ref[0, 0]
        p, ds = _recomputed_p_ds(
            qi, kj, vj, doi, lse_ref[0, 0, :, 0], delta_ref[0, 0, :, 0],
            scale=scale,
            causal=causal, i=i, j=j, kv_len=kv_len, q_len=q_len,
            block_q=block_q, block_k=block_k)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(doi.dtype), doi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(qi.dtype), qi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        first_key = j * block_k
        last_q = i * block_q + block_q - 1 + (kv_len - q_len)
        pl.when(first_key <= last_q)(_compute)
    else:
        _compute()

    @pl.when(i == n_i - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dq_kernel(k_ref, v_ref, do_ref, lse_ref, delta_ref, q_ref,
                     dq_ref, dq_acc, *, scale: float, causal: bool,
                     kv_len: int, q_len: int, block_q: int, block_k: int):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block (innermost, sequential)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        qi = q_ref[0, 0]
        doi = do_ref[0, 0]
        kj = k_ref[0, 0]
        vj = v_ref[0, 0]
        _, ds = _recomputed_p_ds(
            qi, kj, vj, doi, lse_ref[0, 0, :, 0], delta_ref[0, 0, :, 0],
            scale=scale,
            causal=causal, i=i, j=j, kv_len=kv_len, q_len=q_len,
            block_q=block_q, block_k=block_k)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kj.dtype), kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        first_key = j * block_k
        last_q = i * block_q + block_q - 1 + (kv_len - q_len)
        pl.when(first_key <= last_q)(_compute)
    else:
        _compute()

    @pl.when(j == n_k - 1)
    def _emit():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _pad_bhtd(x, Tp):
    """(B, T, H, D) → padded (B, H, Tp, D)."""
    T = x.shape[1]
    return jnp.pad(x.transpose(0, 2, 1, 3),
                   ((0, 0), (0, 0), (0, Tp - T), (0, 0)))


def _blocks(block_q, block_k, T, S):
    bq, bk = min(block_q, T), min(block_k, S)
    n_q, n_k = -(-T // bq), -(-S // bk)
    return bq, bk, n_q, n_k


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    B, T, H, D = q.shape
    S = k.shape[1]
    bq, bk, n_q, n_k = _blocks(block_q, block_k, T, S)
    Tp, Sp = n_q * bq, n_k * bk

    # (B,T,H,D) → (B,H,T,D): heads become a parallel grid dim, sequence
    # tiles land on the (sublane, lane) layout the MXU wants.
    qt, kt, vt = _pad_bhtd(q, Tp), _pad_bhtd(k, Sp), _pad_bhtd(v, Sp)

    kernel = functools.partial(
        _flash_kernel, scale=D ** -0.5, causal=causal, kv_len=S, q_len=T,
        block_q=bq, block_k=bk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, D), jnp.float32),   # f32 accumulator
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return out[:, :, :T].transpose(0, 2, 1, 3), lse


def _flash_bwd_impl(q, k, v, out, lse, do, causal, block_q, block_k,
                    interpret):
    B, T, H, D = q.shape
    S = k.shape[1]
    bq, bk, n_q, n_k = _blocks(block_q, block_k, T, S)
    Tp, Sp = n_q * bq, n_k * bk
    scale = D ** -0.5

    qt, dot_ = _pad_bhtd(q, Tp), _pad_bhtd(do, Tp)
    kt, vt = _pad_bhtd(k, Sp), _pad_bhtd(v, Sp)
    # lse is (B,H,Tp) already; padded rows carry _PAD_LSE so p == 0 there.
    delta = jnp.pad(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1).transpose(0, 2, 1),
        ((0, 0), (0, 0), (0, Tp - T)))[..., None]   # (B, H, Tp, 1)

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda b, h, j, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0))

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, scale=scale, causal=causal, kv_len=S,
            q_len=T, block_q=bq, block_k=bk),
        grid=(B, H, n_k, n_q),
        in_specs=[q_spec, q_spec, row_spec, row_spec, kv_spec, kv_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qt, dot_, lse, delta, kt, vt)

    q_spec2 = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq, 1),
                             lambda b, h, i, j: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, scale=scale, causal=causal, kv_len=S,
            q_len=T, block_q=bq, block_k=bk),
        grid=(B, H, n_q, n_k),
        in_specs=[kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2,
                  q_spec2],
        out_specs=q_spec2,
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(kt, vt, dot_, lse, delta, qt)

    dq = dq[:, :, :T].transpose(0, 2, 1, 3)
    dk = dk[:, :, :S].transpose(0, 2, 1, 3)
    dv = dv[:, :, :S].transpose(0, 2, 1, 3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, do):
    q, k, v, out, lse = residuals
    return _flash_bwd_impl(q, k, v, out, lse, do, causal, block_q, block_k,
                           interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def pallas_flash_attention(q: jax.Array,
                           k: jax.Array,
                           v: jax.Array,
                           *,
                           causal: bool = False,
                           block_q: int = 512,
                           block_k: int = 1024,
                           interpret: bool = False) -> jax.Array:
    """Flash attention via pallas, differentiable. Shapes (B, T, H, D).

    Default tiles are from a v5e train-step (fwd+bwd) sweep: 512×1024
    beats both the 128×128 tiles this kernel started with (~2x) and XLA's
    fused attention — 1.8x at T=512 and ~20x at T=8192, where XLA's
    materialized scores stop scaling. Blocks clamp to the actual lengths,
    so short sequences are unaffected. ``interpret=True`` runs the same
    kernels in the pallas interpreter (CPU testing path, no TPU).
    """
    return _flash(q, k, v, causal, block_q, block_k, interpret)
