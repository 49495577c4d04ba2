"""Hand-tiled pallas flash-attention kernels for TPU — forward AND backward.

The kernels read ``q``/``k``/``v`` where the model leaves them: the
``(B, T, H, D)`` projections viewed as ``(B, T, H·D)`` (a bitcast), cut
into lane blocks of 128 — two heads of 64 a block, one of 128 — so no
transpose or pad stands between the ``qkv`` matmul and the kernel, and
every load and store is a full lane row. Inside a block the heads are told
apart by lane masks on one matmul operand (a contraction over ``D = 64``
fills half the MXU's depth whether the other half is sliced away or zero).

Scores are kept **transposed**, ``sᵀ = k qᵀ`` of shape ``(bk, bq)``: the
softmax statistics of a query are then one lane each — ``(1, bq)`` rows,
reduced over sublanes, stored densely — and the output-side matmuls
(``vᵀ pᵀ``, ``kᵀ dsᵀ``) have the head dim as their *row* count, where 64
costs 64.

Forward: grid ``(B, H·D/128, n_q)``; K and V of the head block are resident
(their block index does not move with the query block) and the kernel loops
over the KV blocks a query block can see — a causal query block never
visits a block above the diagonal. Online softmax in f32 (running max,
denominator, ``(D, bq)`` accumulator); matmul operands at the input dtype
with f32 accumulation. Emits the output and the logsumexp.

Backward (FlashAttention-2's recompute form, one kernel): grid
``(B, H·D/128, n_k)``; Q, dO, the logsumexp and ``delta = rowsum(dO ⊙ O)``
(a jnp reduction) of the head block are resident, and for KV block ``j``
the kernel loops over the query blocks that see it, rebuilding
``pᵀ = exp(sᵀ − lse)`` once for all three gradients: ``dv += pᵀ dO``,
``dk += dsᵀ q`` with ``dsᵀ = pᵀ ⊙ (v dOᵀ − delta)``, and
``dqᵀ[i] += kᵀ dsᵀ`` into a resident f32 accumulator written out at the
last KV block.

The public entry is wrapped in ``jax.custom_vjp`` (a bare ``pallas_call``
has no JVP rule). What the tiles and this form were chosen from — the
medium train cell's step program timed on the chip — is in ``PERF.md``
section 6 (PR 33).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
# lse of a query row that sees no key (end-aligned causal with more
# queries than keys): exp(s - big) == 0 for any finite s, so the row
# contributes exactly nothing to dk/dv.
_PAD_LSE = 1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
#: the largest score tile, queries x keys, in both kernels (PERF.md
#: section 6, PR 33: 512 x 512 beat every smaller and larger form timed)
_TILE = 512


def head_lanes(head_dim: int):
    """``(lanes a block spans, heads a block holds)`` for a head dim the
    kernels tile — a divisor or a multiple of the 128-lane row — else
    ``None``."""
    if head_dim % _LANES == 0:
        return head_dim, 1
    if _LANES % head_dim == 0:
        return _LANES, _LANES // head_dim
    return None


def _blocks(block_q, block_k, T, S):
    """Tiles from the lengths: the largest multiple of 128 up to ``_TILE``
    that divides the length rounded up to 128 (a query block is the lane
    dim of a score tile), so 1024 runs 512s, 768 runs 384s, and nothing is
    padded but a ragged tail. A caller's own block is taken as it is."""
    def tile(n):
        n = -(-n // _LANES) * _LANES
        return max(b for b in range(_LANES, _TILE + 1, _LANES) if n % b == 0)

    bq, bk = block_q or tile(T), block_k or tile(S)
    return bq, bk, -(-T // bq), -(-S // bk)


def _lane_heads(W, D, hd, block):
    """Head index of each lane of a block, and which lanes hold data (the
    last block of an odd head count hangs over the array's edge, where a
    load returns whatever was there)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    valid = None if hd % W == 0 else (block * W + lane) < hd
    return lane // D, valid


def _clean(x, valid):
    return x if valid is None else jnp.where(valid, x, jnp.zeros_like(x))


def _rel(bk, bq):
    """Key offset minus query offset inside a (bk, bq) score tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1))


def _allow(rel, i, j, *, causal, S, Sp, bq, bk, shift):
    """The (bk, bq) mask of score tile (j, i), or None when every entry
    counts; ``rel`` is :func:`_rel`'s, made once a kernel body."""
    allow = None
    if causal:
        allow = rel <= i * bq - j * bk + shift
    if Sp != S:
        # rel + query offset == key offset; compare through one lane row
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        live = kpos < S
        allow = live if allow is None else allow & live
    return allow


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, *, scale,
                causal, T, S, Sp, Tp, bq, bk, D, g, hd):
    i = pl.program_id(2)
    W = q_ref.shape[-1]
    n_k = Sp // bk
    shift = S - T
    empty_rows = causal and T > S
    head, valid = _lane_heads(W, D, hd, pl.program_id(1))
    q = _clean(q_ref[0], valid)                        # (bq, W)
    qh = [q if g == 1 else jnp.where(head == h, q, jnp.zeros_like(q))
          for h in range(g)]
    rel = _rel(bk, bq)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def kv_block(j, carry):
        ms, ls = carry
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        kj = _clean(k_ref[0, rows, :], valid)          # (bk, W)
        vT = _clean(v_ref[0, rows, :], valid).T        # (W, bk)
        allow = _allow(rel, i, j, causal=causal, S=S, Sp=Sp, bq=bq, bk=bk,
                       shift=shift)
        new_m, new_l = [], []
        for h in range(g):
            sT = jax.lax.dot_general(
                kj, qh[h], _NT,
                preferred_element_type=jnp.float32) * scale   # (bk, bq)
            if allow is not None:
                sT = jnp.where(allow, sT, _BIG_NEG)
            m = jnp.maximum(ms[h], jnp.max(sT, axis=0, keepdims=True))
            pT = jnp.exp(sT - m)
            if empty_rows:
                pT = jnp.where(allow, pT, 0.0)
            alpha = jnp.exp(ms[h] - m)                 # (1, bq)
            new_m.append(m)
            new_l.append(ls[h] * alpha
                         + jnp.sum(pT, axis=0, keepdims=True))
            d = slice(h * D, (h + 1) * D)
            acc_ref[d, :] = acc_ref[d, :] * alpha + jnp.dot(
                vT[d, :], pT.astype(vT.dtype),
                preferred_element_type=jnp.float32)    # (D, bq)
        return tuple(new_m), tuple(new_l)

    if causal:
        # KV blocks wholly above the diagonal are never visited
        n_live = jnp.clip((i * bq + bq - 1 + shift) // bk + 1, 0, n_k)
    else:
        n_live = n_k
    init = (tuple(jnp.full((1, bq), _BIG_NEG, jnp.float32)
                  for _ in range(g)),
            tuple(jnp.zeros((1, bq), jnp.float32) for _ in range(g)))
    ms, ls = jax.lax.fori_loop(0, n_live, kv_block, init)

    for h in range(g):
        d = slice(h * D, (h + 1) * D)
        l, lse = ls[h], ms[h] + jnp.log(jnp.maximum(ls[h], 1e-30))
        if empty_rows:
            acc_ref[d, :] = jnp.where(
                l > 0, acc_ref[d, :] / jnp.maximum(l, 1e-30), 0.0)
            lse = jnp.where(l > 0, lse, _PAD_LSE)
        else:
            acc_ref[d, :] = acc_ref[d, :] / l
        lse_ref[0, 0, 0, h:h + 1, :] = lse
    o_ref[0] = acc_ref[:].T.astype(o_ref.dtype)


def _bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref,
                dk_ref, dv_ref, dqT_ref, dk_acc, dv_acc, *, scale, causal,
                T, S, Sp, Tp, bq, bk, D, g, hd):
    j = pl.program_id(2)
    W = k_ref.shape[-1]
    n_q, n_k = Tp // bq, Sp // bk
    shift = S - T
    head, valid = _lane_heads(W, D, hd, pl.program_id(1))
    kj = _clean(k_ref[0], valid)                       # (bk, W)
    vj = _clean(v_ref[0], valid)
    kT = kj.T                                          # (W, bk)
    zero = jnp.zeros_like(kj)
    kh = [kj if g == 1 else jnp.where(head == h, kj, zero)
          for h in range(g)]
    vh = [vj if g == 1 else jnp.where(head == h, vj, zero)
          for h in range(g)]
    rel = _rel(bk, bq)

    @pl.when(j == 0)
    def _():
        dqT_ref[:] = jnp.zeros_like(dqT_ref)

    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def q_block(i, _):
        rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
        qi = _clean(q_ref[0, rows, :], valid)          # (bq, W)
        doi = _clean(do_ref[0, rows, :], valid)
        allow = _allow(rel, i, j, causal=causal, S=S, Sp=Sp, bq=bq, bk=bk,
                       shift=shift)
        for h in range(g):
            sT = jax.lax.dot_general(
                kh[h], qi, _NT,
                preferred_element_type=jnp.float32) * scale   # (bk, bq)
            pT = jnp.exp(sT - lse_ref[0, 0, i, h:h + 1, :])
            if allow is not None:
                pT = jnp.where(allow, pT, 0.0)
            dpT = jax.lax.dot_general(
                vh[h], doi, _NT, preferred_element_type=jnp.float32)
            dsT = (pT * (dpT - delta_ref[0, 0, i, h:h + 1, :])).astype(
                qi.dtype)
            # full lane rows: the columns of the block's other heads are
            # dropped when the accumulators are folded below
            dv_acc[h] += jnp.dot(pT.astype(doi.dtype), doi,
                                 preferred_element_type=jnp.float32)
            dk_acc[h] += jnp.dot(dsT, qi,
                                 preferred_element_type=jnp.float32)
            d = slice(h * D, (h + 1) * D)
            dqT_ref[i, d, :] += jnp.dot(
                kT[d, :], dsT, preferred_element_type=jnp.float32)
        return 0

    # query blocks wholly before key block j see none of its keys
    i0 = jnp.clip((j * bk - shift) // bq, 0, n_q) if causal else 0
    jax.lax.fori_loop(i0, n_q, q_block, 0)

    dk, dv = dk_acc[0], dv_acc[0]
    for h in range(1, g):
        dk = jnp.where(head == h, dk_acc[h], dk)
        dv = jnp.where(head == h, dv_acc[h], dv)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(j == n_k - 1)
    def _():
        for i in range(n_q):
            dq_ref[0, i * bq:(i + 1) * bq, :] = (
                dqT_ref[i].T * scale).astype(dq_ref.dtype)


def _packed(x, length):
    """``(B, L, H, D)`` as ``(B, length, H·D)``: a bitcast, and a pad only
    when the length is no multiple of its block."""
    B, L, H, D = x.shape
    x = x.reshape(B, L, H * D)
    return x if L == length else jnp.pad(
        x, ((0, 0), (0, length - L), (0, 0)))


def _params(resident_bytes: int):
    # resident K/V (or Q/dO) are double-buffered; leave room for the tiles
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(min(100 << 20,
                                 max(32 << 20, 3 * resident_bytes))))


def _geometry(q, k, causal, block_q, block_k):
    """What both calls derive from the shapes: the kernels' static
    arguments, and ``(W, n_h, n_q, n_k)`` — lanes a block, head blocks,
    query blocks, key blocks."""
    B, T, H, D = q.shape
    S = k.shape[1]
    W, g = head_lanes(D)
    bq, bk, n_q, n_k = _blocks(block_q, block_k, T, S)
    static = dict(scale=D ** -0.5, causal=causal, T=T, S=S, Sp=n_k * bk,
                  Tp=n_q * bq, bq=bq, bk=bk, D=D, g=g, hd=H * D)
    return static, (W, -(-H * D // W), n_q, n_k)


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    B, T, H, D = q.shape
    static, (W, n_h, n_q, n_k) = _geometry(q, k, causal, block_q, block_k)
    bq, g, hd, Tp, Sp = (static[n] for n in ("bq", "g", "hd", "Tp", "Sp"))
    q_spec = pl.BlockSpec((1, bq, W), lambda b, h, i: (b, i, h))
    kv_spec = pl.BlockSpec((1, Sp, W), lambda b, h, i: (b, 0, h))
    with jax.named_scope("attention/flash_fwd"):
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, **static),
            grid=(B, n_h, n_q),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, 1, g, bq),
                             lambda b, h, i: (b, h, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Tp, hd), q.dtype),
                jax.ShapeDtypeStruct((B, n_h, n_q, g, bq), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((W, bq), jnp.float32)],
            compiler_params=_params(4 * Sp * W * q.dtype.itemsize),
            interpret=interpret,
            name="flash_attention_fwd",
        )(_packed(q, Tp), _packed(k, Sp), _packed(v, Sp))
    return out[:, :T].reshape(B, T, H, D), lse


def _flash_bwd_impl(q, k, v, out, lse, do, causal, block_q, block_k,
                    interpret):
    B, T, H, D = q.shape
    S = k.shape[1]
    static, (W, n_h, n_q, n_k) = _geometry(q, k, causal, block_q, block_k)
    bq, bk, g, hd, Tp, Sp = (static[n] for n in
                             ("bq", "bk", "g", "hd", "Tp", "Sp"))
    q_spec = pl.BlockSpec((1, Tp, W), lambda b, h, j: (b, 0, h))
    row_spec = pl.BlockSpec((1, 1, n_q, g, bq),
                            lambda b, h, j: (b, h, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, W), lambda b, h, j: (b, j, h))
    with jax.named_scope("attention/flash_bwd"):
        # delta in the logsumexp's layout (B, n_h, n_q, g, bq): head slots
        # past H (an odd head count) and query rows past T read 0
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                          # (B, T, H)
        delta = jnp.pad(delta, ((0, 0), (0, Tp - T), (0, n_h * g - H)))
        delta = delta.reshape(B, n_q, bq, n_h, g).transpose(0, 3, 1, 4, 2)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel, **static),
            grid=(B, n_h, n_k),
            in_specs=[q_spec, q_spec, row_spec, row_spec, kv_spec,
                      kv_spec],
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, Tp, hd), q.dtype),
                jax.ShapeDtypeStruct((B, Sp, hd), k.dtype),
                jax.ShapeDtypeStruct((B, Sp, hd), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_q, W, bq), jnp.float32),    # dq, transposed
                pltpu.VMEM((g, bk, W), jnp.float32),
                pltpu.VMEM((g, bk, W), jnp.float32),
            ],
            compiler_params=_params(
                Tp * W * (6 * q.dtype.itemsize + 4)),
            interpret=interpret,
            name="flash_attention_bwd",
        )(_packed(q, Tp), _packed(do, Tp), lse, delta, _packed(k, Sp),
          _packed(v, Sp))
    return (dq[:, :T].reshape(q.shape), dk[:, :S].reshape(k.shape),
            dv[:, :S].reshape(v.shape))


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
                           block_q: int = None,
                           block_k: int = None,
                           interpret: bool = False) -> jax.Array:
    """Flash attention via pallas, differentiable. Shapes (B, T, H, D);
    the head dim divides or is a multiple of 128 (:func:`head_lanes`).

    The tiles default to what the lengths allow of 512 queries x 512 keys
    (``PERF.md`` section 6, PR 33: the medium train cell's step program
    timed over the tile forms). ``interpret=True`` runs the same kernels
    in the pallas interpreter (CPU testing path, no TPU).
    """
    if head_lanes(q.shape[-1]) is None:
        raise ValueError(
            f"head dim {q.shape[-1]} neither divides nor is a multiple of "
            f"the {_LANES}-lane row the flash kernels tile")
    return _flash(q, k, v, causal, block_q, block_k, interpret)
