"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) over a block
of positions and over one.

Per head the state is a matrix ``S (d_v, d_k)``; position ``t`` brings a
query and a key ``(d_k,)``, a value ``(d_v,)``, a decay ``alpha_t`` in
``(0, 1]`` and a write strength ``beta_t`` in ``[0, 2]``::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

The rule *reads the state before it writes it*: with ``u_t = beta_t (v_t
- alpha_t S_{t-1} k_t)`` it is ``S_t = alpha_t S_{t-1} + u_t k_t^T``.

:func:`gated_delta_chunk` is the chunkwise (WY / UT) form over blocks of
:data:`BLOCK` positions. With ``g_t`` the running sum of ``log alpha``
inside a block and ``S_0`` the state the block starts from, the rows
``u_t`` of ``U`` solve the unit-lower-triangular system

    (I + A) U = beta * V - (beta * exp(g) * K) S_0^T,
    A[t, i] = beta_t exp(g_t - g_i) (k_t . k_i)   for i < t,

and then ``O = (exp(g) * Q) S_0^T + (tril(Q K^T) * exp(g_t - g_i)) U``,
``S_C = exp(g_C) S_0 + U^T (exp(g_C - g) * K)``. Everything that does
not hold ``S_0`` (``A``, its inverse by :func:`unit_lower_inverse`, the
two solves against ``beta V`` and ``beta exp(g) K``, ``Q K^T``) is
computed for all blocks at once; the
``lax.scan`` over blocks carries the state and does the matmuls against
it. Decays enter only as ``exp(g_t - g_i)`` with ``i <= t``, which is at
most 1.

All of it is float32 at ``Precision.HIGHEST`` (on a TPU the default
precision of a float32 matmul is one bfloat16 pass): the state, the
solve and the products against the state are what a bfloat16 run gets
wrong first (``tests/test_olmo_hybrid.py``). Plain ``jax.numpy`` / XLA;
a Pallas version belongs to a later PR with the cell's trace in hand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: positions a block of the chunkwise form holds
BLOCK = 64

_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def hold(state, dtype):
    """``state`` (float32) with the precision of ``dtype``, as what is
    kept between positions or blocks. ``reduce_precision``, not a cast
    there and back, which XLA may elide."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return state
    return lax.reduce_precision(state, info.nexp, info.nmant)


def to_blocks(x, block: int):
    """``(B, T, H, ...)`` -> ``(n, B, H, block, ...)`` float32: the
    positions cut into ``n`` blocks (the last one padded with zeros), the
    blocks first and each head's positions beside its features — the
    layout the chunkwise forms scan over (here and ``ops/ssd.py``)."""
    B, T = x.shape[:2]
    n = -(-T // block)
    x = jnp.pad(x.astype(F32), ((0, 0), (0, n * block - T)) + ((0, 0),) * (
        x.ndim - 2))
    x = x.reshape((B, n, block) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)


def from_blocks(o, T: int):
    """``(n, B, H, block, d)`` -> ``(B, T, H, d)``: :func:`to_blocks`
    undone, the pad tail cut."""
    n, B, H, block, d = o.shape
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # (B, n, block, H, d)
    return o.reshape(B, n * block, H, d)[:, :T]


def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a (..., n, n)``,
    ``n`` a power of two: the inverses of the diagonal blocks of size
    ``m`` give those of size ``2 m`` —

        [[L11, 0], [L21, L22]]^-1 = [[X11, 0], [-X22 L21 X11, X22]]

    — from the 1 x 1 blocks (whose inverse is 1) up. With ``X`` the
    block-diagonal matrix of the inverses so far and ``a_m`` the part of
    ``a`` inside the blocks of ``2 m`` and outside those of ``m`` (the
    ``L21`` quarters, picked by a constant mask), a round is ``X <- X - X
    a_m X``: ``log2 n`` rounds of two batched matmuls, no gather and no
    slice. The same algebra as a blocked back-substitution, so nothing
    larger than the inverse itself is ever formed; XLA's own
    ``triangular_solve`` inverts a 64-block by a custom call that took
    2.3 ms a layer and dispatch on a v5e (PERF.md section 6, PR 32)."""
    n = a.shape[-1]
    row = jnp.arange(n)[:, None]
    col = jnp.arange(n)[None, :]
    x = jnp.broadcast_to(jnp.eye(n, dtype=F32), a.shape)
    m = 1
    while m < n:
        quarter = (row // (2 * m) == col // (2 * m)) & (row // m != col // m)
        x = x - jnp.einsum("...ij,...jk,...kl->...il", x,
                           jnp.where(quarter, a, 0.0), x, precision=_HI)
        m *= 2
    return x


def gated_delta_step(q, k, v, log_alpha, beta, state):
    """One position. ``q`` / ``k`` ``(B, H, d_k)``, ``v`` ``(B, H, d_v)``,
    ``log_alpha`` / ``beta`` ``(B, H)``, ``state`` ``(B, H, d_v, d_k)``
    float32 -> ``(o (B, H, d_v), state')``."""
    q, k, v = (x.astype(F32) for x in (q, k, v))
    decayed = jnp.exp(log_alpha.astype(F32))[..., None, None] * state
    read = jnp.einsum("bhvk,bhk->bhv", decayed, k, precision=_HI)
    u = beta.astype(F32)[..., None] * (v - read)
    state = decayed + u[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvk,bhk->bhv", state, q, precision=_HI), state


def gated_delta_chunk(q, k, v, log_alpha, beta, state, lengths,
                      state_dtype=F32):
    """``T`` positions a row, from ``state``. ``q`` / ``k``
    ``(B, T, H, d_k)``, ``v`` ``(B, T, H, d_v)``, ``log_alpha`` / ``beta``
    ``(B, T, H)``, ``state`` ``(B, H, d_v, d_k)`` float32, ``lengths``
    ``(B,)``: positions at or past a row's length leave its state
    untouched (``beta = 0``, ``alpha = 1``; their outputs are finite and
    mean nothing). ``T`` need not be a multiple of :data:`BLOCK`.
    ``state_dtype`` is the precision the state is held in between
    blocks. Returns ``(o (B, T, H, d_v) float32, state')``."""
    T = q.shape[1]
    dv = v.shape[-1]
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    blocks = lambda x: to_blocks(x, BLOCK)              # noqa: E731

    q, k, v = blocks(q), blocks(k), blocks(v)
    beta = blocks(jnp.where(valid[..., None], beta, 0.0))  # (n, B, H, C)
    g = jnp.cumsum(blocks(jnp.where(valid[..., None], log_alpha, 0.0)),
                   axis=-1)
    # exp(g_t - g_i) for i <= t; the upper triangle is masked out before
    # the exp so that it cannot overflow
    lower = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...tk,...ik->...ti", k, k, precision=_HI)
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(g))[..., None] * k], axis=-1)
    solved = jnp.einsum("...ti,...ic->...tc", unit_lower_inverse(a), rhs,
                        precision=_HI)
    u_v, w = solved[..., :dv], solved[..., dv:]         # U = u_v - w S^T
    attn = decay * jnp.einsum("...tk,...ik->...ti", q, k, precision=_HI)
    q_in = jnp.exp(g)[..., None] * q
    k_out = jnp.exp(g[..., -1:] - g)[..., None] * k
    g_end = jnp.exp(g[..., -1])

    def body(s, xs):
        u_v, w, attn, q_in, k_out, g_end = xs
        u = u_v - jnp.einsum("bhtk,bhvk->bhtv", w, s, precision=_HI)
        o = (jnp.einsum("bhtk,bhvk->bhtv", q_in, s, precision=_HI)
             + jnp.einsum("bhti,bhiv->bhtv", attn, u, precision=_HI))
        s = g_end[..., None, None] * s \
            + jnp.einsum("bhtv,bhtk->bhvk", u, k_out, precision=_HI)
        return hold(s, state_dtype), o

    state, o = lax.scan(body, state.astype(F32),
                        (u_v, w, attn, q_in, k_out, g_end))
    return from_blocks(o, T), state
