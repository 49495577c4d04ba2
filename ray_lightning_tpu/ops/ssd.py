"""Mamba-2's state-space dual (SSD, arXiv:2405.21060) over a block of
positions and over one, with ``B`` and ``C`` shared by the heads of a
group.

Per head ``h`` the state is a matrix ``S (P, N)``; position ``t`` brings
an input ``x_t (P,)``, a step ``dt_t > 0`` and, from the head's group
``h // (H / G)``, a write key ``B_t (N,)`` and a read key ``C_t (N,)``.
With ``A < 0`` one scalar a head::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

(the ``D`` skip and the gate are the caller's). It is the gated delta
rule of ``ops/gated_delta.py`` with the write ``u_t = dt_t x_t`` fixed
before the state is read, so the chunkwise form needs no solve: with
``g_t`` the running sum of ``dt A`` inside a block and ``S_0`` the state
the block starts from,

    Y = exp(g) * (C S_0^T) + (tril(C B^T) * exp(g_t - g_i)) (dt * X),
    S_C = exp(g_C) S_0 + (exp(g_C - g) * dt * X)^T B.

:func:`ssd_chunk` computes the in-block term (``C B^T`` a group, its
decays and the product against ``dt X`` a head) for all blocks at once,
then a ``lax.scan`` over blocks carries the state and does the two
products against it. Decays enter only as ``exp`` of a difference that
is at most 0. Blocks are laid out as the delta rule's
(:func:`~ray_lightning_tpu.ops.gated_delta.to_blocks`).

All of it is float32 at ``Precision.HIGHEST`` (a TPU's default float32
matmul is one bfloat16 pass), and the state is held in ``state_dtype``
between blocks (:func:`~ray_lightning_tpu.ops.gated_delta.hold`): a
bfloat16 state is what ``tests/test_falcon_h1.py`` shows failing. Plain
``jax.numpy`` / XLA.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_lightning_tpu.ops.gated_delta import from_blocks, hold, to_blocks

#: positions a block of the chunkwise form holds (``mamba_chunk_size``)
BLOCK = 128

_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _per_head(k, heads: int):
    """A group's ``(..., G, N)`` key for each of ``heads`` heads."""
    return jnp.repeat(k, heads // k.shape[-2], axis=-2)


def ssd_step(x, dt, A, B, C, state):
    """One position. ``x (Bt, H, P)``, ``dt (Bt, H)``, ``A (H,)``, ``B`` /
    ``C (Bt, G, N)``, ``state (Bt, H, P, N)`` float32 -> ``(y (Bt, H, P),
    state')``: the state read and written once."""
    H = x.shape[1]
    x, dt = x.astype(F32), dt.astype(F32)
    B, C = _per_head(B.astype(F32), H), _per_head(C.astype(F32), H)
    state = jnp.exp(dt * A)[..., None, None] * state \
        + (dt[..., None] * x)[..., :, None] * B[..., None, :]
    return jnp.einsum("bhpn,bhn->bhp", state, C, precision=_HI), state


def ssd_chunk(x, dt, A, B, C, state, lengths, block: int = BLOCK,
              state_dtype=F32):
    """``T`` positions a row, from ``state``. ``x (Bt, T, H, P)``, ``dt
    (Bt, T, H)``, ``A (H,)``, ``B`` / ``C (Bt, T, G, N)``, ``state (Bt,
    H, P, N)`` float32, ``lengths (Bt,)``: positions at or past a row's
    length leave its state untouched (``dt`` = 0 there; their outputs
    are finite and mean nothing). ``T`` need not be a multiple of
    ``block``. Returns ``(y (Bt, T, H, P) float32, state')``."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = H // G                      # heads a group
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    dt = jnp.where(valid[..., None], dt.astype(F32), 0.0)
    n = -(-T // block)

    def heads(z):                   # (n, Bt, H, ...) -> (n, Bt, G, R, ...)
        return z.reshape((n, Bt, G, R) + z.shape[3:])

    u = heads(to_blocks(dt[..., None] * x.astype(F32), block))  # dt * X
    Bm, Cm = to_blocks(B, block), to_blocks(C, block)   # (n, Bt, G, c, N)
    g = jnp.cumsum(heads(to_blocks(dt * A, block)), axis=-1)
    lower = jnp.tril(jnp.ones((block, block), bool))
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    cb = jnp.einsum("...tn,...in->...ti", Cm, Bm, precision=_HI)
    y_in = jnp.einsum("...rti,...rip->...rtp", decay * cb[..., None, :, :],
                      u, precision=_HI)
    u_out = jnp.exp(g[..., -1:] - g)[..., None] * u
    g_in, g_end = jnp.exp(g), jnp.exp(g[..., -1])

    def body(s, xs):                # s (Bt, G, R, P, N)
        y_in, u_out, Bm, Cm, g_in, g_end = xs
        y = y_in + g_in[..., None] * jnp.einsum(
            "bgrpn,bgtn->bgrtp", s, Cm, precision=_HI)
        s = g_end[..., None, None] * s + jnp.einsum(
            "bgrtp,bgtn->bgrpn", u_out, Bm, precision=_HI)
        return hold(s, state_dtype), y

    state, y = lax.scan(body, state.astype(F32).reshape(Bt, G, R, P, N),
                        (y_in, u_out, Bm, Cm, g_in, g_end))
    return (from_blocks(y.reshape(n, Bt, H, block, P), T),
            state.reshape(Bt, H, P, N))
