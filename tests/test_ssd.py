"""Mamba-2's SSD (``ops/ssd.py``): the chunkwise form against its own
one-position rule, position by position, on the CPU.

``OP_TOL`` 5e-5 on outputs of size ~15 and states of size ~2: float32
rounding of a 64-position block (the recurrence and the blocks sum in
different orders). A state held in bfloat16 between blocks reads ~1e-2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops.gated_delta import from_blocks, to_blocks
from ray_lightning_tpu.ops.ssd import ssd_chunk, ssd_step

OP_TOL = 5e-5


def _inputs(B=2, T=150, H=4, P=8, G=2, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (B, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0),
        A=-jnp.exp(2.0 * jax.random.uniform(ks[2], (H,))),
        B=jax.random.normal(ks[3], (B, T, G, N)),
        C=jax.random.normal(ks[4], (B, T, G, N)),
        state=jax.random.normal(ks[5], (B, H, P, N)))     # not zero


def _recurrence(x, lengths):
    """The one-position rule, position by position; a row's state stops
    at its length."""
    state, ys = x["state"], []
    for t in range(x["x"].shape[1]):
        y, new = ssd_step(x["x"][:, t], x["dt"][:, t], x["A"],
                          x["B"][:, t], x["C"][:, t], state)
        state = jnp.where((t < lengths)[:, None, None, None], new, state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("lengths", [(150, 150), (150, 77), (64, 1),
                                     (150, 0)],
                         ids=["whole", "ragged", "block_and_one", "empty_row"])
def test_chunk_matches_the_recurrence(lengths):
    """150 positions are two blocks of 64 and a ragged third (a pad tail
    of 42); two groups of B and C, two heads each; a non-zero state is
    carried in; a row of length 0 keeps its state exactly."""
    x = _inputs()
    lengths = jnp.asarray(lengths)
    y, state = jax.jit(ssd_chunk, static_argnames="block")(
        **x, lengths=lengths, block=64)
    want_y, want_state = _recurrence(x, lengths)
    assert float(jnp.abs(state - want_state).max()) < OP_TOL
    for b, n in enumerate(np.asarray(lengths)):
        assert float(jnp.abs(y[b, :n] - want_y[b, :n]).max(
            initial=0.0)) < OP_TOL
        if n == 0:
            np.testing.assert_array_equal(np.asarray(state[b]),
                                          np.asarray(x["state"][b]))
    assert bool(jnp.isfinite(y).all())


def test_step_is_the_equation():
    """``S' = exp(dt A) S + dt x B^T``, ``y = S' C``, head ``h`` reading
    group ``h // (H / G)``, written out in numpy."""
    x = {k: np.asarray(v, np.float64) for k, v in _inputs(T=1).items()}
    y, state = ssd_step(*(jnp.asarray(x[k][:, 0] if x[k].ndim > 2
                                      and k != "state" else x[k],
                                      jnp.float32)
                          for k in ("x", "dt", "A", "B", "C", "state")))
    group = np.arange(4) // 2
    want = (np.exp(x["dt"][:, 0] * x["A"])[..., None, None] * x["state"]
            + np.einsum("bh,bhp,bhn->bhpn", x["dt"][:, 0], x["x"][:, 0],
                        x["B"][:, 0][:, group]))
    np.testing.assert_allclose(np.asarray(state), want, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y), np.einsum("bhpn,bhn->bhp", want,
                                 x["C"][:, 0][:, group]), atol=1e-4)


def test_bf16_state_between_blocks_is_seen():
    x = _inputs()
    lengths = jnp.asarray((150, 150))
    _, want = _recurrence(x, lengths)
    _, state = ssd_chunk(**x, lengths=lengths, block=64,
                         state_dtype=jnp.bfloat16)
    assert float(jnp.abs(state - want).max()) > 20 * OP_TOL


@pytest.mark.parametrize("T", [64, 150], ids=["whole_blocks", "pad_tail"])
def test_blocks_round_trip(T):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 3, 5))
    blocks = to_blocks(x, 64)
    assert blocks.shape == (-(-T // 64), 2, 3, 64, 5)
    np.testing.assert_array_equal(np.asarray(from_blocks(blocks, T)),
                                  np.asarray(x))
