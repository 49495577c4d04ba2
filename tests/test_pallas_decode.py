"""The per-row decode kernel (``ops/pallas_decode.py``) against the dense
masked read it replaces, in the pallas interpreter: each row attends its
first ``lengths[b]`` cached positions and nothing past them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops.attention import dot_product_attention
from ray_lightning_tpu.ops.pallas_decode import (BLOCK, blocks_read,
                                                 decode_attention,
                                                 decode_block)

KB, L = 16, 64          # a small block, so a slot holds four


def _qkv(B, H, D, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, H, D), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _masked(q, k, v, lengths):
    """The dense path's read: every position, the per-row mask."""
    key = jnp.arange(k.shape[1])[None, None, None, :]
    mask = jnp.where(key < lengths[:, None, None, None], 0.0,
                     jnp.finfo(jnp.float32).min)
    return dot_product_attention(q, k, v, mask=mask)


@pytest.mark.parametrize("H,D", [(2, 64), (1, 128), (4, 32), (20, 64)],
                         ids=["two_heads_of_64", "one_head_of_128",
                              "four_heads_of_32", "closed40_heads"])
def test_kernel_against_the_masked_read(H, D):
    """Lengths 1, kb - 1, kb, kb + 1 and L, and a row of one position
    beside a full one: the rows' outputs agree to bf16 rounding."""
    lengths = jnp.array([1, KB - 1, KB, KB + 1, L, 1], jnp.int32)
    q, k, v = _qkv(len(lengths), H, D)
    got = decode_attention(q, k, v, lengths, block=KB, interpret=True)
    want = _masked(q, k, v, lengths)
    assert got.shape == q.shape and got.dtype == q.dtype
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(g, w, atol=2e-2 * np.abs(w).max())


def test_positions_past_the_length_are_never_read():
    """K/V past each row's length poisoned — NaN in the blocks past it,
    large finite junk in the tail of its last block — leave every output
    equal to the clean cache's: those blocks are not copied, that tail is
    masked."""
    lengths = jnp.array([1, KB + 3, 2 * KB, 3 * KB - 1], jnp.int32)
    q, k, v = _qkv(len(lengths), 2, 64, seed=1)
    key = jnp.arange(L)[None, :, None, None]
    n = lengths[:, None, None, None]
    unread = key >= -(-n // KB) * KB

    def poison(x):
        return jnp.where(unread, jnp.nan, jnp.where(key >= n, 300.0, x))

    poisoned = [poison(x) for x in (k, v)]
    clean = decode_attention(q, k, v, lengths, block=KB, interpret=True)
    got = decode_attention(q, *poisoned, lengths, block=KB, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_a_length_past_the_slot_reads_the_whole_slot():
    lengths = jnp.array([L, L + 40], jnp.int32)
    q, k, v = _qkv(2, 2, 64, seed=2)
    q = q.at[1].set(q[0])
    k, v = k.at[1].set(k[0]), v.at[1].set(v[0])
    got = np.asarray(decode_attention(q, k, v, lengths, block=KB,
                                      interpret=True))
    np.testing.assert_array_equal(got[0], got[1])


def test_the_call_refuses_what_it_cannot_tile():
    q, k, v = _qkv(2, 2, 64)
    with pytest.raises(ValueError, match="one query a row"):
        decode_attention(jnp.concatenate([q, q], axis=1), k, v,
                         jnp.ones((2,), jnp.int32), block=KB, interpret=True)
    with pytest.raises(ValueError, match="whole blocks"):
        decode_attention(q, k, v, jnp.ones((2,), jnp.int32), block=24,
                         interpret=True)


def test_blocks_follow_the_slot_length():
    assert decode_block(1024) == BLOCK and decode_block(4608) == BLOCK
    assert decode_block(1000) is None
    lengths = np.array([1, BLOCK - 1, BLOCK, BLOCK + 1, 8 * BLOCK])
    assert blocks_read(lengths).tolist() == [1, 1, 1, 2, 8]
