"""Attention op correctness: flash (XLA + pallas-interpret) and ring vs the
dot-product reference, across causal/non-causal, ragged lengths, bf16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu.ops.attention import dot_product_attention
from ray_lightning_tpu.ops.flash_attention import flash_attention
from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention
from ray_lightning_tpu.parallel.ring_attention import ring_attention


def _qkv(B=2, T=64, S=None, H=4, D=16, dtype=jnp.float32, seed=0):
    S = T if S is None else S
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, T, H, D), dtype)
    k = jax.random.normal(kk, (B, S, H, D), dtype)
    v = jax.random.normal(kv, (B, S, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,S,block", [(64, 64, 16), (48, 80, 32),
                                       (128, 128, 128), (100, 100, 64)])
def test_flash_matches_dot(causal, T, S, block):
    # cross-length causal (48, 80) uses the end-aligned convention in both
    q, k, v = _qkv(T=T, S=S)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = _qkv(T=64, dtype=jnp.bfloat16)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          use_pallas=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,S,block", [(64, 64, 32), (96, 96, 64)])
def test_pallas_flash_interpret_matches_dot(causal, T, S, block):
    """Same kernel code the TPU runs, via the pallas interpreter."""
    q, k, v = _qkv(T=T, S=S)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = pallas_flash_attention(q, k, v, causal=causal, block_q=block,
                                 block_k=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_mask_fallback():
    """Arbitrary masks route to the reference implementation."""
    q, k, v = _qkv(T=32)
    mask = jnp.where(
        jax.random.bernoulli(jax.random.PRNGKey(1), 0.8, (1, 1, 32, 32)),
        0.0, jnp.finfo(jnp.float32).min)
    ref = dot_product_attention(q, k, v, mask=mask)
    out = flash_attention(q, k, v, mask=mask, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dot(causal):
    """Ring over a 4-way sp mesh ≡ full attention."""
    devices = np.array(jax.devices()[:4])
    mesh = Mesh(devices, ("sp",))
    q, k, v = _qkv(B=2, T=64, H=2, D=8)
    ref = dot_product_attention(q, k, v, causal=causal)

    def local_fn(q, k, v):
        return ring_attention(q, k, v, causal=causal)

    out = jax.jit(shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_fallback_outside_shard_map():
    q, k, v = _qkv(T=32)
    ref = dot_product_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_gpt_with_flash_attention(tmp_path):
    """attention_impl='flash' trains through the full stack."""
    from ray_lightning_tpu import RayStrategy, Trainer
    from ray_lightning_tpu.models.gpt import GPTModule, gpt2_config

    cfg = gpt2_config("nano", vocab_size=256, max_seq_len=32,
                      attention_impl="flash")
    model = GPTModule(config=cfg, batch_size=4, seq_len=32, num_samples=16,
                      lr=1e-3)
    trainer = Trainer(strategy=RayStrategy(num_workers=2), max_epochs=1,
                      limit_train_batches=2, limit_val_batches=1,
                      default_root_dir=str(tmp_path))
    trainer.fit(model)
    assert trainer.global_step == 2


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,S,block", [(64, 64, 32), (96, 48, 64)])
def test_pallas_flash_grads_interpret(causal, T, S, block):
    """The pallas backward kernels (custom_vjp) match XLA's autodiff of
    the reference — round-2 find: the bare kernel had no JVP rule, so
    attention_impl='flash' crashed every TPU training step."""
    q, k, v = _qkv(T=T, S=S)
    do = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)

    def f(q, k, v):
        return jnp.sum(pallas_flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block,
            interpret=True) * do)

    def r(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) * do)

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "q k v".split()):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


def test_pallas_flash_grads_bf16_interpret():
    q, k, v = _qkv(T=64, dtype=jnp.bfloat16)
    do = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.float32)

    def f(q, k, v):
        return jnp.sum(pallas_flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32,
            interpret=True).astype(jnp.float32) * do)

    def r(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, causal=True).astype(jnp.float32) * do)

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=5e-2, atol=5e-2)
