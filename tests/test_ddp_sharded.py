"""Sharded (ZeRO-1) strategy tests, mirroring ``tests/test_ddp_sharded.py``.

The reference validates FairScale-backed sharding indirectly (params identical
after save/load ``:46-63``, worker-count resize on resume ``:83-137``). Here
we can additionally assert the *actual sharding layout* of the optimizer
state, since it's first-class in the API rather than hidden inside FairScale.
"""
import jax
import numpy as np
import pytest

from ray_lightning_tpu import (FSDPStrategy, RayShardedStrategy, RayStrategy,
                               Trainer)
from ray_lightning_tpu.models import BoringModel, LightningMNISTClassifier

from utils import get_trainer, train_test


@pytest.mark.parametrize("num_workers", [1, 2])
def test_train_sharded(tmp_root, num_workers):
    """Parity: tests/test_ddp_sharded.py:28-43 (fit works)."""
    model = BoringModel()
    strategy = RayShardedStrategy(num_workers=num_workers)
    trainer = get_trainer(tmp_root, strategy=strategy,
                          checkpoint_callback=False)
    train_test(trainer, model)


def test_opt_state_actually_sharded(tmp_root):
    """ZeRO-1 semantics: optimizer moments are laid out across dp, params
    replicated."""
    model = LightningMNISTClassifier(config={"batch_size": 32},
                                     num_samples=256)
    strategy = RayShardedStrategy(num_workers=4)
    trainer = get_trainer(tmp_root, strategy=strategy, max_epochs=1,
                          limit_train_batches=2, limit_val_batches=0,
                          checkpoint_callback=False)
    trainer.fit(model)
    # params: every leaf fully replicated
    for leaf in jax.tree_util.tree_leaves(trainer.train_state.params):
        assert leaf.sharding.is_fully_replicated
    # opt state: at least the large moment arrays must be sharded 4-ways
    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(
            trainer.train_state.opt_state)
        if hasattr(leaf, "sharding") and not leaf.sharding.is_fully_replicated
    ]
    assert sharded, "no optimizer-state leaf was sharded"
    big = max(sharded, key=lambda l: l.size)
    assert len(big.sharding.device_set) == 4


def test_sharded_matches_ddp(tmp_root):
    """ZeRO-1 must be numerically equivalent to plain DDP (sharding is a
    layout, not a math change)."""
    def run(strategy):
        model = BoringModel()
        trainer = get_trainer(tmp_root, strategy=strategy, max_epochs=1,
                              limit_train_batches=4, limit_val_batches=0,
                              checkpoint_callback=False, seed=3)
        trainer.fit(model)
        return jax.device_get(trainer.train_state.params)

    p_ddp = run(RayStrategy(num_workers=2))
    p_shard = run(RayShardedStrategy(num_workers=2))
    for a, b in zip(jax.tree_util.tree_leaves(p_ddp),
                    jax.tree_util.tree_leaves(p_shard)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_checkpoint_roundtrip_sharded(tmp_root):
    """Params identical after save/load. Parity:
    tests/test_ddp_sharded.py:46-63."""
    model = BoringModel()
    trainer = get_trainer(tmp_root, strategy=RayShardedStrategy(num_workers=2),
                          max_epochs=1)
    trainer.fit(model)
    best = trainer.checkpoint_callback.best_model_path
    assert best
    model2 = BoringModel()
    trainer2 = get_trainer(tmp_root, strategy=RayShardedStrategy(num_workers=2),
                           max_epochs=0, checkpoint_callback=False)
    # max_epochs=0 with resume: state restores, no further training
    trainer2.max_epochs = trainer.current_epoch + 1
    trainer2.limit_train_batches = 0
    trainer2.fit(model2, ckpt_path=best)
    a = jax.device_get(trainer.train_state.params)
    b = jax.device_get(trainer2.train_state.params)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


@pytest.mark.parametrize("resume_workers", [1, 4])
def test_resize_workers_on_resume(tmp_root, resume_workers):
    """Train on 2 shards, resume on 1 or 4. Parity:
    tests/test_ddp_sharded.py:83-137 (shrinking worker count)."""
    model = BoringModel()
    trainer = get_trainer(tmp_root, strategy=RayShardedStrategy(num_workers=2),
                          max_epochs=1)
    trainer.fit(model)
    best = trainer.checkpoint_callback.best_model_path
    model2 = BoringModel()
    trainer2 = get_trainer(
        tmp_root, strategy=RayShardedStrategy(num_workers=resume_workers),
        max_epochs=2, checkpoint_callback=False)
    trainer2.fit(model2, ckpt_path=best)
    assert trainer2.current_epoch == 1
    assert trainer2.train_state is not None


@pytest.mark.parametrize("num_workers", [2, 4])
def test_fsdp_params_sharded(tmp_root, num_workers):
    """FSDP lays parameters across the fsdp axis and still trains."""
    model = LightningMNISTClassifier(config={"batch_size": 32},
                                     num_samples=256)
    strategy = FSDPStrategy(num_workers=num_workers)
    trainer = get_trainer(tmp_root, strategy=strategy, max_epochs=1,
                          limit_train_batches=4, limit_val_batches=2,
                          checkpoint_callback=False)
    trainer.fit(model)
    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(
            trainer.train_state.params)
        if not leaf.sharding.is_fully_replicated
    ]
    assert sharded, "no parameter leaf was sharded under FSDP"


def _five_head_gpt():
    """A nano GPT whose 5 heads divide no mesh of 2 or 4 (GPT-2 XL's 25
    over 4 chips at test size). Plain SGD: Adam would turn the key bias's
    noise-level gradient into a full-size step of either sign."""
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.models.gpt import GPTModule
    from ray_lightning_tpu.models.transformer import TransformerConfig

    class SgdGPT(GPTModule):
        def configure_optimizers(self):
            return optax.sgd(0.1)

    cfg = TransformerConfig(vocab_size=257, max_seq_len=32, d_model=80,
                            n_heads=5, n_layers=2, d_ff=320, causal=True,
                            dtype=jnp.float32, scan_layers=True)
    return SgdGPT(config=cfg, batch_size=8, seq_len=32, num_samples=64)


@pytest.mark.parametrize("make_model,num_workers,steps", [
    (BoringModel, 2, 4), (_five_head_gpt, 4, 3)],
    ids=["boring", "gpt_heads_not_dividing_the_mesh"])
def test_fsdp_matches_ddp(tmp_root, make_model, num_workers, steps):
    def run(strategy):
        trainer = get_trainer(tmp_root, strategy=strategy, max_epochs=1,
                              limit_train_batches=steps,
                              limit_val_batches=0,
                              checkpoint_callback=False, seed=11)
        trainer.fit(make_model())
        return (jax.device_get(trainer.train_state.params),
                float(trainer.callback_metrics["train_loss"]))

    p_ddp, loss_ddp = run(RayStrategy(num_workers=num_workers))
    p_fsdp, loss_fsdp = run(FSDPStrategy(num_workers=num_workers))
    np.testing.assert_allclose(loss_fsdp, loss_ddp, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_ddp),
                    jax.tree_util.tree_leaves(p_fsdp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
