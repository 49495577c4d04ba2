"""GPipe-style pipeline parallelism over the ``pp`` axis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu.parallel.pipeline import (pipeline_apply,
                                                 split_microbatches)


def _block(p, x):
    """One residual MLP layer: x + tanh(x @ W + b)."""
    return x + jnp.tanh(x @ p["w"] + p["b"])


def _stage_fn(stage_params, x):
    """Apply this stage's stack of layers (leading dim = layers/stage)."""
    def body(x, p):
        return _block(p, x), None
    out, _ = jax.lax.scan(body, x, stage_params)
    return out


def _stacked_params(n_layers, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), n_layers)
    return {
        "w": jnp.stack([jax.random.normal(k, (d, d)) * 0.1 for k in ks]),
        "b": jnp.zeros((n_layers, d)),
    }


def _serial_reference(params, x):
    def body(x, p):
        return _block(p, x), None
    out, _ = jax.lax.scan(body, x, params)
    return out


def _pipelined(mesh, params, microbatches):
    fn = shard_map(
        lambda p, mb: pipeline_apply(_stage_fn, p, mb),
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False)
    return jax.jit(fn)(params, microbatches)


@pytest.mark.parametrize("n_stages,n_layers,n_micro", [(4, 8, 8), (2, 6, 4),
                                                       (8, 8, 3)])
def test_pipeline_matches_serial(n_stages, n_layers, n_micro):
    """S-stage pipeline over M microbatches == serial layer stack, incl.
    M < S (all-bubble) and uneven M vs S."""
    d = 16
    mesh = Mesh(np.array(jax.devices()[:n_stages]), ("pp",))
    params = _stacked_params(n_layers, d)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, d))
    mb = split_microbatches(x, n_micro)

    out = _pipelined(mesh, params, mb)
    want = _serial_reference(params, x)
    np.testing.assert_allclose(
        np.asarray(out.reshape(-1, d)), np.asarray(want), rtol=2e-5,
        atol=2e-5)


def test_pipeline_grads_match_serial():
    """Autodiff through the schedule: grads w.r.t. params and input match
    the serial stack (the pipelined backward is derived, not hand-built)."""
    d = 8
    mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))
    params = _stacked_params(8, d)
    x = jax.random.normal(jax.random.PRNGKey(2), (16, d))
    mb = split_microbatches(x, 8)

    def pipe_loss(params, mb):
        fn = shard_map(
            lambda p, m: pipeline_apply(_stage_fn, p, m),
            mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
            check_vma=False)
        return jnp.sum(fn(params, mb) ** 2)

    def serial_loss(params, x):
        return jnp.sum(_serial_reference(params, x) ** 2)

    g_pipe = jax.jit(jax.grad(pipe_loss))(params, mb)
    g_ser = jax.grad(serial_loss)(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_ser)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5,
                                   atol=5e-5)


def test_pipelined_training_step_dp_x_pp():
    """A full dp×pp training step: batch split over dp, layers over pp,
    grads psum'd over dp — loss decreases over a few SGD steps."""
    d, n_layers = 8, 4
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "pp"))
    params = _stacked_params(n_layers, d, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, d))
    y = jax.random.normal(jax.random.PRNGKey(5), (32, d)) * 0.1

    def local_step(params, xb, yb):
        mb_x = split_microbatches(xb, 4)

        def loss_fn(p):
            out = pipeline_apply(_stage_fn, p, mb_x)
            return jnp.mean((out.reshape(yb.shape) - yb) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.pmean(loss, "dp")
        grads = jax.lax.pmean(grads, "dp")
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params,
                                     grads)
        return new, loss

    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P("pp"), P("dp"), P("dp")),
        out_specs=(P("pp"), P()),
        check_vma=False))

    losses = []
    for _ in range(5):
        params, loss = step(params, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_split_microbatches_validates():
    with pytest.raises(ValueError, match="divisible"):
        split_microbatches(jnp.zeros((10, 4)), 3)
    assert split_microbatches(jnp.zeros((12, 4)), 3).shape == (3, 4, 4)


def test_pipeline_mixed_dtype_stage():
    """bf16 microbatches through f32 params (the bf16-mixed pattern):
    carries adopt the promoted output dtype instead of crashing."""
    d = 8
    mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))
    params = _stacked_params(8, d)  # f32
    x = jax.random.normal(jax.random.PRNGKey(6), (16, d),
                          dtype=jnp.bfloat16)
    mb = split_microbatches(x, 4)
    out = _pipelined(mesh, params, mb)
    want = _serial_reference(params, x.astype(jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out.reshape(-1, d)),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_pipeline_rejects_shape_changing_stage():
    mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))
    params = _stacked_params(4, 8)

    def bad_stage(p, x):
        return jnp.concatenate([x, x], axis=-1)

    fn = shard_map(
        lambda p, mb: pipeline_apply(bad_stage, p, mb),
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False)
    with pytest.raises(ValueError, match="preserve"):
        jax.jit(fn)(params, jnp.zeros((4, 4, 8)))


def test_pipelined_lm_trains_on_dp_x_pp(tmp_root):
    """Trainer-integrated pipeline: the stacked blocks shard over pp via
    pipeline_parallel_rule and the GPipe schedule runs inside the jitted
    step; params match the same model trained serially (same seed)."""
    import optax

    from ray_lightning_tpu import MeshStrategy, RayStrategy, Trainer
    from ray_lightning_tpu.models.pipelined_lm import PipelinedLMModule
    from ray_lightning_tpu.parallel.pipeline import pipeline_parallel_rule

    class SgdPipe(PipelinedLMModule):
        def configure_optimizers(self):
            return optax.sgd(0.1)

    def run(strategy):
        model = SgdPipe(n_layers=4, batch_size=16, seq_len=32,
                        num_samples=64, n_microbatches=4)
        # f32 compute isolates layout effects (same rationale as the SP
        # equivalence test)
        model.cfg = model.cfg.__class__(
            **{**model.cfg.__dict__, "dtype": jnp.float32})
        trainer = Trainer(strategy=strategy, max_epochs=1,
                          limit_train_batches=3, limit_val_batches=0,
                          num_sanity_val_steps=0,
                          enable_checkpointing=False,
                          default_root_dir=tmp_root, seed=11)
        trainer.fit(model)
        return trainer

    pp_trainer = run(MeshStrategy(axes={"pp": 4, "dp": 2},
                                  param_rule=pipeline_parallel_rule))
    # layout probe: stacked blocks sharded over pp, embeddings replicated
    flat = jax.tree_util.tree_flatten_with_path(
        pp_trainer.train_state.params)[0]
    pp_sharded = 0
    for path, leaf in flat:
        names = "/".join(str(getattr(p, "key", p)) for p in path)
        if "blocks" in names and leaf.ndim >= 1:
            assert leaf.sharding.spec[0] == "pp", (names,
                                                   leaf.sharding.spec)
            pp_sharded += 1
        elif "wte" in names:
            assert all(s is None for s in leaf.sharding.spec)
    assert pp_sharded >= 4

    serial_trainer = run(RayStrategy(num_workers=2))
    for a, b in zip(
            jax.tree_util.tree_leaves(
                jax.device_get(pp_trainer.train_state.params)),
            jax.tree_util.tree_leaves(
                jax.device_get(serial_trainer.train_state.params))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-4)


def test_pipelined_stack_explicit_microbatches_validated():
    from ray_lightning_tpu.parallel import pipeline as pipe

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "pp"))
    pipe.set_pp_mesh(mesh)
    try:
        params = _stacked_params(4, 8)
        with pytest.raises(ValueError, match="divisible"):
            pipe.pipelined_stack(_block, params,
                                 jnp.zeros((16, 8)), n_microbatches=3)
    finally:
        pipe.set_pp_mesh(None)


def test_pipelined_lm_rejects_dropout():
    from ray_lightning_tpu.models.pipelined_lm import PipelinedTransformerLM
    from ray_lightning_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, max_seq_len=16, d_model=16,
                            n_heads=2, n_layers=2, d_ff=32, causal=True,
                            scan_layers=False, dropout=0.1)
    model = PipelinedTransformerLM(cfg)
    with pytest.raises(NotImplementedError, match="dropout"):
        model.init(jax.random.PRNGKey(0),
                   jnp.zeros((2, 16), dtype=jnp.int32))
