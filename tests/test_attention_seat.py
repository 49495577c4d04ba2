"""The default attention seat (``models/transformer.py::attention_seat``):
one rule reading the call and the ambient mesh — blockwise kernel or dense
path — and the kernel it picks, per device under a mesh.

Everything here runs on the CPU: the rule is asked with ``backend="tpu"``
or through a monkeypatched ``jax.default_backend`` (the program has no
option for it), the kernel runs in the pallas interpreter.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu import FSDPStrategy, RayStrategy, Trainer
from ray_lightning_tpu.models import transformer
from ray_lightning_tpu.models.gpt import GPTModule
from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM,
                                                  attention_seat)
from ray_lightning_tpu.obs import Telemetry, seats
from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention
from ray_lightning_tpu.parallel import sharding as shardlib

QKV = (8, 1024, 16, 64)
MASK = np.zeros((1, 1, 1024, 1024), np.float32)


def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))


def _ask(q=QKV, k=None, *, mesh=None, manual=False, backend="tpu", **kw):
    call = dict(decode=False, causal=True, mask=None, dropout=False)
    call.update(kw)

    def ask():
        return attention_seat(q, k or q, backend=backend, **call)

    if mesh is None:
        return ask()
    if not manual:
        return shardlib.under_mesh(mesh, ask)()
    seen = []

    def local(x):
        seen.append(ask())
        return x

    shardlib.under_mesh(mesh, lambda: jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P("fsdp"), out_specs=P("fsdp")))(
            jnp.zeros((8, 2))))()
    return seen[0]


@pytest.mark.parametrize("case,kwargs,want", [
    ("kernel", {}, (True, "local")),
    ("decode", dict(decode=True), (False, "decode")),
    ("mask", dict(mask=MASK), (False, "mask")),
    ("dropout", dict(dropout=True), (False, "dropout")),
    ("non_causal", dict(causal=False), (False, "non_causal")),
    ("short", dict(q=(8, 128, 16, 64)), (False, "length")),
    ("at_threshold", dict(q=(8, 256, 16, 64)), (True, "local")),
    ("unequal_lengths", dict(k=(8, 2048, 16, 64)), (False, "length")),
    ("odd_head_dim", dict(q=(8, 1024, 16, 80)), (False, "head_dim")),
    ("head_dim_128", dict(q=(8, 1024, 8, 128)), (True, "local")),
    ("cpu_backend", dict(backend="cpu"), (False, "backend")),
    ("default_backend_here", dict(backend=None), (False, "backend")),
    ("one_device_mesh", dict(mesh=("dp", 1)), (True, "local")),
    ("fsdp4", dict(mesh=("fsdp", 4)), (True, "sharded")),
    ("dp2_fsdp2", dict(mesh=("dp", 2, "fsdp", 2)), (True, "sharded")),
    ("tp_alone", dict(mesh=("tp", 2)), (True, "sharded")),
    ("batch_indivisible", dict(q=(6, 1024, 16, 64), mesh=("fsdp", 4)),
     (False, "batch_indivisible")),
    ("sequence_cut", dict(mesh=("dp", 2, "sp", 2)),
     (False, "sequence_cut")),
    ("manual_region", dict(mesh=("fsdp", 4), manual=True),
     (True, "local")),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_seat_decides_from_its_call_and_the_mesh(case, kwargs, want):
    kwargs = dict(kwargs)
    if "mesh" in kwargs:
        m = kwargs["mesh"]
        kwargs["mesh"] = _mesh(**dict(zip(m[::2], m[1::2])))
    assert _ask(**kwargs) == want


def _force_kernel(monkeypatch, min_len=128):
    """The seat as a TPU would decide it, the kernel interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(transformer, "FLASH_MIN_LEN", min_len)
    monkeypatch.setattr(
        transformer, "pallas_flash_attention",
        functools.partial(pallas_flash_attention, interpret=True))
    jax.clear_caches()


def _nano(T=128, **kw):
    return TransformerConfig(
        vocab_size=97, max_seq_len=T, d_model=128, n_heads=2, n_layers=2,
        d_ff=256, dtype=jnp.bfloat16, causal=True, scan_layers=True,
        remat=True, remat_policy="dots_with_no_batch_dims", **kw)


def test_gpt_train_step_kernel_against_dense(monkeypatch):
    """Loss and every leaf's gradient of a nano GPT (two heads of 64, bf16
    compute, remat ``dots_with_no_batch_dims``, scanned layers): the seat
    on the kernel against the seat on the dense path."""
    T = 128
    model = TransformerLM(_nano(T))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, T + 1), 0, 97)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]

    def loss(p):
        logits = model.apply({"params": p}, tokens[:, :-1]).astype(
            jnp.float32)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), tokens[:, 1:, None], axis=-1))

    dense_loss, dense = jax.jit(jax.value_and_grad(loss))(params)
    _force_kernel(monkeypatch)
    took = {}
    with seats.tally(took):
        kernel_loss, kernel = jax.jit(jax.value_and_grad(loss))(params)
    # one scanned seat, traced once a pass flax makes over the block
    assert took["attn_kernel"] >= 1 and took["attn_dense"] == 0
    np.testing.assert_allclose(kernel_loss, dense_loss, rtol=2e-3)
    flat, _ = jax.tree_util.tree_flatten_with_path(dense)
    for (path, want), got in zip(flat, jax.tree_util.tree_leaves(kernel)):
        want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
        scale = np.abs(want).max() + 1e-6
        assert np.abs(got - want).max() <= 3e-2 * scale, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("axes,heads", [
    (dict(fsdp=4), 2), (dict(dp=2, fsdp=2), 3), (dict(fsdp=2, tp=2), 2)],
    ids=["fsdp4", "dp2_fsdp2_odd_heads", "fsdp2_tp2"])
def test_sharded_nest_equals_the_single_device_kernel(monkeypatch, axes,
                                                      heads):
    """The ``shard_map`` nest over four host devices against the plain
    kernel call: forward and the three gradients."""
    _force_kernel(monkeypatch)
    mesh = _mesh(**axes)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, do = (jax.random.normal(kk, (4, 128, heads, 64), jnp.float32)
                   for kk in ks)

    def local(q, k, v):
        return jnp.sum(transformer._blockwise_attention(q, k, v, "local")
                       * do)

    def nested(q, k, v):
        assert attention_seat(q.shape, k.shape, decode=False, causal=True,
                              mask=None, dropout=False) == (True, "sharded")
        return jnp.sum(transformer._blockwise_attention(q, k, v, "sharded")
                       * do)

    want = jax.value_and_grad(local, argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(shardlib.under_mesh(
        mesh, jax.value_and_grad(nested, argnums=(0, 1, 2))))(q, k, v)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        # (the summed scalar differs by the order of its partial sums)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def _first_step_span(strategy, cfg, batch=4):
    tel = Telemetry()
    trainer = Trainer(strategy=strategy, max_epochs=1, limit_train_batches=2,
                      limit_val_batches=0, enable_checkpointing=False,
                      enable_progress_bar=False, telemetry=tel)
    trainer.fit(GPTModule(config=cfg, batch_size=batch,
                          seq_len=cfg.max_seq_len, num_samples=2 * batch))
    first, second = tel.spans.spans("trainer.train_step")[:2]
    return first.args, second.args


def test_the_step_span_counts_the_seats(monkeypatch):
    """``trainer.train_step``'s first span (the one that traces the step)
    says how many seats took the kernel and why the others did not; later
    spans, which trace nothing, say nothing."""
    cfg = _nano(128)
    first, second = _first_step_span(RayStrategy(num_workers=1), cfg)
    assert first["attn_kernel"] == 0 and first["attn_dense"] >= 1
    assert first["attn_dense_reason"] == "length"
    assert second == {}
    _force_kernel(monkeypatch)
    first, _ = _first_step_span(FSDPStrategy(num_workers=4), cfg)
    assert first["attn_kernel"] >= 1 and first["attn_dense"] == 0


def test_dropout_in_training_keeps_the_dense_path(monkeypatch):
    _force_kernel(monkeypatch)
    model = TransformerLM(_nano(128, dropout=0.1))
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    took = {}
    with seats.tally(took):
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, tokens, deterministic=False,
            rngs={"dropout": jax.random.PRNGKey(1)}), params)
    assert took["attn_kernel"] == 0 and took["attn_dense"] >= 1
    assert took["attn_dense_reason"] == "dropout"
    took = {}
    with seats.tally(took):     # evaluation: no active dropout
        jax.eval_shape(lambda p: model.apply({"params": p}, tokens), params)
    assert took["attn_kernel"] >= 1 and took["attn_dense"] == 0
