"""The default attention seat (``models/transformer.py::attention_seat``):
one rule reading the call and the ambient mesh — blockwise kernel or dense
path — and the kernel it picks, per device under a mesh.

Everything here runs on the CPU: the rule is asked with ``backend="tpu"``
or through a monkeypatched ``jax.default_backend`` (the program has no
option for it), the kernel runs in the pallas interpreter.
"""
import dataclasses
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
                                                  attention_seat,
                                                  decode_seat)
from ray_lightning_tpu.obs import Telemetry, seats
from ray_lightning_tpu.ops.pallas_decode import decode_attention
from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention
from ray_lightning_tpu.parallel import sharding as shardlib

QKV = (8, 1024, 16, 64)
MASK = np.zeros((1, 1, 1024, 1024), np.float32)
BLOCK = (4, 512, 20, 64)        # gpt2-large.serve.closed40's prefill block


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
    # a decode-mode call: only a fresh prefill block (transformer.
    # fresh_cache) goes on to the other tests — closed40's 4 x 512 block
    ("fresh_block", dict(q=BLOCK, decode=True, fresh=True), (True, "local")),
    ("fresh_short", dict(q=(4, 128, 20, 64), decode=True, fresh=True),
     (False, "length")),
    ("fresh_masked", dict(q=BLOCK, decode=True, fresh=True,
                          mask=MASK[..., :512, :512]), (False, "mask")),
    ("fresh_on_cpu", dict(q=BLOCK, decode=True, fresh=True, backend="cpu"),
     (False, "backend")),
    ("fresh_fsdp4", dict(q=BLOCK, decode=True, fresh=True,
                         mesh=("fsdp", 4)), (True, "sharded")),
    ("cache_read", dict(q=BLOCK, k=(4, 1024, 20, 64), decode=True,
                        causal=False, mask=MASK[..., :512, :]),
     (False, "decode")),
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


# --------------------------------------------------------------------- #
# decode mode: a fresh prefill block is the one cached call the seat may
# hand to the kernel (transformer.fresh_cache, opened by _prefill_impl)
# --------------------------------------------------------------------- #
@pytest.fixture
def seat_on_tpu(monkeypatch):
    """The seats alone asked as a TPU would ask them (the blockwise one from
    128 positions), the kernels interpreted; the rest of the program
    (cache writes, donation) stays the CPU's. Compiled programs traced
    either way are dropped on both sides of the test."""
    monkeypatch.setattr(transformer, "attention_seat",
                        functools.partial(attention_seat, backend="tpu"))
    monkeypatch.setattr(transformer, "decode_seat",
                        functools.partial(decode_seat, backend="tpu"))
    monkeypatch.setattr(transformer, "FLASH_MIN_LEN", 128)
    monkeypatch.setattr(
        transformer, "pallas_flash_attention",
        functools.partial(pallas_flash_attention, interpret=True))
    monkeypatch.setattr(transformer, "decode_attention",
                        functools.partial(decode_attention, interpret=True))
    jax.clear_caches()
    yield
    jax.clear_caches()


def _decode_nano(L=512, scan=False):
    """Nano widths with heads of 64 (the kernel's case), bf16 compute."""
    return TransformerConfig(
        vocab_size=97, max_seq_len=L, d_model=128, n_heads=2, n_layers=2,
        d_ff=256, dtype=jnp.bfloat16, causal=True, decode=True,
        scan_layers=scan)


def _params(cfg):
    train = dataclasses.replace(cfg, decode=False)
    return TransformerLM(train).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _cache_at(model, B, offset):
    """An empty cache whose shared index points at ``offset``: what the
    chunk program hands the model for a piece of a prompt."""
    with seats.unseen():        # the shape-building pass is no call
        cache = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((B, 1), jnp.int32),
                           positions=jnp.zeros((B, 1), jnp.int32))["cache"]
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: (jnp.full(leaf.shape, offset, leaf.dtype)
                         if "cache_index" in jax.tree_util.keystr(p)
                         else leaf), cache)


def _continuation(kind, model, params, T=256):
    """One decode-mode call after a prefill of ``T`` positions, traced."""
    from ray_lightning_tpu.models.generate import decode_step, verify_step
    B = 2
    cache = _cache_at(model, B, T)
    one = jnp.zeros((B, 1), jnp.int32)
    block = jnp.zeros((B, 128), jnp.int32)
    at = jnp.full((B, 1), T, jnp.int32)
    if kind == "step":              # a shared-index T = 1 step
        return model.apply({"params": params, "cache": cache}, one,
                           positions=at, mutable=["cache"])
    if kind == "per_row_step":      # the engine's and generate()'s step
        return decode_step(model, params, cache, one, at)
    if kind == "per_row_block":     # a speculative verify block
        return verify_step(model, params, cache, block,
                           T + jnp.arange(128)[None, :].repeat(B, 0))
    if kind == "block_after_prefill":   # a shared-index block at T
        return model.apply({"params": params, "cache": cache}, block,
                           positions=T + jnp.arange(128)[None, :],
                           mutable=["cache"])
    assert kind == "chunk_at_offset"    # the chunk program's traced index

    def piece(offset):
        return model.apply(
            {"params": params, "cache": _cache_at(model, B, offset)}, block,
            positions=offset + jnp.arange(128)[None, :], mutable=["cache"])
    return piece(jnp.int32(T))


@pytest.mark.parametrize("kind", ["step", "per_row_step", "per_row_block",
                                  "block_after_prefill", "chunk_at_offset"])
def test_every_continuation_keeps_the_dense_path(seat_on_tpu, kind):
    """Only the prefill's first block is fresh: a call that attends what an
    earlier call cached is refused by the blockwise seat with ``decode``,
    whatever its length. The per-row calls go to the decode seat instead:
    a one-token step takes the per-row decode kernel, a block stays dense
    with ``verify``."""
    from ray_lightning_tpu.models.generate import prefill
    model = TransformerLM(_decode_nano())
    params = _params(model.cfg)
    took = {}
    with seats.tally(took):
        jax.eval_shape(lambda p: prefill(
            model, p, jnp.zeros((2, 256), jnp.int32)), params)
    assert took["attn_kernel"] == 2 and took["attn_dense"] == 0
    took = {}
    with seats.tally(took):
        jax.eval_shape(lambda p: _continuation(kind, model, p), params)
    if kind == "per_row_step":
        assert took == {"attn_kernel": 2, "attn_dense": 0}
        return
    assert took["attn_kernel"] == 0 and took["attn_dense"] == 2
    assert took["attn_dense_reason"] == (
        "verify" if kind == "per_row_block" else "decode")


@pytest.mark.parametrize("seat", ["kernel", "dense"])
@pytest.mark.parametrize("scan,L", [(False, 512), (True, 256)],
                         ids=["unrolled-cache512", "scanned-cache256"])
def test_fresh_prefill_against_the_masked_cache_read(request, monkeypatch,
                                                    seat, scan, L):
    """``prefill()`` on ragged rows of 256 positions: the fresh block on
    the kernel (or dense over its own keys) against the read of the whole
    cache under its mask — the last logits, and K / V at every valid
    position of every layer, to the train test's tolerance."""
    import contextlib
    import importlib

    from ray_lightning_tpu.models.generate import _prefill_impl, prefill
    gen = importlib.import_module("ray_lightning_tpu.models.generate")
    model = TransformerLM(_decode_nano(L, scan))
    params = _params(model.cfg)
    B, P = 3, 256
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, 97)
    lengths = jnp.array([256, 200, 131], jnp.int32)
    with monkeypatch.context() as m:    # no block is fresh: the mask path
        m.setattr(gen, "fresh_cache", contextlib.nullcontext)
        want_cache, want = jax.jit(
            lambda p, t, n: _prefill_impl(model, p, t, n))(
                params, tokens, lengths)
    if seat == "kernel":
        request.getfixturevalue("seat_on_tpu")
    took = {}
    with seats.tally(took):
        got_cache, got = prefill(model, params, tokens, lengths)
    if seat == "kernel":
        assert took["attn_kernel"] >= 1 and took["attn_dense"] == 0
    else:
        assert took["attn_kernel"] == 0
        assert took["attn_dense_reason"] == "backend"

    def close(g, w, what):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 3e-2 * (np.abs(w).max() + 1e-6), what

    close(got, want, "last logits")
    valid = np.arange(L)[None, :] < np.asarray(lengths)[:, None]   # (B, L)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_cache)
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(got_cache)):
        name = jax.tree_util.keystr(path)
        if "cache_index" in name:
            assert int(np.asarray(g).ravel()[0]) == P, name
            continue
        g, w = np.asarray(g), np.asarray(w)
        if scan:                            # (layers, B, L, H, D)
            g, w = g[:, valid], w[:, valid]
        else:                               # (B, L, H, D)
            g, w = g[valid], w[valid]
        close(g, w, name)


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_the_prefill_span_counts_the_seats(seat_on_tpu, kind):
    """The engine's inject program, dense or paged, traced by the armed
    ``engine.prefill.call``: every seat on the kernel; a later prefill
    traces nothing and says nothing."""
    from ray_lightning_tpu.serve import Request, ServeEngine
    model = TransformerLM(_decode_nano())
    tel = Telemetry()
    engine = ServeEngine(model, _params(model.cfg), num_slots=2,
                         prefill_len=256, prefill_batch=2, telemetry=tel,
                         **({"page_size": 64} if kind == "paged" else {}))
    for ids in ((0, 1), (2,)):
        engine.prefill([Request(id=i, prompt=list(range(1, 40 + i)),
                                max_new_tokens=1) for i in ids])
    first, second = tel.spans.spans("engine.prefill.call")
    assert first.args["attn_kernel"] == 2 and first.args["attn_dense"] == 0
    assert "attn_kernel" not in second.args


# --------------------------------------------------------------------- #
# decode mode, per-row one-token steps: the decode seat (transformer.
# decode_seat) and the per-row kernel of ops/pallas_decode.py
# --------------------------------------------------------------------- #
ROW = (40, 1, 20, 64)           # gpt2-large.serve.closed40's step


@pytest.mark.parametrize("case,kwargs,want", [
    ("kernel", {}, (True, "local")),
    ("verify", dict(q=(40, 5, 20, 64)), (False, "verify")),
    ("mask", dict(mask=MASK[..., :1, :]), (False, "mask")),
    ("quantized", dict(quantized=True), (False, "quantized")),
    ("head_lanes_not_a_row", dict(q=(40, 1, 3, 64)), (False, "head_dim")),
    ("odd_head_dim", dict(q=(40, 1, 16, 80)), (False, "head_dim")),
    ("head_dim_128", dict(q=(16, 1, 30, 128), cache_len=4608),
     (True, "local")),
    ("ragged_slot", dict(cache_len=1000), (False, "length")),
    ("cpu_backend", dict(backend="cpu"), (False, "backend")),
    ("default_backend_here", dict(backend=None), (False, "backend")),
    ("one_device_mesh", dict(mesh=("dp", 1)), (False, "mesh")),
    ("fsdp4", dict(mesh=("fsdp", 4)), (False, "mesh")),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_decode_seat_decides_from_its_call(case, kwargs, want):
    call = dict(q=ROW, cache_len=1024, mask=None, quantized=False,
                backend="tpu")
    call.update(kwargs)
    q, cache_len, mesh = call.pop("q"), call.pop("cache_len"), \
        call.pop("mesh", None)

    def ask():
        return decode_seat(q, cache_len, **call)

    if mesh is not None:
        ask = shardlib.under_mesh(_mesh(**dict(zip(mesh[::2], mesh[1::2]))),
                                  ask)
    assert ask() == want


@pytest.mark.parametrize("seat", ["kernel", "dense"])
@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
def test_decode_step_kernel_against_the_masked_read(request, seat, scan):
    """A nano GPT's ``decode_step`` over ragged rows — one writing position
    0 of its slot, one at the end of a block, one past it, one deep in the
    slot — through the per-row kernel and through the dense masked read:
    the logits and the written cache (a layer's K/V follow the layers
    below it) agree to the train test's tolerance."""
    from ray_lightning_tpu.models.generate import decode_step, prefill
    model = TransformerLM(_decode_nano(256, scan))
    params = _params(model.cfg)
    lengths = jnp.array([1, 127, 128, 200], jnp.int32)
    prompts = jax.random.randint(jax.random.PRNGKey(4), (4, 200), 0, 97)
    cache, _ = prefill(model, params, prompts, lengths)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 1), 0, 97)
    at = jnp.array([[0], [127], [128], [200]], jnp.int32)
    want, want_cache = jax.jit(
        lambda p, c: decode_step(model, p, c, tokens, at))(params, cache)
    if seat == "kernel":
        request.getfixturevalue("seat_on_tpu")
    took = {}
    with seats.tally(took):
        got, got_cache = jax.jit(
            lambda p, c: decode_step(model, p, c, tokens, at))(params, cache)
    if seat == "kernel":
        assert took["attn_kernel"] >= 1 and took["attn_dense"] == 0
    else:
        assert took["attn_kernel"] == 0
        assert took["attn_dense_reason"] == "backend"
    for g, w in zip(jax.tree_util.tree_leaves((got, got_cache)),
                    jax.tree_util.tree_leaves((want, want_cache))):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 3e-2 * (np.abs(w).max() + 1e-6)


@pytest.mark.parametrize("kind,took", [
    ("dense", {"attn_kernel": 2, "attn_dense": 0}),
    ("paged", {"attn_kernel": 2, "attn_dense": 0}),
    ("int8", {"attn_kernel": 0, "attn_dense": 2,
              "attn_dense_reason": "quantized"}),
])
def test_the_step_span_counts_the_decode_seats(seat_on_tpu, kind, took):
    """The engine's step program traced by the armed ``engine.step.call``:
    a dense pool and a paged pool's gathered view take the per-row kernel,
    int8 storage keeps the dense read with ``quantized``; a later step
    traces nothing and says nothing. Every step names the key blocks its
    live rows hold against the pool's."""
    from ray_lightning_tpu.serve import Request, ServeEngine
    model = TransformerLM(_decode_nano())
    tel = Telemetry()
    extra = {"paged": {"page_size": 64}, "int8": {"kv_dtype": "int8"},
             "dense": {}}[kind]
    engine = ServeEngine(model, _params(model.cfg), num_slots=2,
                         prefill_len=256, prefill_batch=2, telemetry=tel,
                         **extra)
    engine.prefill([Request(id=i, prompt=list(range(1, 128 + i)),
                            max_new_tokens=4) for i in (0, 1)])
    engine.step()
    engine.step()
    first, second = tel.spans.spans("engine.step.call")[:2]
    assert {k: v for k, v in first.args.items()
            if k.startswith("attn_")} == took
    assert "attn_kernel" not in second.args
    # contexts 128 and 129 after the prefill: one block and two, of 2 x 4
    assert (first.args["kv_blocks_read"],
            first.args["kv_blocks_slot"]) == (3, 8)
    assert second.args["kv_blocks_read"] == 4
