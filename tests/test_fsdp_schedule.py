"""The FSDP schedule, read from the compiled step (``obs/census.py``).

A parameter's cut over ``fsdp`` is storage only: with the model's
``constrain_batch`` seats keeping activations split over the mesh's data
axes, the compiled train step all-gathers a layer's weights where the layer
uses them and reduces their gradients back — and holds no collective that
carries the global batch (partial activations or attention scores summed
across chips: the partitioner computing on the stored cut, which is what
the parent did for a head count that does not divide the mesh).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu import (FSDPStrategy, MeshStrategy, RayStrategy,
                               Trainer)
from ray_lightning_tpu.models import transformer
from ray_lightning_tpu.models.gpt import GPTModule
from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                  tensor_parallel_rule)
from ray_lightning_tpu.obs.census import (Collective, collective_census,
                                          format_census)
from ray_lightning_tpu.parallel import sharding as shardlib

B, T, HEAD_DIM, LAYERS, VOCAB = 8, 64, 16, 2, 257


def _setup(strategy, n_heads, scan_layers=True):
    """A Trainer set up for a nano GPT (bf16 compute, remat as the
    benchmark's train cells run it): ``(trainer, state, device batch)``."""
    d = n_heads * HEAD_DIM
    cfg = TransformerConfig(
        vocab_size=VOCAB, max_seq_len=T, d_model=d, n_heads=n_heads,
        n_layers=LAYERS, d_ff=4 * d, causal=True, dtype=jnp.bfloat16,
        scan_layers=scan_layers, remat=True,
        remat_policy="dots_with_no_batch_dims")
    trainer = Trainer(strategy=strategy, max_epochs=1,
                      enable_checkpointing=False, enable_progress_bar=False)
    trainer._attach(GPTModule(config=cfg, batch_size=B, seq_len=T), None)
    tokens = np.zeros((B, T), np.int32)
    state = trainer._setup_state((tokens, tokens))
    batch = shardlib.put_global_batch((tokens, tokens),
                                      trainer._batch_sharding)
    return trainer, state, batch


def _batch_activations(census):
    """The batch-carrying collectives that move activations: all but the
    token ids' gather (int32, 4 bytes a token — a wte stored along d looks
    every row up on its quarter of d), which the schedule keeps."""
    return [c for c in census if c.carries_batch
            and c.dtype not in ("s32", "u32", "pred")]


def _lowered_step(strategy, n_heads, scan_layers=True):
    trainer, state, batch = _setup(strategy, n_heads, scan_layers)
    try:
        return trainer._train_step.lower(state, batch)
    finally:
        strategy.teardown()


@pytest.mark.parametrize("strategy", ["fsdp4", "dp2_fsdp2"])
@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "unrolled"])
@pytest.mark.parametrize("n_heads", [5, 4])
def test_fsdp_step_gathers_weights_and_keeps_the_batch_split(
        n_heads, scan_layers, strategy):
    """5 heads do not divide the mesh (GPT-2 XL's 25 over 4 chips), 4 do;
    the scanned and the unrolled stack pass through the same block."""
    strat = (FSDPStrategy(num_workers=4) if strategy == "fsdp4"
             else MeshStrategy(axes={"dp": 2, "fsdp": 2}))
    census = collective_census(
        _lowered_step(strat, n_heads, scan_layers).compile(), batch=B)
    shown = format_census(census)

    # (a) no activation crosses chips at the global batch's size, and no
    # [.,H,T,T] scores at any
    assert not _batch_activations(census), shown
    scores = [c for c in census
              if len(c.shape) == 4 and c.shape[-2:] == (T, T)]
    assert not scores, shown

    # (b) the block's four kernels are all-gathered where the layer runs:
    # inside the scan's while body, forward and remat'd backward; unrolled,
    # once per layer and pass
    d = n_heads * HEAD_DIM
    gathered = [c for c in census if c.kind == "all-gather"
                and c.in_loop == scan_layers]
    for name, size, kernels in (("qkv", d * 3 * d, 1), ("out", d * d, 1),
                                ("up/down", d * 4 * d, 2)):
        n = sum(math.prod(c.shape) == size for c in gathered)
        want = kernels * (1 if scan_layers else LAYERS)
        assert n >= want, f"{name}: {n} gathers, want {want}\n{shown}"


@pytest.mark.parametrize("strategy", ["fsdp4", "dp2_fsdp2"])
def test_fsdp_step_on_the_blockwise_kernel_keeps_the_batch_split(
        strategy, monkeypatch):
    """The default seat on the kernel (as a TPU decides it; interpreted
    here) nests it in a ``shard_map`` over the data axes: still no
    activation at the global batch's size, no scores, the same gathers."""
    from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(transformer, "FLASH_MIN_LEN", T)
    monkeypatch.setattr(
        transformer, "pallas_flash_attention",
        functools.partial(pallas_flash_attention, interpret=True))
    seen = []
    seat = transformer.attention_seat
    monkeypatch.setattr(
        transformer, "attention_seat",
        lambda *a, **k: seen.append(seat(*a, **k)) or seen[-1])
    jax.clear_caches()
    strat = (FSDPStrategy(num_workers=4) if strategy == "fsdp4"
             else MeshStrategy(axes={"dp": 2, "fsdp": 2}))
    census = collective_census(_lowered_step(strat, 5).compile(), batch=B)
    # (model.init traces the seat too, with no mesh ambient: "local")
    assert seen[-1] == (True, "sharded") and all(k for k, _ in seen)
    shown = format_census(census)
    assert not _batch_activations(census), shown
    assert not [c for c in census
                if len(c.shape) == 4 and c.shape[-2:] == (T, T)], shown
    d = 5 * HEAD_DIM
    gathered = [c for c in census if c.kind == "all-gather" and c.in_loop]
    assert sum(math.prod(c.shape) == d * 3 * d for c in gathered) >= 1, shown


def test_identity_on_a_one_device_mesh(monkeypatch):
    """``RayStrategy(1)`` (the one-chip train cell): the lowered step is
    the same text with the helper as with the identity in its place."""
    with_helper = _lowered_step(RayStrategy(num_workers=1), 4).as_text()
    monkeypatch.setattr(transformer, "constrain_batch", lambda x: x)
    jax.clear_caches()
    without = _lowered_step(RayStrategy(num_workers=1), 4).as_text()
    assert with_helper == without
    assert "sharding_constraint" not in with_helper


def test_identity_with_no_mesh_in_the_serve_decode_program(
        serve_nano_family, monkeypatch):
    """The serve engine compiles its own programs under no strategy: its
    decode step lowers to the same text with the helper as without."""
    from ray_lightning_tpu.serve.engine import ServeEngine
    dec, params = serve_nano_family[:2]
    text = ServeEngine(dec, params, num_slots=2,
                       prefill_len=4).lowered_step_text()
    monkeypatch.setattr(transformer, "constrain_batch", lambda x: x)
    jax.clear_caches()
    assert text == ServeEngine(dec, params, num_slots=2,
                               prefill_len=4).lowered_step_text()


def test_constrain_batch_steps_aside():
    """No ambient mesh, a batch the data axes do not divide, a manual
    region: the same object back."""
    mesh = FSDPStrategy(num_workers=4).mesh
    x, odd = jnp.zeros((8, 4)), jnp.zeros((6, 4))
    assert shardlib.constrain_batch(x) is x
    seen = []

    def local(block):
        seen.append(shardlib.constrain_batch(block) is block)
        return block

    @functools.partial(shardlib.under_mesh, mesh)
    def with_mesh():
        assert shardlib.constrain_batch(odd) is odd
        jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("fsdp"),
                              out_specs=P("fsdp")))(x)
        return jax.jit(shardlib.constrain_batch).lower(x).as_text()

    lowered = with_mesh()
    assert seen == [True]
    assert "sharding_constraint" in lowered.lower() \
        or "sdy.sharding" in lowered
    assert shardlib.constrain_batch(x) is x     # the mesh left with the call


def test_tp_dims_still_shard_beside_fsdp():
    """``MeshStrategy`` with ``tp=2`` beside ``fsdp=2``: the helper names
    the batch dim only, so the tp cut of the other dims propagates — the
    step still computes on tp-sharded kernels (no gather of a kernel over
    ``tp``) and sums the row-parallel projections over ``tp``."""
    strat = MeshStrategy(axes={"fsdp": 2, "tp": 2},
                         param_rule=tensor_parallel_rule)
    trainer, state, batch = _setup(strat, 4)
    try:
        compiled = trainer._train_step.lower(state, batch).compile()
        qkv = state.params["stack"]["layers"]["block"]["attn"]["qkv"][
            "kernel"]
        assert "tp" in tuple(qkv.sharding.spec)
        new_state, _ = trainer._train_step(state, batch)
        new_qkv = new_state.params["stack"]["layers"]["block"]["attn"][
            "qkv"]["kernel"]
        assert new_qkv.sharding.shard_shape(new_qkv.shape) == \
            qkv.sharding.shard_shape(qkv.shape)
    finally:
        strat.teardown()
    census = collective_census(compiled, batch=B)
    d = 4 * HEAD_DIM
    # Megatron's exchange is there: an activation-shaped all-reduce over
    # tp at the per-fsdp-shard batch, none at the global batch
    assert any(c.kind == "all-reduce" and c.shape[:1] == (B // 2,)
               for c in census), format_census(census)
    assert not _batch_activations(census)
    # and no kernel is gathered whole (d x 3d, d x d, d x 4d elements)
    whole = {d * 3 * d, d * d, d * 4 * d}
    assert not [c for c in census if c.kind == "all-gather"
                and math.prod(c.shape) in whole], format_census(census)


def test_sequence_cut_propagates_through_the_seats(monkeypatch):
    """``SequenceParallelStrategy``: the seats name dim 0 only and leave
    the rest UNCONSTRAINED, so the residual stream keeps its ``sp`` cut of
    the sequence dim — the step holds the same collectives with the helper
    as without (``None`` in place of UNCONSTRAINED would gather the
    ``[B/dp, T, d]`` stream at every seat)."""
    from ray_lightning_tpu import SequenceParallelStrategy

    def census():
        return sorted(
            (c.kind, c.dtype, c.shape, c.in_loop)
            for c in collective_census(_lowered_step(
                SequenceParallelStrategy(dp=2, sp=2), 4).compile()))

    with_helper = census()
    monkeypatch.setattr(transformer, "constrain_batch", lambda x: x)
    jax.clear_caches()
    assert with_helper == census()


def test_census_reads_async_starts_and_loop_bodies():
    """The reader on a hand-written program text: tuple-typed async
    starts, a collective inside a ``while`` body's callee."""
    text = """HloModule m
%wrapped (p: f32[4,8]) -> f32[1,8] {
  %rs = f32[1,8]{1,0} reduce-scatter(f32[4,8]{1,0} %p), dimensions={0}
}
%body (c: (s32[], f32[2,8])) -> (s32[], f32[2,8]) {
  %ags = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(bf16[2,8]{1,0} %w)
  %agd = bf16[8,8]{1,0} all-gather-done(%ags)
  %a = ((f32[4,8]), f32[1,8]) async-start(%g), calls=%wrapped
}
%cond (c: (s32[], f32[2,8])) -> pred[] {
  %lt = pred[] compare(%i, %n), direction=LT
}
ENTRY %main (x: f32[12,8]) -> f32[12,8] {
  %w = (s32[], f32[2,8]) while(%init), condition=%cond, body=%body
  %ar = (f32[12,8]{1,0}, f32[8]{0}) all-reduce(%x, %b), to_apply=%add
  %cp = (f32[3,8], f32[3,8], u32[], u32[]) collective-permute-start(%y)
}
"""
    assert collective_census(text, batch=12) == [
        Collective("reduce-scatter", "f32", (1, 8), True, False),
        Collective("all-gather", "bf16", (8, 8), True, False),
        Collective("all-reduce", "f32", (12, 8), False, True),
        Collective("all-reduce", "f32", (8,), False, False),
        Collective("collective-permute", "f32", (3, 8), False, False),
    ]
    assert Collective("all-gather", "bf16", (8, 8), True, False).bytes == 128
