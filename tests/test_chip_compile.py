"""Ask the chip's compiler before the chip: the Pallas kernels of the
main path, at the GPT-2-small shapes the serve engine and the train step
really pass them, compiled for a *described* TPU v5e (nothing attached).

This is the only test file that describes the chip. The topology call
lives in a module-scoped fixture — never at import, in a ``skipif``, in
``parametrize`` or in ``conftest.py`` — because only one process may load
the TPU's library: under xdist every worker imports this file, and only
the worker that runs it may make the call. Compiles happen in the test's
own process, with the persistent compile cache off (a described-chip
executable is written but cannot be read back without a chip).

A pass here is a compiler verdict, not a chip run: nothing executes, so
it says nothing about results or times (``chip_smoke.py`` does that).
A kernel the compiler refuses keeps its test as ``xfail(strict=True)`` —
the day it lowers, the marker must go (none is refused today).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

# GPT-2-small, the shapes of models/transformer.py's page-native call
# site and quant.matmul_view: 8 slots, 64 pages of 16 per slot, 12 heads
# of 64; T = 1 (decode) and spec_k + 1 = 5 (verify)
B, H, D, PS, PP = 8, 12, 64, 16, 64
D_MODEL, D_FF = 768, 3072


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(one_chip):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return S


# --------------------------------------------------------------------- #
# flash attention (train path): ops/pallas_flash.py fwd + bwd
# --------------------------------------------------------------------- #
def test_flash_attention_fwd_bwd_compiles(one_chip):
    from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention
    S = _spec(one_chip)
    qkv = [S((8, 1024, H, D), jnp.bfloat16)] * 3

    def loss(q, k, v):
        return pallas_flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    # the forward kernel and the one backward kernel (dq, dk, dv)
    assert text.count("tpu_custom_call") >= 2


def test_default_seat_takes_the_kernel_at_the_medium_cells_shapes(
        one_chip, monkeypatch):
    """``gpt2-medium.train.b12-t1024``'s attention through the *default*
    seat (no ``attention_impl`` named): the forward kernel and the
    backward kernel, and no ``[12,16,1024,1024]`` scores anywhere."""
    from ray_lightning_tpu.models import transformer
    # the seat asks the backend; the test steers it (as for cache_write)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S = _spec(one_chip)
    qkv = [S((12, 1024, 16, 64), jnp.bfloat16)] * 3
    seat = transformer._attention_fn(transformer.TransformerConfig())

    def loss(q, k, v):
        return seat(q, k, v, causal=True, mask=None, dropout_rate=0.0,
                    dropout_rng=None).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert text.count("tpu_custom_call") == 2
    assert "[12,16,1024,1024]" not in text
    short = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                           *[S((12, 128, 16, 64), jnp.bfloat16)] * 3)
    assert "tpu_custom_call" not in short       # under FLASH_MIN_LEN


def test_fsdp4_step_runs_the_kernel_on_each_chips_rows(topo, one_chip,
                                                       monkeypatch):
    """An FSDP-over-4 train step on the described ``v5e:2x2`` at GPT-2 XL's
    per-layer shapes (two layers, a global batch of 12 x 1024): the kernels
    get each chip's 3 rows, and no collective carries the global batch."""
    import optax
    from jax.sharding import NamedSharding

    from ray_lightning_tpu import FSDPStrategy
    from ray_lightning_tpu.core.train_state import TrainState
    from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                      TransformerLM)
    from ray_lightning_tpu.obs.census import collective_census, format_census
    from ray_lightning_tpu.parallel.mesh import build_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T = 12, 1024
    strat = FSDPStrategy(num_workers=4)
    strat._mesh = build_mesh(strat.mesh_spec(), topo.devices)
    model = TransformerLM(TransformerConfig(
        vocab_size=8192, max_seq_len=T, d_model=1600, n_heads=25,
        n_layers=2, d_ff=6400, dtype=jnp.bfloat16, causal=True,
        scan_layers=True, remat=True,
        remat_policy="dots_with_no_batch_dims"))
    tx = optax.adamw(3e-4)
    tokens = jax.ShapeDtypeStruct((rows, T), jnp.int32)

    def init(t):
        params = model.init(jax.random.PRNGKey(0), t)["params"]
        return TrainState.create(params, tx.init(params))

    abstract = jax.eval_shape(init, tokens)
    shardings = TrainState(
        step=strat.scalar_sharding(),
        params=strat.params_sharding(abstract.params),
        opt_state=strat.opt_state_sharding(abstract.opt_state),
        model_state={}, rng=strat.scalar_sharding())

    def loss_fn(params, model_state, batch, rng):
        x, y = batch
        logp = jax.nn.log_softmax(
            model.apply({"params": params}, x).astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
        return loss, ({}, model_state)

    step = strat.make_train_step(loss_fn, tx, shardings,
                                 strat.batch_sharding(), donate=False)
    place = lambda a, s: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=s)
    state = jax.tree_util.tree_map(place, abstract, shardings)
    batch = (place(tokens, strat.batch_sharding()),) * 2
    text = step.lower(state, batch).compile().as_text()

    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert len(calls) >= 3, len(calls)     # forward, its remat, backward
    per_chip = f"bf16[{rows // 4},{T},1600]"
    assert all(per_chip in ln for ln in calls), calls[0][:400]
    assert not any(f"[{rows},{T}," in ln for ln in calls)
    census = collective_census(text, batch=rows)
    moved = [c for c in census if c.carries_batch
             and c.dtype not in ("s32", "u32", "pred")]
    assert not moved, format_census(census)


# --------------------------------------------------------------------- #
# paged attention (serve path): models/pallas_attention.py
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("T", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_attention_compiles(one_chip, kv, T):
    from ray_lightning_tpu.models.pallas_attention import paged_attention
    S = _spec(one_chip)
    P = B * PP
    quantized = kv == "int8"
    kd = jnp.int8 if quantized else jnp.bfloat16
    scales = ([S((P, 1, H, 1), jnp.float32)] * 2 if quantized
              else [None, None])
    text = _compiled_text(
        lambda *a: paged_attention(*a, interpret=False),
        S((B, T, H, D), jnp.bfloat16), S((P, PS, H, D), kd),
        S((P, PS, H, D), kd), *scales, S((B, T), jnp.int32),
        S((B, PP), jnp.int32))
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------- #
# quantized matmul (serve path): models/pallas_matmul.py
# --------------------------------------------------------------------- #
def _qtensor(S, shape, bits, group_size=64):
    """A QTensor of ShapeDtypeStructs laid out as quantize_params does."""
    from ray_lightning_tpu.models.quant import QTensor
    if bits == 8:
        scale = (1,) * (len(shape) - 1) + (shape[-1],)
        return QTensor(S(shape, jnp.int8), S(scale, jnp.float32), 8, None,
                       shape, np.float32)
    packed = shape[:-1] + (shape[-1] // 2,)
    scale = shape[:-1] + (shape[-1] // group_size, 1)
    return QTensor(S(packed, jnp.int8), S(scale, jnp.float32), 4,
                   group_size, shape, np.float32)


#: (leaf shape, transpose): the qkv DenseGeneral kernel, mlp up and down,
#: and the tied LM head at the published and the 128-padded vocab
_PROJECTIONS = {
    "qkv": ((D_MODEL, 3, H, D), False),
    "mlp_up": ((D_MODEL, D_FF), False),
    "mlp_down": ((D_FF, D_MODEL), False),
    "tied_head_50257": ((50257, D_MODEL), True),
    "tied_head_50304": ((50304, D_MODEL), True),
}


@pytest.mark.parametrize("proj", sorted(_PROJECTIONS))
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quantized_matmul_compiles(one_chip, bits, proj):
    from ray_lightning_tpu.models.pallas_matmul import quantized_matmul
    S = _spec(one_chip)
    shape, transpose = _PROJECTIONS[proj]
    K = shape[-1] if transpose else shape[0]
    qt = _qtensor(S, shape, bits)
    # M = 8 decode rows and the 8 x 5 verify block share one tiling rule
    for M in (B, B * 5):
        text = _compiled_text(
            lambda x, q: quantized_matmul(x, q, transpose=transpose,
                                          interpret=False),
            S((M, K), jnp.bfloat16), qt)
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("proj", ["mlp_down", "tied_head_50257"])
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quantized_matmul_k_tiled_compiles(one_chip, bits, proj):
    """``tile_k < K``: partial dots accumulated in f32 VMEM scratch over
    the innermost grid axis — the mode no engine default selects."""
    from ray_lightning_tpu.models.pallas_matmul import quantized_matmul
    S = _spec(one_chip)
    shape, transpose = _PROJECTIONS[proj]
    K = shape[-1] if transpose else shape[0]
    text = _compiled_text(
        lambda x, q: quantized_matmul(x, q, transpose=transpose, tile_k=256,
                                      interpret=False),
        S((B, K), jnp.bfloat16), _qtensor(S, shape, bits))
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------- #
# the decode step's cache write (serve path): ops/cache_write.py
# --------------------------------------------------------------------- #
#: (B, L, H, D, T): closed40's K/V, reason48's rings and layer 17's K/V
#: (bf16 pools as the cells hold them), a float32 pool, a verify block
_POOLS = {
    "closed40": (40, 1024, 20, 64, 1, jnp.bfloat16),
    "reason48_ring": (48, 512, 20, 64, 1, jnp.bfloat16),
    "reason48_full": (48, 2048, 20, 64, 1, jnp.bfloat16),
    "f32_verify": (8, 1024, 12, 64, 5, jnp.float32),
}


@pytest.mark.parametrize("pool", sorted(_POOLS))
def test_cache_write_rows_compiles_in_place(one_chip, pool, monkeypatch):
    """One kernel call a position for K and V together, the donated
    leaves aliased through it: the program holds no second copy of a
    leaf (the transposes around the kernel are bitcasts of the chip's
    position-minor layout)."""
    from ray_lightning_tpu.ops.cache_write import write_rows
    # the helper asks the backend whether to interpret; the test steers
    # it (on-chip-measurement guide), the program has no option for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S = _spec(one_chip)
    Bp, L, Hp, Dp, T, dtype = _POOLS[pool]
    leaf, block = S((Bp, L, Hp, Dp), dtype), S((Bp, T, Hp, Dp), dtype)
    compiled = jax.jit(write_rows, donate_argnums=0).lower(
        (leaf, leaf), (block, block), S((Bp,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= T
    mem = compiled.memory_analysis()
    leaf_bytes = Bp * L * Hp * Dp * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes == 2 * leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes // 8


# --------------------------------------------------------------------- #
# the flagship forward the driver compile-checks: __graft_entry__.entry
# --------------------------------------------------------------------- #
def test_gpt2_small_forward_compiles_and_fits(one_chip):
    """GPT-2-small forward at entry()'s shapes, from ``jax.eval_shape``
    (no concrete params): compiles for one v5e and fits its 16 GB."""
    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    cfg = gpt2_config("small", vocab_size=32768, max_seq_len=512,
                      scan_layers=True)
    model = TransformerLM(cfg)
    tokens = jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=one_chip)
    abstract = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"],
        jax.ShapeDtypeStruct((4, 512), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract)
    compiled = jax.jit(
        lambda p, t: model.apply({"params": p}, t)).lower(
        params, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, total


# --------------------------------------------------------------------- #
# SambaY (Phi-4-mini-flash-reasoning) at its published widths: the two
# engine programs of benchmark cell phi4-mini-flash-reasoning.serve.reason48
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("program", ["step", "prefill"])
def test_sambay_engine_programs_compile_and_fit(one_chip, program,
                                                monkeypatch):
    """Hidden 2560, 40 / 20 heads of 64, MLP 10240, window 512, the whole
    200064-row vocabulary, 48 slots of 2048 positions — at 8 layers (one
    period: every one of the five mixers, the memory, the shared K/V), a
    quarter of the published depth, to keep the compile short. The
    selective scan, the ring gather and the rank-3 state's injection must
    lower for a v5e; the program must fit its 16 GB."""
    from ray_lightning_tpu.models.sambay import SambaYConfig, SambaYLM
    from ray_lightning_tpu.serve import engine as E
    # the step's cache write (ops/cache_write.py) compiles as the chip's
    # kernel, not as its interpretation
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S = _spec(one_chip)
    slots, rows, plen = 48, 4, 256

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), tree)

    cfg = SambaYConfig(num_hidden_layers=8, decode=True)
    model = SambaYLM(cfg)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
        positions=jnp.zeros((slots, 1), jnp.int32)))
    params, cache = abstract(init["params"]), abstract(init["cache"])
    if program == "step":
        compiled = jax.jit(
            E._engine_step_impl, static_argnames=("model", "steps"),
            donate_argnums=(2,)).lower(
                model, params, cache, S((slots, 1), jnp.int32),
                S((slots, 1), jnp.int32), S((slots,), jnp.bool_),
                S((slots,), jnp.int32), S((slots,), jnp.float32),
                S((slots,), jnp.int32), S((slots,), jnp.int32),
                S((slots, 2), jnp.uint32), S((slots,), jnp.int32), None,
                steps=1).compile()
    else:
        compiled = jax.jit(
            E._prefill_inject_impl, static_argnames=("model",),
            donate_argnums=(2,)).lower(
                model, params, cache, S((rows, plen), jnp.int32),
                S((rows,), jnp.int32), S((rows,), jnp.int32),
                S((rows,), jnp.bool_), S((rows, 2), jnp.uint32),
                S((rows,), jnp.float32), S((rows,), jnp.int32),
                S((rows,), jnp.int32), None).compile()
    # these 8 layers hold two windowed and the full self-attention
    # layer: each writes its K and V through one kernel call; the
    # prefill writes whole rows and holds none
    assert compiled.as_text().count("tpu_custom_call") == \
        (3 if program == "step" else 0)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 8 layers: 1.9 GB of bf16 weights + 0.9 GB of cache; the published
    # depth reads 9.4 GB + 1.0 GB of temporaries (PERF.md section 6)
    assert total < 6e9, total


# --------------------------------------------------------------------- #
# Olmo-Hybrid at its published widths: the step and the dense-slot chunk
# program of benchmark cell olmo-hybrid-7b.serve.longdoc16
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("program", ["step", "chunk"])
def test_olmo_hybrid_engine_programs_compile_and_fit(one_chip, program):
    """Hidden 3840, 30 heads of 128, MLP 11008, 30 delta-rule heads of
    96 x 192, the whole 100352-row vocabulary, 16 slots of 4608 positions
    — at 4 layers (one period: three Gated-DeltaNet layers and the full
    attention layer), a quarter of the cell's depth, to keep the compile
    short. The chunkwise rule, its block inverse and the in-place piece
    write must lower for a v5e and fit; and **no program may copy a K/V
    leaf of the pool whole**: the step did, twice a leaf, while it wrote
    through ``ops.cache_write.write_rows`` (whose position-minor view is
    a transpose where a head is a lane row of 128; PERF.md section 6,
    PR 32), and read 26 % of its roofline for it."""
    from ray_lightning_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                      OlmoHybridLM)
    from ray_lightning_tpu.serve import engine as E
    S = _spec(one_chip)
    slots, rows, piece = 16, 2, 512

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), tree)

    cfg = OlmoHybridConfig(num_hidden_layers=4,
                           layer_types=OlmoHybridConfig().layer_types[:4],
                           max_seq_len=4608, decode=True)
    model = OlmoHybridLM(cfg)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32)))
    params, cache = abstract(init["params"]), abstract(init["cache"])
    if program == "step":
        compiled = jax.jit(
            E._engine_step_impl, static_argnames=("model", "steps"),
            donate_argnums=(2,)).lower(
                model, params, cache, S((slots, 1), jnp.int32),
                S((slots, 1), jnp.int32), S((slots,), jnp.bool_),
                S((slots,), jnp.int32), S((slots,), jnp.float32),
                S((slots,), jnp.int32), S((slots,), jnp.int32),
                S((slots, 2), jnp.uint32), S((slots,), jnp.int32), None,
                steps=1).compile()
    else:
        compiled = jax.jit(
            E._chunk_prefill_dense_impl, static_argnames=("model",),
            donate_argnums=(2,)).lower(
                model, params, cache, S((rows, piece), jnp.int32),
                S((rows,), jnp.int32), S((rows,), jnp.int32),
                S((rows,), jnp.int32), S((rows,), jnp.bool_),
                S((rows, 2), jnp.uint32), S((rows,), jnp.float32),
                S((rows,), jnp.int32), S((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not [line for line in text.splitlines()
                if "bf16[16,4608,30,128]" in line.split(" = ")[-1][:40]
                and " copy(" in line]
    mem = compiled.memory_analysis()
    leaf = slots * 4608 * 30 * 128 * 2
    # the step's temporaries are its logits and a row's scores; the
    # chunk's the two rows' K/V taken out of the pool, a block of
    # scores and the rule's block products
    assert mem.temp_size_in_bytes < (leaf // 8 if program == "step"
                                     else 2 * leaf)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 4 layers: 2.4 GB of bf16 weights + 1.3 GB of cache; the cell's 16
    # layers read 13.3 GB + 1.1 GB of temporaries (PERF.md section 6)
    assert total < 6e9, total


# --------------------------------------------------------------------- #
# Trinity / AFMoE at its published widths: the step and the dense-slot
# chunk program of benchmark cell trinity-large-preview.serve.mixedlen32
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("program", ["step", "chunk"])
def test_afmoe_engine_programs_compile_and_fit(one_chip, program):
    """Hidden 3072, 48 / 8 heads of 128, a window layer (a ring of 4096)
    and a full layer (8192 positions), both with 32 held experts of 3072
    out of 256 router outputs, an eighth of the vocabulary, 32 slots — 2
    of the cell's 5 layers, to keep the compile short. The ragged
    products must lower for a v5e as the compiler's own grouped product
    (a ``ragged-dot`` custom call, not the expansion over every held
    expert), and **no program may copy or transpose a K/V leaf of the
    pool whole**: the one-position write and the piece inject go in
    place, and the scores' product reads the leaf as it lies (heads
    before positions; ``(B, L, 8 x 128)`` was transposed every step)."""
    from ray_lightning_tpu.models.afmoe import (FULL, SLIDING, AfmoeConfig,
                                                AfmoeLM)
    from ray_lightning_tpu.serve import engine as E
    S = _spec(one_chip)
    slots, rows, piece, positions = 32, 2, 512, 8192

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), tree)

    cfg = AfmoeConfig(num_hidden_layers=2, num_dense_layers=0,
                      layer_types=(SLIDING, FULL), vocab_size=25024,
                      experts_held=32, max_seq_len=positions, decode=True)
    model = AfmoeLM(cfg)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32)))
    params, cache = abstract(init["params"]), abstract(init["cache"])
    assert cache["layer_0_attn"]["ring_key"].shape == (slots, 8, 4096, 128)
    assert cache["layer_1_attn"]["cached_key"].shape \
        == (slots, 8, positions, 128)
    if program == "step":
        compiled = jax.jit(
            E._engine_step_impl, static_argnames=("model", "steps"),
            donate_argnums=(2,)).lower(
                model, params, cache, S((slots, 1), jnp.int32),
                S((slots, 1), jnp.int32), S((slots,), jnp.bool_),
                S((slots,), jnp.int32), S((slots,), jnp.float32),
                S((slots,), jnp.int32), S((slots,), jnp.int32),
                S((slots, 2), jnp.uint32), S((slots,), jnp.int32), None,
                steps=1).compile()
    else:
        compiled = jax.jit(
            E._chunk_prefill_dense_impl, static_argnames=("model",),
            donate_argnums=(2,)).lower(
                model, params, cache, S((rows, piece), jnp.int32),
                S((rows,), jnp.int32), S((rows,), jnp.int32),
                S((rows,), jnp.int32), S((rows,), jnp.bool_),
                S((rows, 2), jnp.uint32), S((rows,), jnp.float32),
                S((rows,), jnp.int32), S((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text     # (the compiler's own custom call)
    # a pool leaf, in whatever order of its axes, is the result of no copy
    # and no transpose (32 x 8 x 4096 x 128 and 32 x 8 x 8192 x 128 values)
    leaf_sizes = {slots * 8 * 4096 * 128, slots * 8 * positions * 128}
    for line in text.splitlines():
        head = line.split(" = ")[-1][:80]
        if " copy(" not in line and " transpose(" not in line:
            continue
        dims = head[head.find("[") + 1:head.find("]")]
        if dims.replace(",", "").isdigit():
            assert int(np.prod([int(d) for d in dims.split(",")])) \
                not in leaf_sizes, line[:200]
    mem = compiled.memory_analysis()
    # the step's temporaries are its logits and one layer's scores; the
    # chunk's the two rows taken out of the pool, a block of scores and
    # the grouped rows of the 4096 assignments
    assert mem.temp_size_in_bytes < (0.1e9 if program == "step" else 0.6e9)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 2 expert layers: 4.0 GB of bf16 weights + 0.15 GB of vocabulary +
    # 1.6 GB of cache; the cell's 5 layers compile to 11.87 + 0.39 GB
    assert total < 6.5e9, total
