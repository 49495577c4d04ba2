"""Pallas fused dequant-matmul kernel (`matmul_kernel="pallas"`).

The load-bearing assertion mirrors ``tests/test_pallas_attention.py``:
the kernel computes the dequantize-then-XLA-matmul path's dot (same
``codes x scales`` products, same promoted operands, same contraction)
with the f32 accumulator the chip's matmul unit requires — equal to
that path up to accumulation order (a few ulps; the unit tests hold it
there, under pallas interpret mode on this CPU tier), which on the
pinned nano configs keeps greedy token identity
between ``matmul_kernel="pallas"`` and the materialized-dequant "xla"
engines ENFORCED at 0 mismatches across int8/int4 weights,
page-native + pallas-attention layouts, spec, async dispatch, crash
replay, and 3-replica fleet failover. ``tile_k < K`` splits the
reduction into f32-accumulated partial dots (``docs/serving.md``).

The unit tests at the top pin the kernel directly against
``QTensor.dequantize`` + the XLA dot, including the in-kernel int4
nibble unpack over ALL 16 code values laid across tile boundaries,
both weight orientations (Dense and the tied LM head's ``x @ E.T``),
and the tile-shape validation surface.

Engines here reuse the session-scoped ``serve_nano_family`` pair and
the serve-family pinned shapes (num_slots=3 / prefill_len=8 / the
4-request staggered TRACE), so every XLA reference leg runs on
programs test_quant/test_paged already compile — the only new
compiled shapes are the pallas-matmul programs themselves.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import TransformerLM, gpt2_config
from ray_lightning_tpu.models.generate import generate
from ray_lightning_tpu.models.pallas_matmul import (kernel_calls,
                                                    quantized_matmul,
                                                    unpack_int4_block)
from ray_lightning_tpu.models.quant import (_quantize_leaf_int4,
                                            _quantize_leaf_int8,
                                            dequantize_params,
                                            is_quantized,
                                            materialize_for_program,
                                            param_bytes, quantize_params,
                                            unpack_int4)
from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
from ray_lightning_tpu.serve import ReplicaFleet, ServeClient, ServeEngine

pytestmark = [pytest.mark.serve, pytest.mark.matmul]

#: the serve-family nano group size (divides every nano leaf's last
#: axis, incl. head_dim)
GS = 8

PROMPTS = [[5, 17, 3, 9], [9, 2, 44], [42, 7], [1]]
TRACE = [
    (0, dict(prompt=PROMPTS[0], max_new_tokens=6)),
    (0, dict(prompt=PROMPTS[1], max_new_tokens=6)),
    (3, dict(prompt=PROMPTS[2], max_new_tokens=6)),
    (5, dict(prompt=PROMPTS[3], max_new_tokens=6)),
]


@pytest.fixture(scope="module")
def nano(serve_nano_family):
    return serve_nano_family[:2]


def _run(dec, params, trace=TRACE, **kw):
    client = ServeClient(dec, params, num_slots=3, prefill_len=8, **kw)
    out = client.serve_trace(list(trace))
    client.shutdown()
    return out


def _tokens(out):
    return {rid: c.tokens for rid, c in out.items()}


def _quant_kw(weight_dtype):
    kw = dict(weight_dtype=weight_dtype)
    if weight_dtype == "int4":
        kw["weight_group_size"] = GS
    return kw


# --------------------------------------------------------------------- #
# kernel unit: a few ulps of dequantize-then-XLA-dot
# --------------------------------------------------------------------- #
def test_unpack_block_matches_reference_all_bytes():
    """The int32-shift in-kernel unpack is value-for-value the int8
    arithmetic-shift reference over every possible packed byte (all
    16 x 16 nibble pairs)."""
    packed = jnp.arange(-128, 128, dtype=jnp.int8).reshape(16, 16)
    assert jnp.array_equal(unpack_int4_block(packed), unpack_int4(packed))


def test_int4_unpack_all_codes_at_tile_boundaries():
    """A weight whose int4 codes cycle all 16 values, contracted with
    the identity, read back through tiles that split both the packed
    byte stream and the scale groups across block boundaries — the
    kernel output must be bitwise the dequantized weight."""
    K, N = 16, 64
    # values spanning every code bucket in every group/tile
    w = jnp.asarray(
        (np.arange(K * N).reshape(K, N) % 15 - 7) * 0.125, jnp.float32)
    qt = _quantize_leaf_int4(w, GS)
    codes = unpack_int4(qt.q)
    assert set(np.unique(np.asarray(codes))) >= set(range(-7, 8))
    eye = jnp.eye(K, dtype=jnp.float32)
    ref = jax.jit(lambda x, w: x @ w)(eye, qt.dequantize())
    for tile_n in (GS, 2 * GS, N):   # boundaries inside / across groups
        out = jax.jit(lambda x: quantized_matmul(x, qt, tile_n=tile_n))(
            eye)
        assert jnp.array_equal(out, ref), tile_n


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("tiles", [dict(), dict(tile_n=16, tile_m=5)],
                         ids=["default", "forced-tiles"])
def test_dense_orientation_matches(bits, tiles):
    """x (..., K) @ W for Dense/DenseGeneral leaves (contraction over
    the stored axis 0, multi-dim features flattened): the
    dequantize-then-XLA dot up to accumulation order."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(24, 2, 4, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 5, 24)), jnp.float32)
    qt = (_quantize_leaf_int8(w) if bits == 8
          else _quantize_leaf_int4(w, GS))
    ref = jax.jit(lambda x, w: jax.lax.dot_general(
        x, w.reshape(w.shape[0], -1), (((2,), (0,)), ((), ()))))(
        x, qt.dequantize())
    out = jax.jit(lambda x: quantized_matmul(x, qt, **tiles))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_attend_orientation_matches(bits):
    """The tied LM head's ``x @ E.T`` (contraction over the stored
    LAST axis — int8 scales ride the contraction, int4 groups split
    along it): the dequantize-then-``jnp.dot`` path up to accumulation
    order."""
    rng = np.random.default_rng(4)
    E = jnp.asarray(rng.normal(size=(96, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32)
    qt = (_quantize_leaf_int8(E) if bits == 8
          else _quantize_leaf_int4(E, GS))
    ref = jax.jit(lambda x, E: jnp.dot(x, E.T))(x, qt.dequantize())
    for tiles in (dict(), dict(tile_n=16)):
        out = jax.jit(lambda x, t=tuple(tiles.items()): quantized_matmul(
            x, qt, transpose=True, **dict(t)))(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5, err_msg=str(tiles))


def test_bf16_compute_matches():
    """bf16 compute: the kernel promotes the dequantized tile exactly
    like flax (f32 codes x scales -> param dtype -> compute dtype),
    accumulates in f32 and rounds once to bf16 — within one bf16 ulp of
    the XLA dot on the same operands."""
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32).astype(
        jnp.bfloat16)
    qt = _quantize_leaf_int8(w)
    ref = jax.jit(lambda x, w: jax.lax.dot_general(
        x, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ()))))(
        x, qt.dequantize())
    out = jax.jit(lambda x: quantized_matmul(x, qt))(x)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2 ** -7, atol=2 ** -7)


def test_ktiled_accumulation_close_not_contracted():
    """tile_k < K: f32-accumulated partial dots in VMEM scratch,
    correct to reduction-order rounding (docs/serving.md)."""
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    qt = _quantize_leaf_int8(w)
    ref = x @ qt.dequantize()
    out = jax.jit(lambda x: quantized_matmul(x, qt, tile_k=16))(x)
    assert jnp.allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("transpose", [False, True],
                         ids=["dense", "attend"])
@pytest.mark.parametrize("bits", [8, 4])
def test_divisor_poor_output_axis_takes_ragged_final_tile(bits, transpose):
    """An output axis longer than the tile cap with no lane-aligned
    divisor (an unpadded 50257-class vocab) gets a lane-aligned tile
    and a ragged, masked final block — the same derivation the chip
    compiles (tests/test_chip_compile.py), not one full-width tile. A
    flattened token count over its cap goes ragged the same way."""
    rng = np.random.default_rng(8)
    # > DEFAULT_TILE_N with no aligned divisor; the dense int4 leaf's
    # last axis must still divide into groups
    N = GS * 263 if bits == 4 and not transpose else 1031
    M, K = 261, 32                        # M > DEFAULT_TILE_M, prime
    w = jnp.asarray(rng.normal(size=(N, K) if transpose else (K, N)),
                    jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    qt = (_quantize_leaf_int8(w) if bits == 8
          else _quantize_leaf_int4(w, GS))
    deq = qt.dequantize()
    ref = x @ (deq.T if transpose else deq)
    out = jax.jit(lambda x: quantized_matmul(x, qt, transpose=transpose))(x)
    assert out.shape == (M, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tile_validation_errors():
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    qt8 = _quantize_leaf_int8(w)
    qt4 = _quantize_leaf_int4(w, 16)
    # ragged final tiles refuse on every axis
    for kw in (dict(tile_n=7), dict(tile_k=7), dict(tile_m=3)):
        with pytest.raises(ValueError, match="ragged final"):
            quantized_matmul(x, qt8, **kw)
    # int4 group boundaries must not split across tiles: the group
    # axis is tile_n in the dense orientation...
    with pytest.raises(ValueError, match="group_size.*tile_n"):
        quantized_matmul(x, qt4, tile_n=8)
    # ...and tile_k in the transpose orientation (groups ride the
    # contraction axis there)
    with pytest.raises(ValueError, match="group_size.*tile_k"):
        quantized_matmul(x, qt4, transpose=True, tile_k=8)
    with pytest.raises(ValueError, match="contraction mismatch"):
        quantized_matmul(jnp.zeros((4, 32), jnp.float32), qt8)


def test_materialize_for_program_seam(nano):
    """The shared program-entry guard: identity on plain trees,
    materializes for 'xla' configs, passes codes through for 'pallas'
    configs, and refuses scanned-layer pallas (nn.scan cannot slice
    broadcast-shaped scales along a layer axis)."""
    dec, params = nano
    assert materialize_for_program(params, dec.cfg) is params
    q = quantize_params(params, "int8")
    out = materialize_for_program(q, dec.cfg)          # xla: materialize
    assert not is_quantized(out)
    pal = dataclasses.replace(dec.cfg, matmul_kernel="pallas")
    assert materialize_for_program(q, pal) is q        # pallas: pass
    scanned = dataclasses.replace(pal, scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers"):
        materialize_for_program(q, scanned)


# --------------------------------------------------------------------- #
# engine identity: pallas matmul == materialized dequant, ENFORCED 0
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("weight_dtype", ["int8", "int4"])
def test_matmul_matches_xla_engine(nano, weight_dtype):
    """The acceptance pin, dense engine: `matmul_kernel="pallas"`
    emits exactly the materialized-dequant engine's greedy tokens —
    and the armed engine's params stay codes+scales (no dequantized
    tree anywhere: the at-rest bytes ARE the per-dispatch stream)."""
    dec, params = nano
    kw = _quant_kw(weight_dtype)
    ref = _run(dec, params, **kw)
    calls0 = kernel_calls()
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         matmul_kernel="pallas", **kw)
    assert is_quantized(client.engine.params)
    assert param_bytes(client.engine.params) < 0.6 * param_bytes(params)
    # ... and exactly the bytes of the codes+scales the xla engine holds
    assert param_bytes(client.engine.params) == param_bytes(quantize_params(
        params, weight_dtype, group_size=kw.get("weight_group_size")))
    out = client.serve_trace(list(TRACE))
    client.shutdown()
    # trace-witness binds on the first in-process compile of these
    # programs; a warm jit cache (in-process rerun) skips retracing
    assert kernel_calls() > calls0 or calls0 > 0
    for rid in ref:
        assert out[rid].tokens == ref[rid].tokens, (weight_dtype, rid)
        assert out[rid].finish_reason == ref[rid].finish_reason


@pytest.mark.parametrize("weight_dtype", ["int8", "int4"])
@pytest.mark.parametrize("layout", ["paged", "page_native", "pallas_attn"])
def test_matmul_composes_with_paged_layouts(nano, layout, weight_dtype):
    """Quantized weights through the kernel on every KV layout —
    including both pallas kernels stacked (fused attention reads KV
    codes while the projections read weight codes)."""
    dec, params = nano
    kw = dict(_quant_kw(weight_dtype), page_size=4)
    if layout != "paged":
        kw["page_native"] = True
    if layout == "pallas_attn":
        kw["attention_kernel"] = "pallas"
    ref = _run(dec, params, **kw)
    out = _run(dec, params, matmul_kernel="pallas", **kw)
    assert _tokens(out) == _tokens(ref)


def test_matmul_spec_compose(serve_nano_family):
    """spec + int4 target + int8 draft, both models' matmuls through
    the kernel (the engine clones the draft config too) — identical
    to the materialized-dequant spec engine."""
    dec, params, draft, dparams = serve_nano_family
    kw = dict(_quant_kw("int4"), draft_model=draft, draft_params=dparams,
              spec_k=2, draft_weight_dtype="int8")
    ref = _run(dec, params, **kw)
    out = _run(dec, params, matmul_kernel="pallas", **kw)
    assert _tokens(out) == _tokens(ref)


def test_matmul_async_dispatch_identity(nano):
    """The depth-2 pipelined driver enqueues the same pallas programs:
    tokens identical to the sync materialized-dequant run."""
    dec, params = nano
    ref = _run(dec, params, **_quant_kw("int4"))
    out = _run(dec, params, matmul_kernel="pallas", async_dispatch=True,
               **_quant_kw("int4"))
    assert _tokens(out) == _tokens(ref)


def test_matmul_sampled_streams(nano):
    """Sampled (temperature/top_k/seeded) streams ride the shared
    position-indexed key machinery — draw-for-draw identical."""
    dec, params = nano
    trace = [(t, dict(kw, temperature=0.8, top_k=8, seed=50 + i))
             for i, (t, kw) in enumerate(TRACE)]
    ref = _run(dec, params, trace=trace, **_quant_kw("int8"))
    out = _run(dec, params, trace=trace, matmul_kernel="pallas",
               **_quant_kw("int8"))
    for rid in ref:
        assert out[rid].tokens == ref[rid].tokens, rid


def test_matmul_crash_replay_identity(nano):
    """Rebuild-and-replay re-enters the ctor with the same kwargs: the
    clone re-selects the kernel, re-quantizes bit-identical codes, and
    the replayed stream matches the uninterrupted pallas run."""
    dec, params = nano
    kw = dict(_quant_kw("int4"), matmul_kernel="pallas")
    ref = _run(dec, params, **kw)
    plan = FaultPlan.at("serve.dispatch", [4])
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         retry_policy=RetryPolicy(max_attempts=3,
                                                  base_delay=0.0), **kw)
    with plan.armed():
        out = client.serve_trace(list(TRACE))
    client.shutdown()
    assert plan.fired == 1
    assert _tokens(out) == _tokens(ref)


def test_matmul_fleet_failover_identity(nano):
    """A replica killed mid-decode re-admits onto siblings that
    re-quantized the same raw params and re-selected the same kernel —
    failover streams match the uninterrupted single-engine run."""
    dec, params = nano
    kw = dict(_quant_kw("int4"), matmul_kernel="pallas")
    ref = _run(dec, params, **kw)
    fleet = ReplicaFleet(dec, params, num_replicas=3, num_standby=1,
                         num_slots=3, prefill_len=8, **kw)
    plan = FaultPlan.at("serve.replica", [6])   # mid-decode
    with plan.armed():
        out = fleet.serve_trace(list(TRACE))
    assert plan.fired == 1 and fleet.failovers == 1
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
    fleet.shutdown()


def test_generate_path_identity(nano):
    """Direct generate() callers get the same seam: a decode config
    built with matmul_kernel="pallas" consumes quantized params
    through the kernel, token-identical to dequantize-then-generate."""
    dec, params = nano
    q = quantize_params(params, "int4", group_size=GS)
    pal = TransformerLM(dataclasses.replace(dec.cfg,
                                            matmul_kernel="pallas"))
    prompts = jnp.asarray([PROMPTS[0], [9, 2, 44, 1]], jnp.int32)
    ref = generate(dec, dequantize_params(q), prompts, 6,
                   rng=jax.random.PRNGKey(0), temperature=0.0)
    out = generate(pal, q, prompts, 6, rng=jax.random.PRNGKey(0),
                   temperature=0.0)
    assert jnp.array_equal(out, ref)


# --------------------------------------------------------------------- #
# configuration surface
# --------------------------------------------------------------------- #
def test_matmul_kernel_validation(nano):
    dec, params = nano
    with pytest.raises(ValueError, match="matmul_kernel"):
        ServeEngine(dec, params, num_slots=2, prefill_len=8,
                    matmul_kernel="mosaic")
    with pytest.raises(ValueError, match="matmul_kernel"):
        gpt2_config("nano", matmul_kernel="mosaic")
    # the kernel only consumes QTensor leaves: without weight
    # quantization it would be silently inert — refused
    with pytest.raises(ValueError, match="weight_dtype"):
        ServeEngine(dec, params, num_slots=2, prefill_len=8,
                    matmul_kernel="pallas")
    # scanned layers cannot carry QTensor leaves through nn.scan
    mk = dict(vocab_size=128, max_seq_len=32, dtype=jnp.float32,
              scan_layers=True)
    sdec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    sparams = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(0), np.zeros((2, 4), np.int32))["params"]
    with pytest.raises(ValueError, match="scan_layers"):
        ServeEngine(sdec, sparams, num_slots=2, prefill_len=8,
                    weight_dtype="int8", matmul_kernel="pallas")
    # the cfg field is the source of truth: a model built with the
    # kernel in its config needs no engine kwarg
    pal_cfg = dataclasses.replace(dec.cfg, matmul_kernel="pallas")
    eng = ServeEngine(TransformerLM(pal_cfg), params, num_slots=2,
                      prefill_len=8, weight_dtype="int8")
    assert eng.matmul_kernel == "pallas"
    assert eng.model.cfg.matmul_kernel == "pallas"
    eng.shutdown()
    eng = ServeEngine(dec, params, num_slots=2, prefill_len=8,
                      weight_dtype="int8", matmul_kernel="pallas")
    assert eng.matmul_kernel == "pallas"
    assert eng.model.cfg.matmul_kernel == "pallas"
    eng.shutdown()
