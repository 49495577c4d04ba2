"""Weight-only int8/int4 quantization (models/quant.py) + its serve wiring.

The load-bearing assertions:

- **Bounded, idempotent storage**: quantize→dequantize error is bounded
  by half a quantization step of each group's absmax (per-output-channel
  int8, group-wise int4), int4 nibble packing round-trips every code,
  and re-quantizing dequantized weights reproduces codes and scales
  bit-for-bit — the property that makes supervisor rebuilds (which
  re-quantize from raw params) token-identical.
- **Determinism, not logit-identity**: quantized weights PERTURB logits
  by design, so quantized engines are pinned against themselves —
  identical across runs, across dense-gather vs page-native storage,
  across crash replay, and across fleet failover — never token for
  token against the full-precision engine; against it they are held to
  a teacher-forced top-1 **agreement floor**.
- **Exact byte accounting**: ``param_bytes()`` is the single source of
  truth equal-byte and bandwidth-floor math cites; the int8/int4
  ratios it reports are enforced here on real model trees.
- **Composition**: spec decoding + ``kv_dtype="int8"`` +
  ``weight_dtype="int4"`` + page-native attention all stack on one
  engine and match the same-quantized plain engine token-for-token.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.quant import (QTensor, dequantize_params,
                                            is_quantized, pack_int4,
                                            param_bytes, quantize_params,
                                            unpack_int4)
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
from ray_lightning_tpu.serve import (FINISH_LENGTH, ReplicaFleet,
                                     ServeClient, ServeEngine)

pytestmark = [pytest.mark.serve, pytest.mark.quant]

#: nano dims (d_model 48, head_dim 12 for gpt2_config "nano"? — the
#: group size every nano leaf's last axis divides is set per-test)
GS = 8


@pytest.fixture(scope="module")
def nano(serve_nano_family):
    """Target (gpt2-nano, f32 — real argmax margins) + 1-layer draft
    — the shared serve-family pair (conftest)."""
    return serve_nano_family


PROMPTS = [[5, 17, 3, 9], [9, 2, 44], [42, 7], [1]]


def _trace(n=6, **kw):
    return [
        (0, dict(prompt=PROMPTS[0], max_new_tokens=n, **kw)),
        (0, dict(prompt=PROMPTS[1], max_new_tokens=n, **kw)),
        (3, dict(prompt=PROMPTS[2], max_new_tokens=n, **kw)),
        (5, dict(prompt=PROMPTS[3], max_new_tokens=n, **kw)),
    ]


def _run(dec, params, trace=None, **kw):
    client = ServeClient(dec, params, num_slots=3, prefill_len=8, **kw)
    out = client.serve_trace(list(trace if trace is not None
                                  else _trace()))
    client.shutdown()
    return {rid: c.tokens for rid, c in out.items()}


# --------------------------------------------------------------------- #
# storage: round-trip bounds, packing, idempotency
# --------------------------------------------------------------------- #
def test_int8_roundtrip_bound_and_idempotent_on_real_weights(nano):
    """Per-output-channel int8 on REAL model leaves: elementwise error
    <= half a step of the channel absmax, codes saturate at exactly
    127, and re-quantizing the dequantized weights reproduces codes AND
    scales bit-for-bit (supervisor rebuilds re-quantize raw params —
    determinism is this property)."""
    _dec, params, _draft, _dparams = nano
    q = quantize_params(params, "int8")
    checked = 0
    for leaf, orig in zip(
            jax.tree_util.tree_leaves(
                q, is_leaf=lambda x: isinstance(x, QTensor)),
            jax.tree_util.tree_leaves(params)):
        if not isinstance(leaf, QTensor):
            assert jnp.array_equal(leaf, orig)
            continue
        deq = leaf.dequantize()
        amax = jnp.max(jnp.abs(orig),
                       axis=tuple(range(orig.ndim - 1)), keepdims=True)
        err = jnp.abs(deq.astype(jnp.float32)
                      - orig.astype(jnp.float32))
        assert float(jnp.max(err - amax / 254.0)) <= 1e-6
        assert int(jnp.max(jnp.abs(leaf.q))) == 127
        q2 = quantize_params({"w": deq}, "int8")["w"]
        assert jnp.array_equal(q2.q, leaf.q)
        assert jnp.allclose(q2.scale, leaf.scale)
        checked += 1
    assert checked >= 10  # kernels + embeddings across the blocks


def test_int4_roundtrip_bound_and_requant_idempotent(nano):
    """Group-wise int4: error <= half a step of the GROUP absmax
    (codes in [-7, 7]), and the dequantized weights re-quantize to the
    same packed codes and scales."""
    _dec, params, _draft, _dparams = nano
    q = quantize_params(params, "int4", group_size=GS)
    checked = 0
    for leaf, orig in zip(
            jax.tree_util.tree_leaves(
                q, is_leaf=lambda x: isinstance(x, QTensor)),
            jax.tree_util.tree_leaves(params)):
        if not isinstance(leaf, QTensor):
            continue
        deq = leaf.dequantize().astype(jnp.float32)
        g = orig.astype(jnp.float32).reshape(
            *orig.shape[:-1], orig.shape[-1] // GS, GS)
        gmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
        err = jnp.abs(deq.reshape(g.shape) - g)
        assert float(jnp.max(err - gmax / 14.0)) <= 1e-6
        q2 = quantize_params({"w": deq}, "int4", group_size=GS)["w"]
        assert jnp.array_equal(q2.q, leaf.q)
        assert jnp.allclose(q2.scale, leaf.scale)
        checked += 1
    assert checked >= 10


def test_int4_pack_unpack_round_trips_every_code():
    """All 16 nibble values survive pack→unpack at every parity
    position (sign extension is the part naive shifts get wrong)."""
    codes = jnp.tile(jnp.arange(-8, 8, dtype=jnp.int8), 4)[None, :]
    assert jnp.array_equal(unpack_int4(pack_int4(codes)), codes)
    rng = np.random.default_rng(0)
    rand = jnp.asarray(rng.integers(-8, 8, size=(3, 5, 64)), jnp.int8)
    assert jnp.array_equal(unpack_int4(pack_int4(rand)), rand)


def test_param_bytes_exact_accounting(nano):
    """param_bytes is exact on plain trees (sum of leaf nbytes), exact
    on quantized trees (codes + scales), works on eval_shape structs
    (no allocation), and the quantized ratios clear the bench's
    enforced gates: int8 <= 0.55x, int4 <= 0.35x."""
    _dec, params, _draft, _dparams = nano
    plain = param_bytes(params)
    assert plain == sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(params))
    q8 = quantize_params(params, "int8")
    q4 = quantize_params(params, "int4", group_size=GS)
    assert param_bytes(q8) / plain <= 0.55
    assert param_bytes(q4) / plain <= 0.35
    # allocation-free accounting: byte-identical on shape structs
    assert param_bytes(jax.eval_shape(lambda p: p, q4)) == param_bytes(q4)
    assert param_bytes(jax.eval_shape(lambda p: p, params)) == plain


def test_quantize_and_engine_validation(nano):
    dec, params, draft, dparams = nano
    with pytest.raises(ValueError, match="weight_dtype"):
        quantize_params(params, "int7")
    with pytest.raises(ValueError, match="group_size is an int4"):
        quantize_params(params, "int8", group_size=8)
    with pytest.raises(ValueError, match="even"):
        quantize_params(params, "int4", group_size=7)
    with pytest.raises(ValueError, match="divide"):
        quantize_params(params, "int4", group_size=GS * 1000)
    with pytest.raises(ValueError, match="already quantized"):
        quantize_params(quantize_params(params, "int8"), "int8")
    with pytest.raises(ValueError, match="weight_dtype"):
        ServeEngine(dec, params, prefill_len=8, weight_dtype="fp8")
    with pytest.raises(ValueError, match="weight_group_size"):
        ServeEngine(dec, params, prefill_len=8, weight_group_size=GS)
    with pytest.raises(ValueError, match="draft_weight_dtype"):
        ServeEngine(dec, params, prefill_len=8, draft_weight_dtype="int8")
    with pytest.raises(ValueError, match="page_native"):
        ServeEngine(dec, params, prefill_len=8, page_native=True)


# --------------------------------------------------------------------- #
# determinism across storage layouts, replay, and failover
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("wd,gs", [("int8", None), ("int4", GS)],
                         ids=["int8", "int4"])
def test_quantized_engine_deterministic_across_layouts(nano, wd, gs):
    """One quantized model, three storage layouts (dense slots, paged
    dense-gather, paged page-native): token-identical streams — the
    quantized-weight sibling of the paged identity pins."""
    dec, params, _draft, _dparams = nano
    kw = dict(weight_dtype=wd, weight_group_size=gs)
    dense = _run(dec, params, **kw)
    paged = _run(dec, params, page_size=4, **kw)
    native = _run(dec, params, page_size=4, page_native=True, **kw)
    assert dense == paged == native
    # and deterministic across fresh engines (fresh quantization)
    assert _run(dec, params, **kw) == dense


@pytest.mark.parametrize("wd,kw,floor", [
    ("int8", {}, 0.95), ("int4", dict(group_size=GS), 0.60)])
def test_teacher_forced_top1_agreement_with_full_precision(nano, wd, kw,
                                                           floor):
    """Quantization perturbs logits within rounding, not beyond: the
    full-precision engine's greedy streams, re-scored position by
    position with the dequantized weights on the SAME (full-precision)
    context, keep their top-1 at >= ``floor`` of positions — agreement,
    not token identity. Weights that are not the model's (a wrong scale,
    a shuffled code) read a few percent here."""
    dec, params = nano[:2]
    full = dec.clone(cfg=dataclasses.replace(dec.cfg, decode=False))
    client = ServeClient(dec, params, num_slots=3, prefill_len=8)
    out = client.serve_trace(_trace(n=24))
    client.shutdown()

    def agreement(p):
        agree = total = 0
        for comp in out.values():
            seq = list(comp.prompt) + list(comp.tokens)
            logits = full.apply({"params": p},
                                jnp.asarray([seq[:-1]], jnp.int32))
            pred = np.asarray(logits[0]).argmax(-1)[len(comp.prompt) - 1:]
            agree += int((pred == np.asarray(comp.tokens)).sum())
            total += len(comp.tokens)
        return agree / total

    assert agreement(params) == 1.0   # the re-scoring is the engine's
    assert agreement(
        dequantize_params(quantize_params(params, wd, **kw))) >= floor


def test_quantized_crash_replay_token_identity(nano):
    """Rebuild-and-replay re-quantizes the raw params: the recovered
    stream is token-identical to the uninterrupted quantized run, on
    dense AND paged storage."""
    dec, params, _draft, _dparams = nano
    for kw in (dict(), dict(page_size=4)):
        ref = _run(dec, params, weight_dtype="int4",
                   weight_group_size=GS, **kw)
        plan = FaultPlan.at("serve.dispatch", [4])
        client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                             weight_dtype="int4", weight_group_size=GS,
                             retry_policy=RetryPolicy(max_attempts=3,
                                                      base_delay=0.0),
                             **kw)
        with plan.armed():
            out = client.serve_trace(_trace())
        client.shutdown()
        assert plan.fired == 1
        assert {r: c.tokens for r, c in out.items()} == ref, kw


def test_quantized_fleet_failover_token_identity(nano):
    """A replica killed mid-decode re-admits its work onto a sibling
    that quantized the SAME raw params — bit-identical codes, so the
    failover stream matches the uninterrupted single-engine run."""
    dec, params, _draft, _dparams = nano
    trace = _trace(n=6)
    ref = _run(dec, params, trace, weight_dtype="int8")
    fleet = ReplicaFleet(dec, params, num_replicas=3, num_standby=1,
                         num_slots=3, prefill_len=8, weight_dtype="int8")
    plan = FaultPlan.at("serve.replica", [6])  # mid-decode
    with plan.armed():
        out = fleet.serve_trace(trace)
    assert plan.fired == 1 and fleet.failovers == 1
    for rid in range(4):
        assert out[rid].tokens == ref[rid], rid
        assert out[rid].finish_reason == FINISH_LENGTH
    fleet.shutdown()


# --------------------------------------------------------------------- #
# composition
# --------------------------------------------------------------------- #
def test_full_stack_composition(nano):
    """spec + kv_dtype="int8" + weight_dtype="int4" + page-native all
    stacked on one engine: token-identical to the same-quantized plain
    (dense-gather, non-spec) engine — spec's accept rule and the
    page-native read path are both exact given fixed params/storage."""
    dec, params, draft, dparams = nano
    base = _run(dec, params, weight_dtype="int4", weight_group_size=GS,
                kv_dtype="int8", page_size=4)
    full = _run(dec, params, weight_dtype="int4", weight_group_size=GS,
                kv_dtype="int8", page_size=4, page_native=True,
                draft_model=draft, draft_params=dparams, spec_k=2,
                draft_weight_dtype="int8")
    assert full == base


def test_quantized_draft_keeps_greedy_target_identity(nano):
    """draft_weight_dtype perturbs only the PROPOSALS — greedy spec
    commits are still the target's own argmax at every step, so the
    stream matches the plain full-precision engine exactly (acceptance
    may drop; correctness may not)."""
    dec, params, draft, dparams = nano
    ref = _run(dec, params)
    out = _run(dec, params, draft_model=draft, draft_params=dparams,
               spec_k=2, draft_weight_dtype="int4",
               weight_group_size=GS)
    assert out == ref


def test_generate_accepts_quantized_params(nano):
    """The dequant guards in the generate()-path programs: quantized
    params produce exactly the tokens of the pre-dequantized tree
    (same numbers, different storage)."""
    from ray_lightning_tpu.models.generate import generate
    dec, params, _draft, _dparams = nano
    q = quantize_params(params, "int4", group_size=GS)
    assert is_quantized(q) and not is_quantized(params)
    batch = np.array([[5, 17, 3, 9], [9, 2, 44, 0]], np.int32)
    lengths = np.array([4, 3], np.int32)
    a = generate(dec, q, jnp.asarray(batch), max_new_tokens=5,
                 rng=jax.random.PRNGKey(3), temperature=0.0,
                 prompt_lengths=jnp.asarray(lengths))
    b = generate(dec, dequantize_params(q), jnp.asarray(batch),
                 max_new_tokens=5, rng=jax.random.PRNGKey(3),
                 temperature=0.0, prompt_lengths=jnp.asarray(lengths))
    assert jnp.array_equal(a, b)


# --------------------------------------------------------------------- #
# observability
# --------------------------------------------------------------------- #
def test_weights_quantized_obs_pinned(nano):
    """engine.weights_quantized events (target + draft, exact payload
    keys, honest byte accounting) + the serve_param_bytes gauge, armed;
    a disarmed run leaks nothing onto a fresh handle."""
    dec, params, draft, dparams = nano
    tel = Telemetry()
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         telemetry=tel, weight_dtype="int4",
                         weight_group_size=GS, draft_model=draft,
                         draft_params=dparams, spec_k=2,
                         draft_weight_dtype="int8")
    events = tel.events("engine.weights_quantized")
    assert [e.payload["model"] for e in events] == ["target", "draft"]
    for e in events:
        assert set(e.payload) == {"model", "dtype", "group_size",
                                  "bytes_before", "bytes_after"}
    tgt, drf = events
    assert tgt.payload["dtype"] == "int4"
    assert tgt.payload["group_size"] == GS
    assert tgt.payload["bytes_before"] == param_bytes(params)
    assert tgt.payload["bytes_after"] == param_bytes(
        client.engine.params)
    assert drf.payload["dtype"] == "int8"
    assert drf.payload["group_size"] is None
    gauge = tel.metrics.get("serve_param_bytes").value
    assert gauge == param_bytes(client.engine.params) + param_bytes(
        client.engine.spec.params)
    client.shutdown()

    # disarmed zero-surface: same workload, no handle anywhere
    fresh = Telemetry()
    _run(dec, params, weight_dtype="int8", draft_model=draft,
         draft_params=dparams, spec_k=2)
    assert not fresh.events()
    # the only series on a fresh handle are the two pre-registered drop
    # counters (event ring, PR 19; span recorder, PR 26), still at zero
    assert fresh.metrics.snapshot() == {
        "obs_events_dropped_total": 0.0, "obs_spans_dropped_total": 0.0}
