"""Pallas paged-attention kernel (`attention_kernel="pallas"`).

The load-bearing assertion mirrors the page-native pins in
``tests/test_paged.py``: the kernel's read side is the XLA page-native
math (same operand roundings, same fused mask, one exact softmax, f32
accumulation — no online-softmax approximation) up to f32 summation
order: the kernel keeps heads on the lane axis so Mosaic can lower it
(``tests/test_chip_compile.py``), and its per-head sums run in a
different order than XLA's einsum. The unit test below holds it to a
few f32 ulps; greedy token identity vs the page-native engine is
ENFORCED at 0 mismatches on the pinned nano configs across page sizes,
int8 arenas, scanned/unrolled layers, spec compose, crash replay, and
fleet failover (empirical, like the int8 page-native pin — what the
chip shows is ``chip_smoke.py``'s agreement report, ``docs/serving.md``).

The unit test at the top pins the kernel directly against a jnp
transcription of ``MultiHeadAttention._page_native_attention``'s read
side, including unmapped (−1) page-table entries and the verify-shaped
``T = k+1`` block.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import TransformerLM, gpt2_config
from ray_lightning_tpu.models.pallas_attention import paged_attention
from ray_lightning_tpu.models.quant import (kv_dequantize, kv_quantize,
                                            kv_scales)
from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
from ray_lightning_tpu.serve import ReplicaFleet, ServeClient, ServeEngine

pytestmark = [pytest.mark.serve, pytest.mark.pallas]

#: the same nano serving shape every serve/paged/spec module pins —
#: reusing it keeps the XLA reference legs on programs the suite has
#: already compiled (tier-1 cold-compile relief, ROADMAP sizing note)
MK = dict(vocab_size=128, max_seq_len=32, dtype=jnp.float32,
          scan_layers=False)

PROMPTS = [[5, 17, 3, 9], [9, 2, 44], [42, 7], [1]]
TRACE = [
    (0, dict(prompt=PROMPTS[0], max_new_tokens=6)),
    (0, dict(prompt=PROMPTS[1], max_new_tokens=6)),
    (3, dict(prompt=PROMPTS[2], max_new_tokens=6)),
    (5, dict(prompt=PROMPTS[3], max_new_tokens=6)),
]


@pytest.fixture(scope="module")
def nano(serve_nano_family):
    # the shared serve-family pair (conftest): the XLA reference legs
    # here run on programs test_paged/test_quant already compiled
    return serve_nano_family[:2]


def _run(dec, params, trace=TRACE, **kw):
    client = ServeClient(dec, params, num_slots=3, prefill_len=8, **kw)
    out = client.serve_trace(list(trace))
    client.shutdown()
    return out


def _tokens(out):
    return {rid: c.tokens for rid, c in out.items()}


# --------------------------------------------------------------------- #
# kernel unit: a few f32 ulps of the XLA page-native read-side math
# --------------------------------------------------------------------- #
def _xla_read_reference(q, kp, vp, ks, vs, pos, pt):
    """jnp transcription of _page_native_attention's read side."""
    B, T, H, D = q.shape
    P, ps = kp.shape[0], kp.shape[1]
    pp = pt.shape[1]
    S = pp * ps

    def read(store, scales, pidx):
        blk = jnp.take(store, pidx, axis=0)
        if scales is None:
            return blk
        return kv_dequantize(blk, jnp.take(scales, pidx, axis=0),
                             q.dtype)

    scores = [jnp.einsum("bqhd,bkhd->bhqk", q,
                         read(kp, ks, jnp.clip(pt[:, j], 0, P - 1)),
                         preferred_element_type=jnp.float32)
              for j in range(pp)]
    logits = jnp.concatenate(scores, axis=3) * D ** -0.5
    key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
    big_neg = jnp.finfo(jnp.float32).min
    logits = logits + jnp.where(key_pos <= pos[:, None, :, None], 0.0,
                                big_neg)
    w = jax.nn.softmax(logits, axis=-1)
    all_masked = jnp.all(logits <= big_neg * 0.5, axis=-1, keepdims=True)
    w = jnp.where(all_masked, 0.0, w).astype(q.dtype)
    out = jnp.zeros((B, T, H, D), jnp.float32)
    for j in range(pp):
        vj = read(vp, vs, jnp.clip(pt[:, j], 0, P - 1))
        wj = jax.lax.dynamic_slice_in_dim(w, j * ps, ps, axis=3)
        out = out + jnp.einsum("bhqk,bkhd->bqhd", wj, vj,
                               preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@pytest.mark.parametrize("T", [1, 3], ids=["decode", "verify"])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["f32", "int8"])
def test_kernel_matches_xla_read_side(T, quantized):
    """Direct kernel call vs the jnp reference, with unmapped (−1)
    rows, ragged positions, and the spec verify's (B, k+1) block shape
    — equal up to f32 summation order (the engine identity pins below
    rest on argmax margins being far wider than this)."""
    rng = np.random.default_rng(7)
    B, H, D, P, ps, pp = 3, 4, 32, 10, 4, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, ps, H, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, H, D)), jnp.float32)
    pt = np.full((B, pp), -1, np.int32)
    pt[0, :3] = [4, 1, 7]
    pt[1, :2] = [0, 2]          # row 2 stays fully unmapped (parked)
    pt = jnp.asarray(pt)
    pos0 = np.array([9, 5, 3], np.int32)
    pos = jnp.asarray(np.stack([pos0 + t for t in range(T)], axis=1))
    if quantized:
        ks, vs = kv_scales(kp, (1, 3)), kv_scales(vp, (1, 3))
        kp, vp = kv_quantize(kp, ks), kv_quantize(vp, vs)
    else:
        ks = vs = None
    ref = _xla_read_reference(q, kp, vp, ks, vs, pos, pt)
    out = paged_attention(q, kp, vp, ks, vs, pos, pt, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


# --------------------------------------------------------------------- #
# engine identity: pallas == XLA page-native, ENFORCED 0 mismatches
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("page_size", [4, 8, 16])
def test_pallas_matches_page_native_engine(nano, page_size):
    """The acceptance pin: `attention_kernel="pallas"` emits exactly
    the XLA page-native engine's greedy tokens on the staggered
    mid-flight trace, across page sizes (pp = 8/4/2 page columns)."""
    dec, params = nano
    kw = dict(page_size=page_size, page_native=True)
    ref = _run(dec, params, **kw)
    out = _run(dec, params, attention_kernel="pallas", **kw)
    for rid in ref:
        assert out[rid].tokens == ref[rid].tokens, (page_size, rid)
        assert out[rid].finish_reason == ref[rid].finish_reason


@pytest.mark.parametrize("steps", [1, 3])
def test_pallas_int8_arena_identity(nano, steps):
    """int8 arenas: codes + per-page-per-head scales stream into the
    kernel and dequantize on VMEM blocks — token-identical to the XLA
    page-native int8 engine (which carries the same empirical
    requant-rounding caveat vs dense-gather, docs/serving.md), incl.
    multi-step dispatch."""
    dec, params = nano
    kw = dict(page_size=4, page_native=True, kv_dtype="int8",
              steps_per_dispatch=steps)
    ref = _run(dec, params, **kw)
    out = _run(dec, params, attention_kernel="pallas", **kw)
    for rid in ref:
        assert out[rid].tokens == ref[rid].tokens, (steps, rid)


def test_pallas_eos_and_sampled_streams(nano):
    """Eos retirement and per-request sampled key streams ride the
    shared bookkeeping — only the attention read side changed — so
    sampled outputs match the XLA page-native engine draw-for-draw."""
    dec, params = nano
    free = _run(dec, params, page_size=4, page_native=True)
    eos = free[0].tokens[2]
    traces = (
        [(t, dict(kw, eos_id=eos)) for t, kw in TRACE],
        [(t, dict(kw, temperature=0.8, top_k=8, seed=50 + i))
         for i, (t, kw) in enumerate(TRACE)],
    )
    for tr in traces:
        ref = _run(dec, params, trace=tr, page_size=4, page_native=True)
        out = _run(dec, params, trace=tr, page_size=4, page_native=True,
                   attention_kernel="pallas")
        for rid in ref:
            assert out[rid].tokens == ref[rid].tokens, rid
            assert out[rid].finish_reason == ref[rid].finish_reason


def test_pallas_scanned_layers_identity():
    """Scanned layouts call the kernel inside the layer scan (each
    layer sees its own arena slice): identical tokens to the scanned
    XLA page-native engine."""
    mk = dict(MK, scan_layers=True)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(0), np.zeros((2, 4), np.int32))["params"]
    for kv in (None, "int8"):
        kw = dict(page_size=4, page_native=True, kv_dtype=kv)
        ref = _run(dec, params, **kw)
        out = _run(dec, params, attention_kernel="pallas", **kw)
        assert _tokens(out) == _tokens(ref), kv


def test_pallas_full_stack_spec_compose(serve_nano_family):
    """spec + kv_dtype="int8" + weight_dtype="int4" + page-native +
    pallas all stacked: the widened (B, k+1) verify runs through the
    kernel too, token-identical to the same-quantized dense-gather
    non-spec engine (the test_quant full-stack pin, plus the kernel)."""
    dec, params, draft, dparams = serve_nano_family
    quant = dict(weight_dtype="int4", weight_group_size=8,
                 kv_dtype="int8")
    base = _run(dec, params, page_size=4, **quant)
    full = _run(dec, params, page_size=4, page_native=True,
                attention_kernel="pallas", draft_model=draft,
                draft_params=dparams, spec_k=2,
                draft_weight_dtype="int8", **quant)
    assert _tokens(full) == _tokens(base)


# --------------------------------------------------------------------- #
# reliability: crash replay + fleet failover stay token-identical
# --------------------------------------------------------------------- #
def test_pallas_crash_replay_identity(nano):
    """Rebuild-and-replay over a pallas-kernel engine: the supervisor
    re-enters the ctor with the same kwargs, the clone re-selects the
    kernel, and the replayed stream matches the uninterrupted run."""
    dec, params = nano
    kw = dict(page_size=4, page_native=True, attention_kernel="pallas")
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


def test_pallas_fleet_failover_identity(nano):
    """A replica killed mid-decode re-admits onto siblings compiled
    with the same kernel — failover streams match the uninterrupted
    single-engine pallas run."""
    dec, params = nano
    kw = dict(page_size=4, page_native=True, attention_kernel="pallas")
    ref = _run(dec, params, **kw)
    fleet = ReplicaFleet(dec, params, num_replicas=3, num_standby=1,
                         num_slots=3, prefill_len=8, **kw)
    plan = FaultPlan.at("serve.replica", [6])  # mid-decode
    with plan.armed():
        out = fleet.serve_trace(list(TRACE))
    assert plan.fired == 1 and fleet.failovers == 1
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
    fleet.shutdown()


# --------------------------------------------------------------------- #
# configuration surface
# --------------------------------------------------------------------- #
def test_attention_kernel_validation(nano):
    dec, params = nano
    # pallas without the page-native layout has nothing to read through
    with pytest.raises(ValueError, match="page_native"):
        ServeEngine(dec, params, num_slots=2, prefill_len=8,
                    attention_kernel="pallas")
    with pytest.raises(ValueError, match="attention_kernel"):
        ServeEngine(dec, params, num_slots=2, prefill_len=8,
                    attention_kernel="mosaic")
    with pytest.raises(ValueError, match="attention_kernel"):
        gpt2_config("nano", attention_kernel="mosaic", **MK)
    # the cfg field is the source of truth: a model built with the
    # kernel in its config needs no engine kwarg, and the engine
    # records the resolved choice either way
    pal_cfg = gpt2_config("nano", decode=True, attention_kernel="pallas",
                          **MK)
    eng = ServeEngine(TransformerLM(pal_cfg), params, num_slots=2,
                      prefill_len=8, page_size=4, page_native=True)
    assert eng.attention_kernel == "pallas"
    eng.shutdown()
    eng = ServeEngine(dec, params, num_slots=2, prefill_len=8,
                      page_size=4, page_native=True,
                      attention_kernel="pallas")
    assert eng.attention_kernel == "pallas"
    assert eng.model.cfg.attention_kernel == "pallas"
    eng.shutdown()
