"""SambaY (Phi-4-mini-flash-reasoning, ``models/sambay.py``) against the
plain float32 reference (``benchmark/sambay_reference.py``, which imports
nothing of the program), at a small size on the CPU: hidden 64, 8 layers
laid out 0-3 self-decoder (Mamba / window) / 4 Mamba-with-memory / 5 full
/ 6 gated memory unit / 7 cross, window 8, vocabulary 256. Weights are
the benchmark's seeded ones (Mamba's own init for ``A_log``, the ``dt``
bias and ``D``), drawn at ``initializer_range`` 1 / sqrt(64) so that the
activations — and the recurrent state's share of them — are O(1) at this
width.

Tolerances, and why:

- ``F32_TOL`` 1e-4 on logits of standard deviation ~1, program with
  float32 matmul operands: program and reference then differ by float32
  summation order only (1e-5 here). A recurrent state held in bfloat16
  reads 5e-2 — five hundred times over; ``test_bf16_state_fails`` holds
  that.
- ``BF16_TOL`` 0.3 with bfloat16 operands as the configuration runs
  them (reads 0.08: 2^-8 relative on every matmul operand through 8
  layers): it cannot tell the state's dtype, which is why the float32
  comparison exists.
- served tokens are held to the reference by the *gap*: at each served
  position, how far the reference's logit of the served token lies below
  the reference's best. Float32 operands: ``F32_TOL`` (a greedy token is
  the reference's argmax unless two logits tie within rounding).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import sambay_reference, sambay_weights
from benchmark.families import phi4flash
from ray_lightning_tpu.models.generate import (CacheLeaf, _prefill_impl,
                                               cache_layout, decode_step)
from ray_lightning_tpu.models.sambay import SambaYConfig, SambaYLM
from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
from ray_lightning_tpu.serve import ServeClient, ServeEngine

pytestmark = pytest.mark.serve

SHAPE = dict(vocab_size=256, hidden_size=64, num_hidden_layers=8,
             num_attention_heads=8, num_key_value_heads=4,
             intermediate_size=128, sliding_window=8, mb_per_layer=2,
             layer_norm_eps=1e-5, tie_word_embeddings=True,
             max_position_embeddings=4096, initializer_range=0.125)
POSITIONS = 48
F32_TOL = 1e-4
BF16_TOL = 0.3


@pytest.fixture(scope="module")
def canon():
    return sambay_weights.make_canonical(sambay_weights.seed_key(3), SHAPE)


@pytest.fixture(scope="module")
def params(canon):
    return phi4flash.program_tree(canon, SHAPE)


@pytest.fixture(scope="module")
def ref(canon):
    fn = sambay_reference.make_logits_fn(SHAPE)
    return lambda tokens, rows: np.asarray(fn(canon, tokens, rows))


def _model(**kw):
    kw.setdefault("dtype", jnp.float32)
    return SambaYLM(phi4flash.config(SHAPE, POSITIONS, **kw))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _full_forward_error(params, ref, **kw):
    toks = np.stack([_tokens(i, 40) for i in range(2)])
    got = np.asarray(_model(**kw).apply({"params": params},
                                        jnp.asarray(toks)))
    want = np.stack([ref(t, np.arange(40)) for t in toks])
    return float(np.abs(got - want).max())


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
def test_full_forward_matches_reference(params, ref, dtype, tol):
    """(a) every position's logits, 40 positions (5 windows deep)."""
    assert _full_forward_error(params, ref, dtype=dtype) < tol


def test_bf16_state_fails(params, ref):
    """(a) is tight enough to see the recurrent state's dtype."""
    err = _full_forward_error(params, ref, state_dtype=jnp.bfloat16)
    assert err > 50 * F32_TOL, err


def test_layout_matches_the_issue():
    cfg = SambaYConfig()
    kinds = [cfg.layer_kind(l) for l in range(32)]
    assert kinds[:17] == ["mamba", "swa"] * 8 + ["mamba"]
    assert kinds[17] == "full"
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    small = phi4flash.config(SHAPE, POSITIONS)
    assert [small.layer_kind(l) for l in range(8)] == [
        "mamba", "swa", "mamba", "swa", "mamba", "full", "gmu", "cross"]


def test_prefill_then_decode_logits_match_reference(params, ref):
    """(b) at the program level: one padded prefill batch of rows of
    unequal length — one past the window, so its ring has wrapped, one a
    single token — then 12 decode steps at per-row positions; the logits
    of every step against the reference's full forward."""
    model = _model(decode=True)
    lengths = np.array([13, 5, 1, 16], np.int32)
    P, steps = 16, 12
    seqs = [_tokens(10 + i, int(n) + steps) for i, n in enumerate(lengths)]
    prompts = np.zeros((4, P), np.int32)
    for i, n in enumerate(lengths):
        prompts[i, :n] = seqs[i][:n]
    cache, last = jax.jit(_prefill_impl, static_argnums=0)(
        model, params, prompts, lengths)
    want = [ref(s, np.arange(len(s))) for s in seqs]
    got = np.asarray(last)
    for i, n in enumerate(lengths):
        assert np.abs(got[i] - want[i][n - 1]).max() < F32_TOL, i
    step = jax.jit(decode_step, static_argnums=0)
    for j in range(steps):
        pos = (lengths + j)[:, None]
        cur = np.array([[s[p]] for s, p in zip(seqs, pos[:, 0])], np.int32)
        logits, cache = step(model, params, cache, cur, pos)
        logits = np.asarray(logits)
        for i in range(4):
            err = np.abs(logits[i] - want[i][pos[i, 0]]).max()
            assert err < F32_TOL, (j, i, err)


def _gap(ref, prompt, tokens):
    """Widest distance of a served token's reference logit below the
    reference's best, over the served positions."""
    seq = list(prompt) + list(tokens)
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    lg = ref(seq, rows)
    return float((lg.max(-1) - lg[np.arange(len(rows)), tokens]).max())


REQUESTS = [dict(prompt=_tokens(20, 13), max_new_tokens=20),
            dict(prompt=_tokens(21, 3), max_new_tokens=9),
            dict(prompt=_tokens(22, 16), max_new_tokens=14),
            dict(prompt=_tokens(23, 1), max_new_tokens=25),
            dict(prompt=_tokens(24, 7), max_new_tokens=6)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
def test_served_tokens_follow_reference(params, ref, dtype, tol):
    """(b) through ``ServeClient``: five ragged requests over three
    slots (two prefill batches, slots handed on mid-flight), greedy;
    every served token is the reference's choice at its position."""
    client = ServeClient(_model(decode=True, dtype=dtype), params,
                         num_slots=3, prefill_len=16, prefill_batch=2)
    out = client.serve_trace([(0, r) for r in REQUESTS])
    assert len(out) == len(REQUESTS)
    for rid, r in enumerate(REQUESTS):
        assert len(out[rid].tokens) == r["max_new_tokens"]
        assert _gap(ref, r["prompt"], out[rid].tokens) < tol, rid


def test_reused_slot_shows_nothing_of_its_predecessor(params, ref):
    """(c) one slot: a long request, then a shorter one in the same slot
    (state, ring and K/V all injected anew) — the second's tokens are
    those of an engine that never saw the first."""
    model = _model(decode=True)
    kw = dict(num_slots=1, prefill_len=16, prefill_batch=1)
    long_, short = REQUESTS[0], REQUESTS[1]
    both = ServeClient(model, params, **kw).serve_trace(
        [(0, long_), (0, short)])
    alone = ServeClient(model, params, **kw).serve_trace([(0, short)])
    assert both[1].tokens == alone[0].tokens
    assert _gap(ref, short["prompt"], both[1].tokens) < F32_TOL


@pytest.mark.parametrize("kw", [
    dict(page_size=8), dict(page_size=8, page_native=True),
    dict(kv_dtype="int8"), dict(page_size=8, prefill_chunk=8),
    dict(page_size=8, prefill_chunk=8, prefix_cache=True),
    dict(draft="self"), dict(max_resident_adapters=2, lora_rank=2)],
    ids=["pages", "page_native", "int8_kv", "chunked_prefill",
         "prefix_cache", "speculative", "lora_bank"])
def test_engine_refuses_what_it_cannot_carry(params, kw):
    """(d) loudly, at construction, naming the option."""
    model = _model(decode=True)
    if kw.pop("draft", None):
        kw.update(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match="recurrent state"):
        ServeEngine(model, params, num_slots=2, prefill_len=16, **kw)


def test_engine_option_the_family_lacks_refuses_by_name(params):
    """``SambaYConfig`` carries none of the options the engine reads with
    defaults (``attention_kernel``, ``matmul_kernel``, ``scan_layers``,
    ``lora``): the defaults run, anything else is refused by name."""
    model = _model(decode=True)
    engine = ServeEngine(model, params, num_slots=2, prefill_len=16,
                         attention_kernel="xla", matmul_kernel="xla")
    assert (engine.attention_kernel, engine.matmul_kernel) == ("xla", "xla")
    with pytest.raises(ValueError, match="has no matmul_kernel option"):
        ServeEngine(model, params, num_slots=2, prefill_len=16,
                    matmul_kernel="pallas", weight_dtype="int8")


def test_crash_replay_rebuilds_the_recurrent_state(params):
    """(e) two dispatch crashes mid-generation: each request is
    re-prefilled from prompt + emitted tokens (inside ``max_replay_len``
    = ``prefill_len``), which rebuilds state, ring and K/V; greedy and
    sampled tokens are the fault-free run's."""
    model = _model(decode=True)
    trace = [(0, dict(prompt=_tokens(30, 9), max_new_tokens=12)),
             (0, dict(prompt=_tokens(31, 4), max_new_tokens=10,
                      temperature=0.6, seed=5)),
             (2, dict(prompt=_tokens(32, 11), max_new_tokens=8))]
    kw = dict(num_slots=2, prefill_len=24, prefill_batch=2)
    base = ServeClient(model, params, **kw).serve_trace(trace)
    plan = FaultPlan.at("serve.dispatch", [4, 9])
    client = ServeClient(model, params, **kw, retry_policy=RetryPolicy(
        max_attempts=3, base_delay=0.0))
    assert client.engine.max_replay_len == 24
    with plan.armed():
        out = client.serve_trace(trace)
    assert plan.fired == 2 and client.engine.rebuilds >= 2
    for rid in base:
        assert out[rid].tokens == base[rid].tokens, rid


# ------------------------------------------- the cache's own declaration
def _declared(model, batch=2):
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        positions=jnp.zeros((batch, 1), jnp.int32))["cache"])
    leaves = jax.tree_util.tree_leaves
    return list(zip(leaves(cache), leaves(cache_layout(model, cache))))


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scanned"])
def test_gpt2_leaves_classify_as_the_rank_test_did(scan_layers):
    """The rule the declaration replaced was ``leaf.ndim >= 4``: GPT-2's
    K/V on the batch axis, ``cache_index`` kept by the pool."""
    from ray_lightning_tpu.models import TransformerLM, gpt2_config
    model = TransformerLM(gpt2_config(
        "nano", decode=True, vocab_size=128, max_seq_len=32,
        scan_layers=scan_layers))
    pairs = _declared(model)
    assert len(pairs) >= 3
    for leaf, decl in pairs:
        assert decl.per_slot == (leaf.ndim >= 4)
        if decl.per_slot:
            axis = 1 if scan_layers else 0
            assert decl == CacheLeaf(axis, "global", seq_axis=axis + 1)
            assert leaf.shape[axis] == 2 and leaf.shape[axis + 1] == 32


def test_sambay_declares_three_kinds_of_state():
    """Rank-3 leaves (the scan state, the conv tail) belong to a slot:
    the rank test would have left them behind."""
    pairs = _declared(_model(decode=True), batch=3)
    by_kind = {}
    for leaf, decl in pairs:
        assert decl.per_slot and leaf.shape[decl.slot_axis] == 3
        by_kind.setdefault(decl.kind, []).append(leaf)
    assert sorted(len(x.shape) for x in by_kind["recurrent"]) == [3] * 6
    assert {x.shape[1] for x in by_kind["window"]} == {8}
    assert {x.shape[1] for x in by_kind["global"]} == {POSITIONS}
    assert len(by_kind["window"]) == 4 and len(by_kind["global"]) == 2
    assert all(x.dtype == jnp.float32 for x in by_kind["recurrent"])


def test_step_spans_carry_live_cache_bytes_by_kind(params):
    """The counters on ``engine.step.call`` / ``engine.prefill.call``:
    bytes from shapes and the synced frontier. Per active row: the
    recurrent state and the rings whole, the full K/V up to the
    context."""
    from ray_lightning_tpu.obs import Telemetry
    tel = Telemetry()
    client = ServeClient(_model(decode=True), params, num_slots=2,
                         prefill_len=16, telemetry=tel)
    client.serve_trace([(0, dict(prompt=_tokens(40, 12),
                                 max_new_tokens=6))])
    d, di, N, K, Hkv, D = 64, 128, 16, 4, 4, 8
    recurrent = 3 * (N * di + (K - 1) * di) * 4
    per_pos = 2 * Hkv * D * 4               # K and V, float32 here
    calls = [s for s in tel.spans.spans() if s.name == "engine.step.call"]
    assert calls and all(s.args["active"] == 1 for s in calls)
    first = calls[0].args                   # context 13: prompt + 1 token
    assert first["recurrent_bytes"] == recurrent
    assert first["window_bytes"] == 2 * per_pos * 8
    assert first["global_bytes"] == per_pos * 13
    pf = next(s for s in tel.spans.spans()
              if s.name == "engine.prefill.call").args
    assert (pf["recurrent_bytes"], pf["window_bytes"],
            pf["global_bytes"]) == (recurrent, 2 * per_pos * 8,
                                    per_pos * 12)
