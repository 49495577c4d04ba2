"""Speculative decoding + int8 KV quantization.

The load-bearing assertions:

- **Greedy token identity**: a spec engine (draft proposals, widened
  verify, accept/rollback) emits EXACTLY the non-spec engine's tokens —
  across k ∈ {2, 4}, dense and paged storage, mid-decode crash replay
  (``serve.verify`` faults through the supervisor), chunked/prefix
  engines, and a replica-fleet failover. The accept rule guarantees it
  by construction (every committed token is the target's own
  greedy/argmax token at its step); these tests pin the construction.
- **Sampled replay-exactness**: every random draw in the
  rejection-resampling rule derives from the request's existing
  ``fold_in(fold_in(base, seed), step)`` stream, so a sampled stream is
  a pure function of (engine seed, request seed, step, context) —
  identical across runs and across crash replays.
- **int8 KV**: quantize→dequantize round-trip error is bounded by half
  a quantization step per per-page-per-head group, the arena admits
  ~2x the requests at equal bytes, and greedy outputs are identical to
  bf16-storage engines on the pinned configs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import TransformerLM
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.reliability import FaultPlan, FaultSpec, RetryPolicy
from ray_lightning_tpu.serve import (FINISH_EOS, FINISH_LENGTH,
                                     PagePool, ReplicaFleet, Request,
                                     ServeClient, ServeEngine)
from ray_lightning_tpu.serve.pages import (kv_dequantize, kv_quantize,
                                           kv_scales)
from ray_lightning_tpu.serve.spec import SpecDecoder

pytestmark = [pytest.mark.serve, pytest.mark.spec]


@pytest.fixture(scope="module")
def nano(serve_nano_family):
    """Target (gpt2-nano) + a 1-layer draft sharing vocab/max_seq_len
    — the shared serve-family pair (conftest)."""
    return serve_nano_family


PROMPTS = [[5, 17, 3, 9], [9, 2, 44], [42, 7], [1]]


def _trace(n=6, temp=0.0, **kw):
    return [
        (0, dict(prompt=PROMPTS[0], max_new_tokens=n, temperature=temp,
                 **kw)),
        (0, dict(prompt=PROMPTS[1], max_new_tokens=n, temperature=temp,
                 **kw)),
        (3, dict(prompt=PROMPTS[2], max_new_tokens=n, temperature=temp,
                 **kw)),
        (5, dict(prompt=PROMPTS[3], max_new_tokens=n, temperature=temp,
                 **kw)),
    ]


def _run(dec, params, trace, **kw):
    client = ServeClient(dec, params, num_slots=3, prefill_len=8, **kw)
    out = client.serve_trace(list(trace))
    client.shutdown()
    return out


# --------------------------------------------------------------------- #
# greedy token identity
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_spec_greedy_token_identity(nano, k, paged):
    """The acceptance pin: spec engines emit the non-spec engine's exact
    greedy tokens — staggered arrivals, slot reuse, mid-round retires —
    for k in {2, 4} on both storage layouts."""
    dec, params, draft, dparams = nano
    ref = _run(dec, params, _trace())
    kw = dict(draft_model=draft, draft_params=dparams, spec_k=k)
    if paged:
        kw["page_size"] = 4
    out = _run(dec, params, _trace(), **kw)
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, \
            (rid, out[rid].tokens, ref[rid].tokens)
        assert out[rid].finish_reason == FINISH_LENGTH


def test_spec_eos_and_budget_mid_round(nano):
    """Commits are cut at the first eos INSIDE a round (FINISH_EOS, eos
    kept) and clamped by a budget smaller than a whole round's k+1
    tokens (FINISH_LENGTH at exactly max_new_tokens)."""
    dec, params, draft, dparams = nano
    free = _run(dec, params, _trace(n=8))
    eos = free[0].tokens[3]
    # the budget-2 request arrives LAST so request ids match trace order
    trace = _trace(n=8, eos_id=eos) + [
        (6, dict(prompt=[33, 4], max_new_tokens=2))]  # budget < k+1
    ref = _run(dec, params, trace)
    out = _run(dec, params, trace, draft_model=draft,
               draft_params=dparams, spec_k=4)
    for rid in range(5):
        assert out[rid].tokens == ref[rid].tokens, rid
        assert out[rid].finish_reason == ref[rid].finish_reason
    assert out[0].tokens[-1] == eos and out[0].finish_reason == FINISH_EOS
    assert len(out[4].tokens) == 2
    assert out[4].finish_reason == FINISH_LENGTH


def test_spec_rounds_per_dispatch(nano):
    """steps_per_dispatch scans spec ROUNDS: same greedy tokens, and the
    accounting counts rounds (target passes), draft steps, and per-slot
    refills (one per activation, not per dispatch)."""
    dec, params, draft, dparams = nano
    ref = _run(dec, params, _trace())
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         steps_per_dispatch=3, draft_model=draft,
                         draft_params=dparams, spec_k=2)
    out = client.serve_trace(_trace())
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
    eng = client.engine
    assert eng.spec_rounds == eng.steps * 3
    assert eng.spec_draft_steps == eng.spec_rounds * 3          # k+1
    assert eng.decode_substeps == eng.spec_rounds
    assert eng.spec_accepted_tokens + eng.spec_rejected_tokens > 0
    assert eng.spec.refills == 4   # one activation per request
    client.shutdown()


def test_spec_full_acceptance_with_identical_draft(nano):
    """A draft that equals the target accepts every proposal: zero
    rejections, k+1 tokens per active round — the dispatch-amortization
    ceiling the bench measures — and still exact greedy identity."""
    dec, params, draft, dparams = nano
    ref = _run(dec, params, _trace())
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         draft_model=dec, draft_params=params, spec_k=2)
    out = client.serve_trace(_trace())
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
    assert client.engine.spec_rejected_tokens == 0
    assert client.engine.spec_accepted_tokens > 0
    client.shutdown()


def test_spec_chunked_prefix_compose(nano):
    """Spec composes with chunked prefill + prefix cache: long prompts
    stream in chunks, adopters reuse published pages, and the draft
    refill rebuilds from the full host-side context either way."""
    dec, params, draft, dparams = nano
    rng = np.random.default_rng(3)
    shared = [int(t) for t in rng.integers(0, 128, size=12)]
    trace = [
        (0, dict(prompt=shared + [1, 2], max_new_tokens=5)),
        # arrives after the first prompt finished prefilling AND
        # publishing its pages, so the adoption actually fires
        (16, dict(prompt=shared + [7, 8], max_new_tokens=5)),
        (17, dict(prompt=[9, 2, 44], max_new_tokens=5)),
    ]
    kw = dict(num_slots=3, prefill_len=8, page_size=4, prefill_chunk=4,
              prefix_cache=True)
    ref_c = ServeClient(dec, params, **kw)
    ref = ref_c.serve_trace(trace)
    ref_c.shutdown()
    client = ServeClient(dec, params, draft_model=draft,
                         draft_params=dparams, spec_k=2, **kw)
    out = client.serve_trace(trace)
    for rid in range(3):
        assert out[rid].tokens == ref[rid].tokens, rid
    assert out[1].prefix_hit_tokens > 0
    client.shutdown()


# --------------------------------------------------------------------- #
# crash replay / faults
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_spec_verify_crash_replay_token_identity(nano, paged):
    """A serve.verify crash mid-decode enters the supervisor's
    rebuild-and-replay path; greedy outputs stay token-identical (the
    replay re-feeds prompt + emitted, the fresh engine's draft refills
    from the same context, and every later commit is still the target's
    own token)."""
    dec, params, draft, dparams = nano
    kw = dict(draft_model=draft, draft_params=dparams, spec_k=2)
    if paged:
        kw["page_size"] = 4
    ref = _run(dec, params, _trace(n=8), **kw)
    plan = FaultPlan.at("serve.verify", [2])
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         retry_policy=RetryPolicy(max_attempts=3,
                                                  base_delay=0.0), **kw)
    with plan.armed():
        out = client.serve_trace(_trace(n=8))
    assert plan.fired == 1
    assert client.engine.rebuilds == 1
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
        assert out[rid].finish_reason == FINISH_LENGTH
    client.shutdown()


def test_spec_verify_stall_mode(nano):
    """serve.verify stall: the dispatch sleeps (injectable clock — the
    plan's sleep is stubbed) and the stream continues unharmed."""
    dec, params, draft, dparams = nano
    slept = []
    plan = FaultPlan([FaultSpec("serve.verify", 1, mode="stall",
                                stall_s=5.0)], sleep=slept.append)
    ref = _run(dec, params, _trace(), draft_model=draft,
               draft_params=dparams, spec_k=2)
    with plan.armed():
        out = _run(dec, params, _trace(), draft_model=draft,
                   draft_params=dparams, spec_k=2)
    assert plan.fired == 1 and slept == [5.0]
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid


def test_spec_sampled_replay_exact(nano):
    """Sampled streams (temperature/top_k mixes) are identical across
    runs AND across a serve.verify crash replay — every draw in the
    rejection-resampling rule keys off (seed, step)."""
    dec, params, draft, dparams = nano
    trace = [
        (0, dict(prompt=PROMPTS[0], max_new_tokens=8, temperature=0.9,
                 top_k=20, seed=11)),
        (1, dict(prompt=PROMPTS[1], max_new_tokens=8, temperature=0.7,
                 seed=23)),
        (2, dict(prompt=PROMPTS[2], max_new_tokens=8)),  # greedy row
    ]
    kw = dict(draft_model=draft, draft_params=dparams, spec_k=2)
    one = _run(dec, params, trace, **kw)
    two = _run(dec, params, trace, **kw)
    for rid in range(3):
        assert one[rid].tokens == two[rid].tokens, rid
    plan = FaultPlan.at("serve.verify", [2])
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         retry_policy=RetryPolicy(max_attempts=3,
                                                  base_delay=0.0), **kw)
    with plan.armed():
        faulted = client.serve_trace(list(trace))
    assert plan.fired == 1
    for rid in range(3):
        assert faulted[rid].tokens == one[rid].tokens, rid
    client.shutdown()


def test_spec_fleet_failover_token_identity(nano):
    """The fleet seat: a 3-replica fleet of SPEC engines with a replica
    killed mid-decode retires every request token-identical to the
    non-spec single-engine run (failover re-admits via replay; the
    promoted replica's draft refills from the replayed context)."""
    dec, params, draft, dparams = nano
    trace = _trace(n=6)
    ref = _run(dec, params, trace)
    # num_slots/prefill_len match the module's other engines, so every
    # replica's programs come straight from the jit cache
    fleet = ReplicaFleet(dec, params, num_replicas=3, num_standby=1,
                         num_slots=3, prefill_len=8,
                         draft_model=draft, draft_params=dparams,
                         spec_k=2)
    plan = FaultPlan.at("serve.replica", [6])  # mid-decode
    with plan.armed():
        out = fleet.serve_trace(trace)
    assert plan.fired == 1 and fleet.failovers == 1
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
        assert out[rid].finish_reason == FINISH_LENGTH
    fleet.shutdown()


def test_spec_cancel_before_dispatch_discards_stale(nano):
    """A deadline cancel between activation and the next spec dispatch
    drops the slot from the refill ledger — the released slot is never
    refilled, and the surviving rows keep exact greedy identity."""
    dec, params, draft, dparams = nano
    ref = _run(dec, params, [(0, dict(prompt=PROMPTS[1],
                                      max_new_tokens=6))])
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         draft_model=draft, draft_params=dparams,
                         spec_k=2)
    client.submit(PROMPTS[0], max_new_tokens=6, deadline=1)
    client.submit(PROMPTS[1], max_new_tokens=6)
    out = client.run_until_idle()
    assert out[0].finish_reason == "timeout"
    assert len(out[0].tokens) == 1        # the prefill token survived
    assert out[1].tokens == ref[0].tokens
    assert client.engine.spec.refills == 1   # only the survivor
    client.shutdown()


# --------------------------------------------------------------------- #
# validation
# --------------------------------------------------------------------- #
def test_spec_validate_headroom_and_config(nano):
    dec, params, draft, dparams = nano
    # num_slots/prefill_len match the module's other engines (programs
    # come from the jit cache — this test is about validation)
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         draft_model=draft, draft_params=dparams,
                         spec_k=4)
    # prompt + budget fills max_seq_len exactly: fine non-spec, but the
    # verify block needs k-1 positions of headroom past it
    with pytest.raises(ValueError, match="headroom"):
        client.submit([1, 2, 3, 4], max_new_tokens=28)
    client.submit([1, 2, 3, 4], max_new_tokens=25)  # 4+25+3 == 32
    client.run_until_idle()
    client.shutdown()
    with pytest.raises(ValueError, match="draft_model"):
        ServeEngine(dec, params, prefill_len=8, spec_k=2)
    with pytest.raises(ValueError, match="draft_params"):
        ServeEngine(dec, params, prefill_len=8, draft_model=draft)
    bad_vocab = TransformerLM(dataclasses.replace(draft.cfg,
                                                  vocab_size=64))
    with pytest.raises(ValueError, match="vocab_size"):
        SpecDecoder(bad_vocab, dparams, num_slots=2, k=2,
                    target_cfg=dec.cfg)
    bad_len = TransformerLM(dataclasses.replace(draft.cfg,
                                                max_seq_len=16))
    with pytest.raises(ValueError, match="max_seq_len"):
        SpecDecoder(bad_len, dparams, num_slots=2, k=2,
                    target_cfg=dec.cfg)


# --------------------------------------------------------------------- #
# observability
# --------------------------------------------------------------------- #
def test_spec_obs_surfaces_pinned(nano):
    """engine.spec_round events + the accept-rate histogram and
    accepted/rejected counters, armed; a disarmed run emits nothing onto
    a fresh handle (allocation-free contract)."""
    dec, params, draft, dparams = nano
    tel = Telemetry()
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         telemetry=tel, draft_model=draft,
                         draft_params=dparams, spec_k=2)
    client.serve_trace(_trace())
    events = tel.events("engine.spec_round")
    assert events, "spec dispatches must land engine.spec_round events"
    for e in events:
        assert set(e.payload) == {"dispatch", "rounds", "judged",
                                  "accepted", "committed", "retired"}
    snap = tel.metrics.snapshot()
    total = (snap["serve_spec_accepted_tokens_total"]
             + snap["serve_spec_rejected_tokens_total"])
    assert total == sum(e.payload["judged"] for e in events)
    assert snap["serve_spec_accept_rate"]["count"] == len(
        [e for e in events if e.payload["judged"]])
    client.shutdown()

    # disarmed zero-surface: same workload, no handle anywhere — then a
    # fresh handle must stay empty (nothing leaked onto a global)
    fresh = Telemetry()
    _run(dec, params, _trace(), draft_model=draft, draft_params=dparams,
         spec_k=2)
    assert not fresh.events()
    # the only series on a fresh handle are the two pre-registered drop
    # counters (event ring, PR 19; span recorder, PR 26), still at zero
    assert fresh.metrics.snapshot() == {
        "obs_events_dropped_total": 0.0, "obs_spans_dropped_total": 0.0}


# --------------------------------------------------------------------- #
# int8 KV quantization
# --------------------------------------------------------------------- #
def test_int8_roundtrip_tolerance_on_kv_leaves(nano):
    """Quantize→dequantize on REAL transformer KV (a prefilled cache):
    elementwise error is bounded by half a quantization step of its
    per-group absmax scale — the bound the identity tests lean on."""
    from ray_lightning_tpu.models.generate import prefill
    dec, params, _draft, _dparams = nano
    toks = np.asarray(
        np.random.default_rng(0).integers(0, 128, size=(2, 16)), np.int32)
    cache, _ = prefill(dec, params, jnp.asarray(toks))
    checked = 0
    for leaf in jax.tree_util.tree_leaves(cache):
        if leaf.ndim < 4:
            continue
        # per-page-per-head grouping at page_size=8 over the seq axis:
        # (B, L, H, D) -> (B*L/8, 8, H, D), reduce (1, 3)
        B, L, H, D = leaf.shape
        pages = jnp.reshape(leaf, (B * L // 8, 8, H, D))
        s = kv_scales(pages, (1, 3))
        q = kv_quantize(pages, s)
        deq = kv_dequantize(q, s, jnp.float32)
        err = jnp.abs(deq - pages.astype(jnp.float32))
        assert float(jnp.max(err - s / 2)) <= 1e-6
        # scale saturates at the group absmax: codes hit exactly ±127
        assert int(jnp.max(jnp.abs(q))) == 127
        # idempotent round-trip: re-quantizing the dequantized values
        # reproduces codes and scales bit-for-bit (parked rows freeze)
        s2 = kv_scales(deq, (1, 3))
        assert jnp.array_equal(kv_quantize(deq, s2), q)
        assert jnp.allclose(s2, s)
        checked += 1
    assert checked >= 2 * dec.cfg.n_layers


def test_int8_capacity_near_2x_at_equal_arena_bytes(nano):
    """The capacity pin (mirrors PR 7's paged-capacity test): at an
    EQUAL at-rest byte budget, the int8 arena holds ~2x the pages
    (codes are half of f32/bf16 minus the per-page-per-head scale tax)
    and admits >= 1.8x the concurrent requests on the pinned mix."""
    dec, params, _draft, _dparams = nano

    def admissions(kv_dtype, budget_bytes):
        probe = PagePool(dec, num_slots=1, page_size=4, num_pages=1,
                         kv_dtype=kv_dtype)
        num_pages = budget_bytes // probe.bytes_per_page
        pool = PagePool(dec, num_slots=256, page_size=4,
                        num_pages=int(num_pages), kv_dtype=kv_dtype)
        rng = np.random.default_rng(1)
        n = 0
        from ray_lightning_tpu.serve.engine import SlotPoolFull
        for i in range(256):
            L = int(rng.integers(4, 13))
            budget = int(rng.integers(4, 17))
            try:
                pool.acquire(Request(id=i, prompt=[1] * L,
                                     max_new_tokens=budget, seed=i))
            except SlotPoolFull:
                break
            n += 1
        return n, pool.num_pages

    base = PagePool(dec, num_slots=1, page_size=4, num_pages=1)
    budget = 64 * base.bytes_per_page   # 64 bf16/f32-sized pages
    plain_n, plain_pages = admissions(None, budget)
    int8_n, int8_pages = admissions("int8", budget)
    assert int8_pages >= 2 * plain_pages * 0.9
    assert int8_n >= 1.8 * plain_n, (int8_n, plain_n)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_int8_greedy_token_identity(nano, paged):
    """bf16/f32-compute + int8-storage greedy outputs are identical to
    full-precision storage on the pinned trace (quantization noise stays
    under the argmax margins here; the bench enforces the same at
    gpt2-small/bf16)."""
    dec, params, _draft, _dparams = nano
    ref = _run(dec, params, _trace())
    kw = dict(kv_dtype="int8")
    if paged:
        kw["page_size"] = 4
    out = _run(dec, params, _trace(), **kw)
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, \
            (rid, out[rid].tokens, ref[rid].tokens)


def test_int8_spec_composed_identity(nano):
    """int8 storage + speculative decoding + paged arena together still
    match the plain engine token-for-token (greedy)."""
    dec, params, draft, dparams = nano
    ref = _run(dec, params, _trace())
    out = _run(dec, params, _trace(), kv_dtype="int8", page_size=4,
               draft_model=draft, draft_params=dparams, spec_k=2)
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid


def test_int8_crash_replay_identity(nano):
    """Rebuild-and-replay over int8 storage: replay prefill re-feeds
    through the quantized arena and greedy outputs still match the
    uninterrupted int8 run."""
    dec, params, _draft, _dparams = nano
    ref = _run(dec, params, _trace(), kv_dtype="int8", page_size=4)
    plan = FaultPlan.at("serve.dispatch", [4])
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         kv_dtype="int8", page_size=4,
                         retry_policy=RetryPolicy(max_attempts=3,
                                                  base_delay=0.0))
    with plan.armed():
        out = client.serve_trace(_trace())
    assert plan.fired == 1
    for rid in range(4):
        assert out[rid].tokens == ref[rid].tokens, rid
    client.shutdown()


def test_kv_dtype_validation(nano):
    dec, params, _draft, _dparams = nano
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeEngine(dec, params, prefill_len=8, kv_dtype="fp8")
