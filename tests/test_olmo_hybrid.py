"""Olmo-Hybrid (``models/olmo_hybrid.py``, ``ops/gated_delta.py``) against
the plain float32 reference (``benchmark/olmo_hybrid_reference.py``: the
token-by-token recurrence, importing nothing of the program), at a small
size on the CPU: hidden 64, 8 layers (two periods of three Gated-DeltaNet
layers and one full-attention layer), 4 heads of 8 x 16 state, vocabulary
256. Weights are the benchmark's seeded ones, drawn at
``initializer_range`` 1 / sqrt(64) so that activations are O(1) at this
width.

Tolerances, and why:

- ``F32_TOL`` 5e-4 on logits of standard deviation ~1, program with
  float32 matmul operands: program and reference then differ by float32
  summation order and by the chunkwise form's algebra (a triangular
  solve a block in place of 64 rank-one updates) — 6e-5 here over 200
  positions. A delta state held in bfloat16 between blocks reads 0.7 —
  a thousand times over; ``test_bf16_state_fails`` holds that.
- ``BF16_TOL`` 2.0 with bfloat16 operands as the configuration runs them
  (reads ~0.7: 2^-8 relative on every matmul operand through 8 layers
  whose inputs are not normed): it cannot tell the state's dtype, which
  is why the float32 comparison exists.
- ``OP_TOL`` 2e-5 for the op alone against the recurrence, on states and
  outputs of size ~5: float32 rounding of a 64-position block.
- served tokens are held to the reference by the *gap*: at each served
  position, how far the reference's logit of the served token lies below
  the reference's best (``F32_TOL`` with float32 operands), and to
  ``generate()`` token for token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import olmo_hybrid_reference, olmo_hybrid_weights
from benchmark.families import olmo_hybrid as family
from ray_lightning_tpu.models import olmo_hybrid as program
from ray_lightning_tpu.models.generate import (_prefill_impl, cache_layout,
                                               decode_step, generate)
from ray_lightning_tpu.models.olmo_hybrid import (FULL, LINEAR,
                                                  OlmoHybridConfig,
                                                  OlmoHybridLM)
from ray_lightning_tpu.ops import gated_delta
from ray_lightning_tpu.ops.gated_delta import (gated_delta_chunk,
                                               gated_delta_step)
from ray_lightning_tpu.serve import ServeClient, ServeEngine

pytestmark = pytest.mark.serve

SHAPE = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=8, num_attention_heads=4,
             num_key_value_heads=4, rms_norm_eps=1e-6,
             tie_word_embeddings=False, max_position_embeddings=65536,
             layer_types=[LINEAR, LINEAR, LINEAR, FULL] * 2,
             linear_num_key_heads=4, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=16,
             linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
             initializer_range=0.125)
POSITIONS = 256
F32_TOL = 5e-4
BF16_TOL = 2.0
OP_TOL = 2e-5


@pytest.fixture(scope="module")
def canon():
    return olmo_hybrid_weights.make_canonical(
        olmo_hybrid_weights.seed_key(3), SHAPE)


@pytest.fixture(scope="module")
def params(canon):
    return family.program_tree(canon, SHAPE)


@pytest.fixture(scope="module")
def ref(canon):
    fn = olmo_hybrid_reference.make_logits_fn(SHAPE, pad_multiple=64)
    return lambda tokens, rows: np.asarray(fn(canon, tokens, rows))


def _model(**kw):
    kw.setdefault("dtype", jnp.float32)
    return OlmoHybridLM(family.config(SHAPE, POSITIONS, **kw))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _full_forward_error(params, ref, n=200, **kw):
    toks = np.stack([_tokens(i, n) for i in range(2)])
    got = np.asarray(_model(**kw).apply({"params": params},
                                        jnp.asarray(toks)))
    want = np.stack([ref(t, np.arange(n)) for t in toks])
    return float(np.abs(got - want).max())


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
def test_full_forward_matches_reference(params, ref, dtype, tol):
    """Every position's logits over 200 positions (four blocks of the
    chunkwise rule, the last one ragged)."""
    assert _full_forward_error(params, ref, dtype=dtype) < tol


def test_bf16_state_fails(params, ref):
    """The float32 comparison is tight enough to see the state's dtype."""
    err = _full_forward_error(params, ref, state_dtype=jnp.bfloat16)
    assert err > 50 * F32_TOL, err


def test_bf16_block_solve_fails(params, ref, monkeypatch):
    """... and the precision of the in-block algebra: the products and
    the solve at one bfloat16 pass (a TPU's default for float32)."""
    monkeypatch.setattr(gated_delta, "_HI", jax.lax.Precision.DEFAULT)
    real = gated_delta.unit_lower_inverse
    low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    monkeypatch.setattr(gated_delta, "unit_lower_inverse",
                        lambda a: low(real(low(a))))
    err = _full_forward_error(params, ref)
    assert err > 50 * F32_TOL, err


def test_layout_matches_the_issue():
    cfg = OlmoHybridConfig()
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 8
    assert (cfg.head_dim, cfg.key_width, cfg.value_width,
            cfg.conv_width) == (128, 2880, 5760, 11520)
    with pytest.raises(ValueError, match="names every layer"):
        OlmoHybridConfig(num_hidden_layers=16)
    model = _model(decode=True)
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32))["cache"])
    leaves = jax.tree_util.tree_leaves
    by_kind = {}
    for leaf, decl in zip(leaves(cache),
                          leaves(cache_layout(model, cache))):
        assert decl.per_slot and leaf.shape[decl.slot_axis] == 3
        by_kind.setdefault(decl.kind, []).append(leaf)
    assert sorted(x.shape for x in by_kind["recurrent"]) == sorted(
        [(3, 4, 16, 8)] * 6 + [(3, 3, 2 * 32 + 64)] * 6)
    assert all(x.dtype == jnp.float32 for x in by_kind["recurrent"])
    assert [x.shape for x in by_kind["global"]] == [
        (3, POSITIONS, 4, 16)] * 4
    assert model.recurrent_state and model.continues_prefill


# ---------------------------------------------------------------- the op
def _op_inputs(B=2, T=150, H=3, dk=8, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    return dict(
        q=unit(jax.random.normal(ks[0], (B, T, H, dk))),
        k=unit(jax.random.normal(ks[1], (B, T, H, dk))),
        v=jax.random.normal(ks[2], (B, T, H, dv)),
        log_alpha=-0.1 * jax.random.uniform(ks[3], (B, T, H)),
        beta=2.0 * jax.random.uniform(ks[4], (B, T, H)),   # up to 2
        state=jax.random.normal(ks[5], (B, H, dv, dk)))     # not zero


def _recurrence(x, lengths):
    """The op's own one-position form, position by position."""
    state, outs = x["state"], []
    for t in range(x["q"].shape[1]):
        o, new = gated_delta_step(x["q"][:, t], x["k"][:, t], x["v"][:, t],
                                  x["log_alpha"][:, t], x["beta"][:, t],
                                  state)
        keep = (t < lengths)[:, None, None, None]
        state = jnp.where(keep, new, state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("lengths", [(150, 150), (150, 77), (64, 1)],
                         ids=["whole", "ragged", "block_and_one"])
def test_chunk_rule_matches_the_recurrence(lengths):
    """150 positions are two blocks and a ragged third; a row's state
    stops at its length, a non-zero initial state is carried."""
    x = _op_inputs()
    lengths = jnp.asarray(lengths)
    o, state = jax.jit(gated_delta_chunk)(**x, lengths=lengths)
    want_o, want_state = _recurrence(x, lengths)
    assert float(jnp.abs(state - want_state).max()) < OP_TOL
    for b, n in enumerate(lengths):
        assert float(jnp.abs(o[b, :n] - want_o[b, :n]).max()) < OP_TOL
    assert bool(jnp.isfinite(o).all())


def test_unit_lower_inverse_is_the_inverse():
    """Entries up to 2 (``beta`` 2, keys that repeat): the inverse's own
    entries reach hundreds and the product is still the identity."""
    a = jnp.tril(2.0 * jax.random.uniform(jax.random.PRNGKey(3),
                                          (5, 64, 64)) - 0.5, -1) * 0.5
    inv = gated_delta.unit_lower_inverse(a)
    eye = jnp.eye(64)
    err = jnp.abs(jnp.einsum("bij,bjk->bik", a + eye, inv,
                             precision="highest") - eye).max()
    assert float(err) < 1e-3 * float(jnp.abs(inv).max()), (err, inv.max())
    assert bool((jnp.triu(inv, 1) == 0).all())


def test_chunk_rule_of_no_valid_position_keeps_the_state():
    x = _op_inputs(T=70)
    _, state = gated_delta_chunk(**x, lengths=jnp.array([0, 0]))
    np.testing.assert_array_equal(np.asarray(state), np.asarray(x["state"]))


# ------------------------------------------------- prefill, continue, step
def test_prefill_then_decode_logits_match_reference(params, ref):
    """One padded prefill batch of rows of unequal length — one past a
    block of the rule, one a single token — then 12 decode steps at
    per-row positions; the logits of every step against the reference's
    full forward."""
    model = _model(decode=True)
    lengths = np.array([70, 5, 1, 96], np.int32)
    P, steps = 96, 12
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


def _feed(model, params, toks, lengths, piece):
    """The prompt rows fed in pieces of ``piece`` tokens through the
    continue mode, from a zero cache; the logits of each row's last
    token and the cache."""
    B, total = toks.shape
    cache = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((B, 1), jnp.int32))["cache"]
    last = [None] * B
    apply = jax.jit(lambda cache, tokens, n, off: model.apply(
        {"params": params, "cache": cache}, tokens, lengths=n, offset=off,
        mutable=["cache"]))
    for off in range(0, total, piece):
        part = np.zeros((B, piece), np.int32)
        width = min(piece, total - off)
        part[:, :width] = toks[:, off:off + width]
        n = np.clip(lengths - off, 0, piece)
        out, updated = apply(cache, part, n, np.full((B,), off, np.int32))
        cache = updated["cache"]
        for b in range(B):
            if n[b] > 0:
                last[b] = np.asarray(out[b, 0])
    return np.stack(last), cache


@pytest.mark.parametrize("key_block", [512, 40], ids=["one_block",
                                                      "ragged_blocks"])
def test_pieces_give_the_state_and_logits_of_one_piece(params, ref,
                                                       monkeypatch,
                                                       key_block):
    """A prompt fed in one piece, in 48-token pieces and in 64-token
    pieces with a ragged last one leaves the same state, the same K/V
    and the same logits; with 40-key blocks the cached attention walks
    seven blocks, the last one overlapping its neighbour."""
    monkeypatch.setattr(program, "KEY_BLOCK", key_block)
    model = _model(decode=True)
    lengths = np.array([150, 101], np.int32)
    toks = np.stack([_tokens(50, 150), _tokens(51, 150)])
    toks[1, 101:] = 0
    whole_last, whole = _feed(model, params, toks, lengths, 150)
    for b, n in enumerate(lengths):
        want = ref(toks[b], np.array([n - 1]))[0]
        assert np.abs(whole_last[b] - want).max() < F32_TOL
    leaves = jax.tree_util.tree_leaves_with_path
    for piece in (48, 64):
        last, cache = _feed(model, params, toks, lengths, piece)
        assert np.abs(last - whole_last).max() < F32_TOL, piece
        for (path, got), (_, want) in zip(leaves(cache), leaves(whole)):
            got, want = np.asarray(got), np.asarray(want)
            if "cached" in str(path[-1]):     # live positions only
                for b, n in enumerate(lengths):
                    assert np.abs(got[b, :n] - want[b, :n]).max() < F32_TOL
            else:
                assert np.abs(got - want).max() < F32_TOL, (piece, path)


def test_inject_blocks_writes_the_piece_and_nothing_else():
    """A K/V leaf takes a row's new positions only, a recurrent leaf its
    row whole; an invalid row and every other slot keep what they had;
    ``take_rows`` hands the slots' rows out in order."""
    from ray_lightning_tpu.models.generate import CacheLeaf
    from ray_lightning_tpu.ops.cache_write import inject_blocks, take_rows
    layout = {"kv": CacheLeaf(0, "global", seq_axis=1),
              "state": CacheLeaf(0, "recurrent"), "index": CacheLeaf(None)}
    rng = np.random.default_rng(0)
    pool = {"kv": jnp.asarray(rng.normal(size=(5, 12, 2, 3)), jnp.float32),
            "state": jnp.asarray(rng.normal(size=(5, 4)), jnp.float32),
            "index": jnp.zeros((), jnp.int32)}
    slots, start = jnp.array([3, 1, 3]), jnp.array([4, 0, 8])
    valid = jnp.array([True, True, False])
    rows = take_rows(pool, layout, slots)
    assert rows["kv"].shape == (3, 12, 2, 3) and rows["index"].shape == ()
    np.testing.assert_array_equal(rows["state"], np.asarray(
        pool["state"])[[3, 1, 3]])
    new = {"kv": rows["kv"] + 100.0, "state": rows["state"] + 100.0,
           "index": rows["index"] + 7}
    out = jax.jit(lambda pool, new, slots, valid, start: inject_blocks(
        pool, new, layout, slots, valid, start, 4))(
            pool, new, slots, valid, start)
    want_kv, want_state = np.array(pool["kv"]), np.array(pool["state"])
    want_kv[3, 4:8] += 100.0
    want_kv[1, 0:4] += 100.0
    want_state[[3, 1]] += 100.0
    np.testing.assert_array_equal(out["kv"], want_kv)
    np.testing.assert_array_equal(out["state"], want_state)
    assert int(out["index"]) == 0


# ------------------------------------------------------------ the engine
REQUESTS = [dict(prompt=_tokens(20, 70), max_new_tokens=20),
            dict(prompt=_tokens(21, 9), max_new_tokens=9),
            dict(prompt=_tokens(22, 33), max_new_tokens=14),
            dict(prompt=_tokens(23, 120), max_new_tokens=12),
            dict(prompt=_tokens(24, 16), max_new_tokens=6),
            dict(prompt=_tokens(25, 97), max_new_tokens=10,
                 temperature=0.8, top_k=20, seed=7)]
ENGINE = dict(num_slots=3, prefill_len=16, prefill_batch=2,
              prefill_chunk=16)


def _gap(ref, prompt, tokens):
    seq = list(prompt) + list(tokens)
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    lg = ref(seq, rows)
    return float((lg.max(-1) - lg[np.arange(len(rows)), tokens]).max())


def test_chunked_dense_slot_tokens_equal_generate(params, ref):
    """Six ragged requests over three dense slots with ``prefill_chunk``
    16: prompts of 9 and 16 keep the batched prefill, the others stream
    in one or two rows a dispatch, each at its own offset, between decode steps
    of the rows already running (whose state must not move), into slots
    other requests left. Greedy tokens are ``generate()``'s and the
    reference's choice."""
    model = _model(decode=True)
    client = ServeClient(model, params, **ENGINE)
    out = client.serve_trace([(0, r) for r in REQUESTS])
    assert len(out) == len(REQUESTS)
    assert client.engine.chunk_dispatches >= 8
    for rid, r in enumerate(REQUESTS):
        assert len(out[rid].tokens) == r["max_new_tokens"]
        if r.get("temperature"):
            continue
        assert _gap(ref, r["prompt"], out[rid].tokens) < F32_TOL, rid
        alone = generate(model, params, jnp.asarray([r["prompt"]]),
                         max_new_tokens=r["max_new_tokens"],
                         rng=jax.random.PRNGKey(0), temperature=0.0,
                         # per-row positions: the steps this model has
                         prompt_lengths=jnp.asarray([len(r["prompt"])]))
        assert list(np.asarray(alone)[0, len(r["prompt"]):]) \
            == out[rid].tokens, rid


def test_chunk_and_decode_alternate(params):
    """While a row decodes, a chunk dispatch is followed by a step: an
    in-flight request stalls for one piece, not one prompt. The spans
    carry the counts the chunk metrics read."""
    from ray_lightning_tpu.obs import Telemetry
    tel = Telemetry()
    client = ServeClient(_model(decode=True), params, telemetry=tel,
                         **ENGINE)
    client.serve_trace([(0, REQUESTS[1]), (0, REQUESTS[3]),
                        (0, REQUESTS[0])])
    actions = [s.args.get("action") for s in tel.spans.spans()
               if s.name == "serve.tick"]
    assert "chunk" in actions and "step" in actions
    decoding = False
    for a, b in zip(actions, actions[1:]):
        decoding = decoding or a == "step"
        assert not (decoding and a == "chunk" and b == "chunk"), actions
    calls = [s.args for s in tel.spans.spans()
             if s.name == "engine.chunk.call"]
    # the program is compiled a row count: a lone prompt pays for one
    assert calls and all(c["program_tokens"] == c["rows"] * 16
                         for c in calls)
    assert {c["rows"] for c in calls} == {1, 2}
    assert sum(c["tokens"] for c in calls) == 120 + 70
    assert all(len(c["off"]) == c["rows"] for c in calls)
    steps = [s.args for s in tel.spans.spans()
             if s.name == "engine.step.call"]
    state = 6 * (4 * 16 * 8 + 3 * 128) * 4          # float32
    assert all(s["recurrent_bytes"] == state * s["active"] for s in steps)
    assert any(s.name == "engine.chunk.sync" for s in tel.spans.spans())


def test_first_chunk_dispatch_compiles_every_row_count(params):
    """One chunked request alone only ever feeds one row; the two-row
    program is compiled by the first dispatch all the same (over rows
    that feed nothing), so no row count is left to compile under load —
    and the warm-up writes nothing: the tokens are ``generate()``'s."""
    from ray_lightning_tpu.serve import engine as E
    model = OlmoHybridLM(family.config(SHAPE, 160, decode=True,
                                       dtype=jnp.float32))
    before = E._chunk_dense_plain._cache_size()
    client = ServeClient(model, params, **ENGINE)
    out = client.serve_trace([(0, REQUESTS[0])])
    assert E._chunk_dense_plain._cache_size() - before == 2
    r = REQUESTS[0]
    alone = generate(model, params, jnp.asarray([r["prompt"]]),
                     max_new_tokens=r["max_new_tokens"],
                     rng=jax.random.PRNGKey(0), temperature=0.0,
                     prompt_lengths=jnp.asarray([len(r["prompt"])]))
    assert list(np.asarray(alone)[0, len(r["prompt"]):]) == out[0].tokens


def test_reused_slot_starts_from_a_zero_state(params):
    """One slot: a long chunked request, then another chunked one in the
    same slot — the second's tokens are those of an engine that never
    saw the first (a piece at offset 0 starts from zeros)."""
    model = _model(decode=True)
    kw = dict(ENGINE, num_slots=1, prefill_batch=1)
    both = ServeClient(model, params, **kw).serve_trace(
        [(0, REQUESTS[3]), (0, REQUESTS[0])])
    alone = ServeClient(model, params, **kw).serve_trace([(0, REQUESTS[0])])
    assert both[1].tokens == alone[0].tokens


def test_crash_replay_follows_the_chunked_path(params):
    """``max_replay_len`` is the slot's length under ``prefill_chunk``:
    a crash mid-generation re-feeds prompt + emitted tokens in pieces."""
    from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
    model = _model(decode=True)
    trace = [(0, REQUESTS[0]), (0, REQUESTS[5]), (1, REQUESTS[2])]
    base = ServeClient(model, params, **ENGINE).serve_trace(trace)
    client = ServeClient(model, params, **ENGINE, retry_policy=RetryPolicy(
        max_attempts=3, base_delay=0.0))
    assert client.engine.max_replay_len == POSITIONS
    plan = FaultPlan.at("serve.dispatch", [9])
    with plan.armed():
        out = client.serve_trace(trace)
    assert plan.fired == 1 and client.engine.rebuilds >= 1
    for rid in base:
        assert out[rid].tokens == base[rid].tokens, rid


@pytest.mark.parametrize("kw", [
    dict(page_size=8), dict(page_size=8, prefill_chunk=16),
    dict(kv_dtype="int8"), dict(prefill_chunk=16, prefix_cache=True),
    dict(draft="self"), dict(max_resident_adapters=2, lora_rank=2)],
    ids=["pages", "paged_chunks", "int8_kv", "prefix_cache",
         "speculative", "lora_bank"])
def test_engine_still_refuses_the_rest(params, kw):
    """Only ``prefill_chunk`` on dense slots was lifted."""
    model = _model(decode=True)
    if kw.pop("draft", None):
        kw.update(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match="recurrent state|paged-KV"):
        ServeEngine(model, params, num_slots=2, prefill_len=16, **kw)


def test_chunk_must_divide_the_slot(params):
    with pytest.raises(ValueError, match="must divide max_seq_len"):
        ServeEngine(_model(decode=True), params, num_slots=2,
                    prefill_len=16, prefill_chunk=48)


def test_sambay_with_prefill_chunk_is_still_refused_by_name():
    """``SambaYLM`` declares recurrent state and no continue mode."""
    from benchmark import sambay_weights
    from benchmark.families import phi4flash
    from tests.test_sambay import SHAPE as SAMBAY
    from ray_lightning_tpu.models.sambay import SambaYLM
    model = SambaYLM(phi4flash.config(SAMBAY, 48, decode=True,
                                      dtype=jnp.float32))
    weights = phi4flash.program_tree(sambay_weights.make_canonical(
        sambay_weights.seed_key(3), SAMBAY), SAMBAY)
    with pytest.raises(ValueError, match="SambaYLM declares recurrent "
                       "state.*prefill_chunk"):
        ServeEngine(model, weights, num_slots=2, prefill_len=16,
                    prefill_chunk=16)


def test_gpt2_with_prefill_chunk_still_needs_pages():
    from ray_lightning_tpu.models import TransformerLM, gpt2_config
    model = TransformerLM(gpt2_config("nano", decode=True, vocab_size=128,
                                      max_seq_len=32))
    weights = model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 1), jnp.int32))["params"]
    with pytest.raises(ValueError, match="paged-KV"):
        ServeEngine(model, weights, num_slots=2, prefill_len=16,
                    prefill_chunk=16)
