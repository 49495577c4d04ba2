"""Falcon-H1 (``models/falcon_h1.py``, ``ops/ssd.py``) against the plain
float32 reference (``benchmark/falcon_h1_reference.py``: the token-by-token
recurrence, repeated key-value heads, importing nothing of the program),
at a small size on the CPU: hidden 64, 2 layers, 4 query / 2 key-value
heads of 16, a Mamba-2 mixer of 8 heads of 8 x 16 state in 2 groups, SSD
blocks of 32, vocabulary 256. The multipliers are the published ones but
for ``attention_in_multiplier``, 0.5 here (1 as published: dropping it
would change nothing). Weights are the benchmark's seeded ones, drawn so
that every path with its multipliers is a unit-variance map.

Tolerances, and why:

- ``F32_TOL`` 5e-5 on logits of standard deviation ~1, program with
  float32 operands and state: program and reference then differ by
  float32 summation order and by the chunkwise form's algebra (blocks of
  32 in place of one position at a time) — ~3e-6 here. The reference
  itself reads ~3e-6 against ``transformers``' own ``falcon_h1``
  modelling code.
- ``BF16_TOL`` 0.2 with bfloat16 operands as the configuration runs them
  (reads ~0.03); it cannot tell the state's dtype, which is why the
  float32 comparison exists: a state held in bfloat16 reads over 10 x
  ``F32_TOL`` (``test_bf16_state_fails``).
- every planted fault (a multiplier dropped, the gated norm over the
  whole width, B and C read by the wrong heads) reads over 50 x
  ``F32_TOL``; ``benchmark/tests/test_bench_falcon_h1_correct.py`` plants
  the same ones under the cell's own limits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import falcon_h1_reference, falcon_h1_weights
from benchmark.families import falcon_h1 as family
from ray_lightning_tpu.models import falcon_h1 as program
from ray_lightning_tpu.models.falcon_h1 import FalconH1Config, FalconH1LM
from ray_lightning_tpu.models.generate import (_prefill_impl, cache_layout,
                                               decode_step, generate)
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.serve import ServeClient, ServeEngine

pytestmark = pytest.mark.serve

SHAPE = dict(
    model_type="falcon_h1", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e11, rms_norm_eps=1e-5,
    max_position_embeddings=262144, mamba_d_ssm=64, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=32, embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125, attention_in_multiplier=0.5,
    attention_out_multiplier=0.0375, key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284])
POSITIONS = 256
F32_TOL = 5e-5
BF16_TOL = 0.2
F32 = dict(dtype=jnp.float32)


@pytest.fixture(scope="module")
def canon():
    return falcon_h1_weights.make_canonical(falcon_h1_weights.seed_key(3),
                                            SHAPE)


@pytest.fixture(scope="module")
def params(canon):
    return family.program_tree(canon, SHAPE)


@pytest.fixture(scope="module")
def ref(canon):
    fn = falcon_h1_reference.make_logits_fn(SHAPE, pad_multiple=64)
    return lambda tokens, rows: np.asarray(fn(canon, tokens, rows))


def _model(positions=POSITIONS, **kw):
    return FalconH1LM(family.config(SHAPE, positions, **{**F32, **kw}))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _full_forward_error(params, ref, n=150, **kw):
    """The widest logit error over 2 x ``n`` positions."""
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
    """Every position's logits over 150 positions (four blocks of the
    chunkwise form, the last one ragged)."""
    assert _full_forward_error(params, ref, dtype=dtype) < tol


def test_bf16_state_fails(params, ref):
    """The float32 comparison is tight enough to see the state's dtype."""
    err = _full_forward_error(params, ref, n=250, state_dtype=jnp.bfloat16)
    assert err > 10 * F32_TOL, err


def test_reference_is_the_published_modelling_code(canon, ref):
    """The reference against ``transformers``' ``FalconH1ForCausalLM``
    (its plain-PyTorch path) on the same weights: where every multiplier
    sits, the gated norm's groups, B and C a group, the rotation."""
    import torch
    from transformers import FalconH1Config as HFConfig
    from transformers import FalconH1ForCausalLM
    keys = {k: v for k, v in SHAPE.items() if k != "model_type"}
    hf = FalconH1ForCausalLM(HFConfig(
        **keys, mamba_rms_norm=True, mamba_norm_before_gate=False,
        tie_word_embeddings=False, mamba_conv_bias=True,
        mamba_proj_bias=False, projectors_bias=False, attention_bias=False,
        mlp_bias=False, hidden_act="silu")).eval().float()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))   # noqa: E731
    d = {"model.embed_tokens.weight": t(canon["embed"]),
         "lm_head.weight": t(canon["head"]).T,
         "model.final_layernorm.weight": t(canon["normf_g"])}
    Hq, K, D, ff = 4, 2, 16, 128
    for l, w in enumerate(canon["layers"]):
        p = f"model.layers.{l}."
        qkv, gu = t(w["w_qkv"]), t(w["w_gate_up"])
        d.update({
            p + "self_attn.q_proj.weight": qkv[:, :Hq * D].T,
            p + "self_attn.k_proj.weight": qkv[:, Hq * D:(Hq + K) * D].T,
            p + "self_attn.v_proj.weight": qkv[:, (Hq + K) * D:].T,
            p + "self_attn.o_proj.weight": t(w["w_o"]).T,
            p + "mamba.in_proj.weight": t(w["w_in"]).T,
            p + "mamba.out_proj.weight": t(w["w_out"]).T,
            p + "mamba.conv1d.weight": t(w["conv_w"]).T[:, None, :],
            p + "mamba.conv1d.bias": t(w["conv_b"]),
            p + "mamba.A_log": t(w["A_log"]),
            p + "mamba.dt_bias": t(w["dt_bias"]), p + "mamba.D": t(w["D"]),
            p + "mamba.norm.weight": t(w["ssm_norm_g"]),
            p + "feed_forward.gate_proj.weight": gu[:, :ff].T,
            p + "feed_forward.up_proj.weight": gu[:, ff:].T,
            p + "feed_forward.down_proj.weight": t(w["w_down"]).T,
            p + "input_layernorm.weight": t(w["in_norm_g"]),
            p + "pre_ff_layernorm.weight": t(w["ff_norm_g"])})
    missing, unexpected = hf.load_state_dict(d, strict=False)
    assert not missing and not unexpected
    toks = _tokens(1, 100)
    with torch.no_grad():
        got = hf(torch.tensor([toks]), use_cache=False).logits[0].numpy()
    assert np.abs(got - ref(toks, np.arange(100))).max() < F32_TOL


def test_layout_matches_the_published_config():
    """The published widths, and a layer that holds both kinds of leaf:
    the SSM state and the conv tail (recurrent, float32) beside the K/V
    (global, heads before positions)."""
    cfg = FalconH1Config()
    assert (cfg.conv_width, cfg.in_proj_width) == (5120, 9248)
    assert cfg.mamba_n_heads // cfg.mamba_n_groups == 16
    model = FalconH1LM(FalconH1Config(num_hidden_layers=1, max_seq_len=64,
                                      decode=True))
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32))["cache"])
    leaves = jax.tree_util.tree_leaves
    by_kind = {}
    for leaf, decl in zip(leaves(cache), leaves(cache_layout(model, cache))):
        assert decl.per_slot and leaf.shape[decl.slot_axis] == 3
        by_kind.setdefault(decl.kind, []).append((leaf.shape, leaf.dtype))
    assert sorted(by_kind["recurrent"]) == [
        ((3, 32, 128, 256), jnp.float32), ((3, 3 * 5120), jnp.float32)]
    assert by_kind["global"] == [((3, 4, 64, 128), jnp.bfloat16)] * 2
    assert set(cache["layer_0_ssm"]) == {"ssm_state", "conv_state"}
    assert model.recurrent_state and model.continues_prefill
    with pytest.raises(ValueError, match="not tied"):
        FalconH1Config(tie_word_embeddings=True)
    with pytest.raises(ValueError, match="gate before the norm"):
        FalconH1Config(mamba_norm_before_gate=True)


# -------------------------------------------------------- planted faults
MULTIPLIERS = ["embedding_multiplier", "key_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier",
               "ssm_multipliers.z", "ssm_multipliers.x", "ssm_multipliers.B",
               "ssm_multipliers.C", "ssm_multipliers.dt",
               "mlp_multipliers.gate", "mlp_multipliers.down",
               "lm_head_multiplier"]
PARTS = {"ssm_multipliers": ("z", "x", "B", "C", "dt"),
         "mlp_multipliers": ("gate", "down")}
PLANTED = MULTIPLIERS + ["norm_over_the_whole_width", "bc_read_per_head"]


def plant(monkeypatch, fault):
    """One fault in the program (``benchmark/tests`` plants the same ones
    under the cell's own limits): a multiplier dropped (set to 1), the
    gated norm taken over all of ``mamba_d_ssm`` at once, or head ``h``
    reading B and C of group ``h % G`` (each head its own, interleaved)
    instead of ``h // (H / G)``."""
    if fault in MULTIPLIERS:
        name, _, part = fault.partition(".")
        config = program.FalconH1Config  # (family.config looks it up anew)

        class Dropped(config):
            def __init__(self, **kw):
                if part:
                    values = list(kw.get(name, getattr(config, name)))
                    values[PARTS[name].index(part)] = 1.0
                    kw[name] = tuple(values)
                else:
                    kw[name] = 1.0
                super().__init__(**kw)
        monkeypatch.setattr(program, "FalconH1Config", Dropped)
    elif fault == "norm_over_the_whole_width":
        norm = program._gated_norm
        monkeypatch.setattr(
            program, "_gated_norm",
            lambda y, gate, weight, groups, eps: norm(y, gate, weight, 1,
                                                      eps))
    elif fault == "bc_read_per_head":
        chunk, step = program.ssd_chunk, program.ssd_step

        def per_head(x, B, C):
            H, G = x.shape[-2], B.shape[-2]
            index = jnp.arange(H) % G
            return (jnp.take(B, index, axis=-2), jnp.take(C, index, axis=-2))

        monkeypatch.setattr(
            program, "ssd_chunk",
            lambda x, dt, A, B, C, *a: chunk(x, dt, A, *per_head(x, B, C),
                                             *a))
        monkeypatch.setattr(
            program, "ssd_step",
            lambda x, dt, A, B, C, s: step(x, dt, A, *per_head(x, B, C), s))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", PLANTED)
def test_planted_fault_is_seen(params, ref, monkeypatch, fault):
    plant(monkeypatch, fault)
    assert _full_forward_error(params, ref, n=100) > 50 * F32_TOL


# ------------------------------------------------- prefill, continue, step
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


@pytest.mark.parametrize("piece,key_block", [(120, 512), (48, 512),
                                             (48, 40)],
                         ids=["one_piece", "pieces", "ragged_key_blocks"])
def test_pieces_then_decode_match_reference(params, ref, monkeypatch,
                                            piece, key_block):
    """Prompts of unequal length (one past three SSD blocks, one a single
    token) fed in pieces through *continue* — the SSM state and the conv
    tail carried, each piece's K/V written at its offset and the row's
    earlier keys read a block at a time, a row whose prompt has ended
    left as it is — then 12 decode steps: every logit against the
    reference's full forward."""
    monkeypatch.setattr(program, "_read_blocks", _read_blocks_of(key_block))
    model = _model(decode=True)
    lengths = np.array([110, 37, 1, 96], np.int32)
    steps = 12
    seqs = [_tokens(10 + i, int(n) + steps) for i, n in enumerate(lengths)]
    toks = np.zeros((4, 120), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = seqs[i][:n]
    last, cache = _feed(model, params, toks, lengths, piece)
    want = [ref(s, np.arange(len(s))) for s in seqs]
    for i, n in enumerate(lengths):
        assert np.abs(last[i] - want[i][n - 1]).max() < F32_TOL, i
    step = jax.jit(decode_step, static_argnums=0)
    for j in range(steps):
        pos = (lengths + j)[:, None]
        cur = np.array([[s[p]] for s, p in zip(seqs, pos[:, 0])], np.int32)
        logits, cache = step(model, params, cache, cur, pos)
        logits = np.asarray(logits)
        for i in range(4):
            err = np.abs(logits[i] - want[i][pos[i, 0]]).max()
            assert err < F32_TOL, (j, i, err)


def _read_blocks_of(key_block):
    from ray_lightning_tpu.models import afmoe

    def read(*a):
        afmoe_block = afmoe.KEY_BLOCK
        afmoe.KEY_BLOCK = key_block
        try:
            return afmoe._read_blocks(*a)
        finally:
            afmoe.KEY_BLOCK = afmoe_block
    return read


def test_a_row_of_length_zero_is_left_alone(params):
    """A piece that gives a row no token leaves its state and its conv
    tail exactly as they were, and its live K/V too."""
    model = _model(decode=True)
    toks = np.asarray([_tokens(5, 64), _tokens(6, 64)], np.int32)
    _, before = _feed(model, params, toks[:, :32], np.array([32, 32]), 32)
    apply = jax.jit(lambda cache, tokens, n, off: model.apply(
        {"params": params, "cache": cache}, tokens, lengths=n, offset=off,
        mutable=["cache"]))
    _, after = apply(before, toks[:, 32:], np.array([32, 0], np.int32),
                     np.array([32, 32], np.int32))
    for name, leaf in after["cache"]["layer_1_ssm"].items():
        was = np.asarray(before["layer_1_ssm"][name])
        np.testing.assert_array_equal(np.asarray(leaf)[1], was[1])
        assert not np.array_equal(np.asarray(leaf)[0], was[0])
    kv = np.asarray(after["cache"]["layer_1_attn"]["cached_key"])
    np.testing.assert_array_equal(
        kv[1, :, :32],
        np.asarray(before["layer_1_attn"]["cached_key"])[1, :, :32])


def test_prefill_is_continue_from_zero(params, ref):
    """``generate._prefill_impl`` hands the model its row lengths and
    takes the last valid token's logits back."""
    model = _model(decode=True)
    lengths = np.array([70, 7], np.int32)
    seqs = [_tokens(20 + i, int(n)) for i, n in enumerate(lengths)]
    prompts = np.zeros((2, 96), np.int32)
    for i, n in enumerate(lengths):
        prompts[i, :n] = seqs[i]
    _, last = jax.jit(_prefill_impl, static_argnums=0)(
        model, params, prompts, lengths)
    for i, n in enumerate(lengths):
        want = ref(seqs[i], [n - 1])[0]
        assert np.abs(np.asarray(last)[i] - want).max() < F32_TOL


def test_bf16_state_fails_through_the_cache(params, ref):
    """Held in bfloat16, the state is rounded at every decode step: the
    served path sees it too."""
    model = _model(decode=True, state_dtype=jnp.bfloat16)
    seq = _tokens(7, 60)
    prompts = np.asarray([seq[:20]], np.int32)
    cache, _ = jax.jit(_prefill_impl, static_argnums=0)(
        model, params, prompts, np.array([20], np.int32))
    want = ref(seq, np.arange(60))
    step = jax.jit(decode_step, static_argnums=0)
    err = 0.0
    for p in range(20, 60):
        logits, cache = step(model, params, cache,
                             np.array([[seq[p]]], np.int32),
                             np.array([[p]], np.int32))
        err = max(err, float(np.abs(np.asarray(logits)[0] - want[p]).max()))
    assert err > 10 * F32_TOL, err


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


def test_chunked_dense_slots_match_reference(params, ref):
    """Six ragged requests over three dense slots with ``prefill_chunk``
    16: prompts of 9 and 16 take the batched prefill, the others stream
    in one or two rows a dispatch between decode steps of the rows
    already running (whose state must not move). Greedy tokens are the
    reference's choice and ``generate()``'s."""
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
                         prompt_lengths=jnp.asarray([len(r["prompt"])]))
        assert list(np.asarray(alone)[0, len(r["prompt"]):]) \
            == out[rid].tokens, rid


def test_spans_count_both_kinds_of_leaf(params):
    """``engine.step.call`` carries the cache's bytes by the leaves'
    declarations — ``recurrent_bytes`` a row whole, ``global_bytes`` a
    live position — and ``engine.chunk.call`` each row's offset and
    length."""
    tel = Telemetry()
    client = ServeClient(_model(decode=True), params, telemetry=tel,
                         **ENGINE)
    client.serve_trace([(0, REQUESTS[1]), (0, REQUESTS[3])])
    state = 2 * (8 * 8 * 16 + 3 * (64 + 2 * 2 * 16)) * 4    # float32
    per_position = 2 * 2 * 2 * 16 * 4      # 2 layers, K and V, float32
    steps = tel.spans.spans("engine.step.call")
    assert steps and all(
        s.args["recurrent_bytes"] == state * s.args["active"]
        for s in steps)
    assert all(s.args["window_bytes"] == 0 for s in steps)
    assert all(s.args["global_bytes"] % per_position == 0
               and s.args["global_bytes"] > 0 for s in steps)
    calls = [s.args for s in tel.spans.spans("engine.chunk.call")]
    assert sum(c["tokens"] for c in calls) == 120
    assert all(len(c["off"]) == c["rows"] for c in calls)


@pytest.mark.parametrize("kw,name", [
    (dict(page_size=8), "page_size"),
    (dict(page_size=8, prefill_chunk=16, prefix_cache=True), "prefix_cache"),
    (dict(draft="self"), "draft_model")],
    ids=["pages", "prefix_cache", "speculative"])
def test_engine_refuses_by_name_what_it_cannot_give(params, kw, name):
    model = _model(decode=True)
    if kw.pop("draft", None):
        kw.update(draft_model=model, draft_params=params)
    with pytest.raises(ValueError) as err:
        ServeEngine(model, params, num_slots=2, prefill_len=16, **kw)
    assert "FalconH1LM declares recurrent state" in str(err.value)
    assert name in str(err.value)
