"""DeepSeek-V2 (``models/deepseek_v2.py``, ``ops/mla_decode.py``, the
softmax router of ``ops/grouped_experts.py``) against the plain float32
reference (``benchmark/deepseek_v2_reference.py``: multi-head latent
attention in its published expanded form, every held expert under a mask,
importing nothing of the program), at a small size on the CPU: hidden 64,
1 dense and 2 expert layers, 4 heads of 8 + 8 rotary dims (values 8), a
query rank of 32 and a latent of 16, 32 routed experts in 8 groups of 4
of which the chip holds group 0 (the top 3 groups, 6 experts a token, x
16), 2 shared experts of 32, vocabulary 256, YaRN at factor 40 over an
original context of 64 (so that the ramp mixes both kinds of frequency).

Tolerances, and why:

- ``F32_TOL`` 5e-5 on logits of standard deviation ~1.4, program with
  float32 operands: program and reference then differ by float32
  summation order and by the forms — the program's absorbed decode and
  block-by-block expanded pieces against the reference's whole expanded
  softmax (~1e-6 here). The reference itself reads ~1e-6 against
  ``transformers``' own ``deepseek_v2`` modelling code.
- ``ABSORB_TOL`` 1e-5 on one layer's attention output: the absorbed
  form (``W_uk`` folded into the query, ``W_uv`` after the weighted sum of
  latents) is the expanded one re-associated.
- ``BF16_TOL`` 0.25 with bfloat16 operands and cache, as the
  configuration runs them (reads ~0.05).
"""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import deepseek_v2_reference as reference
from benchmark import deepseek_v2_weights
from benchmark.families import deepseek_v2 as family
from ray_lightning_tpu.models import deepseek_v2 as program
from ray_lightning_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                  DeepseekV2LM)
from ray_lightning_tpu.models.generate import cache_layout, decode_step
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.ops import grouped_experts, mla_decode, mla_prefill
from ray_lightning_tpu.serve import ServeClient, ServeEngine

pytestmark = pytest.mark.serve

SHAPE = dict(
    model_type="deepseek_v2", vocab_size=256, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, moe_layer_freq=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    n_routed_experts=4, num_experts_per_tok=6, n_shared_experts=2,
    n_group=8, topk_group=3, topk_method="group_limited_greedy",
    scoring_func="softmax", norm_topk_prob=False, routed_scaling_factor=16,
    rope_theta=10000, rms_norm_eps=1e-6, max_position_embeddings=2560,
    rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                      mscale=0.707, mscale_all_dim=0.707,
                      original_max_position_embeddings=64),
    tie_word_embeddings=False, attention_bias=False,
    published=dict(n_routed_experts=32),
    deployment=dict(expert_offset=0))
POSITIONS = 256
F32_TOL = 5e-5
ABSORB_TOL = 1e-5
BF16_TOL = 0.25
F32 = dict(dtype=jnp.float32)


def _share(offset, held=4):
    return {**SHAPE, "n_routed_experts": held,
            "deployment": dict(expert_offset=offset)}


@pytest.fixture(scope="module")
def canon():
    return deepseek_v2_weights.make_canonical(
        deepseek_v2_weights.seed_key(3), SHAPE)


@pytest.fixture(scope="module")
def params(canon):
    return family.program_tree(canon, SHAPE)


@pytest.fixture(scope="module")
def ref(canon):
    fn = reference.make_logits_fn(SHAPE, pad_multiple=64)
    return lambda tokens, rows: np.asarray(fn(canon, tokens, rows))


def _model(positions=POSITIONS, shape=SHAPE, **kw):
    return DeepseekV2LM(family.config(shape, positions, **{**F32, **kw}))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
def test_full_forward_matches_reference(params, ref, dtype, tol):
    toks = np.stack([_tokens(i, 100) for i in range(2)])
    got = np.asarray(_model(dtype=dtype).apply({"params": params},
                                               jnp.asarray(toks)))
    want = np.stack([ref(t, np.arange(100)) for t in toks])
    assert np.abs(got - want).max() < tol


def test_reference_is_the_published_modelling_code(canon, ref):
    """The reference against ``transformers``' ``DeepseekV2ForCausalLM``
    (eager) on the same weights: the latent paths, YaRN's frequencies and
    the rotary pairs, the group-limited router, the shared experts. That
    port leaves the softmax's ``mscale^2`` out (the model repository's own
    code and the paper's scale have it): its attention modules are given
    the published scale."""
    import torch
    from transformers import DeepseekV2Config as HFConfig
    from transformers import DeepseekV2ForCausalLM
    keys = {k: v for k, v in SHAPE.items()
            if k not in ("model_type", "published", "deployment")}
    keys["n_routed_experts"] = 32
    hf = DeepseekV2ForCausalLM(HFConfig(
        **keys, hidden_act="silu", mlp_bias=False,
        attn_implementation="eager")).eval().float()
    z = deepseek_v2_weights.sizes(SHAPE)
    for layer in hf.model.layers:
        layer.self_attn.scaling = reference.softmax_scale(z)
    t = lambda a: torch.tensor(np.asarray(a, np.float32))   # noqa: E731
    uncut = deepseek_v2_weights.make_canonical(
        deepseek_v2_weights.seed_key(3), _share(0, 32))
    d = {"model.embed_tokens.weight": t(uncut["embed"]),
         "lm_head.weight": t(uncut["head"]).T,
         "model.norm.weight": t(uncut["normf_g"])}
    H, C, F, f = 4, 16, 128, 32
    for l, w in enumerate(uncut["layers"]):
        p = f"model.layers.{l}."
        kv_b = torch.cat([t(w["w_uk"]).permute(0, 2, 1),
                          t(w["w_uv"])], dim=2)         # (H, C, 8 + 8)
        d.update({
            p + "input_layernorm.weight": t(w["attn_norm_g"]),
            p + "post_attention_layernorm.weight": t(w["mlp_norm_g"]),
            p + "self_attn.q_a_proj.weight": t(w["w_dq"]).T,
            p + "self_attn.q_a_layernorm.weight": t(w["q_norm_g"]),
            p + "self_attn.q_b_proj.weight": t(w["w_uq"]).T,
            p + "self_attn.kv_a_proj_with_mqa.weight": t(w["w_dkv"]).T,
            p + "self_attn.kv_a_layernorm.weight": t(w["kv_norm_g"]),
            p + "self_attn.kv_b_proj.weight":
                kv_b.permute(0, 2, 1).reshape(H * 16, C),
            p + "self_attn.o_proj.weight": t(w["w_o"]).T})
        if "w_router" not in w:
            gu = t(w["w_gate_up"])
            d.update({p + "mlp.gate_proj.weight": gu[:, :F].T,
                      p + "mlp.up_proj.weight": gu[:, F:].T,
                      p + "mlp.down_proj.weight": t(w["w_down"]).T})
            continue
        sg = t(w["ws_gate_up"])
        d.update({p + "mlp.gate.weight": t(w["w_router"]).T,
                  p + "mlp.shared_experts.gate_proj.weight": sg[:, :2 * f].T,
                  p + "mlp.shared_experts.up_proj.weight": sg[:, 2 * f:].T,
                  p + "mlp.shared_experts.down_proj.weight":
                      t(w["ws_down"]).T})
        for e in range(32):
            gu = t(w["w_gate_up"][e])
            q = f"{p}mlp.experts.{e}."
            d.update({q + "gate_proj.weight": gu[:, :f].T,
                      q + "up_proj.weight": gu[:, f:].T,
                      q + "down_proj.weight": t(w["w_down"][e]).T})
    missing, unexpected = hf.load_state_dict(d, strict=False)
    assert not missing and not unexpected
    toks = _tokens(1, 100)
    with torch.no_grad():
        got = hf(torch.tensor([toks]), use_cache=False).logits[0].numpy()
    fn = reference.make_logits_fn(_share(0, 32), pad_multiple=64)
    want = np.asarray(fn(uncut, toks, np.arange(100)))
    assert np.abs(got - want).max() < F32_TOL


def test_layout_matches_the_published_config():
    """The published widths, and a cache of one latent and one rotary key
    a position, both ``global`` (satellite (e)), beside the expert-load
    counter."""
    cfg = DeepseekV2Config()
    assert round(cfg.softmax_scale * 192 ** 0.5, 4) == round(
        (0.1 * 0.707 * np.log(40) + 1) ** 2, 4)
    model = DeepseekV2LM(DeepseekV2Config(num_hidden_layers=2,
                                          experts_held=20, max_seq_len=64,
                                          decode=True))
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32))["cache"])
    assert set(cache["layer_0_attn"]) == {"latent", "rope_key"}
    assert cache["layer_0_attn"]["latent"].shape == (3, 64, 512)
    assert cache["layer_0_attn"]["rope_key"].shape == (3, 64, 64)   # R, L
    assert cache["layer_0_attn"]["latent"].dtype == jnp.bfloat16
    assert cache["layer_1_moe"]["expert_load"].shape == (20,)
    layout = cache_layout(model, cache)
    for name, seq_axis in (("latent", 1), ("rope_key", 2)):
        decl = layout["layer_1_attn"][name]
        assert (decl.kind, decl.slot_axis, decl.seq_axis) \
            == ("global", 0, seq_axis)
    assert layout["layer_1_moe"]["expert_load"].kind == "counter"
    assert model.continues_prefill
    with pytest.raises(ValueError, match="group-limited softmax"):
        DeepseekV2Config(scoring_func="sigmoid")


# ------------------------------------------------ the absorbed form, (a)
def _expanded(a, ok, scale):
    """The expanded form in XLA (the model's ``_expand`` under afmoe's
    running softmax) over the positions ``ok (B, T, L)`` allows:
    ``(B, T, H Dv)``."""
    q = jnp.concatenate([a["q_nope"], a["q_pe"]], axis=-1) * scale
    B, H, T = q.shape[:3]
    k, v = program._expand(a["latent"], a["rope_key"], a["w_uk"],
                           a["w_uv"], jnp.float32)
    return program._softmax_finish(program._softmax_update(
        program._softmax_init(B, H, 1, T, 8), q[:, :, None], k, v, ok,
        jnp.float32))


def _attention_inputs(B=3, T=1, L=64, seed=0):
    H, Dn, R, C, Dv = 4, 8, 8, 16, 8
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    n = lambda shape: jax.random.normal(next(k), shape, jnp.float32)  # noqa
    return dict(q_nope=n((B, H, T, Dn)), q_pe=n((B, H, T, R)),
                latent=n((B, L, C)), rope_key=n((B, R, L)),
                w_uk=0.3 * n((H, Dn, C)), w_uv=0.3 * n((H, C, Dv)))


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_absorbed_decode_equals_expanded_attention(kernel):
    """One query a row over its first ``lengths[b]`` latents: ``W_uk``
    folded into the query and ``W_uv`` applied after the weighted sum of
    latents (the dense XLA form, and ``ops/mla_decode.py``'s kernel in
    interpret mode over blocks of 16, a row's last block ragged) against
    the expanded form's keys and values of every head."""
    a = _attention_inputs()
    lengths = jnp.array([64, 17, 1], jnp.int32)
    scale = 0.3
    q_c = jnp.einsum("bhd,hdc->bhc", a["q_nope"][:, :, 0], a["w_uk"])
    q_r = a["q_pe"][:, :, 0]
    if kernel:
        o = mla_decode.mla_decode(q_c, q_r, a["latent"], a["rope_key"],
                                  lengths, scale=scale, block=16,
                                  interpret=True)
    else:
        o = mla_decode.mla_decode_dense(q_c, q_r, a["latent"], a["rope_key"],
                                        lengths, scale=scale)
    got = jnp.einsum("bhc,hce->bhe", o, a["w_uv"])
    ok = (jnp.arange(64)[None, None, :] < lengths[:, None, None])
    want = _expanded(a, ok, scale).reshape(3, 4, 8)
    assert float(jnp.abs(got - want).max()) < ABSORB_TOL


def test_prefill_kernel_equals_the_expanded_blocks():
    """``ops/mla_prefill.py`` (interpret mode, blocks of 16): a piece of
    16 queries at offsets 32 and 5 against the row's cached latents, each
    head's keys and values expanded inside the kernel, against the XLA
    form's running softmax over the same positions."""
    a = _attention_inputs(B=2, T=16)
    offset = jnp.array([32, 5], jnp.int32)
    at = offset[:, None] + jnp.arange(16)[None, :]
    got = mla_prefill.mla_prefill(
        a["q_nope"], a["q_pe"], a["w_uk"], a["w_uv"], a["latent"],
        a["rope_key"], offset, scale=0.3, block=16, interpret=True)
    ok = jnp.arange(64)[None, None, :] <= at[:, :, None]
    want = _expanded(a, ok, 0.3)
    got = jnp.moveaxis(got, 1, 2).reshape(2, 16, 32)
    assert float(jnp.abs(got - want).max()) < ABSORB_TOL


def test_the_kernel_reads_only_live_blocks():
    """What lies past a row's length never reaches it: the blocks past
    its last live one — here NaN — are not copied, and the mask covers
    the rest of the last one — here 1e4."""
    a = _attention_inputs()
    lengths = jnp.array([20, 33, 1], jnp.int32)
    at = jnp.arange(64)[None, :, None]
    poisoned = jnp.where(at >= lengths[:, None, None], 1e4, a["latent"])
    poisoned = jnp.where(at >= 32 * (-(-lengths // 32))[:, None, None],
                         jnp.nan, poisoned)
    q_c = jnp.einsum("bhd,hdc->bhc", a["q_nope"][:, :, 0], a["w_uk"])
    o = mla_decode.mla_decode(q_c, a["q_pe"][:, :, 0], poisoned,
                              a["rope_key"], lengths, scale=0.3, block=32,
                              interpret=True)
    want = mla_decode.mla_decode_dense(q_c, a["q_pe"][:, :, 0], a["latent"],
                                       a["rope_key"], lengths, scale=0.3)
    assert float(jnp.abs(o - want).max()) < ABSORB_TOL


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


@pytest.mark.parametrize("piece,key_block,kernel", [
    (120, 256, False), (48, 40, False), (48, 256, True)],
    ids=["one_piece", "pieces_ragged_key_blocks", "kernels"])
def test_pieces_then_decode_match_reference(params, ref, monkeypatch,
                                            piece, key_block, kernel):
    """Prompts of unequal length fed in pieces through *continue* (the
    piece's latents written at its offset, the row's cached latents
    expanded a block at a time), then 10 decode steps through the
    absorbed form — in XLA, or through the two kernels in interpret mode
    over blocks of 32: every logit against the reference's full
    forward."""
    monkeypatch.setattr(program, "KEY_BLOCK", key_block)
    if kernel:
        monkeypatch.setattr(program, "_kernel_seat", lambda kernel, L: True)
        monkeypatch.setattr(mla_decode, "BLOCK", 32)
        monkeypatch.setattr(mla_prefill, "BLOCK", 32)
    model = _model(decode=True)
    lengths = np.array([110, 37, 1, 96], np.int32)
    steps = 10
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


# ------------------------------------------------------------ the engine
REQUESTS = [dict(prompt=_tokens(20, 70), max_new_tokens=16),
            dict(prompt=_tokens(21, 9), max_new_tokens=9),
            dict(prompt=_tokens(23, 100), max_new_tokens=12),
            dict(prompt=_tokens(24, 16), max_new_tokens=6),
            dict(prompt=_tokens(25, 57), max_new_tokens=10,
                 temperature=0.8, top_k=20, seed=7)]
ENGINE = dict(num_slots=3, prefill_len=16, prefill_batch=2,
              prefill_chunk=16)


def _gap(ref, prompt, tokens):
    seq = list(prompt) + list(tokens)
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    lg = ref(seq, rows)
    return float((lg.max(-1) - lg[np.arange(len(rows)), tokens]).max())


def test_chunked_dense_slots_match_reference(params, ref):
    """(b) through ``ServeEngine``: ragged requests over three dense slots
    with ``prefill_chunk`` 16 — prompts of 9 and 16 take the batched
    prefill, the others stream in pieces between decode steps of the rows
    already running. Each greedy token is the reference's own choice (the
    reference's logit of the served token is its largest)."""
    client = ServeClient(_model(decode=True), params, **ENGINE)
    out = client.serve_trace([(0, r) for r in REQUESTS])
    assert len(out) == len(REQUESTS)
    assert client.engine.chunk_dispatches >= 8
    for rid, r in enumerate(REQUESTS):
        assert len(out[rid].tokens) == r["max_new_tokens"]
        if not r.get("temperature"):
            assert _gap(ref, r["prompt"], out[rid].tokens) < F32_TOL, rid


def test_spans_count_the_latent_leaves(params):
    """``engine.step.call`` counts the latent and rotary leaves as
    ``global_bytes`` (a live position: 16 + 8 float32 values in each of 3
    layers) and the expert load as the counters ``serve.moe_*`` read."""
    tel = Telemetry()
    client = ServeClient(_model(decode=True), params, telemetry=tel,
                         **ENGINE)
    client.serve_trace([(0, REQUESTS[1]), (0, REQUESTS[2])])
    per_position = 3 * (16 + 8) * 4
    steps = tel.spans.spans("engine.step.call")
    assert steps and all(s.args["recurrent_bytes"] == 0 for s in steps)
    assert all(s.args["global_bytes"] % per_position == 0
               and s.args["global_bytes"] > 0 for s in steps)
    assert all(s.args["moe_experts"] == 2 * 4 for s in steps)
    assert all(0 <= s.args["moe_experts_hit"] <= 8 for s in steps)


@pytest.mark.parametrize("kw,name", [
    (dict(page_size=8), "page_size"),
    (dict(prefill_chunk=16, prefix_cache=True), "prefix_cache"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(draft="self"), "draft_model")],
    ids=["pages", "prefix_cache", "int8_kv", "speculative"])
def test_engine_refuses_by_name_what_it_cannot_give(params, kw, name):
    """(e) pages, prefix reuse, int8 storage and speculation assume K/V
    rows of heads at absolute positions: refused at construction."""
    model = _model(decode=True)
    if kw.pop("draft", None):
        kw.update(draft_model=model, draft_params=params)
    with pytest.raises(ValueError) as err:
        ServeEngine(model, params, num_slots=2, prefill_len=16, **kw)
    assert "DeepseekV2LM declares a cache that only its continue mode " \
        "writes" in str(err.value)
    assert name in str(err.value)


# -------------------------------------------------- the expert share, (c)
def test_group_shares_add_up_to_the_uncut_layer():
    """At the cut: each of the 8 routing groups held by one share (4
    experts from offset 4 g) gives its experts' part of an expert layer;
    the 8 parts, with the shared experts counted once, are what the uncut
    reference's whole layer gives — in the program's expert layer and in
    the reference's."""
    key = deepseek_v2_weights.seed_key(11)
    uncut = _share(0, 32)
    z_all = deepseek_v2_weights.sizes(uncut)
    w_all = deepseek_v2_weights.make_layer(key, uncut, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)

    @functools.partial(jax.jit, static_argnames="held")
    def reference_layer(w, offset, held):
        # the offset is the one size a share changes: traced
        z = {**z_all, "held": held, "offset": offset}
        h, experts, weights, _, y = reference._route(x, w, z=z, mode="f32")
        return y + reference._expert_block(
            h, experts, weights, w["w_gate_up"], w["w_down"], offset, z=z,
            mode="f32"), y

    def program_layer(w, shape):
        cfg = family.config(shape, 64, dtype=jnp.float32)
        tree = {"router": w["w_router"], "experts_gate_up": w["w_gate_up"],
                "experts_down": w["w_down"],
                "shared_gate_up": {"kernel": w["ws_gate_up"]},
                "shared_down": {"kernel": w["ws_down"]}}
        h = reference._rms_norm(x, w["mlp_norm_g"], 1e-6)
        return program.ExpertLayer(cfg).apply({"params": tree},
                                              h[None], None)[0]

    whole, shared = reference_layer(w_all, 0, 32)
    parts, program_parts = [], []
    for g in range(8):
        shape = _share(4 * g)
        w = deepseek_v2_weights.make_layer(key, shape, 1)
        np.testing.assert_array_equal(np.asarray(w["w_down"]),
                                      np.asarray(w_all["w_down"][4 * g:
                                                                 4 * g + 4]))
        parts.append(reference_layer(w, 4 * g, 4)[0])
        program_parts.append(program_layer(w, shape))
    for got in (parts, program_parts):
        total = sum(got) - 7 * shared
        assert float(jnp.abs(total - whole).max()) < 1e-4


# ------------------------------------------------------ the router, (d)
def _brute_force(logits, n_group, topk_group, k):
    """Every pair of a set of groups and a set of experts among them that
    the greedy rule could return — each kept group ranks above each left
    out, each taken expert above each allowed one left out, ties to the
    lower number — by enumeration; there must be exactly one."""
    s = np.exp(logits - logits.max())
    s = s / s.sum()
    E = len(s)
    per = E // n_group
    best = s.reshape(n_group, per).max(-1)

    def above(a, b, score):           # does a rank above b
        return score[a] > score[b] or (score[a] == score[b] and a < b)

    found = []
    for groups in itertools.combinations(range(n_group), topk_group):
        if not all(above(g, o, best) for g in groups
                   for o in set(range(n_group)) - set(groups)):
            continue
        allowed = [e for e in range(E) if e // per in groups]
        for experts in itertools.combinations(allowed, k):
            if all(above(e, o, s) for e in experts
                   for o in set(allowed) - set(experts)):
                found.append((groups, experts))
    assert len(found) == 1, found
    experts = np.array(found[0][1])
    return experts, s[experts]


@pytest.mark.parametrize("case", ["distinct", "tied_groups",
                                  "tie_at_the_expert_edge"])
def test_group_limited_routing_matches_brute_force(case):
    """``route(scoring="softmax", n_group, topk_group)`` on hand-made
    logits of 12 experts in 4 groups of 3, the top 2 groups, 3 experts a
    token: the chosen set against enumeration, weights ``s * 16``
    unnormalised (``norm_topk_prob`` false) and ``s / sum s`` times the
    scale where normalised."""
    logits = {
        # group 2 best, then group 0; group 0's second beats group 2's
        "distinct": [3.0, 2.9, 0.1, 0.0, 1.0, 0.2, 3.5, 0.3, -1.0,
                     2.0, 0.4, 0.5],
        # groups 1 and 3 tie on their best (2.5), group 0 best: the lower
        # group (1) is kept, though group 3 holds the larger second
        "tied_groups": [3.0, 0.1, 0.0, 2.5, 0.1, 0.0, -1.0, -2.0, 0.0,
                        2.5, 2.4, 2.3],
        # the 3rd and 4th experts among the kept groups tie (1.7)
        "tie_at_the_expert_edge": [3.0, 1.7, 0.0, 0.2, 0.1, 0.0, 2.8, 1.7,
                                   1.0, -1.0, 0.0, 0.5],
    }[case]
    x = np.array([logits, logits[::-1]], np.float32)
    for norm in (False, True):
        experts, weights = grouped_experts.route(
            jnp.asarray(x), None, 3, normalise=norm, scale=16.0,
            scoring="softmax", n_group=4, topk_group=2)
        for row in range(2):
            want_e, want_s = _brute_force(x[row], 4, 2, 3)
            got_e = np.asarray(experts[row])
            order = np.argsort(got_e)
            np.testing.assert_array_equal(got_e[order], np.sort(want_e))
            want_w = want_s[np.argsort(want_e)] * 16.0
            if norm:
                want_w = want_w / want_s.sum()
            np.testing.assert_allclose(np.asarray(weights[row])[order],
                                       want_w, rtol=1e-5)


# ------------------------------------------- afmoe's program unchanged, (f)
def _parent_route(logits, bias, top_k, normalise=True, scale=1.0):
    """``route`` as it was before the softmax router and the group limit,
    verbatim."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts.astype(jnp.int32), weights * scale


def test_afmoe_step_program_is_unchanged(monkeypatch):
    """The sigmoid router's call lowers to the same step program as before
    the softmax router and the group limit were added: the lowered text
    of an ``afmoe`` engine step (2 layers at nano width) with today's
    ``route`` and with the one it replaced is the same."""
    from ray_lightning_tpu.models.afmoe import (FULL, SLIDING, AfmoeConfig,
                                                AfmoeLM)
    from ray_lightning_tpu.serve import engine as E
    model = AfmoeLM(AfmoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        layer_types=(SLIDING, FULL), sliding_window=16, num_experts=16,
        experts_held=4, num_experts_per_tok=4, max_seq_len=64,
        decode=True))
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32)))
    S = jax.ShapeDtypeStruct
    i32 = lambda *s: S(s, jnp.int32)                    # noqa: E731

    def lowered():
        return jax.jit(E._engine_step_impl,
                       static_argnames=("model", "steps")).lower(
            model, init["params"], init["cache"], i32(4, 1), i32(4, 1),
            S((4,), jnp.bool_), i32(4), S((4,), jnp.float32), i32(4),
            i32(4), S((4, 2), jnp.uint32), i32(4), None, steps=1).as_text()

    def parent(logits, bias, top_k, normalise, scale, *rest, **kw):
        assert not rest and not kw
        return _parent_route(logits, bias, top_k, normalise, scale)

    jax.clear_caches()
    now = lowered()
    monkeypatch.setattr(grouped_experts, "route", parent)
    jax.clear_caches()
    try:
        assert lowered() == now
    finally:
        jax.clear_caches()
