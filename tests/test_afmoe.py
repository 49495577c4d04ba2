"""Trinity / AFMoE (``models/afmoe.py``, ``ops/grouped_experts.py``)
against the plain float32 reference (``benchmark/afmoe_reference.py``: no
cache, no ring, repeated key-value heads, every held expert under a mask,
importing nothing of the program), at a small size on the CPU: hidden 64,
4 layers (one dense, then window, window, full), 4 query / 2 key-value
heads of 16, a window of 24, 16 router outputs of which 2 are held from
offset 4 (an eighth, as the cell holds), 4 experts a token, vocabulary
256. Weights are the benchmark's seeded ones at ``initializer_range`` 1 /
sqrt(64), so that activations, router logits and scores are O(1).

Tolerances, and why:

- ``F32_TOL`` 5e-4 on logits of standard deviation ~1, program with
  float32 operands and a float32 cache: program and reference then
  differ by float32 summation order, the running softmax over key
  blocks, the grouped heads and the grouped product's order — ~1e-5
  here.
- ``OP_TOL`` 2e-5 for the grouped product against the masked dense one,
  on outputs of size ~1.
- the planted faults (``benchmark/tests/test_bench_afmoe_correct.py``
  plants the same ones under the cell's own limits) each read above 100 x
  ``F32_TOL``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import afmoe_reference, afmoe_weights
from benchmark.families import afmoe as family
from ray_lightning_tpu.models import afmoe as program
from ray_lightning_tpu.models.afmoe import (FULL, SLIDING, AfmoeConfig,
                                            AfmoeLM)
from ray_lightning_tpu.models.generate import _prefill_impl, decode_step
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.ops import grouped_experts
from ray_lightning_tpu.serve import ServeClient, ServeEngine

pytestmark = pytest.mark.serve

WINDOW = 24
SHAPE = dict(model_type="afmoe", vocab_size=256, hidden_size=64,
             intermediate_size=128, moe_intermediate_size=32,
             num_hidden_layers=4, num_dense_layers=1,
             layer_types=[SLIDING, SLIDING, SLIDING, FULL],
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             sliding_window=WINDOW, num_experts=2, num_experts_per_tok=4,
             num_shared_experts=1, score_func="sigmoid", route_norm=True,
             route_scale=2.448, n_group=1, topk_group=1, rope_theta=10000,
             rms_norm_eps=1e-5, mup_enabled=True,
             max_position_embeddings=262144, tie_word_embeddings=False,
             published=dict(num_experts=16),
             deployment=dict(expert_offset=4), initializer_range=0.125,
             router_bias_std=0.05)
POSITIONS = 192
F32_TOL = 5e-4
OP_TOL = 2e-5
F32 = dict(dtype=jnp.float32)


@pytest.fixture(scope="module")
def canon():
    return afmoe_weights.make_canonical(afmoe_weights.seed_key(3), SHAPE)


@pytest.fixture(scope="module")
def params(canon):
    return family.program_tree(canon, SHAPE)


@pytest.fixture(scope="module")
def ref(canon):
    fn = afmoe_reference.make_logits_fn(SHAPE, pad_multiple=64)
    return lambda tokens, rows: np.asarray(fn(canon, tokens, rows))


def _model(shape=SHAPE, positions=POSITIONS, **kw):
    return AfmoeLM(family.config(shape, positions, **{**F32, **kw}))


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
def test_full_forward_matches_reference(params, ref):
    """Every position's logits over 150 positions, six windows long."""
    assert _full_forward_error(params, ref) < F32_TOL


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


@pytest.mark.parametrize("piece,key_block", [(16, 512), (40, 512), (16, 10)],
                         ids=["pieces_inside_the_ring",
                              "a_piece_longer_than_the_ring",
                              "ragged_key_blocks"])
def test_pieces_then_decode_match_reference(params, ref, monkeypatch,
                                            piece, key_block):
    """(a) Prompts of unequal length fed in pieces through *continue*
    (each piece rotating by its own offset, reading the ring the earlier
    pieces left **before** its own keys overwrite it, and writing only
    its valid positions), then 30 decode steps through the cache: every
    logit against the reference's full forward. A window of 24: the
    prompts of 100 and 96 wrap the ring four times over, the decode
    steps of the short ones (37 -> 67, 1 -> 31) wrap it for the first
    time."""
    monkeypatch.setattr(program, "KEY_BLOCK", key_block)
    model = _model(decode=True)
    lengths = np.array([100, 37, 1, 96], np.int32)
    steps = 30
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


def test_a_pad_tail_leaves_the_ring_alone(params):
    """A ragged last piece writes its valid positions only: the entries
    its pad tail would land on still hold live positions."""
    model = _model(decode=True)
    toks = np.asarray([_tokens(5, 48)], np.int32)
    _, whole = _feed(model, params, toks, np.array([48], np.int32), 16)
    # 40 tokens: the last piece holds 8 valid tokens and 8 pads
    _, ragged = _feed(model, params, toks[:, :48], np.array([40], np.int32),
                      16)
    ring_w = np.asarray(whole["layer_1_attn"]["ring_key"])
    ring_r = np.asarray(ragged["layer_1_attn"]["ring_key"])
    # positions 24 .. 39 sit at indexes 0 .. 15 in both; 40 .. 47 (indexes
    # 16 .. 23) were fed to `whole` only: `ragged` still holds 16 .. 23
    assert np.array_equal(ring_r[:, :, :16], ring_w[:, :, :16])
    _, before = _feed(model, params, toks[:, :32], np.array([24], np.int32),
                      16)
    assert np.array_equal(ring_r[:, :, 16:],
                          np.asarray(before["layer_1_attn"]["ring_key"])
                          [:, :, 16:])
    assert not np.array_equal(ring_r[:, :, 16:], ring_w[:, :, 16:])


def test_prefill_is_continue_from_zero(params, ref):
    """``generate._prefill_impl`` hands a model that declares
    ``continues_prefill`` its row lengths and takes the last valid
    token's logits back."""
    model = _model(decode=True)
    lengths = np.array([30, 7], np.int32)
    seqs = [_tokens(20 + i, int(n)) for i, n in enumerate(lengths)]
    prompts = np.zeros((2, 32), np.int32)
    for i, n in enumerate(lengths):
        prompts[i, :n] = seqs[i]
    _, last = jax.jit(_prefill_impl, static_argnums=0)(
        model, params, prompts, lengths)
    for i, n in enumerate(lengths):
        want = ref(seqs[i], [n - 1])[0]
        assert np.abs(np.asarray(last)[i] - want).max() < F32_TOL


def test_cache_leaves_are_rings_and_full_rows(params):
    """A window layer's leaves are rings of ``sliding_window`` positions
    declared ``"window"``, the full layer's hold the slot and are
    ``"global"``, heads before positions; each expert layer leaves a
    ``"counter"`` no slot owns."""
    model = _model(decode=True)
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32)))["cache"]
    assert cache["layer_0_attn"]["ring_key"].shape == (3, 2, WINDOW, 16)
    assert cache["layer_3_attn"]["cached_value"].shape \
        == (3, 2, POSITIONS, 16)
    assert cache["layer_1_moe"]["expert_load"].shape == (2,)
    assert "layer_0_moe" not in cache       # the dense layer
    leaf = model.cache_leaf
    assert leaf(("layer_0_attn", "ring_key")).kind == "window"
    assert leaf(("layer_3_attn", "cached_key")).kind == "global"
    assert leaf(("layer_3_attn", "cached_key")).seq_axis == 2
    assert not leaf(("layer_1_moe", "expert_load")).per_slot
    # a slot shorter than the window holds every position it has
    assert _model(positions=16, decode=True).cfg.ring_len == 16


PLANTED = ["top_3", "weights_not_normalised", "bias_inside_the_weight",
           "no_shared_expert", "full_layer_rotated", "window_not_rotated",
           "window_one_short", "no_gate", "no_embedding_multiplier"]


def plant(monkeypatch, fault, full_layers=("layer_3_attn",)):
    """One fault in the program (``benchmark/tests`` plants the same
    ones under the cell's own limits). ``full_layers``: the attention
    modules of the ``full_attention`` layers (the full forward's, so a
    rotation from position 0 is the fault)."""
    route = grouped_experts.route
    if fault == "top_3":
        monkeypatch.setattr(
            grouped_experts, "route",
            lambda logits, bias, top_k, *a: tuple(
                jnp.pad(t, ((0, 0), (0, 1)), constant_values=c)
                for t, c in zip(route(logits, bias, top_k - 1, *a),
                                (-1, 0.0))))
    elif fault == "weights_not_normalised":
        monkeypatch.setattr(
            grouped_experts, "route",
            lambda logits, bias, top_k, normalise=True, scale=1.0:
            route(logits, bias, top_k, False, scale))
    elif fault == "bias_inside_the_weight":
        def biased(logits, bias, top_k, normalise=True, scale=1.0):
            experts, _ = route(logits, bias, top_k, normalise, scale)
            picked = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias,
                                         experts, axis=-1)
            if normalise:
                picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
            return experts, picked * scale
        monkeypatch.setattr(grouped_experts, "route", biased)
    elif fault == "no_shared_expert":
        linear = program._Linear.__call__
        monkeypatch.setattr(
            program._Linear, "__call__",
            lambda self, x: 0.0 * linear(self, x)
            if self.name == "shared_down" else linear(self, x))
    elif fault == "full_layer_rotated":
        norm = program._RMSNorm.__call__

        def rotating(self, x):
            y = norm(self, x)
            if self.name in ("q_norm", "k_norm") \
                    and self.path[-2] in full_layers:
                at = jnp.broadcast_to(jnp.arange(y.shape[1])[None],
                                      y.shape[:2])
                y = program.rope(y, at, 10000.0)
            return y
        monkeypatch.setattr(program._RMSNorm, "__call__", rotating)
    elif fault == "window_not_rotated":
        monkeypatch.setattr(program, "rope",
                            lambda x, pos, theta: x.astype(jnp.float32))
    elif fault == "window_one_short":
        config = program.AfmoeConfig    # (family.config looks it up anew)
        monkeypatch.setattr(
            program, "AfmoeConfig", lambda **kw: config(
                **{**kw, "sliding_window": kw["sliding_window"] - 1}))
    elif fault == "no_gate":
        monkeypatch.setattr(jax.nn, "sigmoid", _sigmoid_but_for_the_gate())
    elif fault == "no_embedding_multiplier":
        config = program.AfmoeConfig
        monkeypatch.setattr(
            program, "AfmoeConfig", lambda **kw: config(
                **{**kw, "mup_enabled": False}))
    else:
        raise ValueError(fault)


def _sigmoid_but_for_the_gate():
    """``jax.nn.sigmoid`` that reads 1 on an attention gate (the one
    sigmoid whose operand is ``(B, T, heads x head_dim)`` wide)."""
    sigmoid = jax.nn.sigmoid

    def gateless(x):
        wide = SHAPE["num_attention_heads"] * SHAPE["head_dim"]
        if x.ndim == 3 and x.shape[-1] == wide:
            return jnp.ones_like(x)
        return sigmoid(x)
    return gateless


@pytest.mark.parametrize("fault", PLANTED)
def test_planted_fault_is_seen(params, ref, monkeypatch, fault):
    plant(monkeypatch, fault)
    assert _full_forward_error(params, ref) > 100 * F32_TOL


# ------------------------------------------------------------- the share
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """(b) One expert layer at small size: the routed parts that the
    eight shares give (offsets 0, 2, .. 14 of 16 experts; each the
    program's expert layer minus the shared expert) plus the shared
    expert once equal the reference's layer with every expert held."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, 40, 64)), jnp.float32)
    whole = {**SHAPE, "num_experts": 16, "deployment": dict(expert_offset=0)}
    key = afmoe_weights.seed_key(3)
    w_all = afmoe_weights.make_canonical(key, whole)["layers"][1]
    z = afmoe_weights.sizes(whole)
    # the reference's stage norms its input itself: hand both the same
    w_ref = {**w_all, "mlp_in_g": jnp.ones((64,), jnp.float32)}
    h, experts, weights, _, shared = afmoe_reference._route(
        x[0], w_ref, z=z, mode="f32", router_dtype=jnp.float32)
    want = shared + afmoe_reference._expert_block(
        h, experts, weights, w_all["w_gate_up"], w_all["w_down"], 0, z=z,
        mode="f32")
    total, seen = None, 0
    for offset in range(0, 16, 2):
        shape = {**SHAPE, "deployment": dict(expert_offset=offset)}
        w = afmoe_weights.make_canonical(key, shape)["layers"][1]
        # the same router on every chip, an eighth of the experts each
        assert np.array_equal(w["w_router"], w_all["w_router"])
        assert np.array_equal(w["router_bias"], w_all["router_bias"])
        assert np.array_equal(w["w_down"], w_all["w_down"][offset:offset + 2])
        tree = family.program_tree(
            {"embed": 0, "head": 0, "normf_g": 0, "layers": [w_all, w]},
            shape)["layer_1_moe"]
        layer = program.ExpertLayer(family.config(shape, POSITIONS, **F32))
        y = layer.apply({"params": tree}, h[None], None)
        # the layer's counter: the rows its held experts took
        _, state = program.ExpertLayer(family.config(
            shape, POSITIONS, decode=True, **F32)).apply(
                {"params": tree,
                 "cache": {"expert_load": jnp.zeros((2,), jnp.int32)}},
                h[None], None, mutable=["cache"])
        seen += int(state["cache"]["expert_load"].sum())
        routed = y[0] - shared
        total = routed if total is None else total + routed
    assert seen == 40 * 4                   # every assignment, once
    assert float(jnp.abs(total + shared - want).max()) < 2e-5
    assert float(jnp.abs(want - shared).max()) > 0.1


# ----------------------------------------------------- the grouped product
def _expert_case(seed, n=24, d=16, f=8, held=4, all_experts=12):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_gate_up = jnp.asarray(rng.normal(size=(held, d, 2 * f)) * 0.3,
                            jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(held, f, d)) * 0.3, jnp.float32)
    logits = jnp.asarray(rng.normal(size=(n, all_experts)) * 2, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(all_experts,)) * 0.01, jnp.float32)
    return x, logits, bias, w_gate_up, w_down


def _masked_dense_experts(x, logits, bias, w_gate_up, w_down, *, top_k: int,
                         offset: int = 0, normalise: bool = True,
                         scale: float = 1.0, dtype=jnp.float32):
    """What ``held_experts`` must equal, the plain way: every token
    through every held expert, under a mask (``held`` times the work)."""
    held, f = w_down.shape[0], w_down.shape[1]
    experts, weights = grouped_experts.route(logits, bias, top_k, normalise,
                                            scale)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):
        gu = jnp.dot(x.astype(dtype), w_gate_up[e].astype(dtype),
                     preferred_element_type=jnp.float32)
        out = jnp.dot((jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(dtype),
                      w_down[e].astype(dtype),
                      preferred_element_type=jnp.float32)
        w_e = jnp.sum(jnp.where(experts == e + offset, weights, 0.0), -1)
        y = y + w_e[:, None] * out
    return y


@pytest.mark.parametrize("case", ["uneven", "an_expert_with_no_token",
                                  "a_token_with_no_local_expert",
                                  "pad_tokens"])
def test_grouped_product_equals_masked_dense(case):
    """(c) Sorted and grouped against every token through every held
    expert under a mask."""
    x, logits, bias, w_gate_up, w_down = _expert_case(1)
    offset, valid = 3, None
    if case == "uneven":
        logits = logits.at[:18, 4].add(9.0)     # expert 4 takes most
    elif case == "an_expert_with_no_token":
        logits = logits.at[:, 5].add(-50.0)
    elif case == "a_token_with_no_local_expert":
        logits = logits.at[:6, 3:7].add(-50.0)
    else:
        valid = jnp.arange(24) < 17
    kw = dict(top_k=3, offset=offset, scale=2.448, dtype=jnp.float32)
    got, sizes = grouped_experts.held_experts(
        x, logits, bias, w_gate_up, w_down, valid=valid, **kw)
    want = _masked_dense_experts(
        x, logits, bias, w_gate_up, w_down, **kw)
    if valid is not None:
        want = jnp.where(valid[:, None], want, 0.0)
    assert float(jnp.abs(got - want).max()) < OP_TOL
    experts, _ = grouped_experts.route(logits, bias, 3)
    local = np.asarray(experts) - offset
    if valid is not None:
        local = local[:17]
    assert sizes.tolist() == [int((local == e).sum()) for e in range(4)]
    if case == "an_expert_with_no_token":
        assert sizes[2] == 0
    if case == "a_token_with_no_local_expert":
        assert float(jnp.abs(got[:6]).max()) == 0.0
        assert float(jnp.abs(got[6:]).max()) > 0.0
    if case == "uneven":
        assert sizes[1] >= 18


# ---------------------------------------------------------------- the router
def test_route_scores_choice_and_weights():
    """(d) Sigmoid scores; the choice by ``s + b``; the weights by ``s``
    alone, over the chosen wherever they live; ``route_norm`` and
    ``route_scale``."""
    _, logits, _, _, _ = _expert_case(2)
    zero = jnp.zeros((12,), jnp.float32)
    experts, weights = grouped_experts.route(logits, zero, 4)
    scores = np.asarray(jax.nn.sigmoid(logits))
    order = np.argsort(-scores, -1)[:, :4]
    assert np.array_equal(np.asarray(experts), order)
    top = np.take_along_axis(scores, order, -1)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    _, raw = grouped_experts.route(logits, zero, 4, False, 2.448)
    np.testing.assert_allclose(raw, 2.448 * top, rtol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weight():
    _, logits, _, _, _ = _expert_case(2)
    zero = jnp.zeros((12,), jnp.float32)
    plain, _ = grouped_experts.route(logits, zero, 4, False)
    # expert 7 is chosen everywhere once its bias outweighs any score
    bias = zero.at[7].set(2.0)
    experts, weights = grouped_experts.route(logits, bias, 4, False)
    assert (np.asarray(experts) == 7).any(-1).all()
    assert not (np.asarray(plain) == 7).any(-1).all()
    at = np.argmax(np.asarray(experts) == 7, -1)
    scores = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), at[:, None], -1)[:, 0],
        scores[:, 7], rtol=1e-6)        # s, not s + b


def test_a_planted_tie_goes_to_the_lower_index():
    """Three experts with one score and room for one of them:
    ``lax.top_k`` keeps the lowest index, in program and reference alike
    (both call it on ``s + b``), and the reference's margin reads 0."""
    logits = jnp.asarray([[0.0, 1.0, 3.0, 1.0, 2.0, 1.0]], jnp.float32)
    experts, _ = grouped_experts.route(
        logits, jnp.zeros((6,), jnp.float32), 3, False)
    assert np.asarray(experts).tolist() == [[2, 4, 1]]
    # the reference norms its input (a positive factor: order and ties
    # stay) and multiplies by the router, here the identity
    w = {"mlp_in_g": jnp.ones((6,)), "w_router": jnp.eye(6),
         "router_bias": jnp.zeros((6,)), "ws_gate_up": jnp.zeros((6, 4)),
         "ws_down": jnp.zeros((2, 6))}
    z = dict(k=3, eps=0.0, route_norm=False, route_scale=1.0, offset=0,
             held=6, f=2)
    _, picked, _, margin, _ = afmoe_reference._route(
        logits, w, z=z, mode="f32", router_dtype=jnp.float32)
    assert np.asarray(picked).tolist() == [[2, 4, 1]]
    assert float(margin[0]) == 0.0


# ------------------------------------------------------------------- rotary
def test_rope_pairs_the_halves():
    """(e) The pair ``(i, i + D / 2)`` turns by ``pos * theta^(-2i/D)``:
    hand values at ``D = 4``, ``theta = 100``, position 3."""
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4)
    got = np.asarray(program.rope(x, jnp.asarray([[3]]), 100.0))[0, 0, 0]
    a0, a1 = 3.0, 3.0 * 100.0 ** -0.5      # 3 and 0.3
    want = [1 * np.cos(a0) - 3 * np.sin(a0), 2 * np.cos(a1) - 4 * np.sin(a1),
            3 * np.cos(a0) + 1 * np.sin(a0), 4 * np.cos(a1) + 2 * np.sin(a1)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the reference's, written apart, agrees
    np.testing.assert_allclose(
        np.asarray(afmoe_reference._rope(x[0], jnp.asarray([3]), 100.0))[0, 0],
        want, rtol=1e-6)


def test_a_piece_at_an_offset_rotates_as_the_whole():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 12, 2, 16)), jnp.float32)
    whole = program.rope(x, jnp.arange(12)[None], 10000.0)
    piece = program.rope(x[:, 8:], (8 + jnp.arange(4))[None], 10000.0)
    np.testing.assert_allclose(piece, whole[:, 8:], rtol=1e-6)
    # scores depend on the distance alone
    q, k = whole[0, 9, 0], whole[0, 4, 0]
    far = program.rope(x, 1000 + jnp.arange(12)[None], 10000.0)
    np.testing.assert_allclose(jnp.dot(q, k),
                               jnp.dot(far[0, 9, 0], far[0, 4, 0]),
                               rtol=1e-4)


def test_window_layers_are_rotated_and_the_full_layer_is_not(params):
    """Shift every position by 7 (a continue call at offset 7 from an
    empty cache): a window layer's stored keys change, the full layer's
    do not."""
    model = _model(decode=True)
    toks = np.asarray([_tokens(8, 8)], np.int32)
    zero = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 1), jnp.int32))["cache"]
    caches = []
    for off in (0, 7):
        _, updated = model.apply(
            {"params": params, "cache": zero}, jnp.asarray(toks),
            lengths=jnp.asarray([8]), offset=jnp.asarray([off]),
            mutable=["cache"])
        caches.append(updated["cache"])
    # layer 0 sees the embeddings alone: its keys differ by the rotation
    ring0 = np.asarray(caches[0]["layer_0_attn"]["ring_key"])[0, :, :8]
    ring7 = np.asarray(caches[1]["layer_0_attn"]["ring_key"])[0, :, 7:15]
    assert np.abs(ring0 - ring7).max() > 0.1
    np.testing.assert_allclose(
        np.linalg.norm(ring0, axis=-1), np.linalg.norm(ring7, axis=-1),
        rtol=1e-5)                      # a rotation keeps the length
    # values are never rotated
    np.testing.assert_allclose(
        np.asarray(caches[0]["layer_0_attn"]["ring_value"])[0, :, :8],
        np.asarray(caches[1]["layer_0_attn"]["ring_value"])[0, :, 7:15],
        rtol=1e-6)


# ------------------------------------------------------------- the engine
def _client(params, telemetry=None, **kw):
    kw = {"num_slots": 3, "prefill_len": 16, "prefill_batch": 2,
          "prefill_chunk": 16, **kw}
    return ServeClient(_model(decode=True), params, telemetry=telemetry,
                       **kw)


def _serve(client, requests):
    ids = [client.submit(prompt=p, max_new_tokens=n, temperature=0.0)
           for p, n in requests]
    done = {}
    while len(done) < len(ids):
        for comp in client.tick():
            done[comp.request_id] = comp
    return [done[i] for i in ids]


def test_dense_slot_chunked_prefill_without_recurrent_state(params, ref):
    """(f) ``prefill_chunk`` on dense slots for a model that declares
    ``continues_prefill`` and no ``recurrent_state``: long prompts enter
    through the chunk program (the 70-token one wraps the ring of 24
    twice while decode steps run between its pieces and park their
    writes on it), short ones through the batched prefill; every served
    token is the reference's best."""
    assert not getattr(AfmoeLM, "recurrent_state", False)
    client = _client(params)
    assert client.engine.prefill_chunk == 16 and not client.engine.paged
    requests = [(_tokens(30, 70), 8), (_tokens(31, 9), 30),
                (_tokens(32, 16), 6), (_tokens(33, 41), 10),
                (_tokens(34, 50), 12)]
    done = _serve(client, requests)
    assert client.engine.chunk_dispatches >= 7
    for (prompt, n), comp in zip(requests, done):
        assert len(comp.tokens) == n
        seq = prompt + list(comp.tokens)
        logits = ref(seq, np.arange(len(prompt) - 1, len(seq) - 1))
        gap = logits.max(-1) - logits[np.arange(n), comp.tokens]
        assert gap.max() < F32_TOL


def test_expert_load_counters_on_the_dispatch_spans(params):
    """Armed: ``engine.step.call`` and ``engine.chunk.call`` carry the
    expert layers' counts; the step's are over every row the program
    runs, the chunk's over the valid tokens of its pieces."""
    tel = Telemetry()
    client = _client(params, telemetry=tel)
    _serve(client, [(_tokens(40, 40), 5), (_tokens(41, 5), 5)])
    layers = SHAPE["num_hidden_layers"] - SHAPE["num_dense_layers"]
    held = SHAPE["num_experts"]
    steps = tel.spans.spans("engine.step.call")
    chunks = tel.spans.spans("engine.chunk.call")
    assert steps and chunks
    for span in steps + chunks:
        a = span.args
        assert a["moe_experts"] == layers * held
        assert 0 <= a["moe_experts_hit"] <= a["moe_experts"]
        assert a["moe_load_max"] <= a["moe_assignments"]
        rows = 3 if span.name == "engine.step.call" else a["tokens"]
        assert a["moe_assignments"] <= rows * 4 * layers
    # 40 prompt tokens x 3 expert layers x 4 experts, an eighth local
    fed = sum(s.args["moe_assignments"] for s in chunks)
    assert 0.4 * 60 < fed < 1.8 * 60
    # unarmed: the same programs, nothing read
    client = _client(params)
    _serve(client, [(_tokens(40, 20), 3)])
    assert client.engine._has_counters


def test_armed_syncs_fetch_the_counters_in_one_batch(params, monkeypatch):
    """Armed, a step's sync makes two fetches — the report, then the
    counter leaves as one list — and says so (``copies``,
    ``serve_sync_copies_total``); a chunk's sync makes one, the first
    tokens and the leaves together. Unarmed, one each and no leaf."""
    from ray_lightning_tpu.serve import engine as E
    seen = []
    real = E._fetch
    monkeypatch.setattr(E, "_fetch",
                        lambda tree: (seen.append(tree), real(tree))[1])
    layers = SHAPE["num_hidden_layers"] - SHAPE["num_dense_layers"]
    work = [(_tokens(40, 40), 5), (_tokens(41, 5), 5)]
    tel = Telemetry()
    client = _client(params, telemetry=tel)
    _serve(client, work)
    steps, chunks = client.engine.steps, client.engine.chunk_dispatches
    assert steps and chunks and len(seen) == 2 * steps + chunks
    lists = [t for t in seen if isinstance(t, list)]
    pairs = [t for t in seen if isinstance(t, tuple)]
    assert len(lists) == steps and all(len(t) == layers for t in lists)
    assert len(pairs) == chunks and all(len(t[1]) == layers for t in pairs)
    syncs = tel.spans.spans("engine.step.sync")
    assert [s.args["copies"] for s in syncs] == [2] * steps
    assert tel.metrics.snapshot()["serve_sync_copies_total"] == 2 * steps
    del seen[:]
    client = _client(params)
    _serve(client, work)
    assert len(seen) == client.engine.steps + client.engine.chunk_dispatches
    assert not any(isinstance(t, (list, tuple)) for t in seen)


def test_byte_counters_follow_the_leaf_kinds(params):
    """``window_bytes`` is a row's rings, whole, whatever its context;
    ``global_bytes`` grows with the context."""
    tel = Telemetry()
    client = _client(params, telemetry=tel)
    _serve(client, [(_tokens(50, 10), 40)])
    spans = tel.spans.spans("engine.step.call")
    ring = 3 * 2 * 2 * WINDOW * 16 * 4      # 3 layers, K and V, float32
    per_position = 2 * 2 * 16 * 4           # the one full layer
    assert {s.args["window_bytes"] for s in spans} == {ring}
    assert all(s.args["recurrent_bytes"] == 0 for s in spans)
    # one active row: 11 live positions at the first step, 49 at the last
    assert spans[0].args["global_bytes"] == per_position * 11
    assert spans[-1].args["global_bytes"] == per_position * 49
    assert spans[-1].args["global_bytes"] > ring // 3 > 0


@pytest.mark.parametrize("kw,name", [
    (dict(page_size=8), "page_size"),
    (dict(page_size=8, prefill_chunk=16), "page_size"),
    (dict(page_size=8, page_native=True), "page_size"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(page_size=8, prefill_chunk=16, prefix_cache=True), "prefix_cache"),
    (dict(draft="self"), "draft_model"),
    (dict(max_resident_adapters=2, lora_rank=2), "max_resident_adapters")],
    ids=["pages", "paged_chunks", "page_native", "int8_kv", "prefix_cache",
         "speculative", "lora_bank"])
def test_engine_refuses_by_name_what_it_cannot_give(params, kw, name):
    model = _model(decode=True)
    if kw.pop("draft", None):
        kw.update(draft_model=model, draft_params=params)
    with pytest.raises(ValueError) as err:
        ServeEngine(model, params, num_slots=2, prefill_len=16, **kw)
    assert "AfmoeLM declares a cache that only its continue mode " \
        "writes" in str(err.value)
    assert name in str(err.value)


def test_config_refuses_what_the_block_is_not_written_for():
    with pytest.raises(ValueError, match="not among the router's"):
        AfmoeConfig(experts_held=32, expert_offset=230)
    with pytest.raises(ValueError, match="not tied"):
        AfmoeConfig(tie_word_embeddings=True)
    with pytest.raises(ValueError, match="sigmoid router"):
        AfmoeConfig(score_func="softmax")
    with pytest.raises(ValueError, match="no group limit"):
        AfmoeConfig(n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="layer_types names every layer"):
        AfmoeConfig(num_hidden_layers=5)
