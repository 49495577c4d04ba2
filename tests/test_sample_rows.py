"""Per-row sampling (PR 31): the ``top_k`` mask comes from
``lax.top_k``'s candidates, the whole-vocabulary ranking only when a row
asks for more than ``TOP_K_CANDIDATES``.

- ``sample_logits_rows`` returns the tokens of the definition it
  replaced — kept HERE (``argsort(-l)``, rank scatter) — on every row:
  ``top_k`` at 1, 20, ``K``, ``K + 1`` and ``V``, mixed greedy / sampled
  / unrestricted rows, exact ties across the k-th place, an all-greedy
  batch, GPT-2's vocabulary and a nano one.
- ``spec._row_probs`` is the softmax of the same processed logits.
- A row's token does not depend on its neighbours' ``top_k``.
- The program, not the result: the narrow branch holds a ``top_k`` and
  neither a sort nor a scatter, and the ranking is reached only under
  ``max(top_k) > K``.
- ``topk_wide`` / ``serve_sample_topk_wide_total`` read what the program
  did.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generate import (TOP_K_CANDIDATES,
                                               processed_logits_row,
                                               sample_logits_rows)
from ray_lightning_tpu.serve import spec

K = TOP_K_CANDIDATES
GPT2_V = 50257
NANO_V = 97
B = 8


def reference_rows(logits, keys, temperature, top_k):
    """``sample_logits_rows`` as it stood before PR 31, without its
    batch-level gates: every row ranks the whole vocabulary."""
    def row(l, k, t, tk):
        greedy = jnp.argmax(l).astype(jnp.int32)
        scaled = l / jnp.where(t > 0, t, 1.0)
        order = jnp.argsort(-l)
        ranks = jnp.zeros_like(order).at[order].set(
            jnp.arange(l.shape[0], dtype=order.dtype))
        scaled = jnp.where((tk > 0) & (ranks >= tk),
                           jnp.finfo(jnp.float32).min, scaled)
        sampled = jax.random.categorical(k, scaled).astype(jnp.int32)
        return jnp.where(t > 0, sampled, greedy)

    return jax.vmap(row)(logits, keys, jnp.asarray(temperature),
                         jnp.asarray(top_k))


_new = jax.jit(sample_logits_rows)
_old = jax.jit(reference_rows)


def batch(V, seed, ties=False):
    rng = np.random.default_rng(seed)
    logits = (3 * rng.standard_normal((B, V))).astype(np.float32)
    if ties:
        # quarters: hundreds of exact ties at every level of a row
        logits = np.round(logits * 4) / 4
    keys = np.asarray(jax.vmap(
        lambda i: jax.random.fold_in(jax.random.PRNGKey(seed), i))(
            jnp.arange(B)))
    return logits, keys


def top_k_case(name, V, rng):
    """(temperature, top_k) of one named batch."""
    sampled = np.full((B,), 0.8, np.float32)
    if name == "all_greedy":
        return np.zeros((B,), np.float32), np.full((B,), 20, np.int32)
    if name == "mixed":
        # greedy rows, sampled rows, unrestricted rows, every k up to K
        t = np.where(np.arange(B) % 3 == 0, 0.0, 0.7).astype(np.float32)
        tk = rng.integers(0, K + 1, size=B).astype(np.int32)
        tk[1] = 0
        return t, tk
    if name == "mixed_wide":
        t = np.where(np.arange(B) % 3 == 0, 0.0, 1.3).astype(np.float32)
        tk = rng.integers(0, 4 * K, size=B).astype(np.int32)
        tk[2], tk[5] = 0, K + 1
        return t, tk
    k = {"1": 1, "20": 20, "K": K, "K+1": K + 1, "V": V}[name]
    return sampled, np.full((B,), k, np.int32)


CASES = ["1", "20", "K", "K+1", "V", "mixed", "mixed_wide", "all_greedy"]


@pytest.mark.parametrize("ties", [False, True], ids=["plain", "ties"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("V", [GPT2_V, NANO_V])
def test_tokens_are_the_ranking_definitions(V, case, ties):
    seed = CASES.index(case) + 100 * ties
    logits, keys = batch(V, seed, ties)
    t, tk = top_k_case(case, V, np.random.default_rng(seed))
    got = np.asarray(_new(logits, keys, t, tk))
    want = np.asarray(_old(logits, keys, t, tk))
    np.testing.assert_array_equal(got, want)
    greedy = t == 0
    np.testing.assert_array_equal(got[greedy],
                                  logits[greedy].argmax(-1))


def test_ties_across_the_kth_place_keep_exactly_k():
    # one level holds places 15..44 of every row: k = 20 cuts through it
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((B, GPT2_V)).astype(np.float32)
    for r in range(B):
        cols = rng.permutation(GPT2_V)
        logits[r, cols[:15]] = 9.0 + np.arange(15)
        logits[r, cols[15:45]] = 8.0
    t, tk = np.full((B,), 1.0, np.float32), np.full((B,), 20, np.int32)
    for path in ("narrow", "wide"):
        kept = np.asarray(jax.vmap(
            lambda l, tt, kk: processed_logits_row(l, tt, kk, path))(
                logits, t, tk)) > np.finfo(np.float32).min
        assert kept.sum(-1).tolist() == [20] * B
        for r in range(B):
            tied = np.flatnonzero(logits[r] == 8.0)
            # the lower index first among equal logits
            np.testing.assert_array_equal(
                np.flatnonzero(kept[r] & (logits[r] == 8.0)), tied[:5])


@pytest.mark.parametrize("case", ["20", "mixed", "mixed_wide", "K+1"])
def test_row_probs_is_the_softmax_of_the_processed_logits(case):
    logits, _ = batch(NANO_V, 11, ties=True)
    t, tk = top_k_case(case, NANO_V, np.random.default_rng(11))
    probs = np.asarray(spec._row_probs(jnp.asarray(logits), t, tk))
    for path in ("narrow", "wide"):
        if path == "narrow" and tk.max() > K:
            continue
        want = jax.vmap(lambda l, tt, kk: jax.nn.softmax(
            processed_logits_row(l, tt, kk, path)))(logits, t, tk)
        np.testing.assert_allclose(probs, np.asarray(want), rtol=1e-6)
    kept = (probs > 0).sum(-1)
    limited = tk > 0
    np.testing.assert_array_equal(kept[limited],
                                  np.minimum(tk, NANO_V)[limited])
    assert (kept[~limited] == NANO_V).all()


@pytest.mark.parametrize("V", [GPT2_V, NANO_V])
def test_a_rows_token_does_not_depend_on_its_neighbours_top_k(V):
    logits, keys = batch(V, 21, ties=True)
    t = np.full((B,), 0.9, np.float32)
    narrow = np.full((B,), 20, np.int32)
    wide = narrow.copy()
    wide[B - 1] = V                # the batch now ranks the vocabulary
    a = np.asarray(_new(logits, keys, t, narrow))
    b = np.asarray(_new(logits, keys, t, wide))
    np.testing.assert_array_equal(a[:B - 1], b[:B - 1])


# --------------------------------------------------------------------- #
# the program
# --------------------------------------------------------------------- #
def eqns_of(jaxpr):
    """Every equation under ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_of(sub)


def primitives(jaxpr):
    return {e.primitive.name for e in eqns_of(jaxpr)}


def test_narrow_branch_holds_a_top_k_and_no_ranking():
    rows, V = 40, GPT2_V
    narrow = jax.make_jaxpr(lambda l, t, tk: jax.vmap(
        lambda l, t, tk: processed_logits_row(l, t, tk, "narrow"))(
            l, t, tk))(
        jnp.zeros((rows, V), jnp.float32), jnp.zeros((rows,), jnp.float32),
        jnp.zeros((rows,), jnp.int32)).jaxpr
    names = primitives(narrow)
    assert "top_k" in names
    assert "sort" not in names
    # the keep set is compares against the k-th candidate: no scatter,
    # of ranks or of anything else
    assert not [n for n in names if n.startswith("scatter")]


def wide_branch(cond, producer):
    """1 when ``cond``'s predicate is ``max(<int row>) > K`` (branch 1
    is a ``lax.cond``'s true side), else None."""
    chain, var = [], cond.invars[0]
    while var in producer and len(chain) < 3:
        chain.append(producer[var])
        var = chain[-1].invars[0]
    if [e.primitive.name for e in chain] != [
            "convert_element_type", "gt", "reduce_max"]:
        return None
    bound = chain[1].invars[1]
    return 1 if int(getattr(bound, "val", -1)) == K else None


def test_ranking_is_reached_only_under_max_top_k_above_K():
    found = {"sort": 0, "top_k": 0, "wide_conds": 0}

    def walk(jaxpr, under_wide):
        producer = {v: e for e in jaxpr.eqns for v in e.outvars}
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in found:
                found[name] += 1
            ranks = name == "sort" or (
                name.startswith("scatter")
                and eqn.invars[2].aval.shape[-1] == GPT2_V)
            assert under_wide or not ranks, \
                f"{name} over the vocabulary outside the wide branch"
            wide = wide_branch(eqn, producer) if name == "cond" else None
            found["wide_conds"] += wide is not None
            subs = ([b.jaxpr for b in eqn.params["branches"]]
                    if name == "cond"
                    else jax.core.jaxprs_in_params(eqn.params))
            for i, sub in enumerate(subs):
                walk(sub, under_wide or i == wide)

    rows = 40
    walk(jax.make_jaxpr(sample_logits_rows)(
        jnp.zeros((rows, GPT2_V), jnp.float32),
        jnp.zeros((rows, 2), jnp.uint32), jnp.zeros((rows,), jnp.float32),
        jnp.zeros((rows,), jnp.int32)).jaxpr, False)
    # one cond on max(top_k) > K: the ranking on its true side, the
    # candidates on the other
    assert found == {"sort": 1, "top_k": 1, "wide_conds": 1}


# --------------------------------------------------------------------- #
# the counter
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def nano():
    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    mk = dict(vocab_size=128, max_seq_len=64, dtype=jnp.float32,
              scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(0), np.zeros((2, 4), np.int32))["params"]
    return dec, params


def served(nano, top_ks, prompt=(5, 17, 3, 9), **kw):
    from ray_lightning_tpu.obs import Telemetry
    from ray_lightning_tpu.serve import ServeClient
    dec, params = nano
    tel = Telemetry()
    client = ServeClient(dec, params, telemetry=tel, num_slots=3,
                         prefill_len=16, prefill_batch=2, **kw)
    for i, tk in enumerate(top_ks):
        client.submit([*prompt, 2 + i], max_new_tokens=4 + 2 * i,
                      temperature=0.8, top_k=tk)
    client.run_until_idle()
    return tel


def wide_flags(tel, name):
    return [s.args["topk_wide"] for s in tel.spans.spans(name)]


def test_topk_wide_reads_zero_up_to_K(nano):
    tel = served(nano, [20, K])
    assert set(wide_flags(tel, "engine.prefill.call")) == {0}
    steps = wide_flags(tel, "engine.step.call")
    assert steps and set(steps) == {0}
    assert tel.metrics.snapshot()["serve_sample_topk_wide_total"] == 0


def test_topk_wide_counts_the_dispatches_with_a_row_above_K(nano):
    # the wide request is the shorter one: the steps after it retires
    # are narrow again, its freed row does not hold the batch wide
    tel = served(nano, [K + 1, 20])
    assert wide_flags(tel, "engine.prefill.call") == [1]
    steps = wide_flags(tel, "engine.step.call")
    assert steps[0] == 1 and steps[-1] == 0
    assert steps == sorted(steps, reverse=True)
    assert tel.metrics.snapshot()["serve_sample_topk_wide_total"] \
        == 1 + sum(steps)


def test_topk_wide_on_a_chunk_dispatch(nano):
    tel = served(nano, [K + 1], prompt=range(1, 11), page_size=8,
                 num_pages=24, prefill_chunk=8)       # 11 tokens: 8 + 3
    assert wide_flags(tel, "engine.chunk.call") == [1, 1]
