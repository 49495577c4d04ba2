"""The slot pool's two writers (``ops/cache_write.py``) against the forms
they replaced, bit for bit, and the shape of the programs they leave.

``write_rows`` replaced a ``vmap`` of ``dynamic_update_slice`` with a
per-row start (jax batches it into one ``stablehlo.scatter``),
``inject_rows`` a whole-pool ``where(keep, pool, take(prefill,
slot_map))``; on the TPU both were rebuilt into fusions whose result is
the whole leaf (PERF.md section 6, PR 29). The old forms live on here as
the references. The structural cases read the lowered text of the two
engine programs of each serve family: no scatter and no select may have
a per-slot cache leaf's shape as its result.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_lightning_tpu.models.generate import CacheLeaf, cache_layout
from ray_lightning_tpu.ops.cache_write import inject_rows, write_rows
from ray_lightning_tpu.serve import ServeClient
from ray_lightning_tpu.serve import engine as E

pytestmark = pytest.mark.serve

B, L, H, D = 5, 16, 2, 4


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _normal(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _vmapped_write(cache, block, start):
    return jax.vmap(lambda c, u, i: lax.dynamic_update_slice(
        c, u, (i, 0, 0)))(cache, block, start)


# ragged: every row at its own position; last: a row parked at the last
# position (a T = 4 block there clamps to L - 4 in both forms); ring: a
# window of L positions written at pos % L by requests past their wrap
STARTS = {"ragged": [0, 3, 7, 11, 2],
          "last": [L - 1, 5, L - 1, 0, L - 2],
          "ring": [p % L for p in (L + 3, 2 * L, L - 1, 3 * L + 15, 4)]}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("starts", sorted(STARTS))
@pytest.mark.parametrize("T", [1, 4])
def test_write_rows_is_the_vmapped_update_slice(T, starts, dtype):
    ck, cv = (_normal(s, (B, L, H, D), dtype) for s in (0, 1))
    k, v = (_normal(s, (B, T, H, D), dtype) for s in (2, 3))
    start = jnp.asarray(STARTS[starts], jnp.int32)
    got_k, got_v = jax.jit(write_rows)((ck, cv), (k, v), start)
    alone = write_rows(ck, k, start)
    for got, cache, block in ((got_k, ck, k), (got_v, cv, v), (alone, ck, k)):
        want = _vmapped_write(cache, block, start)
        assert got.dtype == want.dtype == dtype
        assert np.array_equal(_bits(got), _bits(want))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("T", [1, 3])
def test_write_rows_is_one_aliased_kernel_call_a_position(T):
    """What the chip runs is the kernel, not its interpretation here: K
    and V of a layer go through one ``pallas_call`` a position, each
    leaf aliased to its output, and nothing scatters."""
    leaf = jnp.zeros((B, L, H, D), jnp.bfloat16)
    block = jnp.ones((B, T, H, D), jnp.bfloat16)
    eqns = list(_eqns(jax.make_jaxpr(write_rows)(
        (leaf, leaf), (block, block), jnp.zeros((B,), jnp.int32)).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == T
    for call in calls:
        # operands: starts, K's and V's new position, K's and V's view
        assert dict(call.params["input_output_aliases"]) == {3: 0, 4: 1}
    names = {e.primitive.name for e in eqns}
    assert not names & {"scatter", "scatter-add", "dynamic_update_slice"}


def _whole_pool_select(pool, rows, layout, slots, valid):
    """The inject as PR 28 had it."""
    n_rows = slots.shape[0]
    num_slots = next(
        leaf.shape[decl.slot_axis] for leaf, decl in zip(
            jax.tree_util.tree_leaves(pool),
            jax.tree_util.tree_leaves(layout)) if decl.per_slot)
    slot_map = jnp.full((num_slots,), -1, jnp.int32).at[
        jnp.where(valid, slots, num_slots)].set(
            jnp.arange(n_rows, dtype=jnp.int32), mode="drop")

    def inject(leaf, new, decl):
        if not decl.per_slot:
            return leaf
        shape = [1] * leaf.ndim
        shape[decl.slot_axis] = num_slots
        return jnp.where((slot_map < 0).reshape(shape), leaf, jnp.take(
            new, jnp.maximum(slot_map, 0), axis=decl.slot_axis))

    return jax.tree_util.tree_map(inject, pool, rows, layout)


SLOTS, ROWS = 6, 4
LEAVES = {  # name -> (shape of the leaf at n rows, its declaration)
    "state": (lambda n: (n, 3, 8), CacheLeaf(0, "recurrent")),
    "kv": (lambda n: (n, L, H, D), CacheLeaf(0, "global", 1)),
    "stacked": (lambda n: (3, n, L, H, D), CacheLeaf(1, "global", 2)),
}
FILLS = {  # name -> (slots, valid); an invalid row's slot is the
    # engine's 0, or collides with a valid row's before or after it
    "none": ([0, 0, 0, 0], [False] * 4),
    "one": ([4, 0, 0, 0], [True, False, False, False]),
    "all": ([5, 0, 3, 2], [True] * 4),
    "collide": ([2, 0, 0, 2], [False, True, False, True]),
}


@pytest.mark.parametrize("fill", sorted(FILLS))
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_inject_rows_is_the_whole_pool_select(leaf, fill):
    shape, decl = LEAVES[leaf]
    dtype = jnp.float32 if leaf == "state" else jnp.bfloat16
    pool = {"x": _normal(0, shape(SLOTS), dtype),
            "cache_index": jnp.asarray(7, jnp.int32)}
    rows = {"x": _normal(1, shape(ROWS), dtype),
            "cache_index": jnp.asarray(9, jnp.int32)}
    layout = {"x": decl, "cache_index": CacheLeaf(None)}
    slots, valid = (jnp.asarray(a) for a in FILLS[fill])
    got = jax.jit(lambda *a: inject_rows(a[0], a[1], layout, *a[2:]))(
        pool, rows, slots, valid)
    want = _whole_pool_select(pool, rows, layout, slots, valid)
    assert int(got["cache_index"]) == 7
    assert got["x"].dtype == dtype
    assert np.array_equal(_bits(got["x"]), _bits(want["x"]))
    if fill != "none":      # the case moves something
        assert not np.array_equal(_bits(got["x"]), _bits(pool["x"]))


# ------------------------------------------------------------ structure
def _gpt2_client(serve_nano_family):
    dec, params = serve_nano_family[:2]
    return ServeClient(dec, params, num_slots=3, prefill_len=8,
                       prefill_batch=2)


def _sambay_client(_):
    from benchmark import sambay_weights
    from benchmark.families import phi4flash
    from ray_lightning_tpu.models.sambay import SambaYLM
    from tests.test_sambay import POSITIONS, SHAPE
    params = phi4flash.program_tree(sambay_weights.make_canonical(
        sambay_weights.seed_key(3), SHAPE), SHAPE)
    model = SambaYLM(phi4flash.config(SHAPE, POSITIONS, decode=True,
                                      dtype=jnp.float32))
    return ServeClient(model, params, num_slots=3, prefill_len=16,
                       prefill_batch=2)


def _prefill_operands(engine):
    """What ``ServeEngine.prefill`` hands ``_prefill_inject_impl``: the
    engine's own model, weights and pool, an empty batch."""
    n, p = engine.prefill_batch, engine.prefill_len
    rows = np.zeros((n,), np.int32)
    return (engine.model, engine.params, engine.pool.cache,
            np.zeros((n, p), np.int32), rows + 1, rows, rows > 0,
            np.zeros((n, 2), np.uint32), rows.astype(np.float32), rows,
            rows, None)


_MLIR_DTYPE = {"float32": "f32", "bfloat16": "bf16"}
# a scatter carries a region; its result type closes it
_SCATTER = re.compile(
    r'"stablehlo\.scatter".*?\}\) : \([^)]*\) -> (tensor<[^>]*>)', re.S)
_SELECT = re.compile(r"stablehlo\.select .* (tensor<[^>]*>)$", re.M)


def whole_leaf_rewrites(text, engine):
    """Scatters and selects of ``text`` whose result is a per-slot cache
    leaf of ``engine``'s pool."""
    cache = engine.pool.cache
    leaf_types = {
        "tensor<%s>" % "x".join(
            [str(d) for d in leaf.shape] + [_MLIR_DTYPE[leaf.dtype.name]])
        for leaf, decl in zip(
            jax.tree_util.tree_leaves(cache),
            jax.tree_util.tree_leaves(cache_layout(engine.model, cache)))
        if decl.per_slot}
    assert leaf_types
    found = _SCATTER.findall(text) + _SELECT.findall(text)
    assert found    # the programs do hold both ops (sampling, masks)
    return [t for t in found if t in leaf_types]


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("family", [_gpt2_client, _sambay_client],
                         ids=["gpt2", "sambay"])
def test_engine_programs_rewrite_no_cache_leaf(serve_nano_family, family,
                                               program):
    client = family(serve_nano_family)
    engine = client.engine
    text = engine.lowered_step_text() if program == "step" else \
        E._prefill_inject_plain.lower(*_prefill_operands(engine)).as_text()
    rewrites = whole_leaf_rewrites(text, engine)
    client.shutdown()
    assert "stablehlo.dynamic_update_slice" in text
    assert rewrites == []


def test_the_structural_reading_sees_the_old_forms(serve_nano_family,
                                                   monkeypatch):
    """The reading above is not blind: with the replaced forms back in
    their seats it finds the scatters of K and V in the step program
    and the selects in the prefill program (jax lowers ``where`` to one
    function a shape, so the text holds fewer selects than leaves)."""
    from ray_lightning_tpu.models import transformer

    def old_write(caches, blocks, start):
        return jax.tree_util.tree_map(
            lambda c, u: _vmapped_write(c, u, start), caches, blocks)

    monkeypatch.setattr(transformer, "write_rows", old_write)
    monkeypatch.setattr(E, "inject_rows", _whole_pool_select)
    client = _gpt2_client(serve_nano_family)
    engine = client.engine
    # jit caches a trace by function and arguments: lower the impls anew
    _, args = engine._step_call()
    step = jax.jit(lambda *a: E._engine_step_impl(*a, steps=1),
                   static_argnums=0).lower(*args).as_text()
    prefill = jax.jit(lambda *a: E._prefill_inject_impl(*a),
                      static_argnums=0).lower(
        *_prefill_operands(engine)).as_text()
    found = [len(whole_leaf_rewrites(text, engine))
             for text in (step, prefill)]
    client.shutdown()
    assert found[0] == 2 * engine.model.cfg.n_layers and found[1] > 0
