"""Paged KV allocation: fixed-size pages from one arena + prefix reuse.

This is the host-side half of the paged serving engine (vLLM's
PagedAttention block allocator reshaped for XLA — see
``docs/serving.md``): instead of reserving one whole
``(max_seq_len, H, D)`` KV row per request, :class:`PagePool` backs each
request's logical KV with fixed-size **pages** cut from one
``(num_pages, page_size, H, D)`` arena per KV leaf, tracked by a per-slot
**page table** — a plain ``(num_slots, pages_per_slot)`` int32 gather
index the engine materializes a dense view from around its fixed-shape
compiled programs. A 30-token chat request holds
``ceil((prompt + budget) / page_size)`` pages instead of a
``max_seq_len`` row, so ``num_slots`` (the step program's batch, i.e.
concurrency) decouples from KV memory (the arena).

All allocation decisions are host-side, exact, and deterministic:
lowest-index-first for both slots and pages, so identical op sequences
produce identical page tables (pinned by ``tests/test_paged.py``).

:class:`PrefixCache` adds shared-prefix reuse on top: prompt prefixes
are content-keyed at page granularity (chain links
``(parent_entry_id, page_tokens)`` — equivalent to keying page ``j`` on
the full ``prompt[:(j+1)*page_size]`` tuple, collision-free by
construction, but each key stays O(page_size)), and a request whose
prompt extends a cached chain adopts those pages **read-only**
(refcounted) instead of re-prefilling them.
The cache holds its own reference on every published page, so a
retired publisher keeps its prefix warm; eviction under pressure drops
least-recently-matched entries whose page only the cache still holds.
"""
from __future__ import annotations

from bisect import insort
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models.generate import cache_layout
from ray_lightning_tpu.models.quant import (kv_dequantize, kv_quantize,
                                            kv_scales)
from ray_lightning_tpu.serve.request import OccupancyError

#: accepted ``kv_dtype`` spellings: None/"bf16" = store KV at the model's
#: compute dtype (the default, byte-identical to the pre-quantization
#: engines); "int8" = absmax-scaled int8 storage with f32 scales in a
#: parallel leaf (LLM.int8-style storage-only quantization: compute
#: stays at cfg.dtype, only the at-rest arena bytes halve)
KV_DTYPE_INT8 = "int8"


def check_kv_dtype(kv_dtype) -> bool:
    """Normalize/validate a ``kv_dtype`` option; returns True for the
    quantized path."""
    if kv_dtype in (None, "bf16"):
        return False
    if kv_dtype == KV_DTYPE_INT8:
        return True
    raise ValueError(
        f"kv_dtype must be None, 'bf16' or 'int8', got {kv_dtype!r}")


# ---------------------------------------------------------------- int8 KV
# Quantized KV storage is a 2-tuple ``(q_tree, s_tree)`` with the SAME
# pytree structure as the plain cache: per-slot KV leaves hold int8
# codes in ``q_tree`` and f32 absmax scales (keepdims, reduced axes per
# granularity) in ``s_tree``; shared bookkeeping leaves (cache_index)
# live unchanged in ``q_tree`` with a zero-size placeholder in
# ``s_tree``. Which leaf is which is the model's own declaration
# (``generate.cache_layout``), never a guess from the leaf's rank. The
# tuple flows through the jitted programs as an
# ordinary pytree — dequantize on the way in, re-quantize on the way
# out, both fused into the dispatch.
#
# The absmax quantize/dequantize math itself lives in models/quant.py
# (imported above): the page-native attention path inside the model
# needs the identical functions, and models must not depend on serve —
# re-exported here so existing callers keep their import site.


def slot_leaves(model, tree):
    """``tree`` (of the cache collection's structure) -> the same tree of
    booleans: True where the model declares the leaf per-slot K/V, False
    for shared bookkeeping."""
    return jax.tree_util.tree_map(lambda leaf: leaf.per_slot,
                                  cache_layout(model, tree))


def _dense_reduce_axes(leaf) -> Tuple[int, ...]:
    # dense pool granularity: per (slot, position, head) — reduce the
    # head_dim axis only (finest practical: scales add ~1/(2*D) bytes)
    return (leaf.ndim - 1,)


def quantize_dense_cache(model, values):
    """Plain dense cache tree → the ``(q, s)`` storage tuple
    (per-position-per-head scales)."""
    def q_leaf(leaf, kv):
        if not kv:
            return leaf
        return kv_quantize(leaf, kv_scales(leaf, _dense_reduce_axes(leaf)))

    def s_leaf(leaf, kv):
        if not kv:
            return jnp.zeros((), jnp.float32)
        return kv_scales(leaf, _dense_reduce_axes(leaf))

    tm = jax.tree_util.tree_map
    kv = slot_leaves(model, values)
    return tm(q_leaf, values, kv), tm(s_leaf, values, kv)


@jax.named_scope("kv_load")
def dense_storage_values(model, storage):
    """Materialize compute-dtype KV values from dense storage: identity
    for plain storage, dequantize for the ``(q, s)`` int8 tuple (the
    bookkeeping leaves pass through from ``q``)."""
    if not isinstance(storage, tuple):
        return storage
    q, s = storage
    dt = model.cfg.dtype
    return jax.tree_util.tree_map(
        lambda ql, sl, kv: kv_dequantize(ql, sl, dt) if kv else ql,
        q, s, slot_leaves(model, q))


@jax.named_scope("kv_commit")
def dense_storage_commit(model, storage, values):
    """Write updated compute-dtype values back into dense storage:
    identity for plain storage, re-quantize for int8 (untouched rows
    round-trip idempotently: absmax codes saturate at exactly 127, so
    re-quantizing a dequantized group reproduces the same codes and
    scales — parked rows stay frozen through any number of dispatches)."""
    if not isinstance(storage, tuple):
        return values
    q, s = storage

    def commit_q(ql, vl, kv):
        if not kv:
            return vl   # updated bookkeeping lives in the q tree
        return kv_quantize(vl, kv_scales(vl, _dense_reduce_axes(vl)))

    def commit_s(sl, vl, kv):
        if not kv:
            return sl
        return kv_scales(vl, _dense_reduce_axes(vl))

    tm = jax.tree_util.tree_map
    kv = slot_leaves(model, q)
    return tm(commit_q, q, values, kv), tm(commit_s, s, values, kv)


# --------------------------------------------------- arena gather/scatter
def page_axis(model) -> int:
    """Arena/cache leaves are ``(pages|B, seq, H, D)`` unrolled or
    ``(n_layers, pages|B, seq, H, D)`` scanned — page axis == batch
    axis."""
    return 1 if getattr(model.cfg, "scan_layers", False) else 0


def arena_num_pages(model, arena) -> int:
    axis = page_axis(model)
    tree = arena[0] if isinstance(arena, tuple) else arena
    leaves = jax.tree_util.tree_leaves
    return next(leaf.shape[axis] for leaf, kv in zip(
        leaves(tree), leaves(slot_leaves(model, tree))) if kv)


def _page_reduce_axes(axis: int, leaf) -> Tuple[int, ...]:
    # paged granularity: per (page, head) — reduce page_size and
    # head_dim; scales leaf is (…, P, 1, H, 1)
    return (axis + 1, axis + 3)


@jax.named_scope("page_gather")
def gather_pages(model, arena, page_table):
    """Materialize the dense per-slot KV view from the arena: one gather
    per KV leaf, ``(S, pp)`` page table → ``(S, pp * page_size, …)``
    rows. Unmapped (−1) entries clamp to page 0 — finite stale bytes the
    per-row attention mask never admits (every attended position lies in
    a mapped page by construction) and the scatter never writes back.
    Int8 arenas dequantize inside the gather (page codes × page scales →
    compute dtype), so every program downstream sees the same
    compute-dtype view either way."""
    axis = page_axis(model)
    S, pp = page_table.shape
    idx = jnp.maximum(page_table.reshape(-1), 0)

    def to_view(pages):
        shape = list(pages.shape)
        shape[axis:axis + 2] = [S, pp * shape[axis + 1]]
        return pages.reshape(shape)

    if not isinstance(arena, tuple):
        def gather(leaf, kv):
            if not kv:
                return leaf
            return to_view(jnp.take(leaf, idx, axis=axis))

        return jax.tree_util.tree_map(gather, arena,
                                      slot_leaves(model, arena))

    q, s = arena
    dt = model.cfg.dtype

    def gather_q(ql, sl, kv):
        if not kv:
            return ql
        pages = kv_dequantize(jnp.take(ql, idx, axis=axis),
                              jnp.take(sl, idx, axis=axis), dt)
        return to_view(pages)

    return jax.tree_util.tree_map(gather_q, q, s, slot_leaves(model, q))


@jax.named_scope("page_scatter")
def scatter_pages(model, arena, view, page_table):
    """Write the dense view's rows back to their arena pages (inverse of
    :func:`gather_pages`). Unmapped entries scatter to a dropped
    out-of-range index. Pages shared between slots (refcounted prefix
    pages) receive identical values from every holder — nothing writes
    inside an adopted page (decode and chunk writes land at positions
    past the shared prefix) — so duplicate indices stay deterministic.
    Int8 arenas quantize inside the scatter: per-page-per-head absmax
    scales recomputed from the view's pages (untouched pages round-trip
    idempotently, same saturation argument as the dense commit)."""
    axis = page_axis(model)
    num_pages = arena_num_pages(model, arena)
    S, pp = page_table.shape
    pt = page_table.reshape(-1)
    idx = jnp.where(pt >= 0, pt, num_pages)

    def to_pages(arena_leaf, view_leaf):
        ps = arena_leaf.shape[axis + 1]
        shape = list(view_leaf.shape)
        shape[axis:axis + 2] = [S * pp, ps]
        return view_leaf.reshape(shape)

    def write(arena_leaf, pages):
        if axis == 0:
            return arena_leaf.at[idx].set(pages, mode="drop")
        return arena_leaf.at[:, idx].set(pages, mode="drop")

    if not isinstance(arena, tuple):
        def scatter(arena_leaf, view_leaf, kv):
            if not kv:
                return arena_leaf
            return write(arena_leaf, to_pages(arena_leaf, view_leaf))

        return jax.tree_util.tree_map(scatter, arena, view,
                                      slot_leaves(model, arena))

    q, s = arena

    def scatter_q(ql, sl, vl, kv):
        if not kv:
            return ql
        pages = to_pages(ql, vl)
        return write(ql, kv_quantize(
            pages, kv_scales(pages, _page_reduce_axes(axis, pages))))

    def scatter_s(ql, sl, vl, kv):
        if not kv:
            return sl
        pages = to_pages(ql, vl)
        return write(sl, kv_scales(pages, _page_reduce_axes(axis, pages)))

    tm = jax.tree_util.tree_map
    kv = slot_leaves(model, q)
    return tm(scatter_q, q, s, view, kv), tm(scatter_s, q, s, view, kv)


class SlotPoolFull(OccupancyError):
    """No free KV slot (or, paged, not enough free pages) — admission
    control should have prevented this.

    Carries occupancy context so shed-load callers can log actionable
    rejections instead of a bare "full": ``slots_free``, ``pages_free``
    (None on the dense path), ``pages_needed`` (what the rejected
    request wanted, None for slot exhaustion) and ``active`` (in-flight
    request count).
    """

    def __init__(self, message: str, *, slots_free: Optional[int] = None,
                 pages_free: Optional[int] = None,
                 pages_needed: Optional[int] = None,
                 active: Optional[int] = None, **ctx):
        # **ctx: the tenancy layer extends the context (e.g. the tenant
        # whose max_active_slots quota refused the admission)
        super().__init__(message, slots_free=slots_free,
                         pages_free=pages_free, pages_needed=pages_needed,
                         active=active, **ctx)


def fold_rows(keys: jax.Array, data: jax.Array) -> jax.Array:
    """Per-row ``fold_in``: (B, 2) raw uint32 keys x (B,) ints — the key
    plumbing every serve program shares (engine step, prefill inject,
    spec rounds)."""
    return jax.vmap(jax.random.fold_in)(keys, data)


def pick_donated(donated, plain):
    """Donate device buffers wherever the backend honors it — the CPU
    backend ignores donation loudly, so tests stay quiet on the plain
    variant (one gating policy for every serve program)."""
    return plain if jax.default_backend() == "cpu" else donated


def check_seed_free(active_requests: Dict[int, "Request"],
                    request: "Request") -> None:
    """The no-key-reuse invariant shared by both pools: two co-resident
    slots may never carry the same sampling seed (their per-step
    ``fold_in`` key streams would collide token-for-token)."""
    for req in active_requests.values():
        if req.seed == request.seed:
            raise ValueError(
                f"PRNG key reuse across slots: request {request.id} "
                f"and in-flight request {req.id} share seed "
                f"{request.seed} — co-resident sample streams would "
                "collide; give one an explicit distinct seed")


class PagePool:
    """Owns the paged KV arena and the slot → pages mapping.

    ``arena`` is the cache pytree whose KV leaves are
    ``(num_pages, page_size, H, D)`` (layer-stacked when
    ``scan_layers``); sub-4d leaves (the shared ``cache_index``
    bookkeeping) keep the template values — the engine's per-row
    ``kv_positions`` path never reads them, and the chunk program
    overrides them per dispatch. The arena is built lazily on first
    access so pure accounting users (admission planning, the capacity
    tests) never allocate device memory.

    ``page_table`` is the ``(num_slots, pages_per_slot)`` int32 gather
    index (−1 = unmapped); refcounts make pages shareable: an adopted
    prefix page is freed only when its last holder (slot or
    :class:`PrefixCache`) lets go.
    """

    def __init__(self, model, num_slots: int, page_size: int,
                 num_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None):
        cfg = model.cfg
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if cfg.max_seq_len % page_size != 0:
            raise ValueError(
                f"page_size ({page_size}) must divide max_seq_len "
                f"({cfg.max_seq_len}) — the page table tiles the whole "
                "sequence axis")
        self._model = model
        self.kv_dtype = kv_dtype
        self._quantized = check_kv_dtype(kv_dtype)
        self.page_size = page_size
        self.pages_per_slot = cfg.max_seq_len // page_size
        self.num_pages = (num_pages if num_pages is not None
                          else num_slots * self.pages_per_slot)
        if self.num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got "
                             f"{self.num_pages}")
        self.num_slots = num_slots
        self.page_table = np.full((num_slots, self.pages_per_slot), -1,
                                  np.int32)
        self._arena = None
        self._free_pages: List[int] = list(range(self.num_pages))
        self._free_slots: List[int] = list(range(num_slots))
        self._ref = np.zeros((self.num_pages,), np.int64)
        self._requests: Dict[int, "Request"] = {}   # slot -> request
        self._span: Dict[int, int] = {}             # slot -> mapped pages

    # ------------------------------------------------------------- arena
    def _arena_template(self, shapes_only: bool = False):
        """The plain (unquantized) arena pytree — materialized, or as
        ShapeDtypeStructs when ``shapes_only`` (the byte-accounting
        probe must never allocate device memory)."""
        model = self._model
        run = jax.eval_shape if shapes_only else (
            lambda f, *a, **kw: f(*a, **kw))
        init = run(model.init, jax.random.PRNGKey(0),
                   jnp.zeros((1, 1), jnp.int32),
                   positions=jnp.zeros((1, 1), jnp.int32))
        template = init["cache"]
        axis = page_axis(model)

        def to_arena(leaf, kv):
            if not kv:
                return leaf
            shape = list(leaf.shape)
            shape[axis] = self.num_pages
            shape[axis + 1] = self.page_size
            if shapes_only:
                return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)
            return jnp.zeros(shape, leaf.dtype)

        return jax.tree_util.tree_map(to_arena, template,
                                      slot_leaves(model, template))

    @property
    def arena(self):
        if self._arena is None:
            plain = self._arena_template()
            if self._quantized:
                axis = page_axis(self._model)

                def q_leaf(leaf, kv):
                    if not kv:
                        return leaf
                    return jnp.zeros(leaf.shape, jnp.int8)

                def s_leaf(leaf, kv):
                    if not kv:
                        # placeholder mirrors the bookkeeping leaf's
                        # SHAPE (not a scalar): the page-native path
                        # ships the scales tree as a flax collection,
                        # and scanned layouts slice every leaf of it
                        # along the layer axis
                        return jnp.zeros(leaf.shape, jnp.float32)
                    shape = list(leaf.shape)
                    for ax in _page_reduce_axes(axis, leaf):
                        shape[ax] = 1
                    return jnp.ones(shape, jnp.float32)

                tm = jax.tree_util.tree_map
                kv = slot_leaves(self._model, plain)
                self._arena = (tm(q_leaf, plain, kv), tm(s_leaf, plain, kv))
            else:
                self._arena = plain
        return self._arena

    @arena.setter
    def arena(self, value):
        self._arena = value

    @property
    def bytes_per_page(self) -> int:
        """At-rest bytes one arena page costs across every KV leaf
        (int8: codes + the per-page-per-head f32 scales). Computed from
        shapes only — pure accounting callers (the equal-byte capacity
        tests) never allocate the arena."""
        axis = page_axis(self._model)
        total = 0
        template = self._arena_template(shapes_only=True)
        leaves = jax.tree_util.tree_leaves
        for leaf, kv in zip(leaves(template),
                            leaves(slot_leaves(self._model, template))):
            if not kv:
                continue
            numel = 1
            for d, n in enumerate(leaf.shape):
                if d != axis:
                    numel *= n
            if self._quantized:
                scale_numel = 1
                reduced = _page_reduce_axes(axis, leaf)
                for d, n in enumerate(leaf.shape):
                    if d != axis and d not in reduced:
                        scale_numel *= n
                total += numel + scale_numel * 4
            else:
                total += numel * jnp.dtype(leaf.dtype).itemsize
        return total

    # -------------------------------------------------------- accounting
    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def active(self) -> Dict[int, "Request"]:
        return dict(self._requests)

    def slot_of(self, request_id: int) -> Optional[int]:
        for slot, req in self._requests.items():
            if req.id == request_id:
                return slot
        return None

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def refcounts(self, pages: np.ndarray) -> np.ndarray:
        """Vectorized refcount read (the prefix cache's evictable-count
        probe runs on every scheduling tick)."""
        return self._ref[pages]

    def pages_needed(self, request) -> int:
        """Worst-case pages for one request: its prompt plus its whole
        token budget (allocation is up-front at admission — no mid-decode
        growth, so an admitted request can never OOM the arena)."""
        total = request.prompt_len + request.max_new_tokens
        return -(-total // self.page_size)

    # --------------------------------------------------------- lifecycle
    def acquire(self, request, prefix_pages: Sequence[int] = ()) -> int:
        """Assign a slot and allocate its pages. ``prefix_pages`` are
        already-filled pages adopted read-only from a
        :class:`PrefixCache` chain (refcount bumped here); the remainder
        comes fresh from the free list, lowest index first."""
        if not self._free_slots:
            raise SlotPoolFull(
                f"all {self.num_slots} KV slots in use",
                slots_free=0, pages_free=self.free_pages,
                active=len(self._requests))
        check_seed_free(self._requests, request)
        need = self.pages_needed(request)
        fresh_need = need - len(prefix_pages)
        # adoption is capped below the full prompt (the engine always
        # recomputes at least the final prompt token into a private page)
        assert fresh_need >= 1, (need, len(prefix_pages))
        if fresh_need > len(self._free_pages):
            raise SlotPoolFull(
                f"request {request.id} needs {fresh_need} free KV "
                f"pages ({need} total, {len(prefix_pages)} from prefix "
                f"cache) but only {len(self._free_pages)} are free",
                slots_free=self.free_slots, pages_free=self.free_pages,
                pages_needed=fresh_need, active=len(self._requests))
        slot = self._free_slots.pop(0)
        fresh = [self._free_pages.pop(0) for _ in range(fresh_need)]
        row = list(prefix_pages) + fresh
        self.page_table[slot, :] = -1
        self.page_table[slot, :len(row)] = row
        for p in prefix_pages:
            self._ref[p] += 1
        for p in fresh:
            self._ref[p] = 1
        self._requests[slot] = request
        self._span[slot] = len(row)
        return slot

    def release(self, slot: int):
        """Retire a slot: decref its pages (shared prefix pages survive
        while the cache or another adopter still holds them), clear its
        page-table row, return the request."""
        req = self._requests.pop(slot)
        for j in range(self._span.pop(slot)):
            self.decref(int(self.page_table[slot, j]))
        self.page_table[slot, :] = -1
        insort(self._free_slots, slot)
        return req

    def incref(self, page: int) -> None:
        self._ref[page] += 1

    def decref(self, page: int) -> None:
        self._ref[page] -= 1
        assert self._ref[page] >= 0, page
        if self._ref[page] == 0:
            insort(self._free_pages, page)


class PrefixCache:
    """Content-keyed reuse of prompt-prefix KV pages.

    Entries are keyed by **chain links**: page ``j``'s key is
    ``(parent_entry_id, tokens of page j)``, where the parent is page
    ``j-1``'s entry (id 0 = the empty root). The parent id encodes the
    entire preceding token prefix by identity — exact and collision-free
    like a full ``prompt[:(j+1)*page_size]`` tuple key, but each key is
    O(page_size), so match/publish on a long system prompt stay linear
    instead of quadratic. Ids are assigned in publish order and never
    reused (an evicted middle entry permanently orphans its children;
    unmatchable, they age out through the same LRU eviction).

    The cache holds one page refcount per entry. ``match`` walks the
    longest cached chain for a new prompt (LRU-touching each hit),
    ``publish`` caches a finished prefill's full-prompt pages, and
    ``evict`` frees least-recently-matched entries whose page nobody
    else holds. Hit statistics are recorded by the engine at admission
    (``record_admission``) — AFTER slot/page acquisition succeeds — so
    ``hits`` counts pages actually adopted (the chunk-alignment cap
    applied, rolled-back admissions excluded), in lockstep with the
    ``serve_prefix_pages_reused_total`` counter.

    Adoption is always capped one token short of the whole prompt: the
    final prompt token must be recomputed (its logits seed the first
    sample, and KV caches store K/V, not logits) and that recompute has
    to land in a private page.
    """

    def __init__(self, pool: PagePool):
        self.pool = pool
        # (parent_id, page_tokens) -> (entry_id, arena page)
        self._entries: "OrderedDict[Tuple[int, Tuple[int, ...]], " \
            "Tuple[int, int]]" = OrderedDict()
        self._next_id = 1    # 0 is the empty-prefix root
        self._pages_arr = np.empty((0,), np.int64)  # cached entry pages
        self._pages_dirty = False
        self.hits = 0        # pages adopted by admissions
        self.lookups = 0     # pages that were eligible for adoption
        self.publishes = 0   # pages added to the cache
        self.evictions = 0   # pages dropped under pressure

    def __len__(self) -> int:
        return len(self._entries)

    def match(self, tokens: Sequence[int]) -> List[int]:
        """Longest chain of cached pages covering a strict prefix of
        ``tokens``; every hit page is LRU-touched."""
        ps = self.pool.page_size
        usable = max(0, (len(tokens) - 1) // ps)
        pages: List[int] = []
        parent = 0
        for j in range(usable):
            key = (parent, tuple(tokens[j * ps:(j + 1) * ps]))
            entry = self._entries.get(key)
            if entry is None:
                break
            parent, page = entry
            pages.append(page)
            self._entries.move_to_end(key)
        return pages

    def record_admission(self, eligible: int, adopted: int) -> None:
        """Count one admission's prefix reuse: ``eligible`` pages could
        have come from cache, ``adopted`` actually did."""
        self.lookups += eligible
        self.hits += adopted

    def publish(self, prompt: Sequence[int], slot: int) -> int:
        """Cache every page of ``slot`` wholly covered by ``prompt``
        (their KV is fully written once its prefill completed). Returns
        the number of newly cached pages."""
        pool = self.pool
        ps = pool.page_size
        added = 0
        parent = 0
        for j in range(len(prompt) // ps):
            key = (parent, tuple(prompt[j * ps:(j + 1) * ps]))
            entry = self._entries.get(key)
            if entry is not None:
                parent = entry[0]
                continue
            page = int(pool.page_table[slot, j])
            entry_id = self._next_id
            self._next_id += 1
            self._entries[key] = (entry_id, page)
            pool.incref(page)
            parent = entry_id
            added += 1
        if added:
            self._pages_dirty = True
        self.publishes += added
        return added

    def evictable(self) -> int:
        """Pages the cache could free right now (refcount == 1: only the
        cache still holds them). Called on every scheduling tick with
        waiters, so the entry→page array is cached (invalidated on
        publish/evict/drop) and the refcount test is one vectorized
        read instead of a Python loop over entries."""
        if self._pages_dirty:
            self._pages_arr = np.fromiter(
                (p for _eid, p in self._entries.values()), np.int64,
                count=len(self._entries))
            self._pages_dirty = False
        if not len(self._pages_arr):
            return 0
        return int(np.count_nonzero(
            self.pool.refcounts(self._pages_arr) == 1))

    def evict(self, n: int, protect: Sequence[int] = ()) -> int:
        """Free up to ``n`` pages, least-recently-matched first, skipping
        entries still adopted by a live slot and ``protect``\\ ed pages
        (e.g. a chain the current admission is about to adopt)."""
        guard = set(protect)
        freed = 0
        for key, (_eid, page) in list(self._entries.items()):
            if freed >= n:
                break
            if page in guard or self.pool.refcount(page) != 1:
                continue
            del self._entries[key]
            self._pages_dirty = True
            self.pool.decref(page)
            freed += 1
            self.evictions += 1
        return freed

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def drop(self) -> None:
        """Release every cache-held page reference (engine shutdown)."""
        for _eid, page in self._entries.values():
            self.pool.decref(page)
        self._entries.clear()
        self._pages_dirty = True
