"""Continuous-batching serving engine: paged (or slot-pooled) KV cache +
fixed-shape compiled programs for all in-flight requests.

Iteration-level scheduling (Orca, OSDI '22) on XLA's terms: the engine
owns a fixed batch of **KV slots** (rows of the decode step program) and
a small set of pre-compiled fixed-shape programs, reusing the
prefill/decode split from :mod:`ray_lightning_tpu.models.generate`:

1. **prefill+inject** (``(B_pf, P)`` static shape): batch up to ``B_pf``
   waiting prompts, run the existing single-pass
   :func:`~ray_lightning_tpu.models.generate._prefill_impl` forward,
   sample each row's first token with its own key/params, and write each
   prefilled KV row into its assigned slot (dense path) or scatter its
   pages into the arena (paged path).
2. **step** (``(B, 1)`` static shape): ONE cached decode step for all B
   slots at their own ``kv_positions`` — the factored
   :func:`~ray_lightning_tpu.models.generate.decode_step` that
   ``generate()``'s ragged scan also runs, so engine decode cannot drift
   from one-shot decode. Each row samples with its request's own
   temperature/top_k/key, counts down its own ``max_new_tokens`` budget,
   and latches its own eos — finished rows retire *mid-flight* and their
   slots are handed to the next queued request without recompiling
   anything (all shapes static).
3. **chunk prefill** (``(1, C)`` static shape, paged engines with
   ``prefill_chunk`` set): ONE ``C``-token piece of one prompt, written
   at that request's current offset with chunk-causal masking over its
   already-filled pages — long prompts stream in chunk-sized dispatches
   the scheduler interleaves with decode, so a 4k-token prompt stalls
   in-flight decodes by one chunk, not one prompt (Sarathi-style chunked
   prefill). Prefix-cache hits enter here too: adopted pages skip
   straight to the first un-cached offset.
4. **spec round** (``draft_model=`` engines,
   :mod:`ray_lightning_tpu.serve.spec`): ``step()`` swaps the decode
   step for ONE fused program per dispatch — k+1 cheap draft-model
   steps plus a widened ``(B, k+1)`` target verify whose accept rule
   commits 1..k+1 tokens per row (greedy token-identical to the plain
   step by construction; rejected drafts roll back by position
   decrement). ``steps_per_dispatch`` scans spec ROUNDS here.

``kv_dtype="int8"`` additionally stores KV at rest as absmax int8 +
f32 scales (per-page-per-head paged, per-position-per-head dense) —
dequantized on the way into every program and re-quantized on the way
out, fused into the dispatch; compute stays at ``cfg.dtype``.
``weight_dtype="int8"|"int4"`` applies the same storage-only contract
to the *parameters* (:mod:`ray_lightning_tpu.models.quant`):
per-output-channel int8 or group-wise packed int4 codes + f32 scales,
dequantized ONCE at each program's entry — the at-rest param stream
(what every decode pass reads) shrinks to the codes.
``page_native=True`` (paged engines) swaps the step/verify programs'
dense-view gather/scatter for attention that reads and writes K/V
straight through the page table inside the model — dispatch bytes
scale with *occupied* pages, token-identical to the dense-gather path
(see ``docs/serving.md``). ``attention_kernel="pallas"`` further swaps
that read side for the hand-tiled pallas paged-attention kernel
(``models/pallas_attention.py``): page loads, int8 dequant, masked
blockwise scores, exact tiled softmax and f32 output accumulation all
fused in one kernel — interpret mode off-TPU, identical tokens.

KV layout is split from the programs (the refactor ROADMAP item 1 calls
healthy): the *logical* per-slot ``(max_seq_len, H, D)`` KV each program
computes against is materialized from physical storage at dispatch time.
Dense storage (``page_size=None``) IS the logical layout — one
``(num_slots, max_seq_len, H, D)`` pool, the original static-slot
design. Paged storage (:class:`~ray_lightning_tpu.serve.pages.PagePool`)
is a ``(num_pages, page_size, H, D)`` arena per KV leaf plus a per-slot
page table; the programs stay the same fixed-shape jits — the page
table is just a gather index applied on the way in and a scatter index
on the way out, fused into the dispatch. See ``docs/serving.md`` for
the memory/bandwidth trade; the old "pallas kernel endgame" there is
landed as ``attention_kernel="pallas"``.

Inactive slots still flow through the step program (the batch is
static); they are masked out of sampling/bookkeeping and their parked
KV rewrite is idempotent (dense) or dropped by the scatter (paged), so
they cost FLOPs but never correctness. Keep ``num_slots`` near your
live-traffic working set — paged engines can afford a generous batch
because slots no longer reserve memory.

The step/spec dispatch additionally splits into an **async seat**
(``step_enqueue()`` → :class:`PendingDispatch` → ``step_sync()``):
the device carry chains dispatch-to-dispatch without touching the
host, so ``ServeClient(async_dispatch=True)`` overlaps all host work
with the in-flight dispatch — see the class docs and
``docs/serving.md#async-dispatch``. Whichever driver runs it, a
dispatch costs the host ONE device-to-host transfer: every step /
spec-round program packs what ``step_sync`` reads into one flat int32
**report** (:mod:`ray_lightning_tpu.serve.report`), its copy starts at
enqueue, and ``step_sync`` waits for that buffer alone.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Any, Deque, FrozenSet, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models.generate import (TOP_K_CANDIDATES,
                                               _adapter_kw, _logits_only,
                                               _prefill_impl, cache_layout,
                                               decode_step,
                                               decode_step_paged,
                                               sample_logits_rows)
from ray_lightning_tpu.models.lora import (LoraConfig, adapter_bytes,
                                           install_adapter,
                                           install_lora_bank,
                                           zero_adapter)
from ray_lightning_tpu.models.quant import (DEFAULT_GROUP_SIZE,
                                            check_weight_dtype,
                                            materialize_for_program,
                                            param_bytes, quantize_params)
from ray_lightning_tpu.models.transformer import latch_eos, quantized_cache
from ray_lightning_tpu.obs import seats
from ray_lightning_tpu.obs.spans import NULL_SPAN
from ray_lightning_tpu.ops.cache_write import (inject_blocks, inject_rows,
                                                take_rows)
from ray_lightning_tpu.ops.pallas_decode import blocks_read, decode_block
from ray_lightning_tpu.reliability import faults
from ray_lightning_tpu.serve.adapters import (AdapterRegistry,
                                              UnknownAdapter)
from ray_lightning_tpu.serve.pages import (PagePool, PrefixCache,
                                           SlotPoolFull, check_kv_dtype,
                                           check_seed_free,
                                           dense_storage_commit,
                                           dense_storage_values, fold_rows,
                                           gather_pages, pick_donated,
                                           quantize_dense_cache,
                                           scatter_pages, slot_leaves)
from ray_lightning_tpu.serve.report import pack_report, unpack_report
from ray_lightning_tpu.serve.spec import (SpecDecoder,
                                          _spec_page_native_donated,
                                          _spec_page_native_plain,
                                          _spec_paged_donated,
                                          _spec_paged_plain,
                                          _spec_rounds_donated,
                                          _spec_rounds_plain)
from ray_lightning_tpu.serve.request import (Completion, DEFAULT_TENANT,
                                             FINISH_EOS, FINISH_LENGTH,
                                             FINISH_TIMEOUT, Request)
from ray_lightning_tpu.serve.tenancy import resolve_tenant_classes

__all__ = ["ServeEngine", "KVSlotPool", "SlotPoolFull", "PendingDispatch"]


@dataclass
class PendingDispatch:
    """Deferred-sync handle for one enqueued step / spec-round dispatch.

    :meth:`ServeEngine.step_enqueue` returns one of these instead of
    blocking on the host copy: ``report`` is the program's one packed
    int32 buffer (:mod:`~ray_lightning_tpu.serve.report`: the carry,
    ``emitted``/``finished`` and, for a spec dispatch, the accept
    ledgers), still a device array — a future under JAX's async
    dispatch whose copy to the host was started at enqueue — and
    ``carry`` is the device-side engine state
    (cur/pos/active/remaining/stepno) the NEXT enqueue chains on, so a
    second STEP dispatch can launch before this one's tokens ever touch
    the host. The carry never leaves the device.
    :meth:`ServeEngine.step_sync` materializes the handle: the wait for
    that one buffer (THE blocking point), the retire loop, counters and
    telemetry. Handles must sync in enqueue order; an engine rebuild
    (crash recovery, fleet failover) DISCARDS outstanding handles — the
    synced frontier is the replay truth, an in-flight speculative
    dispatch is regenerated by replay, never committed twice
    (``docs/serving.md#async-dispatch``).
    """
    kind: str          # "step" | "spec"
    dispatch: int      # engine.steps at enqueue (1-based)
    rounds: int        # steps_per_dispatch scanned inside the program
    report: object     # flat int32 device array: all step_sync fetches
    carry: tuple       # (cur, pos, active, remaining, stepno) on device
    owner: object = None       # identity nonce of the issuing engine —
    #                            a rebuilt engine refuses foreign
    #                            handles even when dispatch indices
    #                            realign (e.g. both at 1)
    asynchronous: bool = True  # False: the sync step() round-trip
    # host perf_counter stamp for serve_dispatch_overlap_ms — read only
    # by an ARMED engine (0.0 otherwise: a disarmed dispatch reads no
    # clock)
    enqueued_at: float = 0.0
    # client-clock enqueue stamp (ticks or seconds), set by an ARMED
    # ServeClient only — read back at step_sync to split decode time
    # from reconciliation in request traces (serve.retire `sync`)
    enqueued_tick: Optional[float] = None
    # ARMED engines only, for a model that declares "counter" cache
    # leaves (an expert layer's load): the leaves this dispatch left
    # (step_sync's second fetch, one for the list) and the args of its
    # ``engine.step.call`` span, which step_sync fills
    counters: Optional[list] = None
    call_args: Optional[dict] = None


# shared serve-program plumbing (one copy for engine + spec programs)
_fold_rows = fold_rows
_pick = pick_donated


def _advance_rows(model, last, cur, pos, active, remaining, temp, top_k,
                  eos, keys, stepno):
    """Per-row sampling + bookkeeping for one decode step's logits — the
    ONE copy of the sample/latch/budget math, shared by the dense-view
    step core and the page-native step body so the two storage paths
    cannot drift.

    Per-row semantics (matching the ragged decode scan): ``cur`` is the
    token sampled last step, ``pos`` its absolute position. Inactive rows
    run the same math (static shapes) but their state is frozen: emitted
    is masked to −1 and ``pos``/``stepno`` don't advance.
    """
    with jax.named_scope("sample/keys"):
        step_keys = _fold_rows(keys, stepno)
    nxt = sample_logits_rows(last, step_keys, temp, top_k)
    with jax.named_scope("decode/advance_rows"):
        # per-row eos (−1 = disabled); done=False — finished rows leave
        # the batch instead of repeating eos, the pool hands their slot on
        _, eos_hit = latch_eos(nxt, jnp.zeros_like(active), eos)
        act_i = active.astype(jnp.int32)
        remaining = remaining - act_i
        finished = active & (eos_hit | (remaining <= 0))
        emitted = jnp.where(active, nxt, -1)
        max_pos = model.cfg.max_seq_len - 1
        cur = jnp.where(active[:, None], nxt[:, None], cur)
        pos = jnp.minimum(pos + act_i[:, None], max_pos)
        stepno = stepno + act_i
        active = active & ~finished
    return (cur, pos, active, remaining, stepno, emitted, finished)


def _engine_step_core(model, params, cache, cur, pos, active, remaining,
                      temp, top_k, eos, keys, stepno, adapter_ids=None):
    """One decode step for all B slots. Pure function of the engine state
    arrays; (B, 1) model step shared with generate() via decode_step,
    row bookkeeping shared with the page-native path via
    :func:`_advance_rows`. Re-writing a frozen row's K/V at its frozen
    position is idempotent. ``adapter_ids`` (B,) routes each row through
    its own resident LoRA pair (−1 = base model); ``None`` on engines
    without an adapter bank — the model never sees the kwarg, so
    unadapted programs are byte-for-byte the pre-LoRA ones.
    """
    with jax.named_scope("decode/forward"):
        last, stepped = decode_step(model, params, cache, cur, pos,
                                    adapter_ids)
    if _continues_prefill(model):
        # a row that is not decoding may hold a prompt that is still
        # streaming in through the dense chunk program: its recurrent
        # state stays as the last piece left it (its K/V write is parked
        # past what the prompt has fed by the host, see _prefill_build)
        with jax.named_scope("decode/freeze_rows"):
            stepped = jax.tree_util.tree_map(
                lambda new, old, decl: new if decl.kind != "recurrent"
                else jnp.where(_per_row(active, new, decl), new, old),
                stepped, cache, cache_layout(model, cache))
    cache = stepped
    (cur, pos, active, remaining, stepno, emitted, finished) = \
        _advance_rows(model, last, cur, pos, active, remaining, temp,
                      top_k, eos, keys, stepno)
    return (cache, cur, pos, active, remaining, stepno, emitted, finished)


def _engine_step_impl(model, params, cache, cur, pos, active, remaining,
                      temp, top_k, eos, keys, stepno, adapter_ids=None,
                      *, steps):
    """``steps`` decode steps in ONE dispatch (multi-step scheduling).

    Token-granularity dispatch pays the fixed per-call overhead once per
    token (its size against the device step is not measured on the
    current machine — docs/performance.md), which hands the fused
    one-shot scan an advantage. Scanning ``steps`` iterations of the SAME
    per-row step inside the program amortizes the dispatch 1/steps while
    keeping the math identical (rows that finish mid-block park
    idempotently; emitted is −1-masked per sub-step). The trade is
    scheduling granularity: joins/retires happen every ``steps`` tokens.

    ``cache`` may be int8 dense storage (a ``(q, s)`` tuple): the body
    runs on the dequantized compute-dtype view and the result re-commits
    through the same storage — both fused into this one dispatch.

    Returns the carried state — it stays on the device, the next
    dispatch chains on it — plus the dispatch's **report**
    (:func:`~ray_lightning_tpu.serve.report.pack_report`): the carry and
    ``emitted``/``finished`` stacked ``(steps, B)``, in one flat int32
    buffer that is all the host fetches — it replays sub-steps in order.
    """
    # weight-quantized params dequantize ONCE per dispatch, here at the
    # program top (outside the step scan) — storage-only, same contract
    # as the int8 KV storage below
    params = materialize_for_program(params, model.cfg)
    storage = cache
    cache = dense_storage_values(model, storage)

    def body(carry, _):
        cache, cur, pos, active, remaining, stepno = carry
        (cache, cur, pos, active, remaining, stepno, emitted,
         finished) = _engine_step_core(
            model, params, cache, cur, pos, active, remaining, temp,
            top_k, eos, keys, stepno, adapter_ids)
        return ((cache, cur, pos, active, remaining, stepno),
                (emitted, finished))

    with quantized_cache(isinstance(storage, tuple)):
        (cache, cur, pos, active, remaining, stepno), (emitted, finished) \
            = jax.lax.scan(body, (cache, cur, pos, active, remaining,
                                  stepno), None, length=steps)
    cache = dense_storage_commit(model, storage, cache)
    return (cache, cur, pos, active, remaining, stepno,
            pack_report(cur, pos, active, remaining, stepno, emitted,
                        finished))


def _prefill_inject_impl(model, params, pool_cache, prompts, lengths,
                         slots, valid, keys, temp, top_k, startno,
                         adapter_ids=None):
    """Batched prompt fill + first-token sample + KV injection (dense).

    Runs the standard single-pass prefill at the engine's fixed
    ``(B_pf, P)`` shape (rows left-aligned, ``lengths`` raggedness — the
    same contract as generate()'s ragged prefill), samples each row's
    first token with its own key/params, then writes each valid row's
    whole KV row into its assigned pool slot, in place
    (:func:`ops.cache_write.inject_rows`: a leaf's slot row a loop trip).
    Invalid (padding) rows are computed but written nowhere — the pool
    row is read back and kept, so one compiled program covers every fill
    level of the prefill batch.

    ``startno`` (B,) is each row's sampling-step offset: 0 for a fresh
    request (fold_in(key, 0), the original behavior), k for a
    crash-recovery replay whose row re-feeds the prompt + k emitted
    tokens — the sampled token then continues the request's key stream
    exactly where the dead engine left it (same array shapes, so replay
    reuses the compiled program).

    ``pool_cache`` may be int8 dense storage (a ``(q, s)`` tuple): the
    injection runs on the dequantized view and re-commits, fused.
    """
    storage = pool_cache
    pool_cache = dense_storage_values(model, storage)
    with jax.named_scope("prefill/forward"):
        pf_cache, last = _prefill_impl(model, params, prompts, lengths,
                                       adapter_ids)
    with jax.named_scope("sample/keys"):
        first_keys = _fold_rows(keys, startno)
    first = sample_logits_rows(last, first_keys, temp, top_k)

    # which leaf belongs to a slot, and on which axis, is the cache's own
    # declaration (generate.cache_layout): GPT-2's cached_key/value are
    # (B, L, H, D) unrolled or (n_layers, B, L, H, D) scanned, a
    # recurrent state is (B, N, D) — every such leaf is injected whole.
    # Shared bookkeeping (cache_index) the per-row kv_positions path
    # never reads: keep pool's.
    with jax.named_scope("prefill/kv_inject"):
        layout = cache_layout(model, pool_cache)
        pool_cache = inject_rows(pool_cache, pf_cache, layout, slots,
                                 valid)
        pool_cache = _carry_counters(pool_cache, pf_cache, layout)
    return dense_storage_commit(model, storage, pool_cache), first


# --------------------------------------------------------------- paged
# the arena gather/scatter (and its int8 dequant/quant handling) lives
# with the allocator in serve/pages.py — these aliases keep the program
# impls below readable
_gather_pages = gather_pages
_scatter_pages = scatter_pages


def _paged_step_impl(model, params, arena, page_table, cur, pos, active,
                     remaining, temp, top_k, eos, keys, stepno,
                     adapter_ids=None, *, steps):
    """The decode step program on paged storage: gather the dense view,
    run the IDENTICAL multi-step body (:func:`_engine_step_impl` — token
    identity with the dense engine is by construction), scatter mapped
    pages back. One dispatch, fused by XLA; the view is dispatch-scoped
    scratch, the arena is the only persistent KV allocation.

    Only rows active at dispatch entry scatter back. Inactive rows run
    the same math (static shapes) and "write" their frozen K/V at a
    stale position — dead storage on the dense path, but here the slot's
    pages may belong to a request still streaming chunk prefill (a
    mid-chunking slot is allocated but not yet decoding), so their
    writes must be dropped, not parked. Rows that retire mid-block
    started active and still scatter: their post-retirement sub-step
    rewrites are frozen-idempotent.
    """
    view = _gather_pages(model, arena, page_table)
    write_pt = jnp.where(active[:, None], page_table, -1)
    with quantized_cache(isinstance(arena, tuple)):
        (view, cur, pos, active, remaining, stepno, report) = \
            _engine_step_impl(model, params, view, cur, pos, active,
                              remaining, temp, top_k, eos, keys, stepno,
                              adapter_ids, steps=steps)
    arena = _scatter_pages(model, arena, view, write_pt)
    return (arena, cur, pos, active, remaining, stepno, report)


def _prefill_inject_paged_impl(model, params, arena, prompts, lengths,
                               inject_pt, keys, temp, top_k, startno,
                               adapter_ids=None):
    """Paged sibling of :func:`_prefill_inject_impl`: same prefill
    forward and first-token sample, but the injection is a page scatter —
    ``inject_pt`` (B_pf, pages_per_slot) maps each prefill row's pages to
    arena pages (−1 = drop: padding rows, and the unmapped tail of a
    short request's slot). The prefill cache covers the full
    ``max_seq_len`` row (positions ≥ P are zeros), so every mapped page
    is overwritten — stale KV from the pages' previous tenants never
    leaks (the paged analog of the dense whole-row inject)."""
    with jax.named_scope("prefill/forward"):
        pf_cache, last = _prefill_impl(model, params, prompts, lengths,
                                       adapter_ids)
    with jax.named_scope("sample/keys"):
        first_keys = _fold_rows(keys, startno)
    first = sample_logits_rows(last, first_keys, temp, top_k)
    # the prefill cache rows are already the dense per-slot view
    # (B_pf, max_seq_len, …) = (S, pp * page_size, …)
    arena = _scatter_pages(model, arena, pf_cache, inject_pt)
    return arena, first


def _chunk_prefill_impl(model, params, arena, row_pages, tokens, offset,
                        valid_len, keys, temp, top_k, startno,
                        adapter_ids=None):
    """One ``(1, C)`` chunk of one prompt, at absolute ``offset``.

    Gathers the request's dense row view from its pages, points the
    shared ``cache_index`` bookkeeping at ``offset`` (the block-write
    mode of ``_decode_cache`` then writes this chunk's K/V there and
    masks keys past ``offset + q`` per intra-chunk query — chunk-causal
    attention over everything already filled, including adopted prefix
    pages), runs the forward, scatters mapped pages back, and samples a
    candidate first token from the logits at ``valid_len - 1``. The host
    uses that sample only on the final chunk; earlier chunks discard it
    (one program covers every chunk). ``startno`` continues a replayed
    request's key stream, exactly as the batched prefill does.
    """
    params = materialize_for_program(params, model.cfg)
    pt = row_pages[None, :]
    view = _gather_pages(model, arena, pt)
    view = jax.tree_util.tree_map(
        lambda leaf, kv: (leaf if kv else
                          jnp.full(leaf.shape, offset, leaf.dtype)),
        view, slot_leaves(model, view))
    C = tokens.shape[1]
    with jax.named_scope("chunk/forward"):
        positions = offset + jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        outputs, updated = model.apply(
            {"params": params, "cache": view}, tokens,
            positions=positions, deterministic=True, mutable=["cache"],
            **_adapter_kw(adapter_ids))
        logits = _logits_only(outputs)                      # (1, C, V)
        last = jnp.take_along_axis(
            logits,
            jnp.reshape(valid_len - 1, (1, 1, 1)).astype(jnp.int32),
            axis=1)[:, 0]
    with jax.named_scope("sample/keys"):
        first_keys = _fold_rows(keys, startno)
    first = sample_logits_rows(last, first_keys, temp, top_k)
    arena = _scatter_pages(model, arena, updated["cache"], pt)
    return arena, first


def _per_row(flag, leaf, decl):
    """``flag`` (one entry a row) shaped to broadcast against ``leaf``
    along the axis its declaration gives the rows."""
    shape = [1] * leaf.ndim
    shape[decl.slot_axis] = -1
    return jnp.reshape(flag, shape)


def _continues_prefill(model) -> bool:
    """Does the model take a prompt in pieces, each starting from the
    cache the last one left (``models/olmo_hybrid.py``)?"""
    return bool(getattr(model, "continues_prefill", False))


def _carry_counters(pool_cache, fresh, layout):
    """``pool_cache`` with every ``"counter"`` leaf (what a program's
    last model call counted, owned by no slot: ``models/afmoe.py``'s
    expert load) taken from ``fresh``. A cache that declares none — every
    other model's — comes back as it is, leaf for leaf."""
    return jax.tree_util.tree_map(
        lambda held, new, decl: new if decl.kind == "counter" else held,
        pool_cache, fresh, layout)


def counter_leaves(model, cache) -> list:
    """The ``"counter"`` leaves of ``cache``, in tree order."""
    leaves = jax.tree_util.tree_leaves
    return [leaf for leaf, decl in zip(
        leaves(cache), leaves(cache_layout(model, cache)))
        if decl.kind == "counter"]


def _fetch(tree):
    """A sync's device-to-host fetch: ONE wait for ``tree`` (an array,
    or a list whose leaves travel together — ``jax.device_get`` starts
    every leaf's copy before it reads the first). Every blocking copy of
    ``engine.step.sync`` and ``engine.chunk.sync`` goes through this one
    seam, so a test counts the fetches a sync makes, or fails one."""
    return jax.device_get(tree)


def expert_load_counts(counters: list) -> Dict[str, int]:
    """Span args from a dispatch's expert-load leaves as fetched (one
    ``(held,)`` count a layer; armed engines only):
    ``moe_assignments`` — local assignments, summed over layers —,
    ``moe_experts_hit`` — (layer, expert) pairs that took a row —,
    ``moe_load_max`` — the fullest expert's rows — and ``moe_experts``
    — the pairs there are."""
    load = np.stack(counters)
    return {"moe_assignments": int(load.sum()),
            "moe_experts_hit": int((load > 0).sum()),
            "moe_load_max": int(load.max()),
            "moe_experts": int(load.size)}


def _chunk_prefill_dense_impl(model, params, pool_cache, tokens, offset,
                              lengths, slots, valid, keys, temp, top_k,
                              startno):
    """One ``(rows, C)`` piece of ``rows <= prefill_batch`` prompts into
    **dense slots**, for a model with declared recurrent state that
    continues its prefill from the cache it is given
    (:func:`_continues_prefill`): row ``r`` feeds ``lengths[r]`` tokens
    at absolute ``offset[r]`` into slot ``slots[r]``.

    The rows' cache is taken out of the pool (a row whose ``offset`` is 0
    starts from a zero state, whatever the slot's last tenant left), the
    model continues from it, and what the piece wrote goes back in place
    (:func:`ops.cache_write.inject_blocks`): the recurrent leaves whole,
    the K/V leaves' ``C`` new positions only. A candidate first token is
    sampled at each row's last valid token; the host uses it on a final
    piece only. Invalid rows (the warm-up of a row count) are computed
    and written nowhere.
    """
    params = materialize_for_program(params, model.cfg)
    layout = cache_layout(model, pool_cache)
    with jax.named_scope("chunk/kv_take"):
        rows = take_rows(pool_cache, layout, slots)
        rows = jax.tree_util.tree_map(
            lambda leaf, decl: leaf if decl.kind != "recurrent"
            else jnp.where(_per_row(offset == 0, leaf, decl),
                           jnp.zeros_like(leaf), leaf),
            rows, layout)
    with jax.named_scope("chunk/forward"):
        outputs, updated = model.apply(
            {"params": params, "cache": rows}, tokens, lengths=lengths,
            offset=offset, deterministic=True, mutable=["cache"])
        last = _logits_only(outputs)[:, -1]
    with jax.named_scope("sample/keys"):
        first_keys = _fold_rows(keys, startno)
    first = sample_logits_rows(last, first_keys, temp, top_k)
    with jax.named_scope("chunk/kv_inject"):
        pool_cache = inject_blocks(pool_cache, updated["cache"], layout,
                                   slots, valid, offset, tokens.shape[1])
        pool_cache = _carry_counters(pool_cache, updated["cache"], layout)
    return pool_cache, first


def _page_native_step_impl(model, params, arena, page_table, cur, pos,
                           active, remaining, temp, top_k, eos, keys,
                           stepno, adapter_ids=None, *, steps):
    """The decode step program in **page-native** mode: K/V reads and
    writes go straight through the page table inside the model's
    attention (``decode_step_paged`` →
    ``MultiHeadAttention._page_native_attention``) — the dense
    ``(num_slots, max_seq_len)`` view of :func:`_paged_step_impl` never
    materializes, so the bytes a dispatch touches scale with *occupied*
    pages instead of ``num_slots x max_seq_len``. Row bookkeeping is
    the shared :func:`_advance_rows`, so sampling/eos/budget math is
    identical to the dense-view paths by construction.

    ``page_table`` arrives write-masked (inactive rows' entries −1):
    their parked writes drop inside the attention scatter and their
    reads clamp to page 0 (finite junk the position mask never lets
    into an ACTIVE row — inactive rows' logits are discarded by the
    emitted mask). Rows that retire mid-block keep their mapped entries
    and re-write frozen K/V idempotently, exactly like the dense paths.
    """
    params = materialize_for_program(params, model.cfg)

    def body(carry, _):
        arena, cur, pos, active, remaining, stepno = carry
        with jax.named_scope("decode/forward"):
            last, arena = decode_step_paged(model, params, arena, cur,
                                            pos, page_table, adapter_ids)
        (cur, pos, active, remaining, stepno, emitted, finished) = \
            _advance_rows(model, last, cur, pos, active, remaining,
                          temp, top_k, eos, keys, stepno)
        return ((arena, cur, pos, active, remaining, stepno),
                (emitted, finished))

    (arena, cur, pos, active, remaining, stepno), (emitted, finished) = \
        jax.lax.scan(body, (arena, cur, pos, active, remaining, stepno),
                     None, length=steps)
    return (arena, cur, pos, active, remaining, stepno,
            pack_report(cur, pos, active, remaining, stepno, emitted,
                        finished))


_engine_step_donated = partial(
    jax.jit, static_argnames=("model", "steps"), donate_argnums=(2,))(
        _engine_step_impl)
_engine_step_plain = partial(
    jax.jit, static_argnames=("model", "steps"))(_engine_step_impl)
_prefill_inject_donated = partial(
    jax.jit, static_argnames=("model",), donate_argnums=(2,))(
        _prefill_inject_impl)
_prefill_inject_plain = partial(
    jax.jit, static_argnames=("model",))(_prefill_inject_impl)
_paged_step_donated = partial(
    jax.jit, static_argnames=("model", "steps"), donate_argnums=(2,))(
        _paged_step_impl)
_paged_step_plain = partial(
    jax.jit, static_argnames=("model", "steps"))(_paged_step_impl)
_prefill_paged_donated = partial(
    jax.jit, static_argnames=("model",), donate_argnums=(2,))(
        _prefill_inject_paged_impl)
_prefill_paged_plain = partial(
    jax.jit, static_argnames=("model",))(_prefill_inject_paged_impl)
_chunk_prefill_donated = partial(
    jax.jit, static_argnames=("model",), donate_argnums=(2,))(
        _chunk_prefill_impl)
_chunk_prefill_plain = partial(
    jax.jit, static_argnames=("model",))(_chunk_prefill_impl)
_chunk_dense_donated = partial(
    jax.jit, static_argnames=("model",), donate_argnums=(2,))(
        _chunk_prefill_dense_impl)
_chunk_dense_plain = partial(
    jax.jit, static_argnames=("model",))(_chunk_prefill_dense_impl)
_page_native_step_donated = partial(
    jax.jit, static_argnames=("model", "steps"), donate_argnums=(2,))(
        _page_native_step_impl)
_page_native_step_plain = partial(
    jax.jit, static_argnames=("model", "steps"))(_page_native_step_impl)




class KVSlotPool:
    """Dense storage: owns the (B, max_seq_len) KV cache and the
    request → slot map (the original static-slot layout; the paged
    sibling is :class:`~ray_lightning_tpu.serve.pages.PagePool`).

    Slots are acquired at prefill injection and released on
    eos/max-token/timeout; lowest-index-first allocation keeps traces
    deterministic. The pool also enforces the no-key-reuse invariant: two
    co-resident slots may never carry the same sampling seed (their
    per-step keys would collide stream-for-stream).
    """

    def __init__(self, model, num_slots: int,
                 kv_dtype: Optional[str] = None):
        self.num_slots = num_slots
        self.kv_dtype = kv_dtype
        # jitted, so that only the cache is ever made: run eagerly, init
        # would draw a whole second set of weights beside the real one
        cache = jax.jit(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((num_slots, 1), jnp.int32),
            positions=jnp.zeros((num_slots, 1), jnp.int32))["cache"])()
        if check_kv_dtype(kv_dtype):
            # int8 storage: the (q, s) tuple the dense programs
            # dequantize/re-quantize inside each dispatch
            cache = quantize_dense_cache(model, cache)
        self.cache = cache
        self._free: List[int] = list(range(num_slots))
        self._requests: Dict[int, Request] = {}  # slot -> request

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active(self) -> Dict[int, Request]:
        return dict(self._requests)

    def slot_of(self, request_id: int) -> Optional[int]:
        for slot, req in self._requests.items():
            if req.id == request_id:
                return slot
        return None

    def acquire(self, request: Request) -> int:
        if not self._free:
            raise SlotPoolFull(
                f"all {self.num_slots} KV slots in use",
                slots_free=0, active=len(self._requests))
        check_seed_free(self._requests, request)
        slot = self._free.pop(0)
        self._requests[slot] = request
        return slot

    def release(self, slot: int) -> Request:
        req = self._requests.pop(slot)
        self._free.append(slot)
        self._free.sort()
        return req


@dataclass
class _ChunkState:
    """One mid-chunking prompt: the slot is held, pages are allocated,
    and ``fed[next_off:]`` still has to stream through the chunk
    program."""
    request: Request
    slot: int
    fed: List[int]       # prompt + replayed tokens
    next_off: int        # first position not yet written (admission
    #                      seeds it past any adopted prefix pages)


#: options only some model families carry, and what a config without
#: the field runs: the engine reads them through ``_cfg_option``, so a
#: model need not declare an option it does not have
_CFG_DEFAULTS = {"attention_kernel": "xla", "matmul_kernel": "xla",
                 "scan_layers": False, "lora": None}


def _cfg_option(cfg, name: str):
    return getattr(cfg, name, _CFG_DEFAULTS[name])


def _with_cfg(model, **changes):
    """``model`` rebuilt with config fields replaced; a family without
    the option refuses by name."""
    missing = [k for k in changes if not hasattr(model.cfg, k)]
    if missing:
        raise ValueError(
            f"{type(model).__name__} has no {', '.join(missing)} option")
    return model.clone(cfg=dataclasses.replace(model.cfg, **changes))


class ServeEngine:
    """In-flight batching over a fixed slot batch with dense or paged KV.

    ``model`` must be a decode-mode LM (``cfg.decode=True``; for serving
    throughput build it ``scan_layers=False`` and convert training weights
    with ``unstack_scan_params`` — see ``docs/performance.md``). The
    engine compiles its programs on first use and never again:
    prefill+inject at ``(prefill_batch, prefill_len)``, the decode step
    at ``(num_slots, 1)``, and (chunked engines) the chunk prefill at
    ``(1, prefill_chunk)``.

    Paged mode (``page_size=``): KV lives in a
    ``(num_pages, page_size, H, D)`` arena behind a per-slot page table
    (:class:`~ray_lightning_tpu.serve.pages.PagePool`) — short requests
    hold pages for their own prompt+budget instead of a ``max_seq_len``
    row, so concurrency (``num_slots``) decouples from KV memory
    (``num_pages``). ``prefill_chunk=`` streams long prompts in
    chunk-sized dispatches the scheduler interleaves with decode;
    ``prefix_cache=True`` adds refcounted read-only reuse of
    shared-prompt KV pages (requires ``prefill_chunk`` — adopted chains
    resume at the first un-cached offset, which is a chunk dispatch).

    Speculative decoding (``draft_model=``, ``draft_params=``,
    ``spec_k=4``): ``step()`` runs fused spec rounds instead of decode
    steps — see :mod:`ray_lightning_tpu.serve.spec` and
    ``docs/serving.md``. ``kv_dtype="int8"`` halves at-rest KV bytes
    on either storage layout (``docs/serving.md#int8-kv-storage``);
    ``weight_dtype=`` / ``draft_weight_dtype=`` quantize the weights
    (``weight_group_size=`` sizes the int4 groups) and
    ``page_native=True`` drops the paged dispatch's dense-view
    round-trip — all four compose, with each other and with spec.
    ``attention_kernel="pallas"`` (requires ``page_native=True``) runs
    the page-native read side as one hand-tiled pallas kernel per
    layer instead of blockwise XLA — same tokens, fewer temporaries.
    ``matmul_kernel="pallas"`` (requires ``weight_dtype=`` or
    ``draft_weight_dtype=``, and unrolled layers) streams the
    quantized weight codes straight into a fused dequant-matmul
    kernel per projection (``models/pallas_matmul.py``) instead of
    materializing a dequantized parameter tree once per dispatch —
    the per-dispatch param byte stream drops to the codes+scales
    floor ``param_bytes()`` accounts, and tokens stay identical to
    the materialized path (interpret-mode bitwise on the CPU tier).

    Drive it with :class:`~ray_lightning_tpu.serve.client.ServeClient`
    (scheduler + admission control + clocks) or directly:
    ``prefill([reqs])`` to start requests (chunk-routed prompts advance
    via ``prefill_chunk_step()``), ``step()`` to advance every in-flight
    request; each returns newly finished :class:`Completion`\\ s.
    """

    def __init__(self, model, params, *, num_slots: int = 8,
                 prefill_batch: Optional[int] = None,
                 prefill_len: int = 64, steps_per_dispatch: int = 1,
                 seed: int = 0, telemetry=None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 kv_dtype: Optional[str] = None,
                 page_native: bool = False,
                 attention_kernel: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 weight_group_size: Optional[int] = None,
                 matmul_kernel: Optional[str] = None,
                 draft_model=None, draft_params=None,
                 spec_k: Optional[int] = None,
                 draft_weight_dtype: Optional[str] = None,
                 tenant_classes=None,
                 adapters=None,
                 max_resident_adapters: Optional[int] = None,
                 lora_rank: Optional[int] = None):
        cfg = model.cfg
        if not cfg.decode:
            raise ValueError(
                "ServeEngine needs a decode-mode model: rebuild the "
                "config with decode=True (params are compatible)")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if page_native and page_size is None:
            raise ValueError(
                "page_native=True is a paged-KV mode (attention reads "
                "K/V through the page table): pass page_size= too")
        # chunked prefill into DENSE slots: a model whose prefill
        # continues from the cache it is given (``continues_prefill``)
        # takes a long prompt piece by piece, whatever state it declares
        # carried in its slot — _chunk_prefill_dense_impl
        dense_chunk = (prefill_chunk is not None and page_size is None
                       and _continues_prefill(model))
        recurrent = bool(getattr(model, "recurrent_state", False))
        if recurrent or _continues_prefill(model):
            # a slot of such a model holds what is no K/V row at
            # absolute positions (a recurrence, a ring, a latent row
            # only the model's continue mode writes): what the engine
            # cannot carry it through yet refuses here, by name, and
            # never runs wrongly
            refused = [name for name, on in (
                ("page_size / page_native", page_size is not None),
                ("kv_dtype='int8'", check_kv_dtype(kv_dtype)),
                ("prefix_cache", prefix_cache),
                ("prefill_chunk (the model has no continue mode)",
                 prefill_chunk is not None
                 and not _continues_prefill(model)),
                ("draft_model (speculative decoding)",
                 draft_model is not None),
                ("max_resident_adapters (the LoRA bank)",
                 max_resident_adapters is not None)) if on]
            if refused:
                declares = "recurrent state" if recurrent else \
                    "a cache that only its continue mode writes"
                raise ValueError(
                    f"{type(model).__name__} declares {declares}; "
                    f"the engine cannot give it {', '.join(refused)} yet "
                    "(pages, int8 storage, prefix reuse, chunked prefill "
                    "of a state that cannot be continued and draft "
                    "verification all assume K/V rows at absolute "
                    "positions) — use the dense-slot engine")
        # attention_kernel selects the page-native read-side kernel
        # (models/pallas_attention.py): None inherits the model config
        # (default "xla"); "pallas" swaps in the hand-tiled paged
        # kernel. A config mismatch rebuilds the model with the
        # requested kernel — the cfg field is the single source of
        # truth the attention dispatches on, so supervisor rebuilds and
        # fleet replicas (which re-enter this ctor with the same
        # kwargs) select the identical programs.
        if attention_kernel not in (None, "xla", "pallas"):
            raise ValueError(
                f"attention_kernel must be None, 'xla' or 'pallas', "
                f"got {attention_kernel!r}")
        if attention_kernel is not None and attention_kernel \
                != _cfg_option(cfg, "attention_kernel"):
            model = _with_cfg(model, attention_kernel=attention_kernel)
            cfg = model.cfg
        self.attention_kernel = _cfg_option(cfg, "attention_kernel")
        if self.attention_kernel == "pallas" and not page_native:
            raise ValueError(
                "attention_kernel='pallas' is the page-native paged-"
                "attention kernel (K/V stream through the page table "
                "inside one pallas_call): pass page_native=True (and "
                "page_size=) too")
        check_weight_dtype(weight_dtype)  # unknown dtypes refused here
        check_weight_dtype(draft_weight_dtype)
        # matmul_kernel selects the weight-quantized matmul path
        # (models/pallas_matmul.py), the attention_kernel pattern: None
        # inherits the model config (default "xla" = materialized
        # per-dispatch dequant); "pallas" streams the QTensor codes
        # into a fused dequant-matmul kernel — no dense dequantized
        # weight arena exists in any program. A config mismatch clones
        # the model (and the draft model) with the requested kernel, so
        # supervisor rebuilds and fleet replicas — which re-enter this
        # ctor with the same kwargs — re-select identical programs.
        if matmul_kernel not in (None, "xla", "pallas"):
            raise ValueError(
                f"matmul_kernel must be None, 'xla' or 'pallas', got "
                f"{matmul_kernel!r}")
        if matmul_kernel is not None \
                and matmul_kernel != _cfg_option(cfg, "matmul_kernel"):
            model = _with_cfg(model, matmul_kernel=matmul_kernel)
            cfg = model.cfg
        self.matmul_kernel = _cfg_option(cfg, "matmul_kernel")
        if self.matmul_kernel == "pallas":
            if weight_dtype is None and draft_weight_dtype is None:
                raise ValueError(
                    "matmul_kernel='pallas' is the fused dequant-matmul "
                    "kernel for QUANTIZED weights (QTensor leaves): "
                    "pass weight_dtype='int8'|'int4' (or "
                    "draft_weight_dtype=) too, or drop the kernel — a "
                    "silently inert knob is a bug magnet")
            if _cfg_option(cfg, "scan_layers") \
                    and weight_dtype is not None:
                raise ValueError(
                    "matmul_kernel='pallas' needs scan_layers=False: "
                    "nn.scan slices every param leaf along the layer "
                    "axis and QTensor scales have no such axis (serving "
                    "wants unrolled layers anyway — unstack_scan_params "
                    "the weights; docs/performance.md decode section)")
        if draft_model is not None and _cfg_option(
                draft_model.cfg, "matmul_kernel") != self.matmul_kernel:
            draft_model = _with_cfg(draft_model,
                                    matmul_kernel=self.matmul_kernel)
        if draft_model is not None and draft_weight_dtype is not None \
                and self.matmul_kernel == "pallas" \
                and _cfg_option(draft_model.cfg, "scan_layers"):
            raise ValueError(
                "matmul_kernel='pallas' needs the draft model unrolled "
                "too (scan_layers=False) when its weights are "
                "quantized")
        if weight_group_size is not None \
                and "int4" not in (weight_dtype, draft_weight_dtype):
            raise ValueError(
                "weight_group_size is an int4 grouping option: pass "
                "weight_dtype='int4' (or draft_weight_dtype='int4') to "
                "enable it — int8 scales are per-output-channel")
        if prefill_len > cfg.max_seq_len:
            raise ValueError(
                f"prefill_len ({prefill_len}) exceeds max_seq_len "
                f"({cfg.max_seq_len})")
        if steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got "
                f"{steps_per_dispatch}")
        if page_size is None and (num_pages is not None
                                  or (prefill_chunk is not None
                                      and not dense_chunk)
                                  or prefix_cache):
            raise ValueError(
                "num_pages / prefill_chunk / prefix_cache are paged-KV "
                "features: pass page_size= to enable the page arena "
                "(prefill_chunk alone also serves a model that declares "
                "continues_prefill, on dense slots)")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if not dense_chunk and prefill_chunk % page_size != 0:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be a multiple "
                    f"of page_size ({page_size})")
            if cfg.max_seq_len % prefill_chunk != 0:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must divide "
                    f"max_seq_len ({cfg.max_seq_len}) so chunk offsets "
                    "can never overflow the sequence axis")
        if prefix_cache and prefill_chunk is None:
            raise ValueError(
                "prefix_cache=True needs prefill_chunk= too: an adopted "
                "prefix resumes prefill at its first un-cached offset, "
                "which is a chunk-program dispatch")
        if (spec_k is not None or draft_params is not None) \
                and draft_model is None:
            raise ValueError(
                "spec_k / draft_params are speculative-decoding options: "
                "pass draft_model= (a small decode-mode LM sharing the "
                "target's vocab and max_seq_len) to enable them")
        if draft_model is not None and draft_params is None:
            raise ValueError("draft_model needs draft_params too")
        if draft_weight_dtype is not None and draft_model is None:
            raise ValueError(
                "draft_weight_dtype is a speculative-decoding option: "
                "pass draft_model=/draft_params= to enable it")
        # multi-tenant scheduling (serve/tenancy.py): the engine keeps
        # the resolved class map so validate() refuses unknown tenants
        # and prefill() enforces per-class max_active_slots even for
        # direct (non-ServeClient) callers. The map rides engine_kwargs
        # through supervisor rebuilds and fleet replicas, so recovery
        # re-admission keeps every request's class enforceable.
        # Scheduling policy itself lives in the TenantScheduler — the
        # engine only enforces quotas, it never reorders anything.
        self.tenant_classes = (resolve_tenant_classes(tenant_classes)
                               if tenant_classes else None)
        # batched multi-LoRA serving (models/lora.py + serve/adapters.py):
        # max_resident_adapters= sizes a resident (N, ...) adapter bank
        # on every LoRA-target projection — the bank axis is part of the
        # compiled programs, so hot load/unload/eviction is a data write,
        # never a recompile, and rows bound to different adapters batch
        # in one dispatch. The model is cloned with the LoraConfig here
        # (the attention_kernel/matmul_kernel pattern): supervisor
        # rebuilds and fleet replicas re-enter this ctor with the same
        # kwargs and re-arm the identical bank.
        self.max_resident_adapters = max_resident_adapters
        self.lora_rank = lora_rank
        if max_resident_adapters is None:
            if adapters:
                raise ValueError(
                    "adapters= needs max_resident_adapters= too: the "
                    "bank's num_adapters axis is part of the compiled "
                    "programs and must be sized up front")
            if lora_rank is not None:
                raise ValueError(
                    "lora_rank is a multi-LoRA serving option: pass "
                    "max_resident_adapters= to arm the adapter bank")
        else:
            if max_resident_adapters < 1:
                raise ValueError(
                    f"max_resident_adapters must be >= 1, got "
                    f"{max_resident_adapters}")
            if lora_rank is None or lora_rank < 1:
                raise ValueError(
                    "multi-LoRA serving needs lora_rank >= 1 (the bank's "
                    f"low-rank dimension), got {lora_rank!r}")
            if adapters and len(adapters) > max_resident_adapters:
                raise ValueError(
                    f"{len(adapters)} initial adapters exceed "
                    f"max_resident_adapters={max_resident_adapters}")
            if _cfg_option(cfg, "scan_layers"):
                raise ValueError(
                    "multi-LoRA serving needs scan_layers=False: the "
                    "bank graft walks unrolled layer scopes (serving "
                    "wants unrolled layers anyway — unstack_scan_params "
                    "the weights; docs/performance.md decode section)")
            lora_cfg = LoraConfig(rank=lora_rank,
                                  num_adapters=max_resident_adapters)
            if _cfg_option(cfg, "lora") != lora_cfg:
                model = _with_cfg(model, lora=lora_cfg)
                cfg = model.cfg
        self.model = model
        # weight-only quantization (models/quant.py): storage-only —
        # the programs dequantize once per dispatch, compute stays at
        # cfg.dtype. Quantizing here (not at the call site) keeps
        # supervisor rebuilds deterministic: the raw params re-quantize
        # to bit-identical codes, so crash replay stays token-identical.
        self.weight_dtype = weight_dtype
        self._weights_quantized_events = []
        # weight_group_size feeds whichever models quantize as int4
        # (int8 is per-output-channel — quantize_params refuses a group)
        if weight_dtype is not None:
            params = self._quantize_weights(
                "target", params, weight_dtype,
                weight_group_size if weight_dtype == "int4" else None)
        if draft_weight_dtype is not None:
            draft_params = self._quantize_weights(
                "draft", draft_params, draft_weight_dtype,
                weight_group_size if draft_weight_dtype == "int4"
                else None)
        self.params = params
        # adapter bank graft AFTER weight quantization: the zero-filled
        # (N, ...) lora_A/lora_B banks ride next to the (possibly
        # QTensor) base kernels at full precision — quantize_params
        # skips lora_* leaves by name, and grafting here keeps them out
        # of the quantizer entirely. The LoRA delta therefore rides
        # OUTSIDE the quantized base matmul (pallas fused kernels
        # included), which is what makes the null-adapter row bitwise
        # the unadapted engine.
        self._registry: Optional[AdapterRegistry] = None
        self._adapter_ids: Optional[np.ndarray] = None
        self._adapter_of: Dict[int, str] = {}
        self._adapter_events: List[dict] = []
        if max_resident_adapters is not None:
            self.params = install_lora_bank(self.params, cfg.lora)
            self._registry = AdapterRegistry(
                max_resident_adapters,
                bytes_per_adapter=adapter_bytes(self.params))
            self._adapter_ids = np.full((num_slots,), -1, np.int32)
            for name, tree in dict(adapters or {}).items():
                index, _ = self._registry.admit(name)
                self.params = install_adapter(self.params, tree, index)
                self._adapter_events.append(
                    dict(adapter=name, index=index, evicted=None))
        self.num_slots = num_slots
        if prefill_batch is not None and prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {prefill_batch}")
        self.prefill_batch = min(prefill_batch or num_slots, num_slots)
        if prefill_batch is not None and self.prefill_batch != prefill_batch:
            # the silent clamp bit people: a caller asking for a bigger
            # batch than the engine can inject deserves to know the
            # compiled shape they actually got
            warnings.warn(
                f"prefill_batch={prefill_batch} clamped to "
                f"{self.prefill_batch} (valid range 1..num_slots="
                f"{num_slots}); the prefill program compiles at the "
                "clamped shape", stacklevel=2)
            if telemetry is not None:
                telemetry.event("engine.config_clamped",
                                field="prefill_batch",
                                requested=prefill_batch,
                                effective=self.prefill_batch)
        self.prefill_len = prefill_len
        # >1 = multi-step scheduling: K decode steps per program dispatch
        # (amortizes the fixed per-call overhead; requests join/retire at
        # K-token granularity) — see _engine_step_impl
        self.steps_per_dispatch = steps_per_dispatch
        self.prefill_chunk = prefill_chunk
        # off by default; one attribute read + None check per dispatch
        # when disarmed (docs/observability.md)
        self._tel = telemetry
        # extra args splatted into every engine span — a ReplicaFleet
        # stamps {"seat": replica_id} here so the stitched fleet trace
        # (obs/tracing.py) can put each replica on its own pid track;
        # empty for a standalone engine (span args unchanged)
        self._span_extra: Dict[str, Any] = {}
        self.kv_dtype = kv_dtype
        check_kv_dtype(kv_dtype)
        self.paged = page_size is not None
        self.page_native = page_native
        if self.paged:
            self.pool = PagePool(model, num_slots, page_size,
                                 num_pages=num_pages, kv_dtype=kv_dtype)
        else:
            self.pool = KVSlotPool(model, num_slots, kv_dtype=kv_dtype)
        # speculative decoding: draft proposals verified k+1 tokens per
        # target dispatch (serve/spec.py); steps_per_dispatch scans spec
        # ROUNDS instead of single decode steps when armed
        if draft_model is not None:
            self.spec_k = spec_k if spec_k is not None else 4
            self.spec = SpecDecoder(draft_model, draft_params,
                                    num_slots=num_slots, k=self.spec_k,
                                    target_cfg=cfg)
        else:
            self.spec_k = None
            self.spec = None
        if prefix_cache:
            self.prefix = PrefixCache(self.pool)
        else:
            self.prefix = None
        # at-rest bytes a row (``global``: a position) of each cache kind
        # costs — filled by the first armed dispatch (_live_cache_bytes)
        self._cache_units: Optional[Dict[str, float]] = None
        # does the model leave "counter" leaves in its dense cache (an
        # expert layer's load)? An armed engine reads them a dispatch
        self._has_counters = (
            not self.paged and not check_kv_dtype(kv_dtype)
            and bool(counter_leaves(self.model, self.pool.cache)))
        self._chunk_queue: Deque[_ChunkState] = deque()
        # the requests whose FINAL chunk the last prefill_chunk_step
        # dispatch activated into decode (the paged program feeds one
        # row, the dense one up to prefill_batch) — the driving client
        # stamps TTFT off this without scanning active_requests
        self.chunk_activated: Tuple[Request, ...] = ()
        self._base_key = jax.random.PRNGKey(seed)

        B = num_slots
        self._cur = np.zeros((B, 1), np.int32)
        self._pos = np.zeros((B, 1), np.int32)
        self._active = np.zeros((B,), bool)
        self._remaining = np.zeros((B,), np.int32)
        self._temp = np.zeros((B,), np.float32)
        self._top_k = np.zeros((B,), np.int32)
        self._eos = np.full((B,), -1, np.int32)
        self._keys = np.zeros((B, 2), np.uint32)
        self._stepno = np.zeros((B,), np.int32)
        self._tokens: Dict[int, List[int]] = {}
        # deferred-carry seat (async dispatch): the numpy fields above
        # always hold the SYNCED frontier — the newest dispatch whose
        # tokens the host has seen. When a dispatch is enqueued but not
        # yet synced, its device-side outputs live here and the next
        # enqueue chains on them; step_sync catches the frontier up and
        # clears it. None = fully synced, barrier dispatches allowed.
        self._carry: Optional[tuple] = None
        # highest step-dispatch index step_sync has committed — the
        # in-order guard: handles sync exactly once, in enqueue order,
        # and a rebuilt-away engine's handle (its index can't be the
        # fresh engine's next) fails loudly instead of corrupting
        self._synced_dispatch = 0
        # sync step()'s retry seat: a handle whose host copy failed
        # after its dispatch launched (step_sync left the engine
        # untouched, so the next step() retries the SAME sync instead
        # of wedging behind _require_synced with the handle lost)
        self._retry_sync: Optional[PendingDispatch] = None
        # identity nonce stamped into every handle: step_sync refuses a
        # handle another engine issued — the dispatch-index guard alone
        # has a realignment hole (a dead engine's dispatch-1 handle
        # matches a fresh engine's expected 1)
        self._engine_token = object()

        # counters for the bench / scheduler policy (steps counts
        # dispatches; decode_substeps counts target-model param-read
        # passes: decode token-steps, or spec rounds — one verify reads
        # the params once however many tokens it commits)
        self.steps = 0
        self.decode_substeps = 0
        self.prefills = 0
        self.chunk_dispatches = 0
        self.tokens_generated = 0
        # speculative-decoding accounting (all zero on non-spec engines)
        self.spec_rounds = 0
        self.spec_accepted_tokens = 0
        self.spec_rejected_tokens = 0
        self.spec_draft_steps = 0

        if telemetry is not None:
            for payload in self._weights_quantized_events:
                telemetry.event("engine.weights_quantized", **payload)
            telemetry.metrics.gauge(
                "serve_param_bytes",
                help="at-rest parameter bytes this engine streams per "
                "decode pass (target + draft; quantized codes + scales "
                "when weight_dtype is set)"
            ).set(param_bytes(self.params)
                  + (param_bytes(self.spec.params)
                     if self.spec is not None else 0))
            if self._registry is not None:
                for payload in self._adapter_events:
                    telemetry.event("engine.adapter_loaded", **payload)
                self._set_adapter_gauge()
        self._weights_quantized_events = []
        self._adapter_events = []

    def _quantize_weights(self, which: str, params, weight_dtype: str,
                          group_size: Optional[int]):
        """Quantize one model's params, recording the before/after byte
        accounting for the armed-telemetry event (emitted at the end of
        ``__init__`` — quantization must run before the telemetry handle
        is even assigned)."""
        before = param_bytes(params)
        quantized = quantize_params(params, weight_dtype,
                                    group_size=group_size)
        self._weights_quantized_events.append(dict(
            model=which, dtype=weight_dtype,
            group_size=(None if weight_dtype == "int8"
                        else group_size or DEFAULT_GROUP_SIZE),
            bytes_before=before, bytes_after=param_bytes(quantized)))
        return quantized

    # ----------------------------------------------------- multi-LoRA
    @property
    def resident_adapters(self) -> List[str]:
        """Resident adapter names, least-recently-bound first (the
        deterministic eviction order); empty without a bank."""
        return (self._registry.residents
                if self._registry is not None else [])

    def adapter_bank_bytes(self) -> int:
        """Exact at-rest device bytes of the full adapter bank
        (``capacity * per-adapter slice`` from
        :func:`~ray_lightning_tpu.models.lora.adapter_bytes`) — held
        to that product by ``tests/test_lora.py``."""
        if self._registry is None:
            return 0
        return self._registry.capacity * self._registry.bytes_per_adapter

    def adapter_refcount(self, name: str) -> int:
        """In-flight rows currently pinned to ``name`` (0 when disarmed
        or not resident) — the fleet's pre-unload broadcast check."""
        return (self._registry.refcount(name)
                if self._registry is not None else 0)

    def _set_adapter_gauge(self) -> None:
        self._tel.metrics.gauge(
            "serve_adapter_resident",
            help="LoRA adapters currently resident in the engine's "
            "adapter bank"
        ).set(len(self._registry.residents))

    def load_adapter(self, name: str, adapter) -> Optional[str]:
        """Hot-load (or overwrite) adapter ``name`` into the resident
        bank: claim a bank index (reusing ``name``'s own, else a free
        slot, else deterministically evicting the LRU unpinned
        resident), write the ``(A, B)`` slices in place, no recompile.
        Returns the evicted adapter's name (its future submits shed
        with :class:`~ray_lightning_tpu.serve.adapters.UnknownAdapter`,
        like a :class:`~ray_lightning_tpu.serve.tenancy.ClassQueueFull`
        shed) or ``None``. Needs the synced frontier, like every other
        barrier — the async client drains its pipeline first."""
        if self._registry is None:
            raise ValueError(
                "this engine has no adapter bank — pass "
                "max_resident_adapters=/lora_rank= to arm multi-LoRA "
                "serving")
        self._require_synced("load_adapter")
        index, evicted = self._registry.admit(name)
        self.params = install_adapter(self.params, adapter, index)
        tel = self._tel
        if tel is not None:
            if evicted is not None:
                tel.event("engine.adapter_evicted", adapter=evicted,
                          index=index, by=name)
            tel.event("engine.adapter_loaded", adapter=name,
                      index=index, evicted=evicted)
            self._set_adapter_gauge()
        return evicted

    def unload_adapter(self, name: str) -> None:
        """Release ``name``'s bank slot (refused while in-flight rows
        pin it) and zero its slices — the freed index serves the next
        load with no stale low-rank residue."""
        if self._registry is None:
            raise ValueError(
                "this engine has no adapter bank — pass "
                "max_resident_adapters=/lora_rank= to arm multi-LoRA "
                "serving")
        self._require_synced("unload_adapter")
        index = self._registry.unload(name)
        self.params = zero_adapter(self.params, index)
        tel = self._tel
        if tel is not None:
            tel.event("engine.adapter_unloaded", adapter=name,
                      index=index)
            self._set_adapter_gauge()

    def _effective_adapter(self, request: Request) -> Optional[str]:
        """The adapter this request decodes under: its own binding,
        else its tenant class's default (``TenantClass.adapter=``),
        else ``None`` (the base model)."""
        name = getattr(request, "adapter", None)
        if name is None and self.tenant_classes is not None:
            cls = self.tenant_classes.get(request.tenant)
            if cls is not None:
                name = getattr(cls, "adapter", None)
        return name

    def _bind_adapter(self, req: Request, slot: int) -> int:
        """Pin the request's adapter at admission (inside the atomic
        try block — a mid-batch reject unbinds via
        :meth:`_unbind_adapter`): bumps the registry refcount so
        eviction can never pull a bank slot out from under an in-flight
        row, arms the slot's row id, and stamps the resolved name onto
        the request so crash replay and fleet failover re-bind the
        identical adapter. Returns the bank index (−1 = base model)."""
        name = self._effective_adapter(req)
        if name is None or self._registry is None:
            return -1
        index = self._registry.bind(name)   # UnknownAdapter if evicted
        self._adapter_ids[slot] = index
        self._adapter_of[slot] = name
        req.adapter = name
        tel = self._tel
        if tel is not None:
            tel.event("engine.adapter_bound", id=req.id, adapter=name,
                      slot=slot, index=index)
            tel.metrics.counter(
                f"serve_adapter_requests_total_{name}",
                help="requests admitted under this LoRA adapter"
            ).inc()
        return index

    def _unbind_adapter(self, slot: int) -> None:
        """Drop a slot's adapter pin (retire/cancel/admission
        rollback); no-op for base-model rows and disarmed engines."""
        if self._registry is None:
            return
        name = self._adapter_of.pop(slot, None)
        if name is not None:
            self._registry.unbind(name)
        self._adapter_ids[slot] = -1

    # ------------------------------------------------------------- state
    @property
    def free_slots(self) -> int:
        return self.pool.free_slots

    @property
    def free_pages(self) -> Optional[int]:
        """Free arena pages, or None on the dense path (the client's
        occupancy gauges key off this)."""
        return self.pool.free_pages if self.paged else None

    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def active_requests(self) -> Dict[int, Request]:
        return self.pool.active

    @property
    def chunk_pending(self) -> int:
        """Prompts admitted but still streaming through chunk prefill."""
        return len(self._chunk_queue)

    @property
    def carry_deferred(self) -> bool:
        """True while an enqueued dispatch's device carry has not been
        synced back to the host (an outstanding
        :class:`PendingDispatch` must be ``step_sync``-ed)."""
        return self._carry is not None

    @property
    def retry_pending(self) -> bool:
        """True while a failed sync step's handle waits in the retry
        seat — the next :meth:`step` drains it before dispatching anew
        (the sync driver's tick does this ahead of any barrier, so a
        transient host-copy error cannot wedge deadline cancels or
        admissions)."""
        return self._retry_sync is not None

    @property
    def spec_needs_refill(self) -> bool:
        """True when the next spec dispatch must rebuild draft KV from
        host-side token streams (stale slots — fresh admits, final
        chunks, crash replays). The async client drains its pipeline
        first, so the refill always reads the synced stream."""
        return self.spec is not None and bool(self.spec.stale)

    def _require_synced(self, op: str) -> None:
        """Barrier dispatches (admission, chunk, cancel) mutate the
        host-side row state in place — they need the synced frontier,
        or the next enqueue would chain on stale device carry and drop
        the mutation. The async client drains before every barrier;
        this guard makes direct misuse loud instead of corrupting."""
        if self._carry is not None:
            raise RuntimeError(
                f"{op} needs the synced frontier but an enqueued "
                "dispatch is still pending — step_sync() the "
                "outstanding PendingDispatch first (ServeClient"
                "(async_dispatch=True) drains its pipeline before "
                "admission/chunk/cancel dispatches)")

    def _carry_in(self) -> tuple:
        """The row-state arrays the next dispatch consumes: the device
        carry of the newest enqueued dispatch when one is outstanding
        (pipelined chaining), else the synced numpy frontier."""
        if self._carry is not None:
            return self._carry
        return (self._cur, self._pos, self._active, self._remaining,
                self._stepno)

    @property
    def chunk_pending_ids(self) -> FrozenSet[int]:
        return frozenset(st.request.id for st in self._chunk_queue)

    def occupancy(self) -> Dict[str, Any]:
        """Host-side occupancy snapshot: the engine half of the fleet
        router's scoring signals (``ServeClient.load_stats`` adds the
        scheduler half). Plain ints/None only — this dict crosses the
        process-backend queue transport verbatim."""
        return {
            "active": self.active_count,
            "chunk_pending": self.chunk_pending,
            "free_slots": self.free_slots,
            "free_pages": self.free_pages,
            "num_pages": self.pool.num_pages if self.paged else None,
            "resident_adapters": (len(self._registry.residents)
                                  if self._registry is not None
                                  else None),
        }

    @property
    def max_replay_len(self) -> int:
        """Longest prompt + already-emitted-tokens sequence a crash
        recovery can re-feed: one batched prefill pass without chunking,
        the whole sequence axis with it (chunked replay streams any
        admissible request back in — see docs/reliability.md)."""
        if self.prefill_chunk is not None:
            return self.model.cfg.max_seq_len
        return self.prefill_len

    def validate(self, request: Request) -> None:
        """Admission check: the request must fit the compiled shapes
        (and, tenancy configured, name a declared tenant class)."""
        cfg = self.model.cfg
        tenant = getattr(request, "tenant", DEFAULT_TENANT)
        if self.tenant_classes is not None:
            if tenant not in self.tenant_classes:
                raise ValueError(
                    f"unknown tenant {tenant!r}: this engine's declared "
                    f"classes are {list(self.tenant_classes)}")
        elif tenant != DEFAULT_TENANT:
            raise ValueError(
                f"request names tenant {tenant!r} but the engine has no "
                "tenant classes configured — pass tenant_classes= to "
                "arm multi-tenant scheduling")
        # adapter refusal belongs HERE, at submit — an undeclared or
        # evicted adapter must shed with registry context (the
        # ClassQueueFull pattern), never reach a dispatch as a garbage
        # bank gather
        adapter = self._effective_adapter(request)
        if adapter is not None:
            if self._registry is None:
                raise UnknownAdapter(
                    f"request names adapter {adapter!r} but the engine "
                    "has no adapter bank — pass max_resident_adapters=/"
                    "lora_rank= to arm multi-LoRA serving",
                    adapter=adapter, resident=[], capacity=0)
            self._registry.index_of(adapter)  # UnknownAdapter + context
        if self.prefill_chunk is None \
                and request.prompt_len > self.prefill_len:
            raise ValueError(
                f"prompt length {request.prompt_len} exceeds the engine's "
                f"prefill_len ({self.prefill_len})")
        if request.prompt_len + request.max_new_tokens > cfg.max_seq_len:
            raise ValueError(
                f"prompt ({request.prompt_len}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_seq_len "
                f"({cfg.max_seq_len})")
        if self.spec is not None and (request.prompt_len
                                      + request.max_new_tokens
                                      + self.spec_k - 1) > cfg.max_seq_len:
            raise ValueError(
                f"prompt ({request.prompt_len}) + max_new_tokens "
                f"({request.max_new_tokens}) needs spec_k-1 = "
                f"{self.spec_k - 1} positions of verify headroom beyond "
                f"it (the widened dispatch block-writes k draft "
                f"positions past the last budgeted token) — "
                f"max_seq_len ({cfg.max_seq_len}) is too small")
        if self.paged:
            need = self.pool.pages_needed(request)
            if need > self.pool.num_pages:
                raise ValueError(
                    f"request needs {need} KV pages (prompt "
                    f"{request.prompt_len} + max_new_tokens "
                    f"{request.max_new_tokens} at page_size "
                    f"{self.pool.page_size}) but the arena only has "
                    f"{self.pool.num_pages} — it can never be admitted")

    # ------------------------------------------------------- admission
    def _check_slot_quota(self, request: Request) -> None:
        """Per-class ``max_active_slots`` enforcement at admission
        (tenancy configured): the class may not hold more concurrent KV
        slots than its quota. The TenantScheduler's selection already
        respects this, so the scheduler-driven path never trips it —
        this is the loud defense for direct ``engine.prefill`` callers,
        raising inside the atomic-admission try block so the batch
        rolls back cleanly."""
        if self.tenant_classes is None:
            return
        cls = self.tenant_classes[request.tenant]
        if cls.max_active_slots is None:
            return
        held = sum(1 for r in self.pool.active.values()
                   if r.tenant == request.tenant)
        if held >= cls.max_active_slots:
            raise SlotPoolFull(
                f"tenant {request.tenant!r} at max_active_slots="
                f"{cls.max_active_slots}", tenant=request.tenant,
                slots_free=self.free_slots, active=len(self.pool.active))

    def _routes_chunked(self, request: Request) -> bool:
        """Chunk-prefill routing: everything when the prefix cache is on
        (published pages must all come from the one chunk program), else
        prompts longer than a chunk (bounded decode stall) or longer
        than the batched program can take at all."""
        if self.prefill_chunk is None:
            return False
        if self.prefix is not None:
            return True
        fed = request.prompt_len + len(request.replay_tokens or ())
        return fed > self.prefill_chunk or fed > self.prefill_len

    def _chunk_floor(self, pages: int) -> int:
        """Round a page count down to a whole number of chunks — the ONE
        place the chunk-alignment cap lives, shared by adoption and the
        hit-rate denominator so they can't drift apart."""
        per_chunk = self.prefill_chunk // self.pool.page_size
        return (pages // per_chunk) * per_chunk

    def _adoptable_prefix(self, fed: List[int]) -> List[int]:
        """Cached pages this admission may adopt: the matched chain
        capped to a whole number of chunks, so the resumed prefill
        starts on a chunk boundary and chunk writes can never touch a
        shared page (offsets stay multiples of prefill_chunk, which the
        sequence axis is a multiple of — no clamped-write rebasing)."""
        if self.prefix is None:
            return []
        matched = self.prefix.match(fed)
        return matched[:self._chunk_floor(len(matched))]

    def admissible_prefix(self, requests: List[Request]) -> int:
        """How many of the queue-head ``requests`` this engine can admit
        in one prefill call (FIFO — the count is a prefix, never a
        skip-ahead): slots, the batched program's width, and (paged)
        cumulative page demand against free + cache-evictable pages.
        Page accounting is conservative: prefix hits are counted as
        consuming their pages (adoption pins them un-evictable), never
        as a discount."""
        limit = min(len(requests), self.free_slots)
        if not self.paged:
            return min(limit, self.prefill_batch)
        budget = self.pool.free_pages + (self.prefix.evictable()
                                         if self.prefix is not None else 0)
        n = batched = 0
        for req in requests[:limit]:
            if not self._routes_chunked(req):
                if batched == self.prefill_batch:
                    break
            need = self.pool.pages_needed(req)
            if need > budget:
                break
            budget -= need
            batched += not self._routes_chunked(req)
            n += 1
        return n

    def _admit_paged(self, request: Request, adopt: List[int]) -> int:
        """Acquire slot + pages for one paged admission, evicting
        cache-only pages (protecting the chain being adopted) when the
        free list runs short."""
        fresh_need = self.pool.pages_needed(request) - len(adopt)
        short = fresh_need - self.pool.free_pages
        if short > 0 and self.prefix is not None:
            self.prefix.evict(short, protect=adopt)
        return self.pool.acquire(request, adopt)

    # ---------------------------------------------------------- programs
    def prefill(self, requests: List[Request]) -> List[Completion]:
        """Start ``requests``: slots (and pages) are acquired atomically
        for the whole batch, then prompts short enough for the batched
        program run one fixed-shape prefill pass (first tokens sampled,
        KV injected); chunk-routed prompts (longer than ``prefill_chunk``
        or any prompt under a prefix cache) are queued for
        :meth:`prefill_chunk_step` dispatches instead. Returns
        completions for requests that finish ON their first token
        (eos-on-first or an exhausted budget).

        A request carrying ``replay_tokens`` (crash recovery, see
        :class:`~ray_lightning_tpu.reliability.ServeSupervisor`) re-feeds
        its prompt + those tokens: the prefill rebuilds exactly the KV
        the dead engine held and the sampled token continues the
        request's key stream at step ``len(replay_tokens)``.
        """
        if not requests:
            return []
        tel = self._tel
        with (tel.span("engine.prefill.build", **self._span_extra)
              if tel is not None else NULL_SPAN):
            built = self._prefill_build(requests)
        if built is None:
            return []
        (batched, prompts, lengths, valid, slots, inject_pt, keys, temp,
         top_k, startno, adapter_row) = built
        # None when disarmed: the kwargs guard (_adapter_kw) then keeps
        # the traced programs byte-for-byte the pre-LoRA ones, and model
        # families without the adapter_ids kwarg never see it
        adapter_arg = adapter_row if self._registry is not None else None
        counts = {}
        if tel is not None:
            # counted where the program is dispatched: what it was run
            # over against what it was asked for
            counts = dict(
                ids=[r.id for r in batched], rows=len(batched),
                tokens=int(lengths[:len(batched)].sum()),
                program_tokens=self.prefill_batch * self.prefill_len,
                **self._live_cache_bytes(lengths[:len(batched)]),
                **self._count_topk_wide(tel, top_k))
            m = tel.metrics
            m.counter("serve_prefill_rows_total",
                      help="requests admitted by batched prefill "
                      "dispatches").inc(counts["rows"])
            m.counter("serve_prefill_tokens_total",
                      help="valid prompt (+ replayed) tokens fed to "
                      "batched prefill dispatches").inc(counts["tokens"])
            m.counter("serve_prefill_program_tokens_total",
                      help="tokens the prefill program was run over: "
                      "prefill_batch x prefill_len per dispatch"
                      ).inc(counts["program_tokens"])
        # the call that traces the program also counts its attention
        # seats onto the span (obs/seats.py); later calls add nothing
        with (tel.span("engine.prefill.call", **counts, **self._span_extra)
              if tel is not None else NULL_SPAN) as span_args, \
                seats.tally(span_args):
            if self.paged:
                fn = _pick(_prefill_paged_donated, _prefill_paged_plain)
                self.pool.arena, first = fn(
                    self.model, self.params, self.pool.arena, prompts,
                    lengths, inject_pt, keys, temp, top_k, startno,
                    adapter_arg)
            else:
                fn = _pick(_prefill_inject_donated, _prefill_inject_plain)
                self.pool.cache, first = fn(
                    self.model, self.params, self.pool.cache, prompts,
                    lengths, slots, valid, keys, temp, top_k, startno,
                    adapter_arg)
        with (tel.span("engine.prefill.sync", **self._span_extra)
              if tel is not None else NULL_SPAN):
            first = np.asarray(first)   # THE blocking point of a prefill

        done: List[Completion] = []
        with (tel.span("engine.prefill.activate", ids=counts["ids"],
                       **self._span_extra)
              if tel is not None else NULL_SPAN):
            if tel is not None:
                tel.event("engine.prefill", n=len(batched),
                          ids=counts["ids"],
                          slots=[int(slots[r])
                                 for r in range(len(batched))])
            for r, req in enumerate(batched):
                comp = self._activate(req, int(slots[r]), int(first[r]),
                                      keys[r])
                if comp is not None:
                    done.append(comp)
        self.prefills += 1
        return done

    def _prefill_build(self, requests: List[Request]):
        """The host half of :meth:`prefill` before the dispatch: atomic
        admission of the whole batch (slots, pages, adapters, chunk
        seats) and the program's operand arrays. Returns ``None`` when
        every request was chunk-routed (nothing to dispatch here)."""
        self._require_synced("prefill")
        faults.fire("serve.dispatch")
        n_batched = sum(not self._routes_chunked(r) for r in requests)
        if n_batched > self.prefill_batch \
                or len(requests) > self.free_slots:
            raise SlotPoolFull(
                f"{len(requests)} requests ({n_batched} batched) > "
                f"min(free_slots={self.free_slots}, prefill_batch="
                f"{self.prefill_batch})",
                slots_free=self.free_slots,
                pages_free=self.free_pages,
                active=len(self.pool.active))
        B_pf, P = self.prefill_batch, self.prefill_len
        prompts = np.zeros((B_pf, P), np.int32)
        lengths = np.ones((B_pf,), np.int32)
        valid = np.zeros((B_pf,), bool)
        slots = np.zeros((B_pf,), np.int32)
        inject_pt = np.full(
            (B_pf, self.pool.pages_per_slot if self.paged else 1), -1,
            np.int32)
        keys = np.zeros((B_pf, 2), np.uint32)
        temp = np.zeros((B_pf,), np.float32)
        top_k = np.zeros((B_pf,), np.int32)
        startno = np.zeros((B_pf,), np.int32)
        # per-row adapter bank ids (−1 = base model, padding rows too —
        # their delta is masked to exact zero, so they stay bitwise the
        # unadapted computation)
        adapter_row = np.full((B_pf,), -1, np.int32)
        acquired: List[int] = []
        batched: List[Request] = []
        adoptions: List[Tuple[int, int, Request]] = []
        n_chunked = 0
        try:
            for req in requests:
                self.validate(req)
                self._check_slot_quota(req)
                replay = list(req.replay_tokens or ())
                fed = list(req.prompt) + replay
                if self._routes_chunked(req) and not self.paged:
                    # a dense slot (__init__ allows prefill_chunk without
                    # pages only to a model that continues its prefill).
                    # Until the last piece activates the row, the step
                    # program runs it as an inactive row: its K/V write
                    # is parked on the first position the prompt has
                    # not fed yet (the next piece writes over it; a
                    # parked row's position is also how far the step's
                    # attention reads), and its recurrent state is
                    # frozen in the program (_engine_step_core)
                    slot = self.pool.acquire(req)
                    acquired.append(slot)
                    self._cur[slot, 0] = 0
                    self._pos[slot, 0] = 0
                    self._chunk_queue.append(_ChunkState(
                        request=req, slot=slot, fed=fed, next_off=0))
                    n_chunked += 1
                    continue
                if self._routes_chunked(req):
                    adopt = self._adoptable_prefix(fed)
                    slot = self._admit_paged(req, adopt)
                    acquired.append(slot)
                    self._bind_adapter(req, slot)
                    hit = len(adopt) * self.pool.page_size
                    req.prefix_hit_tokens = hit
                    self._chunk_queue.append(_ChunkState(
                        request=req, slot=slot, fed=fed, next_off=hit))
                    n_chunked += 1
                    if self.prefix is not None:
                        # eligible = what a fully warm cache could have
                        # served under the same chunk-alignment cap
                        eligible = self._chunk_floor(
                            (len(fed) - 1) // self.pool.page_size)
                        adoptions.append((eligible, len(adopt), req))
                    continue
                L = len(fed)
                if L > self.prefill_len:
                    raise ValueError(
                        f"request {req.id}: prompt ({req.prompt_len}) + "
                        f"replayed tokens ({len(replay)}) exceed "
                        f"prefill_len ({self.prefill_len}) — not "
                        "resumable in one prefill pass")
                slot = (self._admit_paged(req, [])
                        if self.paged else self.pool.acquire(req))
                acquired.append(slot)
                r = len(batched)
                adapter_row[r] = self._bind_adapter(req, slot)
                batched.append(req)
                prompts[r, :L] = fed
                lengths[r] = L
                valid[r] = True
                slots[r] = slot
                if self.paged:
                    inject_pt[r] = self.pool.page_table[slot]
                keys[r] = np.asarray(
                    jax.random.fold_in(self._base_key, req.seed))
                temp[r] = req.temperature
                top_k[r] = req.top_k or 0
                startno[r] = len(replay)
        except Exception:
            # atomic admission: a mid-batch reject (seed collision, bad
            # shape, page shortage) must not leak the slots/pages/chunk
            # seats already acquired. Resources only: prefix-cache
            # entries evicted to seat earlier batch members stay evicted
            # (their pages may already be re-acquired) — a retried batch
            # loses some cache warmth, never tokens
            for slot in acquired:
                self.pool.release(slot)
                self._unbind_adapter(slot)
            for _ in range(n_chunked):
                self._chunk_queue.pop()
            raise
        # poison fires AFTER the batch is seated (not with the dispatch
        # fire above, which precedes slot acquisition): a poison crash
        # must leave its request in-flight so snapshot_in_flight() —
        # and therefore the fleet's implication ledger — sees it.
        # Crashing pre-admission would bounce the poison back to the
        # client queue forever, invisible to containment.
        faults.poison_check(requests)
        # stats/telemetry only once the whole batch's admission held —
        # rolled-back admissions never count as hits or misses
        for eligible, adopted, req in adoptions:
            self.prefix.record_admission(eligible, adopted)
            if self._tel is not None and adopted:
                self._tel.event(
                    "engine.prefix_hit", id=req.id, pages=adopted,
                    tokens=adopted * self.pool.page_size)
                self._tel.metrics.counter(
                    "serve_prefix_pages_reused_total",
                    help="KV pages adopted from the prefix cache"
                ).inc(adopted)

        if not batched:
            return None
        # padding rows of the dense path target a real slot but carry
        # valid=False — the inject keeps the pool row, so they write
        # nowhere (paged padding rows are all-(−1) scatter drops)
        for r in range(len(batched), B_pf):
            slots[r] = acquired[0]
        return (batched, prompts, lengths, valid, slots, inject_pt, keys,
                temp, top_k, startno, adapter_row)

    def prefill_chunk_step(self) -> List[Completion]:
        """One chunk-program dispatch for the head of the chunk queue:
        feed the next ``prefill_chunk`` tokens at the request's offset.
        On the final chunk the sampled first token activates the decode
        row (or retires the request, eos-on-first/budget-of-one), and —
        prefix cache armed — the finished prompt's full pages are
        published for future adopters."""
        self.chunk_activated = ()
        if not self._chunk_queue:
            return []
        self._require_synced("prefill_chunk_step")
        faults.fire("serve.dispatch")
        if not self.paged:
            return self._dense_chunk_step()
        st = self._chunk_queue[0]
        faults.poison_check((st.request,))
        req = st.request
        C = self.prefill_chunk
        L = len(st.fed)
        off = st.next_off
        valid = min(C, L - off)
        final = off + valid >= L
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :valid] = st.fed[off:off + valid]
        keys = np.asarray(
            jax.random.fold_in(self._base_key, req.seed))[None]
        temp = np.array([req.temperature], np.float32)
        top_k = np.array([req.top_k or 0], np.int32)
        startno = np.array([len(req.replay_tokens or ())], np.int32)
        row_pages = np.array(self.pool.page_table[st.slot])
        tel = self._tel
        adapter_arg = (np.array([self._adapter_ids[st.slot]], np.int32)
                       if self._registry is not None else None)
        fn = _pick(_chunk_prefill_donated, _chunk_prefill_plain)
        if tel is not None:
            m = tel.metrics
            m.counter("serve_chunk_tokens_total",
                      help="valid prompt tokens fed to chunk-prefill "
                      "dispatches").inc(valid)
            m.counter("serve_chunk_program_tokens_total",
                      help="tokens the chunk program was run over: "
                      "prefill_chunk per dispatch").inc(C)
        with (tel.span("engine.chunk.call", ids=[req.id], off=off,
                       tokens=valid, program_tokens=C, slot=st.slot,
                       **self._count_topk_wide(tel, top_k),
                       **self._span_extra)
              if tel is not None else NULL_SPAN):
            self.pool.arena, first = fn(
                self.model, self.params, self.pool.arena, row_pages,
                tokens, np.int32(off), np.int32(valid), keys, temp,
                top_k, startno, adapter_arg)
        with (tel.span("engine.chunk.sync", slot=st.slot,
                       **self._span_extra)
              if tel is not None else NULL_SPAN):
            first = np.asarray(first)
        st.next_off = off + valid
        self.chunk_dispatches += 1
        if tel is not None:
            tel.event("engine.chunk", id=req.id, off=off, n=valid,
                      final=final)
        if not final:
            return []
        self._chunk_queue.popleft()
        if self.prefix is not None:
            # publish before activation: eos-on-first retires the slot,
            # but the cache's own refs keep the prefix pages warm
            self.prefix.publish(list(req.prompt), st.slot)
        comp = self._activate(req, st.slot, int(first[0]), keys[0])
        if comp is None:
            self.chunk_activated = (req,)
            return []
        return [comp]

    def _dense_chunk_step(self) -> List[Completion]:
        """:meth:`prefill_chunk_step` on dense slots: ONE ``(rows,
        prefill_chunk)`` dispatch that feeds the next piece of each of
        the first ``rows <= prefill_batch`` prompts of the chunk queue,
        every row at its own offset, the state carried in its slot
        (:func:`_chunk_prefill_dense_impl`). Rows whose piece was their
        last activate (or retire on their first token).

        The program is compiled a row count — a lone pending prompt, the
        common case under steady arrivals, does not pay for
        ``prefill_batch`` rows — and the first dispatch runs every other
        count once over rows that feed nothing, so that no count is left
        to compile under load."""
        rows = list(self._chunk_queue)[:self.prefill_batch]
        faults.poison_check([st.request for st in rows])
        C = self.prefill_chunk
        fn = _pick(_chunk_dense_donated, _chunk_dense_plain)

        def operands(rows, feed: bool = True):
            n_rows = len(rows)
            tokens = np.zeros((n_rows, C), np.int32)
            offset = np.zeros((n_rows,), np.int32)
            lengths = np.zeros((n_rows,), np.int32)
            keys = np.zeros((n_rows, 2), np.uint32)
            temp = np.zeros((n_rows,), np.float32)
            top_k = np.zeros((n_rows,), np.int32)
            startno = np.zeros((n_rows,), np.int32)
            for r, st in enumerate(rows if feed else ()):
                req, off = st.request, st.next_off
                n = min(C, len(st.fed) - off)
                tokens[r, :n] = st.fed[off:off + n]
                offset[r], lengths[r] = off, n
                keys[r] = np.asarray(
                    jax.random.fold_in(self._base_key, req.seed))
                temp[r] = req.temperature
                top_k[r] = req.top_k or 0
                startno[r] = len(req.replay_tokens or ())
            slots = np.array([st.slot for st in rows], np.int32)
            # a row that feeds nothing writes back what it reads
            valid = np.full((n_rows,), feed)
            return (tokens, offset, lengths, slots, valid, keys, temp,
                    top_k, startno)

        if not self.chunk_dispatches:
            for n in range(1, self.prefill_batch + 1):
                if n != len(rows):
                    self.pool.cache, _ = fn(
                        self.model, self.params, self.pool.cache,
                        *operands(rows[:1] * n, feed=False))
        args = operands(rows)
        _, offset, lengths, _, _, keys, _, top_k, _ = args
        n_rows, fed_now = len(rows), int(lengths.sum())
        tel = self._tel
        if tel is not None:
            m = tel.metrics
            m.counter("serve_chunk_tokens_total",
                      help="valid prompt tokens fed to chunk-prefill "
                      "dispatches").inc(fed_now)
            m.counter("serve_chunk_program_tokens_total",
                      help="tokens the chunk program was run over: "
                      "rows x prefill_chunk per dense-slot "
                      "dispatch").inc(n_rows * C)
        with (tel.span("engine.chunk.call",
                       ids=[st.request.id for st in rows],
                       off=[int(o) for o in offset],
                       lens=[int(n) for n in lengths],
                       tokens=fed_now, program_tokens=n_rows * C,
                       rows=n_rows, slot=[st.slot for st in rows],
                       **self._count_topk_wide(tel, top_k),
                       **self._span_extra)
              if tel is not None else NULL_SPAN) as call_args:
            self.pool.cache, first = fn(
                self.model, self.params, self.pool.cache, *args)
        with (tel.span("engine.chunk.sync", slot=[st.slot for st in rows],
                       **self._span_extra)
              if tel is not None else NULL_SPAN):
            if tel is not None and self._has_counters:
                # one fetch for the first tokens and the expert load
                first, load = _fetch(
                    (first, counter_leaves(self.model, self.pool.cache)))
                call_args.update(expert_load_counts(load))
            else:
                first = _fetch(first)
        self.chunk_dispatches += 1
        done: List[Completion] = []
        activated: List[Request] = []
        for r, st in enumerate(rows):
            off, n = st.next_off, int(lengths[r])
            st.next_off = off + n
            final = st.next_off >= len(st.fed)
            if tel is not None:
                tel.event("engine.chunk", id=st.request.id, off=off, n=n,
                          final=final)
            if not final:
                # where the step program parks this row's K/V write
                # until its next piece (see _prefill_build)
                self._pos[st.slot, 0] = st.next_off
                continue
            self._chunk_queue.remove(st)
            comp = self._activate(st.request, st.slot, int(first[r]),
                                  keys[r])
            if comp is None:
                activated.append(st.request)
            else:
                done.append(comp)
        self.chunk_activated = tuple(activated)
        return done

    def _activate(self, req: Request, slot: int, tok: int,
                  key: np.ndarray) -> Optional[Completion]:
        """Shared first-token bookkeeping for the batched prefill and the
        final chunk: record the token, retire on eos-on-first/exhausted
        budget, otherwise arm the slot's decode row."""
        toks = list(req.replay_tokens or ())
        if self.spec is None or not toks:
            toks.append(tok)
            self.tokens_generated += 1
        # else: spec-engine replay — the prefill's plain categorical
        # draw is NOT the token the uninterrupted spec stream produced
        # at this step (that one came through the rejection-resampling
        # composition). Discard it and arm the row one step earlier:
        # the next spec round regenerates step len(replay) through the
        # same accept rule, off the same (seed, step) keys — sampled
        # streams stay replay-exact (greedy is indifferent: both paths
        # commit the target argmax). Non-spec engines keep the original
        # contract: the prefill draw IS the stream's next token.
        self._tokens[slot] = toks
        hit_eos = req.eos_id is not None and toks[-1] == req.eos_id
        if hit_eos or len(toks) >= req.max_new_tokens:
            return self._retire(
                slot, FINISH_EOS if hit_eos else FINISH_LENGTH)
        self._cur[slot, 0] = toks[-1]
        self._pos[slot, 0] = req.prompt_len + len(toks) - 1
        self._active[slot] = True
        self._remaining[slot] = req.max_new_tokens - len(toks)
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k or 0
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        self._keys[slot] = key
        self._stepno[slot] = len(toks)
        if self.spec is not None:
            # whatever path armed the row (fresh admit, final chunk,
            # crash replay), the draft KV must be rebuilt from the full
            # context before the next spec dispatch
            self.spec.mark_stale(slot)
        return None

    def step(self) -> List[Completion]:
        """Advance every in-flight request up to ``steps_per_dispatch``
        tokens in one program dispatch; returns the completions of rows
        that finished inside the block (eos or budget — rows finishing at
        sub-step k park idempotently for the remaining sub-steps).

        Speculative engines (``draft_model=``) route here too: each of
        the ``steps_per_dispatch`` scanned units is then one spec ROUND
        (k draft steps + one widened verify) committing 1..k+1 tokens
        per row instead of exactly one.

        Internally this is :meth:`step_enqueue` + :meth:`step_sync`
        back-to-back — the sync driver pays the host round-trip between
        every dispatch; ``ServeClient(async_dispatch=True)`` splits the
        halves across ticks so the device never waits on it. With a
        handle still outstanding this refuses loudly (same misuse class
        as the barrier guards): chaining a sync step past an un-synced
        enqueue would advance the carry while silently dropping the
        outstanding dispatch's tokens. A transient device error at the
        host copy is retryable: the failed sync leaves the engine
        untouched and the handle parks in a retry seat, so the next
        ``step()`` syncs the SAME dispatch before launching anew."""
        if self._retry_sync is not None:
            # a prior step()'s sync failed after its dispatch launched —
            # drain it first (the carry is deliberately still deferred)
            pending, self._retry_sync = self._retry_sync, None
        else:
            self._require_synced("step")
            pending = self._enqueue(asynchronous=False)
            if pending is None:
                return []
        try:
            return self.step_sync(pending)
        except Exception:
            self._retry_sync = pending
            raise

    def step_enqueue(self) -> Optional[PendingDispatch]:
        """Enqueue one step/spec dispatch against the device carry and
        return WITHOUT syncing its outputs (depth-2 pipelining: the
        returned :class:`PendingDispatch` is reconciled by
        :meth:`step_sync` while the NEXT dispatch computes). Rows that
        retire inside an un-synced dispatch are handled by the
        in-program latches the next dispatch already carries — parked
        rows emit −1 and write nothing — so chained enqueues commit
        exactly the sync driver's tokens. Returns ``None`` when nothing
        is in flight at the synced frontier and no carry is deferred."""
        return self._enqueue(asynchronous=True)

    def _enqueue(self, *, asynchronous: bool) -> Optional[PendingDispatch]:
        if self._carry is None and not self._active.any():
            return None
        if self.spec is not None:
            return self._spec_enqueue(asynchronous)
        faults.fire("serve.dispatch")
        faults.poison_check(self.pool.active.values())
        tel = self._tel
        with (tel.span("engine.step.build", **self._span_extra)
              if tel is not None else NULL_SPAN):
            fn, args = self._step_call()
        # the call that traces the program counts its attention seats
        # onto the span (obs/seats.py), as engine.prefill.call does
        with (tel.span("engine.step.call", **self._count_step_rows(tel),
                       **self._span_extra)
              if tel is not None else NULL_SPAN) as call_args, \
                seats.tally(call_args):
            (store, cur, pos, active, remaining, stepno,
             report) = fn(*args, steps=self.steps_per_dispatch)
            # the one transfer of this dispatch starts behind its
            # program; step_sync waits for it and for nothing else
            report.copy_to_host_async()
            if self.paged:
                self.pool.arena = store
            else:
                self.pool.cache = store
        counters = None
        if tel is not None and self._has_counters:
            # read at step_sync; a pipelined dispatch's successor donates
            # the cache first, so it keeps copies (armed only)
            counters = counter_leaves(self.model, store)
            if asynchronous:
                counters = [jnp.copy(c) for c in counters]
            for c in counters:
                c.copy_to_host_async()
        self._carry = (cur, pos, active, remaining, stepno)
        self.steps += 1
        self.decode_substeps += self.steps_per_dispatch
        if tel is not None and asynchronous:
            tel.event("engine.dispatch_enqueued", dispatch=self.steps,
                      kind="step")
        return PendingDispatch(
            kind="step", dispatch=self.steps,
            rounds=self.steps_per_dispatch, report=report,
            carry=self._carry,
            owner=self._engine_token, asynchronous=asynchronous,
            enqueued_at=self._overlap_stamp(asynchronous),
            counters=counters, call_args=call_args)

    def _overlap_stamp(self, asynchronous: bool) -> float:
        """``PendingDispatch.enqueued_at``: a wall stamp for
        ``serve_dispatch_overlap_ms`` when armed and pipelined, else 0.0
        (no clock read)."""
        if self._tel is None or not asynchronous:
            return 0.0
        return time.perf_counter()

    def _count_step_rows(self, tel) -> Dict[str, int]:
        """Armed only: the rows a step/spec dispatch advances against the
        rows its program runs over, as span args and registry counters.
        ``active`` is the synced frontier's view (one dispatch stale
        under ``async_dispatch``)."""
        active = int(self._active.sum())
        m = tel.metrics
        m.counter("serve_step_rows_total",
                  help="active rows summed over step dispatches"
                  ).inc(active)
        m.counter("serve_step_slots_total",
                  help="num_slots summed over step dispatches (the rows "
                  "the step program runs over)").inc(self.num_slots)
        contexts = self._pos[self._active, 0].astype(np.int64) + 1
        return {"active": active, "slots": self.num_slots,
                **self._live_cache_bytes(contexts),
                **self._kv_blocks(contexts),
                **self._count_topk_wide(tel, self._top_k)}

    def _kv_blocks(self, contexts) -> Dict[str, int]:
        """Armed only: the key blocks ``ops/pallas_decode.py`` copies for
        rows holding ``contexts`` positions, against the blocks of the
        whole slot pool (what the masked dense read streams). From the
        synced frontier; empty for a slot of no whole number of blocks."""
        L = getattr(self.model.cfg, "max_seq_len", 0)
        block = decode_block(L) if L else None
        if block is None:
            return {}
        return {"kv_blocks_read": int(blocks_read(contexts, block).sum()),
                "kv_blocks_slot": self.num_slots * (L // block)}

    def _count_topk_wide(self, tel, top_k) -> Dict[str, int]:
        """Armed only: whether the ``top_k`` operand of this dispatch
        holds a row above ``TOP_K_CANDIDATES`` — the host's reading of
        the predicate under which the program ranks the whole vocabulary
        (``generate.top_k_dispatch``) — as a span arg and a counter."""
        wide = int(top_k.max() > TOP_K_CANDIDATES)
        tel.metrics.counter(
            "serve_sample_topk_wide_total",
            help="dispatches whose sampling ranked the whole vocabulary: "
            "some row's top_k above generate.TOP_K_CANDIDATES").inc(wide)
        return {"topk_wide": wide}

    def _live_cache_bytes(self, contexts) -> Dict[str, int]:
        """Armed only: at-rest cache bytes that rows holding ``contexts``
        positions keep live, by the kind each cache leaf declares —
        ``recurrent`` and ``window`` are held whole by every row (a ring
        is allocated, and read, at its full length whatever the
        context), ``global`` grows with the context. From shapes and the
        synced frontier alone: nothing is read from the device."""
        if self._cache_units is None:
            # bytes a row holds of each kind; for ``global``, a position
            storage = self.pool.arena if self.paged else self.pool.cache
            trees = storage if isinstance(storage, tuple) else (storage,)
            leaves = jax.tree_util.tree_leaves
            units = {"recurrent": 0.0, "window": 0.0, "global": 0.0}
            for decl, *held in zip(leaves(cache_layout(self.model,
                                                       trees[0])),
                                   *(leaves(t) for t in trees)):
                if not decl.per_slot:
                    continue
                per = held[0].shape[decl.slot_axis]
                if decl.kind == "global":
                    per *= held[0].shape[decl.seq_axis]
                units[decl.kind] += sum(x.nbytes for x in held) / per
            self._cache_units = units
        contexts = np.asarray(contexts, np.int64)
        units = self._cache_units
        return {"recurrent_bytes": int(units["recurrent"] * len(contexts)),
                "window_bytes": int(units["window"] * len(contexts)),
                "global_bytes": int(units["global"] * contexts.sum())}

    def _step_call(self) -> tuple:
        """``(jitted step program, positional operands)`` of the next
        non-speculative dispatch — the one place the three storage
        layouts pick their program and operands."""
        if self.paged and self.page_native:
            # page-native: attention reads/writes K/V through the
            # (write-masked) page table inside the model — no dense
            # view gather/scatter per dispatch. Token-identical to
            # the dense-gather path up to reduction-order rounding
            # (int8 arenas: plus per-token page requant rounding —
            # docs/serving.md caveat); pinned by tests/test_paged.py
            # ::test_page_native_matches_dense_gather. The write
            # mask comes from the SYNCED frontier: a row that
            # retired inside a still-pending dispatch keeps its
            # entries one extra dispatch and re-writes its frozen
            # K/V idempotently — its pages are only released (and
            # only reusable) at sync, behind the admission barrier.
            fn = _pick(_page_native_step_donated, _page_native_step_plain)
            store = (self.pool.arena, self._write_masked_table())
        elif self.paged:
            fn = _pick(_paged_step_donated, _paged_step_plain)
            # the table copy re-uploads H2D every dispatch though it
            # only changes at admit/retire — known headroom, kept simple
            store = (self.pool.arena, np.array(self.pool.page_table))
        else:
            fn = _pick(_engine_step_donated, _engine_step_plain)
            store = (self.pool.cache,)
        cur, pos, active, remaining, stepno = self._carry_in()
        return fn, (self.model, self.params, *store, cur, pos, active,
                    remaining, self._temp, self._top_k, self._eos,
                    self._keys, stepno, self._adapter_ids)

    def lowered_step_text(self) -> str:
        """The lowered (StableHLO) text of the decode step program this
        engine dispatches, on its current operands — what a caller reads
        to see which kernels the program really holds (a Pallas kernel
        compiled for the chip appears as ``tpu_custom_call``; an
        interpreted one does not). Traces only: nothing compiles or
        runs, no operand is donated."""
        if self.spec is not None:
            raise NotImplementedError(
                "lowered_step_text covers the plain step program; a "
                "speculative engine dispatches spec rounds")
        fn, args = self._step_call()
        return fn.lower(*args, steps=self.steps_per_dispatch).as_text()

    def step_sync(self, pending: PendingDispatch) -> List[Completion]:
        """Materialize one enqueued dispatch: wait for its report, the
        one device-to-host transfer started at enqueue (THE blocking
        point — everything the caller did since :meth:`step_enqueue`
        overlapped the device; an armed engine whose model counts expert
        load makes a second fetch, for those leaves), catch the synced
        frontier up to the carry the report holds, and run the retire
        loop. No other device array is read: the carry arrays stay on
        the device for the next enqueue. Handles must
        sync in enqueue order; a handle from a rebuilt-away engine must
        be DISCARDED, never synced (its tokens were regenerated by
        replay)."""
        if pending.owner is not self._engine_token:
            raise RuntimeError(
                "step_sync on a foreign handle: this PendingDispatch "
                "was issued by another (likely rebuilt-away) engine — "
                "it must be discarded, never synced; its tokens were "
                "regenerated by the replay")
        if pending.dispatch != self._synced_dispatch + 1:
            # same loud-misuse policy as _require_synced: a double sync
            # would duplicate every emitted token (and could retire a
            # slot's NEW tenant on the old row's verdict), and a handle
            # from a rebuilt-away engine must be discarded, never
            # synced — both show up here as an out-of-order index
            raise RuntimeError(
                f"step_sync out of order: handle is dispatch "
                f"{pending.dispatch}, engine expects "
                f"{self._synced_dispatch + 1} — handles sync exactly "
                "once, in enqueue order, and a rebuilt engine's "
                "outstanding handle must be discarded, not synced")
        tel = self._tel
        # wall time by definition (docs/observability.md); only an armed
        # engine's pipelined dispatch reads the clock for it
        overlap_ms = (1e3 * (time.perf_counter() - pending.enqueued_at)
                      if pending.enqueued_at else 0.0)
        # materialize EVERY fallible host copy into locals first: a
        # device error surfacing here must leave the engine untouched —
        # the caller keeps the handle and can retry this same sync (or
        # hit the loud out-of-order guard), instead of resuming past a
        # dispatch whose tokens were silently skipped
        with (tel.span("engine.step.sync", dispatch=pending.dispatch,
                       **self._span_extra)
              if tel is not None else NULL_SPAN):
            # one span a fetch: its end is the moment the host holds it
            with (tel.span("engine.step.fetch", what="report",
                           **self._span_extra)
                  if tel is not None else NULL_SPAN):
                got = _fetch(pending.report)
            # np.array (copy): a fetched buffer is read-only, and the
            # next prefill writes the carry rows in place
            rep = unpack_report(
                np.array(got), self.num_slots, pending.rounds,
                self.spec.k + 1 if pending.kind == "spec" else None)
            if pending.counters:
                with tel.span("engine.step.fetch", what="counters",
                              **self._span_extra):
                    got = _fetch(pending.counters)
                pending.call_args.update(expert_load_counts(got))
        # ---- commit point: everything below is host-side bookkeeping
        self._synced_dispatch = pending.dispatch
        self._cur, self._pos, self._active = rep.cur, rep.pos, rep.active
        self._remaining, self._stepno = rep.remaining, rep.stepno
        if self._carry is pending.carry:
            # frontier caught up with the newest enqueue — barrier
            # dispatches may run again
            self._carry = None
        with (tel.span("engine.step.retire", **self._span_extra)
              if tel is not None else NULL_SPAN) as opened:
            if pending.kind == "spec":
                done = self._sync_spec(pending, rep.emitted, rep.accepted,
                                       rep.rejected, rep.finished,
                                       overlap_ms)
            else:
                done = self._retire_rows(pending, rep.emitted,
                                         rep.finished, overlap_ms)
            if tel is not None:
                opened["ids"] = [c.request_id for c in done]
        return done

    def _retire_rows(self, pending: PendingDispatch, emitted, finished,
                     overlap_ms: float) -> List[Completion]:
        """The plain-step half of :meth:`step_sync` below its commit
        point: commit each slot's tokens, retire the rows that finished,
        emit the dispatch's telemetry."""
        tel = self._tel
        done: List[Completion] = []
        for slot in range(self.num_slots):
            toks = [int(t) for t in emitted[:, slot] if t >= 0]
            if not toks:
                continue
            self._tokens[slot].extend(toks)
            self.tokens_generated += len(toks)
            if finished[:, slot].any():
                req = self.pool.active[slot]
                hit_eos = req.eos_id is not None and toks[-1] == req.eos_id
                done.append(self._retire(
                    slot, FINISH_EOS if hit_eos else FINISH_LENGTH))
        if tel is not None:
            if pending.asynchronous:
                tel.event("engine.dispatch_synced",
                          dispatch=pending.dispatch, kind="step",
                          retired=len(done))
                tel.metrics.histogram(
                    "serve_dispatch_overlap_ms",
                    help="host work overlapped with an in-flight "
                    "dispatch: enqueue return -> sync start, wall ms"
                ).observe(overlap_ms)
            else:
                tel.event("engine.step", dispatch=pending.dispatch,
                          active=self.active_count, retired=len(done))
        return done

    def _write_masked_table(self) -> np.ndarray:
        """The page table with inactive rows' entries masked to −1 —
        what every page-native program receives: a mid-chunking slot's
        pages (allocated, not yet decoding) must never see a parked
        decode write, and retired rows' reads may clamp harmlessly."""
        return np.where(self._active[:, None], self.pool.page_table,
                        -1).astype(np.int32)

    def _spec_enqueue(self, asynchronous: bool) -> PendingDispatch:
        """Enqueue one speculative dispatch: refill stale draft rows
        (host-side, reading the SYNCED token streams — the refill
        ledger is why the async client drains its pipeline before any
        dispatch that marks a slot stale), then launch
        ``steps_per_dispatch`` spec rounds (k+1 draft feeds + one
        ``(B, k+1)`` verify each) in one fused program. Greedy commits
        are token-identical to the plain step path by the accept rule
        (see serve/spec.py); the host-side retire loop
        (:meth:`_sync_spec`) is shared shape-for-shape with
        :meth:`step_sync` at (rounds, k+1)-token granularity."""
        faults.fire("serve.dispatch")
        faults.poison_check(self.pool.active.values())
        spec = self.spec
        tel = self._tel
        with (tel.span("engine.spec.build", **self._span_extra)
              if tel is not None else NULL_SPAN):
            active_req = self.pool.active
            for slot in spec.stale:
                req = active_req.get(slot)
                if req is None or not self._active[slot]:
                    spec.discard(slot)
                    continue
                # draft KV must cover 0..pos-1: full context minus the
                # current token (which the first draft feed supplies)
                spec.refill(slot,
                            list(req.prompt) + self._tokens[slot][:-1])
            faults.fire("serve.verify")
            k, rounds = spec.k, self.steps_per_dispatch
            fn, args = self._spec_call()
        with (tel.span("engine.spec.call", k=k,
                       **self._count_step_rows(tel), **self._span_extra)
              if tel is not None else NULL_SPAN):
            (store, spec.cache, cur, pos, act, remaining, stepno,
             report) = fn(*args, k=k, rounds=rounds)
            report.copy_to_host_async()
            if self.paged:
                self.pool.arena = store
            else:
                self.pool.cache = store
        self._carry = (cur, pos, act, remaining, stepno)
        self.steps += 1
        # one verify = one target param read, however many tokens it
        # committed — the honesty-floor unit stays "target passes"
        self.decode_substeps += rounds
        self.spec_rounds += rounds
        self.spec_draft_steps += (k + 1) * rounds
        if tel is not None and asynchronous:
            tel.event("engine.dispatch_enqueued", dispatch=self.steps,
                      kind="spec")
        return PendingDispatch(
            kind="spec", dispatch=self.steps, rounds=rounds,
            report=report, carry=self._carry,
            owner=self._engine_token, asynchronous=asynchronous,
            enqueued_at=self._overlap_stamp(asynchronous))

    def _spec_call(self) -> tuple:
        """:meth:`_step_call` for a speculative dispatch: the jitted
        spec-round program of this engine's storage layout and its
        positional operands (the draft cache stays dense either way)."""
        spec = self.spec
        if self.paged and self.page_native:
            # the widened verify reads/writes target K/V through the
            # page table too — spec and page-native compose on one engine
            fn = _pick(_spec_page_native_donated, _spec_page_native_plain)
            store = (self.pool.arena, self._write_masked_table(),
                     spec.cache)
        elif self.paged:
            fn = _pick(_spec_paged_donated, _spec_paged_plain)
            store = (self.pool.arena, np.array(self.pool.page_table),
                     spec.cache)
        else:
            fn = _pick(_spec_rounds_donated, _spec_rounds_plain)
            store = (self.pool.cache, spec.cache)
        cur, pos, active, remaining, stepno = self._carry_in()
        return fn, (self.model, spec.model, self.params, spec.params,
                    *store, cur, pos, active, remaining, self._temp,
                    self._top_k, self._eos, self._keys, stepno,
                    self._adapter_ids)

    def _sync_spec(self, pending: PendingDispatch, emitted, accepted,
                   rejected, finished,
                   overlap_ms: float) -> List[Completion]:
        """The spec half of :meth:`step_sync`: (rounds, B, k+1) retire
        loop + acceptance accounting. The arrays arrive already
        materialized — every fallible host copy happens before the
        caller's commit point."""
        tel = self._tel
        rounds = pending.rounds

        done: List[Completion] = []
        committed = 0
        for slot in range(self.num_slots):
            toks = [int(t) for t in emitted[:, slot, :].reshape(-1)
                    if t >= 0]
            if not toks:
                continue
            self._tokens[slot].extend(toks)
            committed += len(toks)
            self.tokens_generated += len(toks)
            if finished[:, slot].any():
                req = self.pool.active[slot]
                hit_eos = req.eos_id is not None and toks[-1] == req.eos_id
                done.append(self._retire(
                    slot, FINISH_EOS if hit_eos else FINISH_LENGTH))
        acc_total = int(accepted.sum())
        rej_total = int(rejected.sum())
        # judged = drafts the verify actually ruled on in the committed
        # stream (accepted + contradicted); agreements cut by a
        # budget/eos clamp count toward neither side, so the rate reads
        # the draft's true quality — 1.0 for a perfectly-agreeing draft
        # even on its final, budget-clamped round
        judged = acc_total + rej_total
        self.spec_accepted_tokens += acc_total
        self.spec_rejected_tokens += rej_total
        if tel is not None:
            if pending.asynchronous:
                tel.event("engine.dispatch_synced",
                          dispatch=pending.dispatch, kind="spec",
                          judged=judged, accepted=acc_total,
                          committed=committed, retired=len(done))
                tel.metrics.histogram(
                    "serve_dispatch_overlap_ms",
                    help="host work overlapped with an in-flight "
                    "dispatch: enqueue return -> sync start, wall ms"
                ).observe(overlap_ms)
            else:
                tel.event("engine.spec_round", dispatch=pending.dispatch,
                          rounds=rounds, judged=judged,
                          accepted=acc_total, committed=committed,
                          retired=len(done))
            m = tel.metrics
            m.counter("serve_spec_accepted_tokens_total",
                      help="draft tokens accepted by the verify step"
                      ).inc(acc_total)
            m.counter("serve_spec_rejected_tokens_total",
                      help="draft tokens contradicted by the verify "
                      "step").inc(rej_total)
            if judged:
                m.histogram(
                    "serve_spec_accept_rate",
                    help="per-dispatch draft acceptance rate "
                    "(accepted / judged)"
                ).observe(acc_total / judged)
        return done

    # -------------------------------------------------------- lifecycle
    def snapshot_in_flight(self) -> List:
        """``[(request, tokens_emitted_so_far)]`` for every in-flight
        slot, in slot order — what a supervisor needs to re-admit this
        engine's work after a crash (copies, never live buffers).
        Mid-chunking prompts have no ``_tokens`` entry (decode hasn't
        started — or, for a replay-of-a-replay, hasn't REstarted), so
        they fall back to their ``replay_tokens``: a second crash during
        a replay's chunk re-feed must not drop the first crash's
        emissions."""
        active = self.pool.active
        return [(active[slot],
                 list(self._tokens.get(
                     slot, active[slot].replay_tokens or ())))
                for slot in sorted(active)]

    def cancel(self, request_id: int,
               reason: str = FINISH_TIMEOUT) -> Optional[Completion]:
        """Abort an in-flight request (deadline expiry): frees its slot,
        returns a completion with the tokens produced so far."""
        slot = self.pool.slot_of(request_id)
        if slot is None:
            return None
        self._require_synced("cancel")
        return self._retire(slot, reason)

    def shutdown(self) -> None:
        """Release the engine's device state: drop prefix-cache refs and
        the KV pool/arena so a retired engine stops pinning HBM. The
        engine is unusable afterwards."""
        if self.prefix is not None:
            self.prefix.drop()
        self.prefix = None
        self.pool = None
        if self.spec is not None:
            self.spec.shutdown()
        self.spec = None
        self._chunk_queue.clear()
        self._tokens.clear()
        self._adapter_of.clear()
        # an in-flight enqueued dispatch is DISCARDED with the carry —
        # the sync-frontier contract: its tokens were never committed,
        # and (failover) a replay regenerates them elsewhere
        self._carry = None
        self._retry_sync = None
        self._active[:] = False

    def _retire(self, slot: int, reason: str) -> Completion:
        # only cancel() can retire a mid-chunking slot — don't rebuild
        # the deque on every normal retirement while chunks stream
        if any(st.slot == slot for st in self._chunk_queue):
            self._chunk_queue = deque(
                st for st in self._chunk_queue if st.slot != slot)
        req = self.pool.release(slot)
        self._active[slot] = False
        # a freed row keeps running the step's math: it must not hold
        # the batch on the whole-vocabulary ranking (top_k_dispatch)
        self._top_k[slot] = 0
        self._unbind_adapter(slot)
        if self.spec is not None:
            # a cancel between activation and the next spec dispatch
            # must not refill a slot that no longer holds the request
            self.spec.discard(slot)
        # a mid-chunking REPLAY has no _tokens entry yet: its pre-crash
        # emissions live in replay_tokens and a cancel/deadline must
        # still surface them (PR 3's partial-tokens contract)
        tokens = self._tokens.pop(slot, None)
        if tokens is None:
            tokens = list(req.replay_tokens or ())
        return Completion(
            request_id=req.id, prompt=list(req.prompt), tokens=tokens,
            finish_reason=reason, arrival_time=req.arrival_time,
            first_token_time=req.first_token_time,
            prefix_hit_tokens=req.prefix_hit_tokens,
            tenant=req.tenant, adapter=getattr(req, "adapter", None))
