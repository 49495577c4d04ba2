"""Autoregressive generation: batched single-pass prefill + tokens-only scan.

TPU-native serving decomposition — the prefill/decode split every
production LM server (vLLM, TGI, JetStream) made canonical:

1. **Prefill** (:func:`prefill`): the whole ``(B, P)`` prompt runs
   through the decode-mode model in ONE compiled forward — a length-P
   block lands in the KV cache via ``dynamic_update_slice`` and attends
   its own keys causally (the cache is built empty in the same program:
   ``transformer.fresh_cache``; the blockwise kernel from
   ``FLASH_MIN_LEN`` positions on a TPU), and the last-position logits
   come back.
   Prompt cost is one matmul-rich pass instead of P sequential
   ~per-token dispatches (history and reasons: the
   ``docs/performance.md`` decode section; today's levels: ``PERF.md``,
   the ``*.serve.*`` cells).
2. **Decode** (tokens-only ``lax.scan``): exactly ``max_new_tokens - 1``
   cached single-token steps (the first new token is sampled from the
   prefill logits), jitted with ``donate_argnums`` on the cache and
   tokens buffers so the carry updates alias in place instead of
   copying.

Static shapes throughout (prompt and generation lengths are baked into
the two compiled programs; same shapes reuse the cache). Each decode
step attends over the KV cache (O(T) per token instead of O(T²)
re-encoding).

Usage::

    cfg = gpt2_config("small", decode=True)     # decode variant
    model = TransformerLM(cfg)
    out = generate(model, params, prompt_tokens, max_new_tokens=64,
                   rng=jax.random.PRNGKey(0), temperature=0.8, top_k=40)

``params`` come from the *training* config (same architecture, decode
off); the decode flag only switches the attention to its cached path.

Batched variable-length prompts: left-align each row, pad the tail to a
common P, and pass ``prompt_lengths`` (B,). Prefill needs no extra
masking for the pad tail — the intra-prompt causal mask already hides
later keys from every valid query, and the pad positions' K/V are
overwritten by the per-row decode scan before any step can attend them
(each row's step *s* writes cache slot ``lengths[row] + s`` and masks
keys beyond it). Each row emits exactly ``max_new_tokens`` tokens at
positions ``lengths[row]..lengths[row]+max_new_tokens-1``; a short
row's positions beyond its window keep whatever pad values the caller
supplied there (the appended region past P is zero-initialized, the
prompt pad is passed through untouched) — slice each row by its own
window, don't sentinel on the tail. ``eos_id`` stops a row once
sampled: every
later position in its window repeats the eos token (the scan still runs
full length — static shapes).

The legacy single-program path (prompt teacher-forced through the same
one-token-at-a-time scan used for sampling) is kept as
:func:`generate_full_scan` — it is the reference the prefill+scan
equivalence tests compare against, and ``generate(...,
use_prefill=False)`` selects it.

Serving tip (measured, ``docs/performance.md`` decode section): build
the decode config with ``scan_layers=False`` and convert scanned
training weights with
:func:`ray_lightning_tpu.models.transformer.unstack_scan_params`.
Scanned layers nest a layer loop inside the token scan, which the TPU
compiler emits far slower per decode step: GPT-2-small/v5e measures
1.66 ms/step scanned vs 0.60 ms/step unrolled (device-differential,
2.8x). Training's compile-time economics favor the scan, serving's do
not — recompilation is paid once per shape.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.quant import materialize_for_program
from ray_lightning_tpu.models.transformer import fresh_cache, latch_eos
from ray_lightning_tpu.obs import seats


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    """What one leaf of a model's ``cache`` collection is — the model's
    own declaration (``model.cache_leaf(path names)``), which the serve
    engine reads instead of guessing from the leaf's rank.

    ``slot_axis`` is the axis along which the leaf holds one entry per
    batch row / engine slot (injected at prefill, handed on with the
    slot); ``None`` marks shared bookkeeping that no per-row path reads
    (the scalar ``cache_index``). ``kind`` says what a live row's bytes
    grow with: ``"global"`` with its context (``seq_axis`` positions, of
    which ``position + 1`` are live), ``"window"`` up to the leaf's
    ``seq_axis`` length, ``"recurrent"`` with nothing."""
    slot_axis: Optional[int]
    kind: str = "global"
    seq_axis: Optional[int] = None

    @property
    def per_slot(self) -> bool:
        return self.slot_axis is not None


def cache_layout(model, cache):
    """``cache`` (the collection, or anything of its tree structure) ->
    the same tree of :class:`CacheLeaf`, asked of the model leaf by
    leaf."""
    def declare(path, _leaf):
        names = tuple(getattr(k, "key", getattr(k, "name", str(k)))
                      for k in path)
        return model.cache_leaf(names)

    return jax.tree_util.tree_map_with_path(declare, cache)


def sample_logits(logits: jax.Array, rng: jax.Array,
                  temperature: float = 1.0,
                  top_k: Optional[int] = None) -> jax.Array:
    """Sample token ids from (B, V) logits.

    ``temperature=0`` is greedy argmax; ``top_k`` restricts sampling to
    the k highest-probability tokens.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min,
                           logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


#: How many candidates a row's ``top_k`` mask is built from. A batch
#: whose largest ``top_k`` is at most this takes ``lax.top_k(l, K)`` and
#: keeps the first ``top_k`` of the candidates; a batch with a wider row
#: ranks the whole vocabulary (:func:`processed_logits_row`). 64 covers
#: HF ``generate``'s default 50 and GPT-2's published 40.
TOP_K_CANDIDATES = 64


def processed_logits_row(l: jax.Array, t: jax.Array, tk: jax.Array,
                         top_k_path: Optional[str]) -> jax.Array:
    """What one row's categorical draws from: ``l`` (V,) scaled by the
    row's temperature ``t`` (0 = greedy: left unscaled) with everything
    outside the row's ``tk`` highest logits masked to ``finfo.min``
    (``tk == 0`` = unrestricted). The one definition shared by
    :func:`sample_logits_rows` and the speculative accept test
    (``serve/spec.py::_row_probs``), so p and q are what the sampler drew
    from.

    ``tk`` is traced, so ``lax.top_k`` cannot take it. ``top_k_path``
    (static; :func:`top_k_dispatch` picks it from the batch) says how
    the keep set is found. ``"narrow"`` takes the ``TOP_K_CANDIDATES``
    highest (valid while ``tk <= TOP_K_CANDIDATES``) and keeps what lies
    above the ``tk``-th of them, plus the logits equal to it up to its
    index — ``lax.top_k`` puts the lower index first among equal values,
    so the ``tk``-th candidate is the last tie kept. Written as compares
    over ``[V]``, not as a scatter of the candidates' indices: inside
    the decode step program the scatter costs 5 ms of a 23 ms step on a
    v5e (``PERF.md`` section 6, PR 31). ``"wide"`` ranks the whole
    vocabulary by a stable descending argsort; ``None`` applies no mask.
    Both keep exactly ``tk`` tokens, the lower index first among equal
    logits, so they return the same row wherever both apply."""
    with jax.named_scope("sample/temperature"):
        scaled = l / jnp.where(t > 0, t, 1.0)
    if top_k_path is None:
        return scaled
    V = l.shape[0]
    with jax.named_scope("sample/top_k"):
        if top_k_path == "narrow":
            K = min(TOP_K_CANDIDATES, V)
            vals, idx = jax.lax.top_k(l, K)
            last = jnp.clip(tk - 1, 0, K - 1)
            kth, kth_at = vals[last], idx[last]
            keep = (l > kth) | ((l == kth) & (jnp.arange(V) <= kth_at))
        else:
            order = jnp.argsort(-l)
            ranks = jnp.zeros_like(order).at[order].set(
                jnp.arange(V, dtype=order.dtype))
            keep = ranks < tk
        return jnp.where((tk > 0) & ~keep, jnp.finfo(jnp.float32).min,
                         scaled)


def top_k_dispatch(top_k: jax.Array, body):
    """``body(top_k_path)`` under the batch-level ``lax.cond``s that pick
    :func:`processed_logits_row`'s path from the input (outside any vmap,
    so one branch executes): no mask when no row restricts ``top_k``, the
    candidates when every row asks for at most ``TOP_K_CANDIDATES``, the
    whole-vocabulary ranking only when some row asks for more."""
    return jax.lax.cond(
        jnp.any(top_k > 0),
        lambda: jax.lax.cond(jnp.max(top_k) > TOP_K_CANDIDATES,
                             lambda: body("wide"),
                             lambda: body("narrow")),
        lambda: body(None))


def sample_logits_rows(logits: jax.Array, keys: jax.Array,
                       temperature: jax.Array,
                       top_k: jax.Array) -> jax.Array:
    """Per-row sampling from (B, V) logits — the batched-heterogeneous
    sibling of :func:`sample_logits` for the serving engine, where every
    slot carries its own request's sampling params.

    ``keys`` (B, 2) is one explicit PRNG key per row (the engine derives
    row r's key as ``fold_in(fold_in(base, request_seed), step)``, so a
    request's sample stream depends only on its seed and step index —
    reproducible across slot assignments and batch compositions, and never
    shared between co-resident slots). ``temperature`` (B,) with 0 = greedy
    argmax for that row (bit-identical to :func:`sample_logits`'s greedy).
    ``top_k`` (B,) int with 0 = unrestricted; exactly k tokens are kept
    (ties at the k-th place broken by index rather than kept, which only
    reweights exactly-tied tail logits) — :func:`processed_logits_row`.

    The expensive machinery is gated at the BATCH level with ``lax.cond``
    (outside the vmap, so XLA executes one branch at runtime): an
    all-greedy batch — the greedy identity tests, and any temperature=0
    deployment — pays one argmax, no per-row categorical; the top_k mask
    engages only when some row actually restricts top_k, and costs
    ``TOP_K_CANDIDATES`` candidates a row unless a row asks for more
    (:func:`top_k_dispatch`). Per-row greedy/sampled mixing stays inside
    the sampled branch.
    """
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)

    def rows_greedy():
        with jax.named_scope("sample/greedy"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def rows_sampled(top_k_path):
        def row(l, k, t, tk):
            with jax.named_scope("sample/greedy"):
                greedy = jnp.argmax(l).astype(jnp.int32)
            scaled = processed_logits_row(l, t, tk, top_k_path)
            with jax.named_scope("sample/draw"):
                sampled = jax.random.categorical(
                    k, scaled).astype(jnp.int32)
                return jnp.where(t > 0, sampled, greedy)

        return jax.vmap(row)(logits, keys, temperature, top_k)

    return jax.lax.cond(jnp.any(temperature > 0.0),
                        lambda: top_k_dispatch(top_k, rows_sampled),
                        rows_greedy)


def _check_decode_model(model, P: int, max_new_tokens: int = 0) -> None:
    cfg = model.cfg
    if not cfg.decode:
        raise ValueError(
            "generate() needs a decode-mode model: rebuild the config "
            "with decode=True (params are compatible)")
    if P + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len})")


def _logits_only(outputs):
    # MoE LMs return (logits, aux_loss); serving only needs the logits
    return outputs[0] if isinstance(outputs, tuple) else outputs


def _row_update(rows: jax.Array, vals: jax.Array,
                starts: jax.Array) -> jax.Array:
    """Per-row ``dynamic_update_slice`` along axis 1: write ``vals``
    (B, 1) into ``rows`` (B, T) at each row's own ``starts`` (B,)."""
    return jax.vmap(
        lambda row, val, i: jax.lax.dynamic_update_slice(row, val, (i,)))(
            rows, vals, starts)


def _adapter_kw(adapter_ids):
    """Kwargs guard for the per-row LoRA adapter ids: ``None`` adds
    nothing (model families without the kwarg — MoE, encoders — and
    unadapted engines never see it, and None-vs-array are different
    pytree structures so unadapted programs never recompile)."""
    return {} if adapter_ids is None else {"adapter_ids": adapter_ids}


def decode_step(model, params, cache, tokens: jax.Array,
                kv_positions: jax.Array, adapter_ids=None):
    """ONE cached single-token decode step at explicit per-row positions —
    the shared core between :func:`generate`'s ragged decode scan and the
    serving engine's continuous-batching step
    (:mod:`ray_lightning_tpu.serve.engine`), so the two paths cannot
    drift.

    ``tokens`` (B, 1) holds each row's current token, ``kv_positions``
    (B, 1) its absolute sequence position: the step writes each row's K/V
    at its own slot (the per-row ``_decode_cache`` mode) and masks keys
    beyond it — rows at *different* sequence lengths share one compiled
    program, which is what lets the engine swap requests in and out of
    batch rows without recompiling.

    Returns ``(last_logits (B, V), cache)``. Sampling stays outside (the
    scan and the engine consume the logits differently — shared rng for a
    homogeneous batch vs per-request keys and sampling params).

    ``params`` may be weight-quantized (:mod:`..models.quant`): the
    shared entry guard (``materialize_for_program`` — a trace-time
    no-op on plain trees) dequantizes under ``matmul_kernel="xla"``
    and passes the codes through to the fused kernel under
    ``"pallas"``. The serve programs guard once at THEIR entry
    (outside the step scans), so this only fires for direct callers.
    """
    params = materialize_for_program(params, model.cfg)
    outputs, updated = model.apply(
        {"params": params, "cache": cache}, tokens,
        positions=kv_positions, kv_positions=kv_positions,
        deterministic=True, mutable=["cache"],
        **_adapter_kw(adapter_ids))
    return _logits_only(outputs)[:, -1], updated["cache"]


def _arena_apply(model, params, arena, tokens, kv_positions, page_table,
                 adapter_ids=None):
    """Shared page-native ``model.apply`` plumbing: the arena's cache
    tree rides as the ``cache`` collection (int8 arenas split their
    ``(codes, scales)`` tuple across ``cache`` + ``kvscale``), and the
    updated arena comes back in the same storage layout."""
    quantized = isinstance(arena, tuple)
    variables = {"params": params}
    if quantized:
        variables["cache"], variables["kvscale"] = arena
        mutable = ["cache", "kvscale"]
    else:
        variables["cache"] = arena
        mutable = ["cache"]
    outputs, updated = model.apply(
        variables, tokens, positions=kv_positions,
        kv_positions=kv_positions, page_table=page_table,
        deterministic=True, mutable=mutable,
        **_adapter_kw(adapter_ids))
    new_arena = ((updated["cache"], updated["kvscale"]) if quantized
                 else updated["cache"])
    return _logits_only(outputs), new_arena


def decode_step_paged(model, params, arena, tokens: jax.Array,
                      kv_positions: jax.Array, page_table: jax.Array,
                      adapter_ids=None):
    """Page-native sibling of :func:`decode_step`: ONE cached
    single-token step whose K/V reads and writes go straight through
    the serving engine's page arena — no dense per-slot view is
    gathered or scattered (see
    ``MultiHeadAttention._page_native_attention``).

    ``arena`` is the paged KV tree (``(num_pages, page_size, H, D)``
    leaves; int8 arenas are the usual ``(codes, scales)`` tuple) and
    ``page_table`` (B, pages_per_slot) maps each row to its pages — the
    engine passes its write-masked table, so retired/chunking rows'
    parked writes drop. Returns ``(last_logits (B, V), arena)``.
    """
    params = materialize_for_program(params, model.cfg)
    logits, arena = _arena_apply(model, params, arena, tokens,
                                 kv_positions, page_table, adapter_ids)
    return logits[:, -1], arena


def verify_step_paged(model, params, arena, tokens: jax.Array,
                      kv_positions: jax.Array, page_table: jax.Array,
                      adapter_ids=None):
    """Page-native sibling of :func:`verify_step`: the speculative
    verify's per-row (B, T) block scoring, reading/writing K/V through
    the page table. Returns ``(logits (B, T, V), arena)`` — every
    offset's logits, as the accept rule requires."""
    params = materialize_for_program(params, model.cfg)
    return _arena_apply(model, params, arena, tokens, kv_positions,
                        page_table, adapter_ids)


def verify_step(model, params, cache, tokens: jax.Array,
                kv_positions: jax.Array, adapter_ids=None):
    """ONE cached block-scoring step at per-row positions — the target
    side of speculative decoding (:mod:`ray_lightning_tpu.serve.spec`).

    ``tokens`` (B, T) holds each row's current token followed by its
    T-1 draft proposals; ``kv_positions`` (B, T) their absolute
    positions (the contiguous run ``pos..pos+T-1`` per row). The step
    block-writes each row's K/V at its own positions (the per-row block
    mode of ``_decode_cache``) under a block-causal mask, so ONE
    dispatch scores every draft token exactly as T sequential
    :func:`decode_step` calls would: offset ``j``'s logits are the
    target's next-token distribution given the row's context plus
    drafts ``< j``.

    Returns ``(logits (B, T, V), cache)`` — all T positions' logits
    (the accept rule needs every offset, not just the last). Rejected
    drafts' K/V stays in the cache at positions past the commit point;
    that is deliberate rollback-by-position-decrement: later writes
    land at or before those positions before any mask re-admits them
    (same argument as the chunk-prefill path).
    """
    params = materialize_for_program(params, model.cfg)
    outputs, updated = model.apply(
        {"params": params, "cache": cache}, tokens,
        positions=kv_positions, kv_positions=kv_positions,
        deterministic=True, mutable=["cache"],
        **_adapter_kw(adapter_ids))
    return _logits_only(outputs), updated["cache"]


def _prefill_impl(model, params, prompt_tokens, prompt_lengths,
                  adapter_ids=None):
    params = materialize_for_program(params, model.cfg)
    B, P = prompt_tokens.shape
    prompt_tokens = prompt_tokens.astype(jnp.int32)
    with seats.unseen():
        cache = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((B, 1), jnp.int32),
                           positions=jnp.zeros((B, 1), jnp.int32))["cache"]
    positions = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    if getattr(model, "recurrent_state", False) \
            or getattr(model, "continues_prefill", False):
        # a recurrence or a ring would eat the pad tail: the model takes
        # the row lengths, leaves every row's state as of its last valid
        # position, and returns that position's logits only (so does a
        # model whose one prefill is its continue mode, state or none)
        lengths = (jnp.full((B,), P, jnp.int32) if prompt_lengths is None
                   else jnp.asarray(prompt_lengths, jnp.int32))
        outputs, updated = model.apply(
            {"params": params, "cache": cache}, prompt_tokens,
            positions=positions, lengths=lengths, deterministic=True,
            mutable=["cache"], **_adapter_kw(adapter_ids))
        return updated["cache"], _logits_only(outputs)[:, -1]
    # the cache above is empty and this block lands at position 0: each
    # attention call may attend the block itself (transformer.fresh_cache)
    with fresh_cache():
        outputs, updated = model.apply(
            {"params": params, "cache": cache}, prompt_tokens,
            positions=positions, deterministic=True, mutable=["cache"],
            **_adapter_kw(adapter_ids))
    logits = _logits_only(outputs)
    if prompt_lengths is None:
        last = logits[:, -1]
    else:
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return updated["cache"], last


@partial(jax.jit, static_argnames=("model",))
def prefill(model, params, prompt_tokens: jax.Array,
            prompt_lengths: Optional[jax.Array] = None):
    """Single-pass prompt fill: run the full ``(B, P)`` prompt through
    the decode-mode model in one forward, writing cache slots ``0..P-1``.

    Returns ``(cache, last_logits)`` where ``last_logits`` (B, V) are the
    logits at each row's final prompt position (``prompt_lengths[i]-1``
    when lengths are given, else ``P-1``) — sample the first generated
    token from them, then continue with per-token cached decode steps.
    For a model whose every cache leaf is attention K/V at absolute
    positions, causality makes them exact for left-aligned ragged rows:
    position ``L-1`` never attends past itself, so the pad tail cannot
    leak in. That holds for attention only. A recurrent state or a ring
    of the last W positions is a function of *every* token fed, pad tail
    included: a model with such state declares ``recurrent_state`` and
    is called with ``lengths`` (B,) — the length contract: after the
    call each row's recurrent state is as of position ``L-1``, its ring
    holds positions ``max(0, L-W)..L-1``, and the call returns the
    ``(B, 1, V)`` logits of position ``L-1`` itself (``models/sambay.py``).

    Ragged continuation contract: after a ragged prefill the cache slots
    ``lengths[i]..P-1`` of short rows hold pad-tail K/V, so the decode
    steps MUST use per-row ``kv_positions`` (each row's step *s* writes
    slot ``lengths[i] + s`` and masks keys beyond it, overwriting the
    garbage before it can be attended) — exactly what :func:`generate`
    does. A plain shared-index step after a ragged prefill would write at
    slot P and let short rows attend their pad-tail slots: silently
    wrong. Uniform prompts (``prompt_lengths=None``) may continue with
    plain shared-index steps.
    """
    _check_decode_model(model, prompt_tokens.shape[1])
    return _prefill_impl(model, params, prompt_tokens, prompt_lengths)


@partial(jax.jit,
         static_argnames=("model", "max_new_tokens", "temperature",
                          "top_k", "eos_id", "ragged"))
def _prefill_start(model, params, prompt_tokens, lengths, rng, *,
                   max_new_tokens, temperature, top_k, eos_id, ragged):
    """Program 1 of the split: prefill + first-token sample + output
    buffer assembly, fused so generate() costs exactly two dispatches."""
    B, P = prompt_tokens.shape
    cache, last = _prefill_impl(model, params, prompt_tokens,
                                lengths if ragged else None)
    rng, sub = jax.random.split(rng)
    first = sample_logits(last, sub, temperature, top_k)
    done = (first == eos_id) if eos_id is not None \
        else jnp.zeros((B,), jnp.bool_)
    tokens = jnp.concatenate(
        [prompt_tokens.astype(jnp.int32),
         jnp.zeros((B, max_new_tokens), jnp.int32)], axis=1)
    if ragged:
        tokens = _row_update(tokens, first[:, None], lengths)
    else:
        tokens = jax.lax.dynamic_update_slice_in_dim(
            tokens, first[:, None], P, axis=1)
    return cache, tokens, rng, done


def _decode_scan(model, cache, tokens, params, lengths, rng, done0, *,
                 steps, temperature, top_k, eos_id, ragged):
    """Program 2 of the split: ``steps`` cached single-token decode steps
    starting from the prefill cache. The cache and tokens buffers are
    donated — the scan carry updates them in place, no per-call copies.
    """
    B, total = tokens.shape

    def step(carry, s):
        cache, tokens, rng, done = carry
        if ragged:
            # rows sit at different lengths: read/write at per-row
            # positions — the shared decode_step (also the serving
            # engine's model step) does the per-row kv_positions write
            pos = (lengths + s)[:, None]
            cur = jnp.take_along_axis(tokens, pos, axis=1)
            last, cache = decode_step(model, params, cache, cur, pos)
        else:
            t = total - steps - 1 + s
            cur = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            pos = jnp.full((B, 1), t, jnp.int32)
            outputs, cache_vars = model.apply(
                {"params": params, "cache": cache}, cur, positions=pos,
                deterministic=True, mutable=["cache"])
            last, cache = _logits_only(outputs)[:, -1], cache_vars["cache"]
        rng, sub = jax.random.split(rng)
        nxt = sample_logits(last, sub, temperature, top_k)
        if eos_id is not None:
            # every scanned step samples strictly past the prompt, so
            # (unlike the teacher-forced legacy scan) latching needs no
            # "generating" gate
            nxt, done = latch_eos(nxt, done, eos_id)
        if ragged:
            tokens = _row_update(tokens, nxt[:, None], lengths + s + 1)
        else:
            tokens = jax.lax.dynamic_update_slice_in_dim(
                tokens, nxt[:, None], total - steps + s, axis=1)
        return (cache, tokens, rng, done), None

    (_, tokens, _, _), _ = jax.lax.scan(
        step, (cache, tokens, rng, done0), jnp.arange(steps))
    return tokens


_SCAN_STATICS = ("model", "steps", "temperature", "top_k", "eos_id",
                 "ragged")
_decode_scan_donated = partial(
    jax.jit, static_argnames=_SCAN_STATICS,
    donate_argnums=(1, 2))(_decode_scan)
_decode_scan_plain = partial(
    jax.jit, static_argnames=_SCAN_STATICS)(_decode_scan)


def _decode_scan_jit():
    """Donate the cache/tokens carry wherever the backend honors it; the
    CPU backend ignores donation with a warning per buffer, so tests stay
    quiet on the plain variant (the programs are otherwise identical)."""
    return (_decode_scan_plain if jax.default_backend() == "cpu"
            else _decode_scan_donated)


def generate(model, params, prompt_tokens: jax.Array,
             max_new_tokens: int, rng: jax.Array,
             temperature: float = 1.0,
             top_k: Optional[int] = None,
             prompt_lengths: Optional[jax.Array] = None,
             eos_id: Optional[int] = None,
             use_prefill: bool = True) -> jax.Array:
    """Generate ``max_new_tokens`` past ``prompt_tokens`` (B, P).

    Returns (B, P + max_new_tokens) int32. ``model.cfg.decode`` must be
    True and ``cfg.max_seq_len >= P + max_new_tokens``.

    Two compiled programs: a batched prompt prefill (one forward for all
    P positions) and a tokens-only decode scan of ``max_new_tokens - 1``
    steps with donated cache/tokens buffers — see the module docstring.
    ``use_prefill=False`` selects the legacy single-program path
    (:func:`generate_full_scan`); greedy outputs are token-identical
    either way (pinned by tests/test_prefill.py). Sampling
    (``temperature > 0``) is equivalent in distribution but consumes the
    rng stream differently from the legacy path (which burned one split
    per teacher-forced prompt position).
    """
    if not use_prefill:
        return generate_full_scan(model, params, prompt_tokens,
                                  max_new_tokens, rng, temperature, top_k,
                                  prompt_lengths, eos_id)
    B, P = prompt_tokens.shape
    _check_decode_model(model, P, max_new_tokens)
    ragged = prompt_lengths is not None
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    lengths = (jnp.asarray(prompt_lengths, jnp.int32) if ragged
               else jnp.full((B,), P, jnp.int32))
    cache, tokens, rng, done = _prefill_start(
        model, params, prompt_tokens, lengths, rng,
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, eos_id=eos_id, ragged=ragged)
    if max_new_tokens == 1:
        return tokens
    return _decode_scan_jit()(
        model, cache, tokens, params, lengths, rng, done,
        steps=max_new_tokens - 1, temperature=temperature,
        top_k=top_k, eos_id=eos_id, ragged=ragged)


@partial(jax.jit,
         static_argnames=("model", "max_new_tokens", "temperature",
                          "top_k", "eos_id"))
def generate_full_scan(model, params, prompt_tokens: jax.Array,
                       max_new_tokens: int, rng: jax.Array,
                       temperature: float = 1.0,
                       top_k: Optional[int] = None,
                       prompt_lengths: Optional[jax.Array] = None,
                       eos_id: Optional[int] = None) -> jax.Array:
    """Legacy one-program path: the prompt is teacher-forced through the
    same one-token-at-a-time scan used for sampling (P sequential steps
    before the first new token). Kept as the equivalence reference for
    the prefill+scan split; prefer :func:`generate`.

    Variable-length note: this path fills every row to the common
    ``P + max_new_tokens`` length (short rows keep generating past their
    ``prompt_lengths[i] + max_new_tokens`` window), where the split path
    stops each row after exactly ``max_new_tokens`` tokens.
    """
    B, P = prompt_tokens.shape
    _check_decode_model(model, P, max_new_tokens)
    total = P + max_new_tokens
    lengths = (jnp.full((B,), P, jnp.int32) if prompt_lengths is None
               else jnp.asarray(prompt_lengths, jnp.int32))

    cache = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((B, 1), jnp.int32),
                       positions=jnp.zeros((B, 1), jnp.int32))["cache"]

    tokens0 = jnp.concatenate(
        [prompt_tokens.astype(jnp.int32),
         jnp.zeros((B, max_new_tokens), jnp.int32)], axis=1)
    done0 = jnp.zeros((B,), jnp.bool_)

    def step(carry, t):
        cache, tokens, rng, done = carry
        cur = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        pos = jnp.full((B, 1), t, jnp.int32)
        outputs, updated = model.apply(
            {"params": params, "cache": cache}, cur, positions=pos,
            deterministic=True, mutable=["cache"])
        logits = _logits_only(outputs)
        rng, sub = jax.random.split(rng)
        nxt = sample_logits(logits[:, -1], sub, temperature, top_k)
        if eos_id is not None:
            # done can only be set while a row is actually GENERATING —
            # throwaway samples during another row's teacher-forced
            # prompt region must not latch it
            generating = (t + 1) >= lengths
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (generating & (nxt == eos_id))
        # teacher-force each row's own prompt; sampling starts at its end
        forced = jnp.where(t + 1 < lengths, tokens[:, t + 1], nxt)
        tokens = jax.lax.dynamic_update_slice_in_dim(
            tokens, forced[:, None], t + 1, axis=1)
        return (updated["cache"], tokens, rng, done), None

    (cache, tokens, rng, _done), _ = jax.lax.scan(
        step, (cache, tokens0, rng, done0), jnp.arange(total - 1))
    return tokens
