"""Speculative decoding: k cheap draft steps + ONE widened target verify.

Leviathan et al. 2023 ("Fast Inference from Transformers via Speculative
Decoding") on the engine's fixed-shape terms: a small **draft model**
(config-supplied — e.g. a 2-layer distilled sibling sharing the target's
tokenizer/embedding shapes) runs ``k`` autoregressive single-token steps
per slot, then the target model scores all ``k`` proposals in ONE
``(num_slots, k+1)`` **verify** dispatch — the per-row block-write mode
of the cached-attention contract
(:func:`ray_lightning_tpu.models.generate.verify_step`). The longest
matching prefix is accepted plus one target-sampled fix-up/bonus token,
so each target dispatch commits 1..k+1 tokens instead of exactly one.

Why this is the decode lever: decode is bandwidth- and dispatch-bound —
every target dispatch reads all params once and pays the fixed per-call
cost (not measured on the current machine), so committing k+1 tokens per
target read/dispatch multiplies throughput by the acceptance rate's
worth of that ceiling. Draft + verify run in the SAME compiled program
(one dispatch per round; ``steps_per_dispatch`` scans that round, so a
spec engine's dispatch amortization composes with multi-step
scheduling).

Acceptance rules (per row, matching the row's own sampling params):

- **greedy** (``temperature == 0``): accept draft token ``d_j`` iff it
  equals the target's argmax at that offset; on divergence commit the
  target argmax instead. Every committed token is therefore EXACTLY the
  token the non-spec engine would have produced — greedy outputs are
  token-identical by construction, invariant to round boundaries,
  acceptance luck, and crash-replay restarts (pinned by
  ``tests/test_spec.py``).
- **sampled** (``temperature > 0``): the standard rejection-resampling
  rule — accept ``d_j`` with probability ``min(1, p(d_j)/q(d_j))``,
  else resample from ``max(p - q, 0)`` normalized — which preserves the
  target distribution exactly. Every random draw derives from the
  request's existing per-step key ``fold_in(fold_in(base, seed),
  step)``: the draft draw from sub-stream ``fold_in(step_key, 1)``, the
  accept uniform from ``fold_in(step_key, 2)``, the resample/bonus from
  ``step_key`` itself. The committed token at step ``s`` is therefore a
  pure function of ``(engine seed, request seed, s, context)`` — round
  boundaries cancel — which is what makes sampled streams replay-exact
  through crash recovery (same argument as the non-spec engine, see
  ``docs/reliability.md``).

Rollback is a position decrement: the verify block-writes K/V for every
draft token, and rejected tokens' K/V simply stays at positions past
the new commit point — later writes land at or before those positions
before any causal mask re-admits them (dense), or land in pages the
slot already owns (paged: no page churn; writes past the slot's
allocated span are scatter-dropped and never needed, since commits are
budget-clamped).

The draft model keeps its own DENSE ``(num_slots, max_seq_len)`` KV
cache regardless of the target's storage (the draft is small — paging
it buys nothing). It is rebuilt per slot activation by a fixed-shape
``(1, max_seq_len)`` full-context prefill (:class:`SpecDecoder` tracks
stale slots), which is also what makes chunked prefill, prefix-cache
adoption, and crash replay compose for free: whatever path activated
the row, the draft re-reads the full host-side context.
"""
from __future__ import annotations

from functools import partial
from typing import List, Set

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models.generate import (_prefill_impl, decode_step,
                                               processed_logits_row,
                                               sample_logits_rows,
                                               top_k_dispatch, verify_step,
                                               verify_step_paged)
from ray_lightning_tpu.models.quant import materialize_for_program
from ray_lightning_tpu.serve.pages import (dense_storage_commit,
                                           dense_storage_values,
                                           fold_rows, gather_pages,
                                           pick_donated, scatter_pages,
                                           slot_leaves)
from ray_lightning_tpu.serve.report import pack_report

__all__ = ["SpecDecoder"]

#: fold_in sub-stream tags off each step key (see the module docstring)
_DRAFT_STREAM = 1
_ACCEPT_STREAM = 2

_fold_rows = fold_rows


def _row_probs(logits: jax.Array, temperature: jax.Array,
               top_k: jax.Array) -> jax.Array:
    """Per-row sampling distribution over (B, V) logits — softmax of
    the processed logits :func:`sample_logits_rows`'s sampled branch
    draws from (:func:`processed_logits_row`: the one definition of
    temperature scaling + the top_k mask, under the same batch-level
    :func:`top_k_dispatch`), so the rejection test's p/q are what the
    samplers actually drew from. Greedy rows (t == 0) get a
    well-defined (unused) distribution."""
    def rows(top_k_path):
        return jax.vmap(lambda l, t, tk: jax.nn.softmax(
            processed_logits_row(l, t, tk, top_k_path)))(
                logits, temperature, top_k)

    return top_k_dispatch(top_k, rows)


def _spec_accept(L, draft_toks, draft_logits, cur, pos, active, remaining,
                 temp, top_k, eos, keys, stepno, max_pos, *, k):
    """Accept/commit for one round, vectorized over rows.

    ``L`` (B, k+1, V) target logits, offset ``j`` conditioned on the
    row's context plus drafts ``< j``; ``draft_toks`` (B, k);
    ``draft_logits`` (B, k, V). Returns the updated row state plus
    ``emitted`` (B, k+1) — committed tokens in order, −1 past each
    row's commit count — ``accepted`` (B,), the number of committed
    DRAFT tokens (the acceptance-rate numerator; the +1 fix-up/bonus
    token is target work, not draft credit), and ``rejected`` (B,), 1
    iff a real divergence entered the committed stream this round.
    Draft agreements cut by the budget/eos clamp are neither accepted
    nor rejected — the verify did not contradict them, so they must
    not drag the acceptance rate below the draft's true quality.
    """
    B = cur.shape[0]
    sampled = temp > 0.0
    tgts = jnp.argmax(L, axis=-1).astype(jnp.int32)      # (B, k+1)

    def greedy_only():
        # all-greedy batch (temperature=0 everywhere — the default and
        # the identity tests' regime): accept is an exact argmax match
        # and every fix IS the argmax — no distributions, no draws.
        # Batch-level lax.cond, the same gate sample_logits_rows uses,
        # so the full-vocab softmax machinery below never
        # executes on the greedy hot path.
        return (jnp.zeros((B, k), jnp.bool_),
                jnp.zeros((B, k), jnp.int32))

    def with_sampled():
        accs = []   # k entries (B,) bool — draft j accepted?
        fixes = []  # k entries (B,) — resample at divergence j
        for j in range(k):
            sk = _fold_rows(keys, stepno + j)
            d = draft_toks[:, j]
            p = _row_probs(L[:, j], temp, top_k)
            q = _row_probs(draft_logits[:, j], temp, top_k)
            p_d = jnp.take_along_axis(p, d[:, None], axis=1)[:, 0]
            q_d = jnp.take_along_axis(q, d[:, None], axis=1)[:, 0]
            u = jax.vmap(jax.random.uniform)(
                _fold_rows(sk, jnp.full((B,), _ACCEPT_STREAM,
                                        jnp.int32)))
            # u < p/q spelled multiplication-first: q_d == 0
            # (numerically impossible for a proposed token, but belt)
            # rejects cleanly
            accs.append(u * q_d < p_d)
            # resample from the residual max(p - q, 0); zero residual
            # mass (p == q exactly — rejection then has probability 0,
            # belt again) falls back to p
            residual = jnp.maximum(p - q, 0.0)
            total = jnp.sum(residual, axis=-1, keepdims=True)
            res_dist = jnp.where(total > 0, residual, p)
            fixes.append(jax.vmap(
                lambda kk, r: jax.random.categorical(
                    kk, jnp.log(r + 1e-30))
            )(sk, res_dist).astype(jnp.int32))
        return jnp.stack(accs, axis=1), jnp.stack(fixes, axis=1)

    acc_s, fix_s = jax.lax.cond(jnp.any(sampled), with_sampled,
                                greedy_only)
    acc = jnp.where(sampled[:, None], acc_s, draft_toks == tgts[:, :k])
    fix = jnp.where(sampled[:, None], fix_s, tgts[:, :k])   # (B, k)
    # bonus token after a fully-accepted block: the target's own sample
    # at offset k, drawn with the plain step key — exactly the draw the
    # non-spec engine would have made at that step (sample_logits_rows
    # gates its own greedy/sampled machinery)
    bonus = sample_logits_rows(L[:, k], _fold_rows(keys, stepno + k),
                               temp, top_k)
    fixes_all = jnp.concatenate([fix, bonus[:, None]], axis=1)

    a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
    fix_at_a = jnp.take_along_axis(fixes_all, a[:, None],
                                   axis=1)                # (B, 1)
    idx = jnp.arange(k + 1)[None, :]                      # (1, k+1)
    drafts_pad = jnp.concatenate(
        [draft_toks, jnp.zeros((B, 1), jnp.int32)], axis=1)
    tok = jnp.where(idx < a[:, None], drafts_pad, fix_at_a)

    # commit mask: a prefix per row — through the accepted drafts plus
    # the fix/bonus, clamped by the token budget, cut after the first
    # eos, zero for inactive rows
    within = (idx <= a[:, None]) & (idx < remaining[:, None])
    is_eos = (tok == eos[:, None]) & (eos >= 0)[:, None]
    eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) \
        - is_eos.astype(jnp.int32)
    within = within & (eos_before == 0) & active[:, None]
    n = jnp.sum(within.astype(jnp.int32), axis=1)        # committed
    emitted = jnp.where(within, tok, -1)
    accepted = jnp.minimum(n, a)                         # draft credit
    # a real rejection = the divergence fix-up actually committed
    # (n == a+1 with a < k); clamped-away drafts were never judged into
    # the stream and count toward neither side of the rate
    rejected = (active & (a < k) & (n == a + 1)).astype(jnp.int32)

    last = jnp.take_along_axis(tok, jnp.clip(n - 1, 0, k)[:, None],
                               axis=1)
    commit = active & (n > 0)
    cur = jnp.where(commit[:, None], last, cur)
    pos = jnp.minimum(pos + n[:, None], max_pos)
    stepno = stepno + n
    remaining = remaining - n
    hit_eos = jnp.any(is_eos & within, axis=1)
    finished = active & (hit_eos | (remaining <= 0))
    active = active & ~finished
    return (cur, pos, active, remaining, stepno, emitted, accepted,
            rejected, finished)


def _draft_propose(draft_model, draft_params, draft_cache, cur, pos,
                   keys, stepno, temp, top_k, max_pos, *, k):
    """One round's draft half, shared by every spec program variant:
    k+1 single-token draft feeds — iteration j feeds token t_j (t_0 =
    cur, then the proposals) at ``pos + j`` and proposes d_{j+1}; the
    last proposal is discarded, its feed is the full-accept KV
    coverage. Returns ``(draft_cache, draft_toks (B, k),
    draft_logits (B, k, V))``."""

    def draft_step(dc, j):
        draft_cache, t = dc
        logits, draft_cache = decode_step(
            draft_model, draft_params, draft_cache, t,
            jnp.minimum(pos + j, max_pos))
        sk = _fold_rows(keys, stepno + j)
        dk = _fold_rows(
            sk, jnp.full(stepno.shape, _DRAFT_STREAM, jnp.int32))
        d = sample_logits_rows(logits, dk, temp, top_k)
        return (draft_cache, d[:, None]), (d, logits)

    (draft_cache, _), (drafts, dlogits) = jax.lax.scan(
        draft_step, (draft_cache, cur), jnp.arange(k + 1))
    draft_toks = jnp.moveaxis(drafts, 0, 1)[:, :k]       # (B, k)
    draft_logits = jnp.moveaxis(dlogits, 0, 1)[:, :k]    # (B, k, V)
    return draft_cache, draft_toks, draft_logits


def _spec_rounds_impl(model, draft_model, params, draft_params, cache,
                      draft_cache, cur, pos, active, remaining, temp,
                      top_k, eos, keys, stepno, adapter_ids=None, *,
                      k, rounds):
    """``rounds`` spec rounds in ONE dispatch. Each round: k+1 draft
    single-token steps (the extra feed writes the last proposal's K/V so
    a fully-accepted round leaves the draft cache covering every
    committed position), one ``(B, k+1)`` target verify, and the accept
    rule — all fused, so the per-dispatch fixed cost amortizes over up
    to ``rounds * (k+1)`` committed tokens.

    Inactive rows run the same math at frozen positions (static
    shapes); their junk draft/verify writes land in storage the next
    admission fully overwrites (dense whole-row inject / paged page
    re-inject — the paged wrapper additionally write-masks them).
    ``cache`` may be int8 dense storage, handled like the plain step;
    ``params``/``draft_params`` may be weight-quantized — dequantized
    here once per dispatch, outside the round scan.

    ``adapter_ids`` (B,) per-row LoRA bank ids reach the TARGET verify
    only: the spec identity contract is "same committed tokens as the
    non-spec engine", and that engine's tokens come from the (adapted)
    target distribution — greedy acceptance compares draft proposals
    against the adapted argmax, sampled acceptance corrects toward the
    adapted ``p``, so the draft model stays UNADAPTED (one draft serves
    every adapter; a mismatched draft only costs acceptance rate, never
    correctness).

    Returns both caches, the device carry and the dispatch's report
    (:func:`~ray_lightning_tpu.serve.report.pack_report`: the carry,
    ``emitted`` ``(rounds, B, k+1)``, ``finished`` and the accept
    ledgers in one flat int32 buffer — all the host fetches).
    """
    params = materialize_for_program(params, model.cfg)
    draft_params = materialize_for_program(draft_params, draft_model.cfg)
    storage = cache
    cache = dense_storage_values(model, storage)
    max_pos = model.cfg.max_seq_len - 1

    def round_body(carry, _):
        cache, draft_cache, cur, pos, active, remaining, stepno = carry
        draft_cache, draft_toks, draft_logits = _draft_propose(
            draft_model, draft_params, draft_cache, cur, pos, keys,
            stepno, temp, top_k, max_pos, k=k)
        tokens_in = jnp.concatenate([cur, draft_toks], axis=1)
        vpos = jnp.minimum(pos + jnp.arange(k + 1)[None, :], max_pos)
        L, cache = verify_step(model, params, cache, tokens_in, vpos,
                               adapter_ids)
        (cur, pos, active, remaining, stepno, emitted, accepted,
         rejected, finished) = _spec_accept(
            L, draft_toks, draft_logits, cur, pos, active, remaining,
            temp, top_k, eos, keys, stepno, max_pos, k=k)
        return ((cache, draft_cache, cur, pos, active, remaining,
                 stepno), (emitted, accepted, rejected, finished))

    (cache, draft_cache, cur, pos, active, remaining, stepno), \
        (emitted, accepted, rejected, finished) = jax.lax.scan(
            round_body,
            (cache, draft_cache, cur, pos, active, remaining, stepno),
            None, length=rounds)
    cache = dense_storage_commit(model, storage, cache)
    return (cache, draft_cache, cur, pos, active, remaining, stepno,
            pack_report(cur, pos, active, remaining, stepno, emitted,
                        finished, accepted, rejected))


def _spec_rounds_paged_impl(model, draft_model, params, draft_params,
                            arena, page_table, draft_cache, cur, pos,
                            active, remaining, temp, top_k, eos, keys,
                            stepno, adapter_ids=None, *, k, rounds):
    """The spec round program on paged target storage: gather the dense
    view (dequantizing int8 arenas), run the IDENTICAL rounds body,
    scatter mapped pages back — rows inactive at dispatch entry are
    write-masked exactly as in the plain paged step."""
    view = gather_pages(model, arena, page_table)
    write_pt = jnp.where(active[:, None], page_table, -1)
    (view, draft_cache, cur, pos, active, remaining, stepno,
     report) = _spec_rounds_impl(
        model, draft_model, params, draft_params, view, draft_cache,
        cur, pos, active, remaining, temp, top_k, eos, keys, stepno,
        adapter_ids, k=k, rounds=rounds)
    arena = scatter_pages(model, arena, view, write_pt)
    return (arena, draft_cache, cur, pos, active, remaining, stepno,
            report)


def _spec_rounds_page_native_impl(model, draft_model, params,
                                  draft_params, arena, page_table,
                                  draft_cache, cur, pos, active,
                                  remaining, temp, top_k, eos, keys,
                                  stepno, adapter_ids=None, *, k,
                                  rounds):
    """The spec round program in **page-native** mode: the widened
    ``(B, k+1)`` verify reads and writes target K/V straight through
    the (write-masked) page table inside the model's attention
    (:func:`~ray_lightning_tpu.models.generate.verify_step_paged`) —
    no dense view gathers or scatters per dispatch. The draft half and
    the accept rule are byte-for-byte the shared
    :func:`_draft_propose` / :func:`_spec_accept`, so commits cannot
    drift from the dense-gather spec path. Rollback stays a position
    decrement: rejected drafts' K/V landed in pages the slot already
    owns, and writes past its span dropped at the page-table mask.
    """
    params = materialize_for_program(params, model.cfg)
    draft_params = materialize_for_program(draft_params, draft_model.cfg)
    max_pos = model.cfg.max_seq_len - 1

    def round_body(carry, _):
        arena, draft_cache, cur, pos, active, remaining, stepno = carry
        draft_cache, draft_toks, draft_logits = _draft_propose(
            draft_model, draft_params, draft_cache, cur, pos, keys,
            stepno, temp, top_k, max_pos, k=k)
        tokens_in = jnp.concatenate([cur, draft_toks], axis=1)
        vpos = jnp.minimum(pos + jnp.arange(k + 1)[None, :], max_pos)
        L, arena = verify_step_paged(model, params, arena, tokens_in,
                                     vpos, page_table, adapter_ids)
        (cur, pos, active, remaining, stepno, emitted, accepted,
         rejected, finished) = _spec_accept(
            L, draft_toks, draft_logits, cur, pos, active, remaining,
            temp, top_k, eos, keys, stepno, max_pos, k=k)
        return ((arena, draft_cache, cur, pos, active, remaining,
                 stepno), (emitted, accepted, rejected, finished))

    (arena, draft_cache, cur, pos, active, remaining, stepno), \
        (emitted, accepted, rejected, finished) = jax.lax.scan(
            round_body,
            (arena, draft_cache, cur, pos, active, remaining, stepno),
            None, length=rounds)
    return (arena, draft_cache, cur, pos, active, remaining, stepno,
            pack_report(cur, pos, active, remaining, stepno, emitted,
                        finished, accepted, rejected))


def _draft_refill_impl(draft_model, draft_params, pool_cache, tokens,
                       length, slot):
    """Rebuild ONE slot's draft KV row from its full host-side context:
    a fixed-shape ``(1, P)`` ragged prefill (P = max_seq_len, so any
    admissible context fits one program) + whole-row inject at ``slot``.
    The row is overwritten end to end — junk from the slot's previous
    tenant or from parked spec rounds never survives an activation."""
    pf_cache, _last = _prefill_impl(draft_model, draft_params, tokens,
                                    length)
    batch_axis = 1 if getattr(draft_model.cfg, "scan_layers", False) else 0

    def inject(pool, pf, kv):
        if not kv:
            return pool
        return jax.lax.dynamic_update_slice_in_dim(pool, pf, slot,
                                                   axis=batch_axis)

    return jax.tree_util.tree_map(inject, pool_cache, pf_cache,
                                  slot_leaves(draft_model, pool_cache))


_STATICS = ("model", "draft_model", "k", "rounds")
_spec_rounds_donated = partial(
    jax.jit, static_argnames=_STATICS, donate_argnums=(4, 5))(
        _spec_rounds_impl)
_spec_rounds_plain = partial(
    jax.jit, static_argnames=_STATICS)(_spec_rounds_impl)
_spec_paged_donated = partial(
    jax.jit, static_argnames=_STATICS, donate_argnums=(4, 6))(
        _spec_rounds_paged_impl)
_spec_paged_plain = partial(
    jax.jit, static_argnames=_STATICS)(_spec_rounds_paged_impl)
_spec_page_native_donated = partial(
    jax.jit, static_argnames=_STATICS, donate_argnums=(4, 6))(
        _spec_rounds_page_native_impl)
_spec_page_native_plain = partial(
    jax.jit, static_argnames=_STATICS)(_spec_rounds_page_native_impl)
_draft_refill_donated = partial(
    jax.jit, static_argnames=("draft_model",), donate_argnums=(2,))(
        _draft_refill_impl)
_draft_refill_plain = partial(
    jax.jit, static_argnames=("draft_model",))(_draft_refill_impl)


_pick = pick_donated  # shared CPU donation gating (serve/pages.py)


class SpecDecoder:
    """Draft-model state + compiled programs for one engine's spec path.

    Owns the draft's dense ``(num_slots, max_seq_len)`` KV cache (device
    memory — released by :meth:`shutdown`, which the owning engine's
    ``shutdown()`` drives) and the stale-slot ledger: every slot
    activation (fresh admit, final chunk, crash replay) marks its row
    stale, and the engine refills stale rows with a full-context draft
    prefill before the next spec dispatch.
    """

    def __init__(self, draft_model, draft_params, *, num_slots: int,
                 k: int, target_cfg):
        cfg = draft_model.cfg
        if not cfg.decode:
            raise ValueError(
                "the draft model must be decode-mode: rebuild its config "
                "with decode=True (params are compatible)")
        if cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size ({cfg.vocab_size}) must match the "
                f"target's ({target_cfg.vocab_size}) — draft proposals "
                "are verified id-for-id")
        if cfg.max_seq_len != target_cfg.max_seq_len:
            raise ValueError(
                f"draft max_seq_len ({cfg.max_seq_len}) must match the "
                f"target's ({target_cfg.max_seq_len}) — draft and target "
                "decode the same absolute positions")
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        self.model = draft_model
        self.params = draft_params
        self.k = k
        self.num_slots = num_slots
        self.prefill_len = cfg.max_seq_len
        self.cache = draft_model.init(
            jax.random.PRNGKey(0), jnp.zeros((num_slots, 1), jnp.int32),
            positions=jnp.zeros((num_slots, 1), jnp.int32))["cache"]
        self._stale: Set[int] = set()
        self.refills = 0

    # ----------------------------------------------------------- ledger
    @property
    def stale(self) -> List[int]:
        return sorted(self._stale)

    def mark_stale(self, slot: int) -> None:
        self._stale.add(slot)

    def discard(self, slot: int) -> None:
        self._stale.discard(slot)

    # --------------------------------------------------------- programs
    def refill(self, slot: int, context: List[int]) -> None:
        """Rebuild ``slot``'s draft KV from ``context`` (the row's
        prompt + all committed tokens except the current one — the
        draft cache must cover positions ``0..pos-1`` so the next round
        feeds the current token at ``pos``)."""
        P = self.prefill_len
        if not 1 <= len(context) <= P:
            raise ValueError(
                f"draft refill context length {len(context)} outside "
                f"[1, {P}]")
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :len(context)] = context
        fn = _pick(_draft_refill_donated, _draft_refill_plain)
        self.cache = fn(self.model, self.params, self.cache, tokens,
                        np.array([len(context)], np.int32),
                        np.int32(slot))
        self.refills += 1
        self._stale.discard(slot)

    def shutdown(self) -> None:
        """Drop the draft KV cache (device memory)."""
        self.cache = None
        self._stale.clear()
