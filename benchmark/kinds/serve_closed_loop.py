"""Traffic kind ``serve_closed_loop``: ``clients`` callers that each wait
for their reply — every ``Completion`` that ``tick()`` returns is answered
at once by that client's next ``submit()``; the slots stay full.

The request *shapes* (prompt length, ``max_new_tokens``) are the workload
file's two log-normals cut into ``pool_requests`` quantiles each (clipped;
prompt + output <= the model's positions): one fixed set of sizes, far
smaller than what a window consumes. ``--seed`` pairs prompts with outputs,
orders the pool (cycled, one shared queue that every caller pulls from) and
draws the token ids, the weights and the sampling streams: every seed
offers the same sizes in another order. Half the clients are greedy, half
sample (``temperature``, ``top_k``); no EOS, so a request's work is fixed
by its shape. The first request of every client is cut short and staggered
(``first_generation_max_new``): set-up ends when the last of them has
retired, so no request that waited for the initial fill or a compile is
ever in the window, and the completions are spread out when it opens. The
window then runs for ``--seconds`` and closes on the first ``tick()`` that
returns after it.

Only the caller's surface is read: ``submit()``, ``tick()``, ``now()`` and
the ``Completion``s. All stamps are on the clock the harness hands the
client (``time.perf_counter``): the harness reads it at ``submit()`` and
when ``tick()`` returns a completion; the first token's stamp is the one
the ``Completion`` carries (the client reads the same clock when the token
exists — it has no stream callback a caller could stamp), and a stamp that
does not lie between the harness's own two fails the request.
``serve_tokens_per_s`` is ``len(Completion.tokens)`` summed over the
requests that completed inside the window.

A traced run profiles the window's last ``trace.seconds`` (the profiler's
stop stalls the process, so it comes after the close) and, for the
per-layer readers only, asks the engine for its in-flight token frontier
(``engine.snapshot_in_flight()``) when the slice opens and closes: the
tokens it shows are the work of the slice.

Workload file keys: ``engine`` (ServeClient keyword arguments),
``clients``, ``prompt`` / ``output`` (median, sigma, min, max),
``pool_requests``, ``sampled`` (temperature, top_k),
``first_generation_max_new`` (lo, hi), ``trace`` (seconds),
``check_requests`` (of each kind, greedy and sampled), ``limits``,
``source``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import reference, weights
from benchmark.harness import note


def shape_pool(w: dict, max_positions: int, rng):
    """The pool of (prompt_len, max_new_tokens) pairs: each length is the
    log-normal's quantiles at (i + 0.5) / n, clipped, so a small pool still
    has the mix's median and tails. The sizes are the same for every seed;
    ``rng`` pairs prompts with outputs and orders the pool."""
    from statistics import NormalDist
    n = int(w["pool_requests"])

    def draw(spec):
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)

    prompts = rng.permutation(draw(w["prompt"]))
    outputs = rng.permutation(draw(w["output"]))
    outputs = np.minimum(outputs, max_positions - prompts)
    return list(zip(prompts.tolist(), outputs.tolist()))


def percentile(values, q: float) -> float:
    """NaN when no request is left to take it over."""
    if not values:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def slice_work(before: dict, after: dict, prompt_len: dict) -> dict:
    """What the engine did between two readings of its token frontier
    (``{request: tokens emitted so far}``; a request the slice retired
    stands in ``after`` with its whole length). Token 0 of a request comes
    from its prefill; token j > 0 is a decode step that attends the prompt
    and the j tokens before it."""
    work = {"prefills": 0, "prefill_tokens": 0, "prefill_sq": 0,
            "decode_tokens": 0, "decode_context_sum": 0}
    for rid, b in after.items():
        a, plen = before.get(rid, 0), prompt_len[rid]
        if b <= a:
            continue
        if a == 0:
            work["prefills"] += 1
            work["prefill_tokens"] += plen
            work["prefill_sq"] += plen * (plen + 1)
        lo = max(a, 1)
        work["decode_tokens"] += b - lo
        work["decode_context_sum"] += sum(plen + j for j in range(lo, b))
    return work


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                      TransformerLM)
    from ray_lightning_tpu.serve import ServeClient

    w, shape = ctx.workload, ctx.shape
    n_head, vocab = shape["n_head"], shape["vocab_size"]
    key = weights.seed_key(ctx.seed)
    cfg = TransformerConfig(
        vocab_size=vocab, max_seq_len=shape["n_positions"],
        d_model=shape["n_embd"], n_heads=n_head, n_layers=shape["n_layer"],
        d_ff=4 * shape["n_embd"], dtype=jnp.bfloat16,
        param_dtype=jnp.float32, causal=True, decode=True,
        scan_layers=False)
    model = TransformerLM(cfg)
    params = jax.jit(lambda k: weights.program_tree(
        weights.make_canonical(k, shape), n_head, scanned=False))(key)

    telemetry = None
    if ctx.slice is not None:
        from ray_lightning_tpu.obs import Telemetry
        telemetry = Telemetry(clock=time.perf_counter, capacity=1 << 20)
    client = ServeClient(model, params, seed=ctx.seed & 0x7FFFFFFF,
                         clock=time.perf_counter, telemetry=telemetry,
                         **w["engine"])

    # ---- the offered requests: one shared queue of shapes, cycled ------
    # Every client that gets its reply takes the next shape off the same
    # list (callers pulling jobs from one queue).
    clients = int(w["clients"])
    rng = np.random.default_rng([ctx.seed, 2])
    pool = shape_pool(w, shape["n_positions"], rng)
    cursor = [0]
    sampled = w["sampled"]
    first_new = w["first_generation_max_new"]

    def next_request(c: int, first: bool = False) -> dict:
        plen, new = pool[cursor[0] % len(pool)]
        cursor[0] += 1
        if first:   # short and staggered: set-up ends when these retire
            lo, hi = first_new
            new = min(new, lo + (hi - lo) * c // clients)
        greedy = c % 2 == 0
        return dict(prompt=rng.integers(0, vocab, size=plen).tolist(),
                    max_new_tokens=int(new),
                    temperature=0.0 if greedy else float(sampled["temperature"]),
                    top_k=None if greedy else int(sampled["top_k"]))

    owner, asked, t_submit = {}, {}, {}

    def submit(c: int, first: bool = False) -> None:
        kw = next_request(c, first)
        now = client.now()
        rid = client.submit(**kw)
        owner[rid], asked[rid], t_submit[rid] = c, kw, now

    def one_tick():
        with ctx.span("tick"):
            done = client.tick()
        return done, client.now()

    def frontier() -> dict:
        return {req.id: len(toks)
                for req, toks in client.engine.snapshot_in_flight()}

    # ---- set-up: fill the slots, run until warm ----------------------
    for c in range(clients):
        submit(c, first=True)
    first_generation = set(owner)
    while first_generation:
        for comp in one_tick()[0]:
            first_generation.discard(comp.request_id)
            submit(owner[comp.request_id])
    ctx.window_open()
    t_open = client.now()

    # ---- the window --------------------------------------------------
    sl = ctx.slice
    slice_s = float(w["trace"]["seconds"]) if sl is not None else 0.0
    before, retired = {}, {}
    records = []        # completions of the window
    failed = 0
    t_close = None
    while t_close is None:
        if sl is not None and not sl.running \
                and client.now() - t_open >= ctx.seconds - slice_s:
            before = frontier()
            sl.start()
        done, now = one_tick()
        for comp in done:
            kw, first = asked[comp.request_id], comp.first_token_time
            ok = (comp.finish_reason == "length"
                  and len(comp.tokens) == kw["max_new_tokens"]
                  and first is not None
                  and t_submit[comp.request_id] <= first <= now)
            failed += 0 if ok else 1
            if sl is not None and sl.running:
                retired[comp.request_id] = len(comp.tokens)
            if ok:
                records.append(dict(
                    rid=comp.request_id, prompt=list(comp.prompt),
                    tokens=list(comp.tokens),
                    greedy=kw["temperature"] == 0.0,
                    ttft=first - t_submit[comp.request_id],
                    tpot=(now - first) / max(1, len(comp.tokens) - 1)))
            with ctx.span("submit"):
                submit(owner[comp.request_id])
        if now - t_open >= ctx.seconds:
            t_close = now
    wall = t_close - t_open
    work = None
    if sl is not None:
        work = slice_work(before, {**frontier(), **retired},
                          {rid: len(kw["prompt"])
                           for rid, kw in asked.items()})
        sl.stop()
    out_tokens = sum(len(r["tokens"]) for r in records)
    ttft_ms = [r["ttft"] * 1e3 for r in records]
    tpot_ms = [r["tpot"] * 1e3 for r in records]
    note(phase="window", completed=len(records) + failed, failed=failed,
         completed_tokens=out_tokens, wall_s=round(wall, 4),
         ticks=client.ops, generator_lateness_s=0.0,
         ttft_p50_ms=percentile(ttft_ms, 50),
         tpot_p50_ms=percentile(tpot_ms, 50),
         tpot_p95_ms=percentile(tpot_ms, 95))

    queue_ms = None
    if telemetry is not None:
        in_window = {r["rid"] for r in records}
        queue_ms = [tr.breakdown().get("queue", 0.0) * 1e3
                    for rid, tr in telemetry.request_traces().items()
                    if rid in in_window]
        note(phase="traced", queue_samples=len(queue_ms), slice_work=work)
    facts = {"kind": "serve_closed_loop", "wall_s": wall,
             "completed": len(records), "queue_ms": queue_ms,
             "tpot_ms": tpot_ms, "slice": work,
             "kv_itemsize": jnp.dtype(cfg.dtype).itemsize,
             "weight_itemsize": jnp.dtype(cfg.param_dtype).itemsize,
             "slice_s": (sl.t1 - sl.t0) if sl is not None else None}
    client.shutdown()

    def check() -> dict:
        nonlocal client, params, model
        client = params = model = None
        gc.collect()
        return judge(ctx, key, records, w)

    return {"attempted": len(records) + failed, "failed": failed,
            "end_to_end": {
                "serve_tokens_per_s": out_tokens / wall,
                "serve_ttft_p95_ms": percentile(ttft_ms, 95)},
            "facts": facts, "check": check, "records": records}


def pick_sample(records: list, seed: int, n: int) -> list:
    """Of the requests the window finished, ``n`` greedy and ``n`` sampled
    ones: the longest of each kind, and ``n - 1`` more drawn from the
    seed."""
    rng = np.random.default_rng([int(seed), 3])
    sample = []
    for greedy in (True, False):
        mine = [r for r in records if r["greedy"] == greedy]
        if not mine:
            continue
        longest = max(mine, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        rest = [r for r in mine if r is not longest]
        take = rng.permutation(len(rest))[:max(0, n - 1)]
        sample += [longest] + [rest[i] for i in take]
    return sample


def served_gaps(ctx, key, sample: list, top_k: int,
                control_mode: str = None) -> dict:
    """Teacher-forced float32 reference logits over each prompt with its
    served tokens. At every served position the *bar* is the reference's
    best logit for a greedy request and its ``top_k``-th best for a sampled
    one, and the gap is how far the served token's reference logit lies
    below the bar (0 at or above it). ``greedy`` / ``sampled``: the widest
    gap of each kind, with the bar where it was found. With
    ``control_mode``, ``control_greedy`` / ``control_sampled``: the same
    for the token that the lower precision puts first / ``top_k``-th at
    each of those positions (the control never decodes)."""
    import jax
    shape = ctx.shape
    params = jax.jit(lambda k: weights.make_canonical(k, shape))(key)
    ref = reference.make_logits_fn(shape, "f32")
    low = reference.make_logits_fn(shape, control_mode) \
        if control_mode else None
    width = shape["n_positions"]
    got = {"greedy": None, "sampled": None, "control_greedy": None,
           "control_sampled": None, "positions": 0, "requests": len(sample)}

    def widen(name, bar, below):
        gaps = np.maximum(0.0, bar - below)
        worst = int(np.argmax(gaps))
        if got[name] is None or gaps[worst] > got[name]:
            got[name] = float(gaps[worst])
            got[name + "_bar"] = float(bar[worst])

    for r in sample:
        seq = r["prompt"] + r["tokens"]
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq
        first = len(r["prompt"]) - 1
        rows = np.arange(first, first + len(r["tokens"]))
        at = np.arange(len(rows))
        lg = np.asarray(ref(params, toks))[rows]       # (n, V) on the host
        kind = "greedy" if r["greedy"] else "sampled"
        rank = 1 if r["greedy"] else top_k
        bar = np.partition(lg, -rank, axis=-1)[:, -rank]
        widen(kind, bar, lg[at, r["tokens"]])
        if low is not None:
            lo = np.asarray(low(params, toks))[rows]
            pick = np.argpartition(lo, -rank, axis=-1)[:, -rank]
            widen("control_" + kind, bar, lg[at, pick])
        got["positions"] += len(r["tokens"])
    del params
    return got


def judge(ctx, key, records: list, w: dict) -> dict:
    """A kind of request of which the window finished none proves nothing:
    its number reads 1e30."""
    sample = pick_sample(records, ctx.seed, int(w["check_requests"]))
    got = served_gaps(ctx, key, sample, int(w["sampled"]["top_k"])) \
        if sample else {}
    note(phase="judge", **got)
    limits = w["limits"]

    def held(name):
        value = got.get(name)
        return 1e30 if value is None else value

    return {"served_logit_gap": (held("greedy"), limits["served_logit_gap"]),
            "sampled_topk_gap": (held("sampled"),
                                 limits["sampled_topk_gap"])}
