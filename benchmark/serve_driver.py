"""What the family-built serve kinds share (``kinds/serve_family_loop.py``
closed loop, ``kinds/serve_open_loop.py`` open loop): they drive
``ServeClient`` exactly as ``kinds/serve_closed_loop.py`` does — the same
clock rules, the same window, the same staggered first generation, the
same ``facts`` keys, so every span- and trace-reading metric works
unchanged — but the model, its weights and its reference come from a
*family* module, ``benchmark/families/<model_type>.py``, named by the
configuration file's ``model_type`` (``benchmark/FAMILIES.md``). The next
architecture adds a family, not a kind.

Arrivals are the one thing the two kinds differ in:

- closed loop (``arrivals`` absent): ``clients`` callers, each answered
  at once by its next ``submit()``; the first request of every caller is
  cut short and staggered, and set-up ends when the last of them has
  retired.
- open loop (``arrivals: {"rate_per_s", "lead_in_tokens"}``): Poisson
  arrivals from ``--seed`` at a fixed rate, submitted when *due* whatever
  the system's state. The first ``clients`` requests are the warm-up:
  ``clients - 1`` short ones that compile every program and retire, then
  — at the moment the arrival clock starts — one *lead-in* request of
  ``lead_in_tokens`` tokens. Set-up ends when the lead-in retires, by
  which time the system has run under the offered load for about one
  residence time and the window opens on a steady state (these are the
  request ids ``0 .. clients - 1`` that ``program_spans`` cuts the
  window by). TTFT counts from the time a request was due, so a late
  generator cannot flatter it; ``generator_lateness_s`` is reported.

Judging (``judge``): as ``serve_closed_loop.py`` — finished requests are
teacher-forced through the family's float32 reference, greedy requests
give ``served_logit_gap``; sampled requests with a ``top_k`` give
``sampled_topk_gap``. Sampled requests *without* a ``top_k`` can emit any
token, so no gap exists for them: they give ``sampled_loglik_z``, the
standard score of the served tokens' log-likelihood under the
reference's own sampling distribution (the magnitude of a standard
normal when the program samples from the reference's distribution; a
temperature ignored or a token that is not the model's reads tens). Only the names under the workload
file's ``limits`` are compared; the rest are printed on the ``judge``
line.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness
from benchmark.harness import note

_closed = harness.load_module("kinds", "serve_closed_loop")
shape_pool, percentile, pick_sample = (_closed.shape_pool, _closed.percentile,
                                       _closed.pick_sample)


#: a compared number's name -> its key in ``served_gaps``' result
COMPARED = {"served_logit_gap": "greedy", "sampled_topk_gap": "sampled",
            "sampled_loglik_z": "sampled_z"}


def family_of(shape: dict):
    return harness.load_module("families", shape["model_type"])


def slice_work(before: dict, after: dict, prompt_len: dict) -> dict:
    """``serve_closed_loop.slice_work`` plus the lengths themselves: the
    prompt length of every prefill and the context of every decoded
    token (what a windowed or recurrent layer's work is counted from)."""
    work = _closed.slice_work(before, after, prompt_len)
    work["prefill_lengths"], work["decode_contexts"] = [], []
    for rid, b in after.items():
        a, plen = before.get(rid, 0), prompt_len[rid]
        if b <= a:
            continue
        if a == 0:
            work["prefill_lengths"].append(plen)
        work["decode_contexts"] += [plen + j for j in range(max(a, 1), b)]
    return work


def run(ctx) -> dict:
    from ray_lightning_tpu.serve import ServeClient

    w, shape = ctx.workload, ctx.shape
    family = family_of(shape)
    key = family.seed_key(ctx.seed)
    model, params, item_sizes = family.build(shape, w, key)
    vocab = shape["vocab_size"]
    positions = family.max_positions(shape, w)

    telemetry = None
    if ctx.slice is not None:
        from ray_lightning_tpu.obs import Telemetry
        telemetry = Telemetry(clock=time.perf_counter, capacity=1 << 20)
    client = ServeClient(model, params, seed=ctx.seed & 0x7FFFFFFF,
                         clock=time.perf_counter, telemetry=telemetry,
                         **w["engine"])

    clients = int(w["clients"])
    arrivals = w.get("arrivals")
    rng = np.random.default_rng([ctx.seed, 2])
    pool = shape_pool(w, positions, rng)
    cursor = [0]
    sampled = w["sampled"]
    first_new = w["first_generation_max_new"]

    def next_request(c: int, max_new=None) -> dict:
        plen, new = pool[cursor[0] % len(pool)]
        cursor[0] += 1
        greedy = c % 2 == 0
        top_k = sampled.get("top_k")
        return dict(prompt=rng.integers(0, vocab, size=plen).tolist(),
                    max_new_tokens=int(new if max_new is None
                                       else min(max_new, positions - plen)),
                    temperature=0.0 if greedy
                    else float(sampled["temperature"]),
                    top_k=None if greedy or top_k is None else int(top_k))

    owner, asked, t_due = {}, {}, {}
    failed = 0

    def submit(c: int, max_new=None, due=None) -> None:
        kw = next_request(c, max_new)
        now = client.now()
        rid = client.submit(**kw)
        owner[rid], asked[rid] = c, kw
        t_due[rid] = now if due is None else due

    def one_tick():
        with ctx.span("tick"):
            done = client.tick()
        return done, client.now()

    def frontier() -> dict:
        return {req.id: len(toks)
                for req, toks in client.engine.snapshot_in_flight()}

    def until_retired(ids: set, resubmit: bool) -> None:
        while ids:
            for comp in one_tick()[0]:
                ids.discard(comp.request_id)
                if resubmit:
                    submit(owner[comp.request_id])

    # ---- set-up ------------------------------------------------------
    lo, hi = first_new
    due_at, lateness = [], []     # open loop: the arrival schedule
    n_arrived = [0]
    if arrivals is None:
        for c in range(clients):    # short and staggered
            submit(c, max_new=lo + (hi - lo) * c // clients)
        until_retired(set(owner), resubmit=True)
    else:
        for c in range(clients - 1):
            submit(c, max_new=lo + (hi - lo) * c // clients)
        until_retired(set(owner), resubmit=False)
        # the arrival clock starts here; the schedule outlasts the run
        rate = float(arrivals["rate_per_s"])
        horizon = 4.0 * (ctx.seconds + 60.0)
        gaps = np.random.default_rng([ctx.seed, 4]).exponential(
            1.0 / rate, size=int(rate * horizon) + 16)
        t_zero = client.now()
        due_at = (t_zero + np.cumsum(gaps)).tolist()
        submit(clients - 1, max_new=int(arrivals["lead_in_tokens"]))
        lead_in = max(owner)

    def offer(now: float) -> None:
        """Open loop: submit every request that is due; with nothing due
        and nothing to do, wait for the next arrival (in steps of 1 ms)
        instead of spinning on empty ticks."""
        nonlocal failed
        if n_arrived[0] < len(due_at) and due_at[n_arrived[0]] > now \
                and not client.busy:
            time.sleep(min(due_at[n_arrived[0]] - now, 1e-3))
            now = client.now()
        while n_arrived[0] < len(due_at) and due_at[n_arrived[0]] <= now:
            due = due_at[n_arrived[0]]
            n_arrived[0] += 1
            lateness.append(client.now() - due)
            try:
                submit(clients + n_arrived[0], due=due)
            except Exception as e:      # shed at admission: a failure
                failed += 1
                note(phase="shed", error=type(e).__name__)

    if arrivals is not None:
        waiting = {lead_in}
        while waiting:
            offer(client.now())
            for comp in one_tick()[0]:
                waiting.discard(comp.request_id)
        lateness.clear()
    ctx.window_open()
    t_open = client.now()

    # ---- the window --------------------------------------------------
    sl = ctx.slice
    slice_s = float(w["trace"]["seconds"]) if sl is not None else 0.0
    before, retired = {}, {}
    records = []
    t_close = None
    while t_close is None:
        if sl is not None and not sl.running \
                and client.now() - t_open >= ctx.seconds - slice_s:
            before = frontier()
            sl.start()
        if arrivals is not None:
            with ctx.span("submit"):
                offer(client.now())
        done, now = one_tick()
        for comp in done:
            kw, first = asked[comp.request_id], comp.first_token_time
            due = t_due[comp.request_id]
            ok = (comp.finish_reason == "length"
                  and len(comp.tokens) == kw["max_new_tokens"]
                  and first is not None and due <= first <= now)
            failed += 0 if ok else 1
            if sl is not None and sl.running:
                retired[comp.request_id] = len(comp.tokens)
            if ok:
                records.append(dict(
                    rid=comp.request_id, prompt=list(comp.prompt),
                    tokens=list(comp.tokens),
                    greedy=kw["temperature"] == 0.0,
                    ttft=first - due,
                    tpot=(now - first) / max(1, len(comp.tokens) - 1)))
            if arrivals is None:
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
         ticks=client.ops, offered=n_arrived[0],
         in_flight_at_close=len(frontier()),
         generator_lateness_s=max(lateness, default=0.0),
         generator_lateness_p50_s=percentile(lateness, 50)
         if lateness else 0.0,
         ttft_p50_ms=percentile(ttft_ms, 50),
         tpot_p50_ms=percentile(tpot_ms, 50),
         tpot_p95_ms=percentile(tpot_ms, 95))

    queue_ms = None
    if telemetry is not None:
        in_window = {r["rid"] for r in records}
        queue_ms = [tr.breakdown().get("queue", 0.0) * 1e3
                    for rid, tr in telemetry.request_traces().items()
                    if rid in in_window]
        note(phase="traced", queue_samples=len(queue_ms), slice_work={
            k: v for k, v in work.items() if not isinstance(v, list)})
    facts = {"kind": w["kind"], "wall_s": wall,
             "completed": len(records), "queue_ms": queue_ms,
             "tpot_ms": tpot_ms, "slice": work, **item_sizes,
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


def _log_softmax(x):
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def served_gaps(ctx, key, sample: list, sampled: dict,
                control_mode: str = None, **reference_kw) -> dict:
    """``serve_closed_loop.served_gaps`` over a family's reference:
    ``greedy`` / ``sampled`` (the widest gap of each kind, with the bar
    where it was found; ``sampled`` only with a ``top_k``) and, with
    ``control_mode``, ``control_*``: the same for the token the lower
    precision puts first / ``top_k``-th there (the control never
    decodes). Without a ``top_k``: ``sampled_z``, and ``control_sampled_z``
    for tokens drawn from the lower precision's distribution at each
    position."""
    family = family_of(ctx.shape)
    ref = family.make_reference(ctx.shape, key, "f32")
    low = family.make_reference(ctx.shape, key, control_mode,
                                **reference_kw) if control_mode else None
    top_k, temp = sampled.get("top_k"), float(sampled["temperature"])
    got = {"greedy": None, "sampled": None, "control_greedy": None,
           "control_sampled": None, "positions": 0, "requests": len(sample)}
    draw = np.random.default_rng([int(ctx.seed), 5])
    z_sum = {"sampled_z": [0.0, 0.0], "control_sampled_z": [0.0, 0.0]}

    def widen(name, bar, below):
        gaps = np.maximum(0.0, bar - below)
        worst = int(np.argmax(gaps))
        if got[name] is None or gaps[worst] > got[name]:
            got[name] = float(gaps[worst])
            got[name + "_bar"] = float(bar[worst])

    def loglik(name, logp, tokens):
        # sum over positions of (log p(token) - E log p), and of Var
        p = np.exp(logp)
        mean = (p * logp).sum(-1)
        var = (p * logp * logp).sum(-1) - mean * mean
        z_sum[name][0] += float((logp[np.arange(len(tokens)), tokens]
                                 - mean).sum())
        z_sum[name][1] += float(var.sum())

    for r in sample:
        seq = r["prompt"] + r["tokens"]
        first = len(r["prompt"]) - 1
        rows = np.arange(first, first + len(r["tokens"]))
        at = np.arange(len(rows))
        lg = np.asarray(ref(seq, rows), np.float64)
        lo = np.asarray(low(seq, rows), np.float64) \
            if low is not None else None
        if r["greedy"] or top_k is not None:
            kind = "greedy" if r["greedy"] else "sampled"
            rank = 1 if r["greedy"] else int(top_k)
            bar = np.partition(lg, -rank, axis=-1)[:, -rank]
            widen(kind, bar, lg[at, r["tokens"]])
            if lo is not None:
                pick = np.argpartition(lo, -rank, axis=-1)[:, -rank]
                widen("control_" + kind, bar, lg[at, pick])
        else:
            logp = _log_softmax(lg / temp)
            loglik("sampled_z", logp, np.asarray(r["tokens"]))
            if lo is not None:
                gumbel = draw.gumbel(size=lo.shape)
                loglik("control_sampled_z", logp,
                       np.argmax(lo / temp + gumbel, axis=-1))
        got["positions"] += len(r["tokens"])
    for name, (total, var) in z_sum.items():
        got[name] = abs(total) / np.sqrt(var) if var > 0 else None
    del ref, low
    gc.collect()
    return got


def judge(ctx, key, records: list, w: dict) -> dict:
    """Only the names under ``limits`` are compared. A kind of request of
    which the window finished none proves nothing: its number reads
    1e30."""
    sample = pick_sample(records, ctx.seed, int(w["check_requests"]))
    got = served_gaps(ctx, key, sample, w["sampled"]) if sample else {}
    note(phase="judge", **got)
    return {name: (1e30 if got.get(COMPARED[name]) is None
                   else got[COMPARED[name]], limit)
            for name, limit in w["limits"].items()}
