"""The span primitive and what opens it (PR 26): ids and parents, raw
clock readings, the profiler's clock, counts at the dispatch boundary,
the trainer's seats, the device scopes.

- ``serve.tick`` is the root of a tree per tick; the self times of the
  tree sum to the tick.
- In wall mode every span is also a ``jax.profiler.TraceAnnotation``: a
  real profile taken around a few armed ticks holds a host event of the
  same name for every recorded span, nested the same way.
- One wall-clock export is one time axis: a request's ``prefill``
  segment lies inside the prefill tick's span (the offset bug of the two
  zeroed clocks).
- Counts at the dispatch boundary: prompts of 5 and 9 tokens in a 2 x 16
  prefill read 14 of 32.
- Disarmed, no span is made and no clock is read for telemetry.
- Every device scope of ``docs/observability.md`` is in the ``op_name``
  the compiler is handed for the train step, the prefill and the decode
  program (read from the lowered text, which the persistent compile
  cache cannot serve stale).
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import gpt2_config
from ray_lightning_tpu.models.transformer import TransformerLM
from ray_lightning_tpu.obs import Telemetry, last_telemetry
from ray_lightning_tpu.obs import spans as spans_mod
from ray_lightning_tpu.obs.spans import SpanRecorder
from ray_lightning_tpu.obs.tracing import fleet_chrome_trace
from ray_lightning_tpu.serve import ServeClient


@pytest.fixture(scope="module")
def nano():
    mk = dict(vocab_size=128, max_seq_len=64, dtype=jnp.float32,
              scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(0), np.zeros((2, 4), np.int32))["params"]
    return dec, params


def armed_client(nano, clock=None, **kw):
    dec, params = nano
    tel = Telemetry(clock=clock)
    kw = {"num_slots": 3, "prefill_len": 16, "prefill_batch": 2, **kw}
    return tel, ServeClient(dec, params, clock=clock, telemetry=tel, **kw)


def drive(client, prompts=((5, 17, 3, 9, 2), (9, 2, 44, 1, 7, 7, 3, 8, 6)),
          new=4):
    for p in prompts:
        client.submit(list(p), max_new_tokens=new)
    return client.run_until_idle()


# --------------------------------------------------------------------- #
# the primitive
# --------------------------------------------------------------------- #
def test_span_has_id_parent_and_raw_clock_readings():
    clk = iter([50.0, 51.0, 52.5, 53.0, 60.0, 61.0])
    rec = SpanRecorder(clock=lambda: next(clk))
    with rec.span("outer", ids=[7]) as args:
        with rec.span("inner"):
            pass
        args["rows"] = 2
    with rec.span("next"):
        pass
    inner, outer, nxt = rec.spans()
    assert (outer.id, outer.parent, outer.depth) == (0, None, 0)
    assert (inner.id, inner.parent, inner.depth) == (1, 0, 1)
    assert (nxt.id, nxt.parent) == (2, None)
    # raw readings: nothing zeroed at the recorder's first span
    assert (outer.start, outer.end) == (50.0, 53.0)
    assert (inner.start, inner.end, inner.dur) == (51.0, 52.5, 1.5)
    assert outer.args == {"ids": [7], "rows": 2}
    assert rec.self_times() == {0: 1.5, 1: 1.5, 2: 1.0}


def test_chrome_export_zeroes_at_the_origin_only_when_exporting():
    clk = iter([50.0, 51.0])
    rec = SpanRecorder(clock=lambda: next(clk))
    with rec.span("a"):
        pass
    ev, = rec.chrome_trace()["traceEvents"]
    assert (ev["ts"], ev["dur"]) == (0.0, 1e6)      # earliest span
    rec.set_origin(48.0)
    rec.set_origin(10.0)                            # the first one wins
    ev, = rec.chrome_trace()["traceEvents"]
    assert (ev["ts"], ev["dur"]) == (2e6, 1e6)
    assert rec.spans()[0].start == 50.0             # the record is raw


def test_tick_mode_enters_no_profiler_annotation(monkeypatch):
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: made.append(a) or pytest.fail(
                            "tick mode must not touch the profiler"))
    rec = SpanRecorder()
    with rec.span("a"):
        with rec.span("b"):
            pass
    assert not made and [s.start for s in rec.spans()] == [1.0, 0.0]


def test_record_closed_is_a_parentless_root_with_its_own_id():
    rec = SpanRecorder(clock=time.perf_counter)
    with rec.span("local"):
        rec.record_closed("engine.prefill.call", 5.0, 7.0, depth=1,
                          args={"seat": 3})
    shipped, local = rec.spans()
    assert (shipped.parent, shipped.depth, shipped.dur) == (None, 1, 2.0)
    assert shipped.args == {"seat": 3} and shipped.id != local.id
    assert rec.self_times()[local.id] == pytest.approx(local.dur)


def test_dropped_spans_show_in_the_registry():
    tel = Telemetry()
    tel.spans._capacity = 2
    assert tel.metrics.snapshot()["obs_spans_dropped_total"] == 0.0
    for i in range(5):
        with tel.span(f"s{i}"):
            pass
    assert tel.spans.dropped == 3
    assert tel.metrics.snapshot()["obs_spans_dropped_total"] == 3.0


def test_last_telemetry_is_the_handle_built_last():
    a = Telemetry()
    assert last_telemetry() is a
    b = Telemetry()
    assert last_telemetry() is b and last_telemetry() is not a


# --------------------------------------------------------------------- #
# the serve tick's tree
# --------------------------------------------------------------------- #
STEP_TICK = ["serve.sweep", "scheduler.plan", "engine.step.build",
             "engine.step.call", "engine.step.sync", "engine.step.retire",
             "serve.finalize"]
PREFILL_TICK = ["serve.sweep", "scheduler.plan", "engine.prefill.build",
                "engine.prefill.call", "engine.prefill.sync",
                "engine.prefill.activate", "serve.stamp", "serve.finalize"]


@pytest.fixture(scope="module")
def tick_run(nano):
    tel, client = armed_client(nano)       # tick clock: deterministic
    done = drive(client)
    return tel, client, done


def children_of(spans, root):
    return [s for s in spans if s.parent == root.id]


@pytest.mark.parametrize("action,names", [("step", STEP_TICK),
                                          ("prefill", PREFILL_TICK)])
def test_tick_is_a_tree_of_named_children(tick_run, action, names):
    tel, _, _ = tick_run
    spans = tel.spans.spans()
    roots = [s for s in spans if s.name == "serve.tick"
             and s.args.get("action") == action]
    assert roots and all(r.parent is None for r in roots)
    for root in roots:
        kids = sorted(children_of(spans, root), key=lambda s: s.start)
        assert [k.name for k in kids] == names
        assert all(root.start < k.start and k.end < root.end for k in kids)


def test_ids_and_parents_form_one_tree_per_tick(tick_run):
    tel, _, _ = tick_run
    spans = tel.spans.spans()
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans)
    for s in spans:
        if s.name in ("serve.tick", "serve.submit"):
            assert s.parent is None and s.depth == 0
            continue
        top = s
        while top.parent is not None:
            assert by_id[top.parent].depth == top.depth - 1
            top = by_id[top.parent]
        assert top.name == "serve.tick"


def test_self_times_of_a_ticks_tree_sum_to_the_tick(nano):
    tel, client = armed_client(nano, clock=time.perf_counter)
    drive(client)
    spans, own = tel.spans.spans(), tel.spans.self_times()
    roots = [s for s in spans if s.name == "serve.tick"]
    assert len(roots) >= 4
    root_of = {}
    for s in reversed(spans):              # parents before children
        root_of[s.id] = root_of.get(s.parent, s.id)
    for root in roots:
        tree = [s for s in spans if root_of[s.id] == root.id]
        assert len(tree) >= 6
        assert sum(own[s.id] for s in tree) == pytest.approx(root.dur)
        assert all(own[s.id] >= 0 for s in tree)


def test_spans_of_requests_carry_their_ids(tick_run):
    tel, _, done = tick_run
    submitted = [s.args["ids"] for s in tel.spans.spans("serve.submit")]
    assert submitted == [[0], [1]]
    call, = tel.spans.spans("engine.prefill.call")
    assert call.args["ids"] == [0, 1]
    retired = [i for s in tel.spans.spans("serve.finalize")
               for i in s.args["ids"]]
    assert sorted(retired) == sorted(done) == [0, 1]
    assert sorted(i for s in tel.spans.spans("engine.step.retire")
                  for i in s.args["ids"]) == [0, 1]


def test_counts_of_a_hand_made_prefill_read_14_of_32(tick_run):
    tel, client, _ = tick_run
    call, = tel.spans.spans("engine.prefill.call")
    assert (call.args["rows"], call.args["tokens"],
            call.args["program_tokens"]) == (2, 14, 32)
    snap = tel.metrics.snapshot()
    assert snap["serve_prefill_rows_total"] == 2
    assert snap["serve_prefill_tokens_total"] == 14
    assert snap["serve_prefill_program_tokens_total"] == 32
    steps = tel.spans.spans("engine.step.call")
    assert len(steps) == client.engine.steps
    assert all(s.args["slots"] == 3 for s in steps)
    assert [s.args["active"] for s in steps] == [2] * len(steps)
    assert snap["serve_step_rows_total"] == 2 * len(steps)
    assert snap["serve_step_slots_total"] == 3 * len(steps)


def test_chunk_dispatch_counts_valid_over_chunk_size(nano):
    tel, client = armed_client(nano, page_size=8, num_pages=24,
                               prefill_chunk=8)
    drive(client, prompts=[tuple(range(1, 12))])        # 11 tokens: 8 + 3
    calls = tel.spans.spans("engine.chunk.call")
    assert [(c.args["tokens"], c.args["program_tokens"])
            for c in calls] == [(8, 8), (3, 8)]
    assert all(c.args["ids"] == [0] for c in calls)
    assert len(tel.spans.spans("engine.chunk.sync")) == 2
    snap = tel.metrics.snapshot()
    assert snap["serve_chunk_tokens_total"] == 11
    assert snap["serve_chunk_program_tokens_total"] == 16


def test_async_dispatch_splits_enqueue_from_sync(nano):
    tel, client = armed_client(nano, clock=time.perf_counter,
                               async_dispatch=True)
    drive(client)
    names = {s.name for s in tel.spans.spans()}
    assert {"engine.step.build", "engine.step.call", "engine.step.sync",
            "engine.step.retire"} <= names
    assert tel.metrics.get("serve_dispatch_overlap_ms").count >= 1


# --------------------------------------------------------------------- #
# disarmed: nothing is made, no clock is read
# --------------------------------------------------------------------- #
def test_disarmed_makes_no_span_and_reads_no_clock(nano, monkeypatch):
    from ray_lightning_tpu.serve import engine as engine_mod
    dec, params = nano

    def refuse(*a, **k):
        raise AssertionError("disarmed path made a span / read a clock")

    monkeypatch.setattr(SpanRecorder, "begin", refuse)
    monkeypatch.setattr(spans_mod.Span, "__init__", refuse)
    monkeypatch.setattr(engine_mod.time, "perf_counter", refuse)
    for kw in ({}, {"async_dispatch": True}):
        client = ServeClient(dec, params, num_slots=3, prefill_len=16, **kw)
        assert client._tel is None and client.engine._tel is None
        assert len(drive(client)) == 2


# --------------------------------------------------------------------- #
# one wall-clock export is one time axis
# --------------------------------------------------------------------- #
def test_wall_export_puts_the_prefill_segment_inside_the_prefill_tick(nano):
    """The recorder and the client used to zero at different moments (the
    recorder's first span, the client's first ``now()``), so the request
    track sat beside the span track by the time between them. A clock
    that moves a long way between the two makes the old offset huge."""
    class Clock:
        t = 1000.0

        def __call__(self):
            self.t += 0.001
            return self.t

    clk = Clock()
    tel, client = armed_client(nano, clock=clk)
    with tel.span("warm_up"):           # the recorder's first span ...
        clk.t += 500.0
    client.submit([5, 17, 3], max_new_tokens=3)   # ... the client's zero
    client.run_until_idle()
    events = fleet_chrome_trace(tel)["traceEvents"]
    seg, = [e for e in events if e["name"] == "req0/prefill"]
    tick, = [e for e in events if e["name"] == "serve.tick"
             and e["args"].get("action") == "prefill"]
    assert tick["ts"] <= seg["ts"]
    assert seg["ts"] + seg["dur"] <= tick["ts"] + tick["dur"]
    assert tel.spans.origin == client._t0
    # the single-recorder export lies on the same axis
    solo = [e for e in tel.spans.chrome_trace()["traceEvents"]
            if e["name"] == "serve.tick"
            and e["args"].get("action") == "prefill"]
    assert solo[0]["ts"] == tick["ts"]


# --------------------------------------------------------------------- #
# inside a profile: the program's spans on the profiler's host plane
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def profiled(nano, tmp_path_factory):
    """A real ``jax.profiler`` trace around a few armed wall-mode ticks:
    ``(recorded spans, host events by name)``, events as (start, end) in
    ns on the profiler's clock."""
    import glob
    from jax.profiler import ProfileData
    tel, client = armed_client(nano, clock=time.perf_counter)
    drive(client, new=2)                     # compile outside the profile
    mark = len(tel.spans.spans())
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        drive(client, new=3)
    finally:
        jax.profiler.stop_trace()
    recorded = tel.spans.spans()[mark:]
    path, = glob.glob(out + "/plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if "." in e.name and e.name.split(".")[0] in (
                        "serve", "engine", "scheduler"):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return recorded, events


SERVE_SPANS = sorted(set(STEP_TICK + PREFILL_TICK
                         + ["serve.tick", "serve.submit"]))


@pytest.mark.parametrize("name", SERVE_SPANS)
def test_profile_holds_a_host_event_for_every_recorded_span(profiled,
                                                            name):
    recorded, events = profiled
    mine = [s for s in recorded if s.name == name]
    assert mine, f"the profiled ticks recorded no {name} span"
    assert len(events.get(name, ())) == len(mine)


@pytest.mark.parametrize("name", sorted(set(STEP_TICK + PREFILL_TICK)))
def test_profile_nests_the_events_as_the_recorder_did(profiled, name):
    _, events = profiled
    ticks = sorted(events["serve.tick"])
    for start, end in events[name]:
        assert any(t0 <= start and end <= t1 for t0, t1 in ticks), name


def test_profile_durations_agree_with_the_recorder(profiled):
    """One clock by construction: the annotation encloses the span, so a
    tick's event is at least as long as its span and not much longer."""
    recorded, events = profiled
    spans = sorted(s.dur for s in recorded if s.name == "serve.tick")
    evs = sorted((e - s) / 1e9 for s, e in events["serve.tick"])
    for span_s, event_s in zip(spans, evs):
        assert span_s <= event_s + 1e-4
        assert event_s - span_s < 0.05


# --------------------------------------------------------------------- #
# the trainer's seats
# --------------------------------------------------------------------- #
TRAINER_SPANS = ["trainer.get_train_batch", "trainer.batch_hooks",
                 "trainer.train_step", "trainer.nonfinite_sync",
                 "trainer.validation", "trainer.epoch_end_callbacks"]


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    from ray_lightning_tpu import RayStrategy, Trainer
    from ray_lightning_tpu.models import BoringModel
    tel = Telemetry(clock=time.perf_counter)
    trainer = Trainer(strategy=RayStrategy(num_workers=1), max_epochs=1,
                      limit_train_batches=3, limit_val_batches=1, seed=0,
                      default_root_dir=str(tmp_path_factory.mktemp("fit")),
                      profiler="simple", nonfinite_action="skip_batch",
                      enable_checkpointing=False, telemetry=tel)
    trainer.fit(BoringModel())
    return tel, trainer


@pytest.mark.parametrize("name", TRAINER_SPANS)
def test_armed_trainer_opens_a_span_at_every_seat(fitted, name):
    tel, _ = fitted
    got = tel.spans.spans(name)
    assert got and all(s.parent is None and s.dur >= 0 for s in got)
    per_batch = {"trainer.train_step": 3, "trainer.nonfinite_sync": 3,
                 "trainer.batch_hooks": 6,
                 "trainer.get_train_batch": 4}     # 3 batches + the end
    if name in per_batch:
        assert len(got) == per_batch[name]


def test_profiler_table_and_spans_read_one_clock(fitted):
    tel, trainer = fitted
    assert trainer.profiler.clock is tel.clock
    calls, total = trainer.profiler.records()["train_step"]
    spans = tel.spans.spans("trainer.train_step")
    assert calls == len(spans) == 3
    # the section sits inside the span, on the same clock
    assert total <= sum(s.dur for s in spans)
    assert total == pytest.approx(sum(s.dur for s in spans), rel=0.25,
                                  abs=2e-3)


# --------------------------------------------------------------------- #
# names on the device
# --------------------------------------------------------------------- #
def scope_names(lowered):
    """Every name stack the lowered program hands the compiler as
    ``op_name`` (StableHLO locations)."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))


def has_scope(names, scope):
    rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    return any(rx.search(n) for n in names)


@pytest.fixture(scope="module")
def programs(nano):
    """name -> the name stacks of that lowered program."""
    from ray_lightning_tpu import RayStrategy, Trainer
    from ray_lightning_tpu.models import GPTModule
    from ray_lightning_tpu.serve import engine as E
    dec, params = nano
    out = {}

    def serve(**kw):
        return ServeClient(dec, params, num_slots=3, prefill_len=16,
                           prefill_batch=2, **kw).engine

    for name, kw in [("decode", {}), ("decode.int8kv", {"kv_dtype": "int8"}),
                     ("decode.paged", {"page_size": 8, "num_pages": 24}),
                     ("decode.page_native", {"page_size": 8, "num_pages": 24,
                                             "page_native": True})]:
        fn, args = serve(**kw)._step_call()
        out[name] = scope_names(fn.lower(*args, steps=1))

    eng = serve()
    b, p = eng.prefill_batch, eng.prefill_len
    i32 = lambda *s: np.zeros(s, np.int32)            # noqa: E731
    out["prefill"] = scope_names(E._prefill_inject_plain.lower(
        eng.model, eng.params, eng.pool.cache, i32(b, p),
        np.ones((b,), np.int32), i32(b), np.ones((b,), bool),
        np.zeros((b, 2), np.uint32), np.ones((b,), np.float32), i32(b),
        i32(b), None))
    eng = serve(page_size=8, num_pages=24, prefill_chunk=8)
    out["chunk"] = scope_names(E._chunk_prefill_plain.lower(
        eng.model, eng.params, eng.pool.arena,
        np.array(eng.pool.page_table[0]), i32(1, 8), np.int32(0),
        np.int32(8), np.zeros((1, 2), np.uint32),
        np.ones((1,), np.float32), i32(1), i32(1), None))

    module = GPTModule(size="nano", batch_size=2, seq_len=16,
                       num_samples=4, vocab_size=128)
    trainer = Trainer(strategy=RayStrategy(num_workers=1), max_epochs=1,
                      limit_train_batches=1, limit_val_batches=0, seed=0,
                      enable_checkpointing=False, track_grad_norm=True,
                      nonfinite_action="skip_batch")
    trainer.fit(module)
    batch = next(iter(module.train_dataloader()))
    out["train_step"] = scope_names(
        trainer._train_step.lower(trainer.train_state, batch))
    return out


ATTENTION = ["attention/scores", "attention/softmax", "attention/context"]
SAMPLE = ["sample/keys", "sample/greedy", "sample/temperature",
          "sample/top_k", "sample/draw"]
SCOPES = (
    [("train_step", s) for s in ["loss", "jvp(loss)", "transpose(jvp(loss))",
                                 "xent", "grad_norm", "optimizer",
                                 "nonfinite_guard"] + ATTENTION]
    + [("prefill", s) for s in ["prefill/forward", "prefill/kv_inject",
                                "kv_write"] + SAMPLE + ATTENTION]
    + [("decode", s) for s in ["decode/forward", "decode/advance_rows",
                               "kv_write"] + SAMPLE + ATTENTION]
    + [("decode.int8kv", s) for s in ["kv_load", "kv_commit"]]
    + [("decode.paged", s) for s in ["page_gather", "page_scatter"]]
    + [("decode.page_native", s) for s in ["decode/forward", "kv_write"]
       + ATTENTION]
    + [("chunk", s) for s in ["chunk/forward", "page_gather",
                              "page_scatter", "sample/keys"]])


@pytest.mark.parametrize("program,scope", SCOPES,
                         ids=[f"{p}:{s}" for p, s in SCOPES])
def test_scope_is_in_the_programs_op_names(programs, program, scope):
    assert has_scope(programs[program], scope), (
        f"no op of the {program} program is named under {scope!r}")


@pytest.mark.parametrize("scope", ["loss", "grad_exchange", "optimizer"])
def test_explicit_allreduce_step_names_its_exchange(scope):
    import optax

    from ray_lightning_tpu import HorovodRayStrategy
    from ray_lightning_tpu.core.train_state import TrainState
    strategy = HorovodRayStrategy(num_workers=2)
    tx = optax.sgd(0.1)
    params = {"w": jnp.ones((4, 4))}

    def loss_fn(p, model_state, batch, rng):
        return jnp.sum((batch @ p["w"]) ** 2), ({}, model_state)

    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=tx.init(params), model_state={},
                       rng=jax.random.PRNGKey(0))
    step = strategy.make_train_step(
        loss_fn, tx, None, strategy.batch_sharding(), donate=False)
    names = scope_names(step.lower(state, jnp.ones((2, 4))))
    assert has_scope(names, scope)
