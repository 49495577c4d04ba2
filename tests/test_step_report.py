"""One transfer per decode dispatch (``serve/report.py``).

Every step / spec-round program packs what ``step_sync`` reads into one
flat int32 report; ``_enqueue`` starts its copy and ``step_sync`` waits
for that buffer alone. Held here, over dense / paged / page-native /
speculative engines at ``steps_per_dispatch`` 1 and 4:

(a) a dispatch's sync makes exactly ``copies`` device-to-host fetches —
    the span arg, ``serve_sync_copies_total`` and a counted stub of the
    fetch agree;
(b) the report unpacks to what the program's separate outputs hold;
(c) a fault at the one copy leaves the engine untouched, and the
    retried sync yields the sync driver's tokens.
"""
import jax
import numpy as np
import pytest

from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.serve import Request, ServeEngine
from ray_lightning_tpu.serve import engine as E
from ray_lightning_tpu.serve import spec as S
from ray_lightning_tpu.serve.report import pack_report, unpack_report

pytestmark = [pytest.mark.serve]

PROMPTS = [[5, 17, 3, 9], [9, 2, 44], [42, 7]]
BUDGETS = [9, 6, 11]       # rows retire inside different dispatches

KINDS = {
    "dense": dict(),
    "paged": dict(page_size=4),
    "page_native": dict(page_size=4, page_native=True),
    "spec": dict(spec_k=3),
}
CASES = [pytest.param(kind, spd, id=f"{kind}-spd{spd}",
                      marks=[pytest.mark.spec] if kind == "spec" else [])
         for kind in KINDS for spd in (1, 4)]


def _engine(nano, kind, spd, **kw):
    dec, params, draft, dparams = nano
    if kind == "spec":
        kw.update(draft_model=draft, draft_params=dparams)
    engine = ServeEngine(dec, params, num_slots=4, prefill_len=8,
                         steps_per_dispatch=spd, **KINDS[kind], **kw)
    engine.prefill([Request(id=i, prompt=p, max_new_tokens=n)
                    for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS))])
    return engine


def _drain(engine, step=None):
    """Run ``engine`` dry through ``step`` (default: its own
    ``step()``); ``{request id: tokens}`` of what completed."""
    done = {}
    while engine.active_count or engine.retry_pending:
        for comp in (step or engine.step)():
            done[comp.request_id] = list(comp.tokens)
    return done


@pytest.fixture
def fetches(monkeypatch):
    """A counted stub of the engine's one fetch seam."""
    seen = []
    real = E._fetch

    def counted(tree):
        seen.append(tree)
        return real(tree)

    monkeypatch.setattr(E, "_fetch", counted)
    return seen


@pytest.mark.parametrize("kind,spd", CASES)
def test_sync_makes_one_fetch_a_dispatch(serve_nano_family, fetches,
                                         kind, spd):
    """(a) one fetch a dispatch, of the report and of nothing else: the
    stub's count, the ``copies`` span arg and the registry counter
    agree, armed or not."""
    tel = Telemetry()
    engine = _engine(serve_nano_family, kind, spd, telemetry=tel)
    reports = []
    while engine.active_count:
        pending = engine.step_enqueue()
        reports.append(pending.report)
        engine.step_sync(pending)
    engine.shutdown()
    assert len(fetches) == len(reports) == engine.steps
    assert all(got is rep for got, rep in zip(fetches, reports))
    syncs = tel.spans.spans("engine.step.sync")
    assert [s.args["copies"] for s in syncs] == [1] * engine.steps
    assert tel.metrics.snapshot()["serve_sync_copies_total"] \
        == engine.steps
    # unarmed: the same single fetch, no span and no counter to fill
    del fetches[:]
    engine = _engine(serve_nano_family, kind, spd)
    _drain(engine)
    assert len(fetches) == engine.steps
    engine.shutdown()


@pytest.mark.parametrize("kind,spd", CASES)
def test_report_unpacks_to_the_programs_outputs(serve_nano_family,
                                                monkeypatch, kind, spd):
    """(b) the same program traced with its parts handed back unpacked
    (``pack_report`` swapped for the identity) returns, part for part,
    what the report unpacks to; the carry the program returns beside
    the report is the report's own."""
    engine = _engine(serve_nano_family, kind, spd)
    spec = kind == "spec"
    fn, args = engine._spec_call() if spec else engine._step_call()
    kw = dict(k=engine.spec.k, rounds=spd) if spec else dict(steps=spd)
    width = engine.spec.k + 1 if spec else None

    def run(impl):
        # a fresh function: jit caches a trace by function and arguments
        return jax.jit(lambda *a: impl(*a, **kw), static_argnums=(
            (0, 1) if spec else (0,)))(*args)

    *carry, report = run(fn.__wrapped__)[-6:]
    monkeypatch.setattr(E, "pack_report", lambda *parts: parts)
    monkeypatch.setattr(S, "pack_report", lambda *parts: parts)
    *_, parts = run(fn.__wrapped__)
    assert len(parts) == (9 if spec else 7)
    assert report.dtype == np.int32 and report.ndim == 1
    assert report.size == sum(np.size(p) for p in parts) \
        == (5 + spd * ((width or 1) + 1 + 2 * spec)) * engine.num_slots
    got = unpack_report(np.array(report), engine.num_slots, spd, width)
    assert len(got) == 9 and (got.accepted is not None) == spec
    for name, mine, theirs in zip(got._fields, got, parts):
        theirs = np.asarray(theirs)
        assert mine.shape == theirs.shape and mine.dtype == theirs.dtype, \
            name
        assert mine.flags.writeable, name
        np.testing.assert_array_equal(mine, theirs, err_msg=name)
    for mine, theirs in zip(got[:5], carry):
        np.testing.assert_array_equal(mine, np.asarray(theirs))
    assert got.active.any() and (got.emitted >= 0).any()
    engine.shutdown()


@pytest.mark.parametrize("kind,spd", CASES)
def test_fault_at_the_one_copy_is_retryable(serve_nano_family,
                                            monkeypatch, kind, spd):
    """(c) the one fetch fails once, mid-stream: the engine's synced
    state is untouched, the handle syncs on the retry, and the streams
    are the sync driver's."""
    ref_engine = _engine(serve_nano_family, kind, spd)
    ref = _drain(ref_engine)
    ref_engine.shutdown()
    assert sorted(ref) == [0, 1, 2]

    real = E._fetch
    state = {"calls": 0}

    def flaky(tree):
        state["calls"] += 1
        if state["calls"] == 2:
            raise RuntimeError("synthetic device error at the host copy")
        return real(tree)

    monkeypatch.setattr(E, "_fetch", flaky)
    engine = _engine(serve_nano_family, kind, spd)
    crashes = []

    def step():
        before = (engine._synced_dispatch, engine.tokens_generated,
                  engine._cur.copy(), engine._pos.copy(),
                  engine._active.copy(), engine._remaining.copy(),
                  engine._stepno.copy(),
                  {s: list(t) for s, t in engine._tokens.items()})
        try:
            return engine.step()
        except RuntimeError as exc:
            assert "synthetic" in str(exc)
            crashes.append(engine.steps)
            after = (engine._synced_dispatch, engine.tokens_generated,
                     engine._cur, engine._pos, engine._active,
                     engine._remaining, engine._stepno, engine._tokens)
            assert before[:2] == after[:2] and before[7] == after[7]
            for b, a in zip(before[2:7], after[2:7]):
                np.testing.assert_array_equal(b, a)
            assert engine.retry_pending
            return []

    out = _drain(engine, step)
    engine.shutdown()
    assert crashes == [2] and out == ref


@pytest.mark.parametrize("spec_width", [None, 4])
@pytest.mark.parametrize("rounds", [1, 4])
def test_pack_unpack_round_trip(rounds, spec_width):
    """The shape rule alone: ``5 x B + rounds x B x (...)``, booleans as
    0 / 1, and a buffer of any other size refused."""
    rng = np.random.default_rng(rounds * 7 + (spec_width or 0))
    B = 5
    ints = lambda *shape: rng.integers(-1, 100, shape).astype(np.int32)
    flags = lambda *shape: rng.integers(0, 2, shape).astype(bool)
    emitted = ints(rounds, B) if spec_width is None \
        else ints(rounds, B, spec_width)
    parts = [ints(B, 1), ints(B, 1), flags(B), ints(B), ints(B), emitted,
             flags(rounds, B)]
    if spec_width is not None:
        parts += [ints(rounds, B), ints(rounds, B)]
    buf = np.array(pack_report(*parts))
    got = unpack_report(buf, B, rounds, spec_width)
    for mine, theirs in zip(got, parts):
        assert mine.dtype == theirs.dtype
        np.testing.assert_array_equal(mine, theirs)
    with pytest.raises(ValueError, match="step report"):
        unpack_report(buf[:-1], B, rounds, spec_width)
    with pytest.raises(ValueError, match="step report"):
        unpack_report(buf, B + 1, rounds, spec_width)
