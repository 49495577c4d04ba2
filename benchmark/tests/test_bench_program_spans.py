"""The in-process reader of the program's spans (``program_spans.py``) and
the five metric files over it, on a handle built by hand with a clock the
test advances; then the serve cell's traced rehearsal prints all five."""
import json

import pytest

from benchmark import harness, program_spans, run

SERVE = "gpt2-large.serve.closed40"
NEW = ["serve.tick_host_ms_p50", "serve.sync_wait_share",
       "serve.prefill_share", "serve.prefill_useful_share",
       "serve.batch_occupancy"]


class Clock:
    t = 100.0          # raw readings: nothing below may assume a zero

    def __call__(self):
        return self.t


def tick(tel, clk, action, children, retired=()):
    """One root tick: the children in order, each ``(name, seconds,
    args)``, then the finalize span with the ids the tick retired; 2 ms of
    the caller's own time follow."""
    with tel.span("serve.tick") as root:
        root["action"] = action
        for name, seconds, args in children:
            with tel.span(name, **args):
                clk.t += seconds
        with tel.span("serve.finalize", ids=list(retired)):
            clk.t += 0.001
    clk.t += 0.002


def step(sync, active, slots=4):
    return [("engine.step.build", 0.002, {}),
            ("engine.step.call", 0.003, {"active": active, "slots": slots}),
            ("engine.step.sync", sync, {}),
            ("engine.step.retire", 0.004, {})]


PREFILL = [("engine.prefill.build", 0.010, {}),
           ("engine.prefill.call", 0.020,
            {"rows": 2, "tokens": 14, "program_tokens": 32}),
           ("engine.prefill.sync", 0.050, {}),
           ("engine.prefill.activate", 0.001, {})]


@pytest.fixture
def facts():
    """Two callers: set-up ends with the tick that retires id 1; the
    window then holds a prefill tick and two step ticks (0.082 + 0.050 +
    0.070 s, 2 ms of caller time after each); a last tick starts after
    ``wall_s`` and is outside."""
    from ray_lightning_tpu.obs import Telemetry
    clk = Clock()
    tel = Telemetry(clock=clk)
    tick(tel, clk, "prefill", PREFILL)
    tick(tel, clk, "step", step(0.030, 2), retired=[0])
    tick(tel, clk, "step", step(0.030, 1), retired=[1])   # window opens
    tick(tel, clk, "prefill", PREFILL)
    tick(tel, clk, "step", step(0.040, 3), retired=[5])
    tick(tel, clk, "step", step(0.060, 4))
    tick(tel, clk, "step", step(0.500, 4))                # outside
    return {"wall_s": 0.2, "workload": {"clients": 2}}


def metric(name, facts):
    return harness.load_module("metrics", name).compute(facts)


def test_window_is_cut_at_the_first_generations_last_retirement(
        facts, capsys):
    ticks = program_spans.window_ticks(facts)
    assert [t.action for t in ticks] == ["prefill", "step", "step"]
    assert [round(t.dur, 6) for t in ticks] == [0.082, 0.050, 0.070]
    assert ticks[0].counts["engine.prefill.call"]["tokens"] == 14
    noted = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert noted["phase"] == "program_spans" and noted["ticks"] == 3
    assert noted["by_action"] == {"prefill": 1, "step": 2}
    program_spans.window_ticks(facts)          # computed once, noted once
    assert capsys.readouterr().out == ""


def test_self_times_of_a_ticks_tree_sum_to_the_tick(facts):
    for t in program_spans.window_ticks(facts):
        assert sum(t.self_by_name.values()) == pytest.approx(t.dur)
        assert t.self_by_name["serve.tick"] == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("name,expected", [
    # step ticks less their blocking copies: 0.010 and 0.010 s
    ("serve.tick_host_ms_p50", 10.0),
    # 0.050 + 0.040 + 0.060 s blocked of 0.2 s
    ("serve.sync_wait_share", 75.0),
    # one prefill tick of 0.082 s
    ("serve.prefill_share", 41.0),
    # 14 valid of 32 run over
    ("serve.prefill_useful_share", 43.75),
    # 3 / 4 and 4 / 4 rows
    ("serve.batch_occupancy", 87.5),
])
def test_metric_reads_the_hand_built_window(facts, name, expected):
    assert metric(name, facts) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_metric_is_silent_without_a_handle(monkeypatch, name):
    from ray_lightning_tpu import obs
    monkeypatch.setattr(obs, "_LAST", None)
    assert metric(name, {"wall_s": 1.0, "workload": {"clients": 2}}) is None


@pytest.mark.parametrize("name", NEW)
def test_metric_is_silent_on_a_program_without_the_reader(monkeypatch,
                                                          name):
    """The parent commit has no ``last_telemetry``: nothing is raised."""
    from ray_lightning_tpu import obs
    monkeypatch.delattr(obs, "last_telemetry")
    assert metric(name, {"wall_s": 1.0, "workload": {"clients": 2}}) is None


def test_traced_rehearsal_of_the_serve_cell_prints_all_five(capsys):
    rc = run.main(["--workload", SERVE, "--seed", "7", "--seconds", "1",
                   "--trace", "1", "--rehearse"])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    noted = [ln for ln in lines if ln.get("phase") == "program_spans"]
    assert len(noted) == 1 and noted[0]["ticks"] > 20
    assert noted[0]["spans_dropped"] == 0
    result = lines[-1]
    assert result["correct"] and set(NEW) <= set(result["metrics"])
    got = {n: result["metrics"][n]["value"] for n in NEW}
    assert 0 < got["serve.prefill_useful_share"] <= 100
    assert 0 < got["serve.batch_occupancy"] <= 100
    assert 0 < got["serve.sync_wait_share"] + got["serve.prefill_share"]
    assert got["serve.tick_host_ms_p50"] > 0
