"""The reducer on traces built by hand."""
import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Ev


def test_busy_union_counts_overlap_once():
    ops = [Ev(0, 10, "a"), Ev(5, 15, "b"), Ev(20, 30, "c"), Ev(22, 25, "d")]
    assert tr.busy_ns(ops) == 25
    assert tr.merge((e.start, e.end) for e in ops) == [(0, 15), (20, 30)]


def test_clip_cuts_events_to_the_window():
    ops = [Ev(0, 10, "a"), Ev(8, 30, "b"), Ev(40, 50, "c")]
    got = tr.clip(ops, 5, 20)
    assert [(e.start, e.end) for e in got] == [(5, 10), (8, 20)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    ops = [Ev(0, 100, "while"), Ev(10, 30, "fusion"), Ev(30, 60, "dot"),
           Ev(200, 210, "copy")]
    by = tr.time_by_name(ops)
    assert by["while"] == pytest.approx(50e-9)
    assert by["dot"] == pytest.approx(30e-9)
    leaves = {ev.name for ev, _, leaf in tr.self_times(ops) if leaf}
    assert leaves == {"fusion", "dot", "copy"}


def test_gap_between_two_events_of_one_program():
    mods = [Ev(0, 10, "jit_step(1)"), Ev(14, 24, "jit_step(1)"),
            Ev(25, 30, "jit_prefill(2)"), Ev(33, 40, "jit_step(1)"),
            Ev(41, 50, "jit_step(1)")]
    # the pair around the prefill is skipped: the device was not idle there
    assert tr.program_gaps(mods, r"jit_step") == pytest.approx(
        [4e-9, 1e-9])


def test_collective_half_covered_by_compute():
    ops = [Ev(0, 100, "all-gather.1"), Ev(50, 120, "fusion.2"),
           Ev(200, 220, "all-reduce.3")]
    exposed, total = tr.collective_exposed_ns(ops, r"all-gather|all-reduce")
    assert total == 120
    assert exposed == 70          # 50 of the gather + the whole reduce


def test_collective_inside_a_while_is_not_hidden_by_it():
    ops = [Ev(0, 100, "while.1"), Ev(10, 40, "all-gather.2"),
           Ev(40, 90, "fusion.3")]
    exposed, total = tr.collective_exposed_ns(ops, r"all-gather")
    assert (exposed, total) == (30, 30)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    ops = [Ev(0, 10, "a"), Ev(30, 40, "b"), Ev(45, 50, "c")]
    host = [Ev(0, 100, "harness.tick"), Ev(40, 46, "harness.submit")]
    gaps = tr.idle_gaps(ops, host, 0, 60, top=3)
    assert gaps[0] == ("harness.tick", pytest.approx(20e-9))
    assert gaps[1] == ("harness.tick", pytest.approx(10e-9))
    assert gaps[2] == ("harness.submit", pytest.approx(5e-9))


def test_summary_reports_the_worst_device():
    trace = tr.Trace(
        devices=[tr.DeviceTrace("d0", [Ev(0, 80, "x")], []),
                 tr.DeviceTrace("d1", [Ev(0, 50, "x")], [])],
        host=[], window=(0, 100))
    s = tr.summarize(trace)
    assert s["busy_s"] == pytest.approx(65e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["idle_share_worst"] == pytest.approx(0.5)
    assert s["idle_gaps"] == [("unattributed", pytest.approx(50e-9))]
