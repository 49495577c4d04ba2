"""The new cell rehearses at nano width, and ``correct`` comes out false
when the ``afmoe`` block is broken: each fault of ``tests/test_afmoe.py``
planted under the cell's own rehearsal limits, and the reference one
precision down in the program's place."""
import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, run

CELL = "trinity-large-preview.serve.mixedlen32"
BENCH = harness.load_json("BENCHMARK.json")


def _program_tests():
    path = os.path.join(harness.ROOT, "tests", "test_afmoe.py")
    spec = importlib.util.spec_from_file_location("afmoe_program_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


PROGRAM_TESTS = _program_tests()


def drive(capsys, seed=2147490001, trace=0):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    return line, [json.loads(l) for l in out[:-1] if l.startswith("{")]


def test_sound_run_is_correct_and_reports_every_listed_metric(capsys):
    line, notes = drive(capsys, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert all(v <= lim for v, lim in line["compared"].values())
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m["workloads"]}
    # a rehearsal has no device trace to take a roofline from
    on_device = {"serve.afmoe_step_mfu", "serve.afmoe_decode_step_roofline",
                 "serve.afmoe_chunk_roofline", "serve.peak_hbm_gb"}
    assert set(line["metrics"]) == listed - on_device
    assert line["metrics"]["serve.moe_experts_hit_share"]["value"] > 0
    margins = [n for n in notes if n.get("phase") == "router_margins"]
    assert margins and all(n["left_out"] for n in margins)
    assert margins[-1]["share_so_far"] < 0.5


@pytest.mark.parametrize("fault", PROGRAM_TESTS.PLANTED)
def test_planted_fault_reads_incorrect(capsys, monkeypatch, fault):
    PROGRAM_TESTS.plant(monkeypatch, fault, full_layers=("layer_2_attn",))
    jax.clear_caches()      # the sound run's traces must not be reused
    try:
        line, _ = drive(capsys)
    finally:
        jax.clear_caches()
    assert not line["correct"], line["compared"]
    assert any(v > lim for v, lim in line["compared"].values())
