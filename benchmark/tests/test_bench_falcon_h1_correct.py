"""The new cell rehearses at nano width, and ``correct`` comes out false
when the ``falcon_h1`` block is broken: each fault of
``tests/test_falcon_h1.py`` (a multiplier dropped, the gated norm over
the whole width, B and C read by the wrong heads) planted under the
cell's own rehearsal limits, and the reference one precision down in
the program's place.

One fault is the CPU tests' alone: ``lm_head_multiplier`` dropped scales
every logit of a row by one number, which moves no greedy choice and no
rank — a judge that reads which tokens were served cannot see it, and
``tests/test_falcon_h1.py`` compares the logits themselves."""
import importlib.util
import json
import os
import types

import jax
import numpy as np
import pytest

from benchmark import harness, run, serve_driver

CELL = "falcon-h1-34b-instruct.serve.chat48"
BENCH = harness.load_json("BENCHMARK.json")


def _program_tests():
    path = os.path.join(harness.ROOT, "tests", "test_falcon_h1.py")
    spec = importlib.util.spec_from_file_location("falcon_h1_program_tests",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


PROGRAM_TESTS = _program_tests()


def drive(capsys, seed=3000000017, trace=0):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    return line, [json.loads(x) for x in out[:-1] if x.startswith("{")]


def test_sound_run_is_correct_and_reports_every_listed_metric(capsys):
    line, notes = drive(capsys, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert all(v <= lim for v, lim in line["compared"].values())
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m["workloads"]}
    # a rehearsal has no device trace to take a roofline or a peak from
    on_device = {"serve.falcon_h1_step_mfu",
                 "serve.falcon_h1_decode_step_roofline",
                 "serve.falcon_h1_chunk_roofline", "serve.peak_hbm_gb"}
    assert set(line["metrics"]) == listed - on_device
    # the cell reports no TTFT end to end, so nothing that moves it
    assert "serve_ttft_p95_ms" not in line["metrics"]
    assert 0.0 < line["metrics"]["serve.chunk_share"]["value"] < 100.0
    spans = next(n for n in notes if n.get("phase") == "program_spans")
    assert spans["by_action"].get("chunk", 0) > 0


@pytest.mark.parametrize("fault", [f for f in PROGRAM_TESTS.PLANTED
                                   if f != "lm_head_multiplier"])
def test_planted_fault_reads_incorrect(capsys, monkeypatch, fault):
    PROGRAM_TESTS.plant(monkeypatch, fault)
    jax.clear_caches()      # the sound run's traces must not be reused
    try:
        line, _ = drive(capsys)
    finally:
        jax.clear_caches()
    assert line["failed"] == 0 and line["attempted"] > 0
    assert not line["correct"], line["compared"]
    assert any(v > lim for v, lim in line["compared"].values())


def test_the_control_reads_above_a_sound_run():
    """At nano width, greedy continuations of the float32 reference read
    0 against it; the reference one precision down puts tokens first
    that it ranks well below its best. (The bf16-state witness moves no
    token at this width: the CPU tests see it on the logits.)"""
    w = harness.load_json("benchmark", "workloads", CELL + ".json")
    shape = {**harness.load_json("benchmark", "configs",
                                 "falcon-h1-34b-instruct.json"),
             **w["rehearse"]["shape"]}
    seed = 3000000029
    family = serve_driver.family_of(shape)
    key = family.seed_key(seed)
    ref = family.make_reference(shape, key, "f32")
    rng = np.random.default_rng([seed, 9])
    records = []
    for _ in range(2):
        seq = rng.integers(0, shape["vocab_size"], size=40).tolist()
        for _ in range(40):
            seq.append(int(np.argmax(np.asarray(
                ref(seq, [len(seq) - 1]))[0])))
        records.append(dict(prompt=seq[:40], tokens=seq[40:], greedy=True))
    got = serve_driver.served_gaps(
        types.SimpleNamespace(shape=shape, seed=seed), key, records,
        w["sampled"], control_mode="fp8")
    assert got["greedy"] < 1e-4
    assert got["control_greedy"] > \
        w["rehearse"]["workload"]["limits"]["served_logit_gap"]
