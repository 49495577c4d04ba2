"""``sambay_work.py`` against counts made by hand for
Phi-4-mini-flash-reasoning at its published sizes, and the two new cells'
rehearsals."""
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, run, sambay_work, serve_driver

ROOT = harness.ROOT
PHI = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "phi4-mini-flash-reasoning.json")))

MLP = 3 * 2560 * 10240                                  # 78,643,200
MAMBA = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
ATTN = 2560 * 5120 + 2560 * 2560
CROSS = 2 * 2560 * 2560
GMU = 2 * 2560 * 5120
HEAD = 200064 * 2560
SCAN = 9 * (6 * 5120 * 16 + 2 * 4 * 5120)


def test_matmul_params_add_up_to_the_published_size():
    mixer = sambay_work.mixer_params(PHI)
    assert (mixer["mamba"], mixer["swa"], mixer["full"], mixer["cross"],
            mixer["gmu"]) == (MAMBA, ATTN, ATTN, CROSS, GMU)
    total = 32 * MLP + 9 * MAMBA + 9 * ATTN + 7 * CROSS + 7 * GMU + HEAD
    assert sambay_work.matmul_params(PHI) == total
    assert 3.84e9 < total < 3.86e9
    self_ = 18 * MLP + 9 * MAMBA + 9 * ATTN
    assert sambay_work.matmul_params(PHI, "self", with_head=False) == self_
    assert sambay_work.matmul_params(PHI, "cross") == total - self_


def test_decode_flops():
    total = sambay_work.matmul_params(PHI)
    # context 100: every attention layer sees 100 keys (8 windowed, 1 full
    # and 7 cross = 16 layers x 6 d S)
    assert sambay_work.decode_flops(PHI, 100) == (
        2 * total + SCAN + 6 * 2560 * 16 * 100)
    # context 800: the windowed layers stop at 512
    assert sambay_work.decode_flops(PHI, 800) == (
        2 * total + SCAN + 6 * 2560 * (8 * 512 + 8 * 800))


def test_prefill_flops():
    self_ = 18 * MLP + 9 * MAMBA + 9 * ATTN
    cross = 14 * MLP + 7 * CROSS + 7 * GMU + HEAD
    # 3 tokens: self-attention over 1 + 2 + 3 keys in 9 layers; the
    # cross-decoder and the head on the last token, 7 layers x 3 keys
    assert sambay_work.prefill_flops(PHI, 3) == (
        2 * self_ * 3 + SCAN * 3 + 6 * 2560 * 9 * 6
        + 2 * cross + 6 * 2560 * 7 * 3)
    # 600 tokens: the window clips the 8 windowed layers
    win = 512 * 513 // 2 + 88 * 512
    assert sambay_work.prefill_flops(PHI, 600) == (
        2 * self_ * 600 + SCAN * 600
        + 6 * 2560 * (8 * win + 600 * 601 // 2)
        + 2 * cross + 6 * 2560 * 7 * 600)
    # one token: what one decode over a context of 1 costs
    assert sambay_work.prefill_flops(PHI, 1) == sambay_work.decode_flops(
        PHI, 1)


def test_cache_and_step_bytes():
    row = sambay_work.cache_bytes_per_row(PHI, 2048)
    assert row == {"recurrent": 9 * 4 * (16 * 5120 + 3 * 5120),
                   "window": 8 * 2 * 20 * 64 * 2 * 512,
                   "global": 2 * 20 * 64 * 2 * 2048}
    assert 3.4e6 < row["recurrent"] < 3.6e6         # ISSUE: ~3.2 MB + tail
    assert row["window"] == 20_971_520              # 21 MB
    assert row["global"] == 10_485_760              # 5120 B a token
    short = sambay_work.cache_bytes_per_row(PHI, 100)
    assert short["window"] == 8 * 5120 * 100 and short["global"] == 512_000
    weights = 2 * sambay_work.matmul_params(PHI)
    assert sambay_work.decode_step_bytes(PHI, []) == weights
    assert sambay_work.decode_step_bytes(PHI, [100, 2048]) == (
        weights + 2 * 2 * row["recurrent"] + short["window"] + row["window"]
        + 8 * (short["global"] + row["global"]))


OPEN_STEADY = "gpt2-large.serve.open-steady"


def _rehearse(capsys, cell, trace):
    """``run.main --rehearse``; a cell kept as files without an entry
    (``OPEN_STEADY``) is given its entry in memory for the walk."""
    bench = harness.load_json("BENCHMARK.json")
    if cell not in {w["name"] for w in bench["workloads"]}:
        w = harness.load_json("benchmark", "workloads", cell + ".json")
        bench["workloads"].append(dict(
            name=cell, config=w["config"], chips=w["chips"], why=w["why"],
            traffic=cell[len(w["config"]) + 1:]))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "gpt2-large.serve.closed40" in m.get("workloads", ()):
                m["workloads"].append(cell)
    real = harness.load_json
    harness.load_json = lambda *parts: (
        bench if parts == ("BENCHMARK.json",) else real(*parts))
    try:
        rc = run.main(["--workload", cell, "--seed", str(2**31 + 12345),
                       "--seconds", "1.5", "--trace", str(trace),
                       "--rehearse"])
    finally:
        harness.load_json = real
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    return line, [json.loads(x) for x in out[:-1] if x.startswith("{")]


@pytest.mark.parametrize("trace", [0, 1])
def test_reason48_rehearsal(capsys, trace):
    line, notes = _rehearse(
        capsys, "phi4-mini-flash-reasoning.serve.reason48", trace)
    assert set(line["compared"]) == {"served_logit_gap", "sampled_loglik_z"}
    if trace:
        m = line["metrics"]
        assert {"serve.cache_mb_per_slot", "serve.batch_occupancy",
                "serve.queue_ms_p95", "serve.prefill_useful_share",
                "serve.tick_host_ms_p50"} <= set(m)
        # the nano model's row: 3 Mamba states + 2 rings of 8 + the K/V
        assert 0.0 < m["serve.cache_mb_per_slot"]["value"] < 0.1


@pytest.mark.parametrize("trace", [0, 1])
def test_open_steady_rehearsal(capsys, trace):
    line, notes = _rehearse(capsys, OPEN_STEADY, trace)
    assert set(line["compared"]) == {"served_logit_gap", "sampled_topk_gap"}
    window = next(n for n in notes if n.get("phase") == "window")
    assert window["offered"] > 0 and window["generator_lateness_s"] >= 0.0
    if trace:
        assert {"serve.queue_ms_p95", "serve.batch_occupancy"} <= set(
            line["metrics"])


REASON48 = "phi4-mini-flash-reasoning.serve.reason48"


@pytest.mark.parametrize("fault, sound", [
    (None, True), ("temperature_ignored", False), ("wrong_tokens", False)])
def test_sampled_loglik_z_holds_the_sampled_half(fault, sound):
    """The cell's sampled callers have no ``top_k``: ``sampled_loglik_z``
    under the workload file's own limit passes tokens drawn from the
    reference's distribution at the cell's temperature, and fails tokens
    drawn at temperature 1 (the request's ignored) and tokens that are
    not the model's at all (a stale state, a wrong row) — at nano width,
    on 4 requests of 64 tokens, a tenth of what a chip run judges."""
    w = harness.load_json("benchmark", "workloads", REASON48 + ".json")
    shape, seed = {**PHI, **w["rehearse"]["shape"]}, 2 ** 31 + 77
    family = serve_driver.family_of(shape)
    key = family.seed_key(seed)
    ref = family.make_reference(shape, key, "f32")
    rng = np.random.default_rng([seed, 9])
    temp = 1.0 if fault == "temperature_ignored" \
        else w["sampled"]["temperature"]
    records = []
    for _ in range(4):
        seq = rng.integers(0, shape["vocab_size"], size=8).tolist()
        for _ in range(64):
            logits = np.asarray(ref(seq, [len(seq) - 1]), np.float64)[0]
            seq.append(int(rng.integers(0, shape["vocab_size"]))
                       if fault == "wrong_tokens" else int(np.argmax(
                           logits / temp + rng.gumbel(size=logits.shape))))
        records.append(dict(prompt=seq[:8], tokens=seq[8:], greedy=False))
    got = serve_driver.served_gaps(
        types.SimpleNamespace(shape=shape, seed=seed), key, records,
        w["sampled"])
    assert got["positions"] == 256 and got["greedy"] is None
    assert (got["sampled_z"] <= w["limits"]["sampled_loglik_z"]) == sound
