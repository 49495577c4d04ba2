"""``olmo_hybrid_work.py`` against counts made by hand for Olmo-Hybrid-7B
as the benchmark runs it (16 of 32 layers, published widths), the new
cells' rehearsals, and planted faults that ``correct`` has to catch."""
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, olmo_hybrid_work, run, serve_driver

ROOT = harness.ROOT
OLMO = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "olmo-hybrid-7b.json")))
LONGDOC = "olmo-hybrid-7b.serve.longdoc16"
PREFILL_HEAVY = "gpt2-large.serve.prefill-heavy"

MLP = 3 * 3840 * 11008                                  # 126,812,160
# W_q, W_k 3840 x 2880, W_v 3840 x 5760 (one matrix of 11520 columns),
# W_g 3840 x 5760, W_a and W_b 3840 x 30 each, W_o 5760 x 3840
LINEAR = 3840 * 11520 + 3840 * 5760 + 3840 * 60 + 5760 * 3840
FULL = 4 * 3840 * 3840
HEAD = 100352 * 3840
STATE = 30 * 192 * 96                                   # a layer's, floats
CONV = 2 * 4 * 11520                                    # a token, a layer
STEP_RULE = 12 * (30 * 7 * 192 * 96 + CONV)
CHUNK_RULE = 12 * (30 * (6 * 192 * 96 + 64 * (3 * 96 + 2 * 192)) + CONV)


def test_matmul_params_add_up_to_the_cut():
    mixer = olmo_hybrid_work.mixer_params(OLMO)
    assert (mixer["linear_attention"], mixer["full_attention"]) == (
        LINEAR, FULL)
    assert 88.4e6 < LINEAR < 88.8e6 and FULL == 58_982_400   # ISSUE 32
    total = 16 * MLP + 12 * LINEAR + 4 * FULL + HEAD
    assert olmo_hybrid_work.matmul_params(OLMO) == total
    assert olmo_hybrid_work.matmul_params(OLMO, with_head=False) \
        == total - HEAD
    # with the embedding (a lookup, not a matmul): the file's 4.10 B
    assert 4.09e9 < total + HEAD < 4.11e9
    # at the published depth: 7.43 B
    assert 7.42e9 < 32 * MLP + 24 * LINEAR + 8 * FULL + 2 * HEAD < 7.44e9


def test_decode_flops():
    total = olmo_hybrid_work.matmul_params(OLMO)
    assert olmo_hybrid_work.rule_flops_per_token(OLMO, False) == STEP_RULE
    # context 1000: the 4 full layers see 1000 keys, 4 d S each
    assert olmo_hybrid_work.decode_flops(OLMO, 1000) == (
        2 * total + STEP_RULE + 4 * 3840 * 4 * 1000)


def test_piece_and_prefill_flops():
    body = olmo_hybrid_work.matmul_params(OLMO, with_head=False)
    assert olmo_hybrid_work.rule_flops_per_token(OLMO, True) == CHUNK_RULE
    # 3 tokens from 0: attention over 1 + 2 + 3 keys, the head once
    assert olmo_hybrid_work.prefill_flops(OLMO, 3) == (
        2 * body * 3 + CHUNK_RULE * 3 + 4 * 3840 * 4 * 6 + 2 * HEAD)
    # a piece of 512 at offset 1024: keys 1025 .. 1536, no head
    keys = sum(range(1025, 1537))
    assert olmo_hybrid_work.piece_flops(OLMO, 1024, 512) == (
        2 * body * 512 + CHUNK_RULE * 512 + 4 * 3840 * 4 * keys)
    # pieces add up to the prompt they make (but for the head)
    whole = olmo_hybrid_work.prefill_flops(OLMO, 1300) - 2 * HEAD
    parts = sum(olmo_hybrid_work.piece_flops(OLMO, off, n)
                for off, n in ((0, 512), (512, 512), (1024, 276)))
    assert parts == whole
    # ISSUE 32: ~6.7 GFLOP a prompt token before attention
    assert 6.6e9 < 2 * body + CHUNK_RULE < 6.9e9


def test_cache_and_step_bytes():
    row = olmo_hybrid_work.cache_bytes_per_row(OLMO, 4608)
    assert row == {"recurrent": 12 * 4 * (STATE + 3 * 11520), "window": 0,
                   "global": 4 * 2 * 3840 * 2 * 4608}
    assert 2.2e6 < 4 * STATE < 2.22e6           # ISSUE: 2.2 MB a layer
    assert 28.1e6 < row["recurrent"] < 28.3e6   # 12 x 2.35 MB
    assert row["global"] == 283_115_520         # 283 MB a slot
    short = olmo_hybrid_work.cache_bytes_per_row(OLMO, 100)
    assert short["global"] == 61_440 * 100      # 61 KB a position
    weights = 2 * olmo_hybrid_work.matmul_params(OLMO)
    assert 7.4e9 < weights < 7.5e9              # ISSUE: ~7.4 GB a step
    assert olmo_hybrid_work.decode_step_bytes(OLMO, []) == weights
    assert olmo_hybrid_work.decode_step_bytes(OLMO, [100, 4608]) == (
        weights + 2 * 2 * row["recurrent"] + short["global"]
        + row["global"])


def test_slice_pieces_reads_the_last_slice_of_the_window(monkeypatch):
    from benchmark import program_spans
    tick = lambda start, counts: program_spans.Tick(     # noqa: E731
        start, 0.05, "chunk", {}, {}, counts)
    call = lambda off, lens: {"engine.chunk.call": {      # noqa: E731
        "off": off, "lens": lens, "tokens": sum(lens)}}
    ticks = [tick(0.0, call([0], [512])), tick(5.0, {}),
             tick(6.1, call([512, 0], [512, 300])),
             tick(9.95, call([1024], [7]))]
    monkeypatch.setattr(program_spans, "window_ticks", lambda run: ticks)
    assert olmo_hybrid_work.slice_pieces({"slice_s": 4.0}) == [
        [(512, 512), (0, 300)], [(1024, 7)]]
    assert olmo_hybrid_work.slice_pieces({"slice_s": None}) is None
    monkeypatch.setattr(program_spans, "window_ticks", lambda run: None)
    assert olmo_hybrid_work.slice_pieces({"slice_s": 4.0}) is None


# ------------------------------------------------------------ rehearsals
def _rehearse(capsys, cell, trace, seed=2**31 + 12345):
    """``run.main --rehearse``; a cell kept as files without an entry
    (``PREFILL_HEAVY``) is given its entry in memory for the walk, beside
    ``closed40`` in every list that cell is in."""
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
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "3", "--trace", str(trace),
                       "--rehearse"])
    finally:
        harness.load_json = real
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    return line, [json.loads(x) for x in out[:-1] if x.startswith("{")]


@pytest.mark.parametrize("trace", [0, 1])
def test_longdoc16_rehearsal(capsys, trace):
    line, notes = _rehearse(capsys, LONGDOC, trace)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == {"served_logit_gap", "sampled_topk_gap"}
    if trace:
        m = line["metrics"]
        assert {"serve.chunk_share", "serve.chunk_useful_share",
                "serve.cache_mb_per_slot", "serve.batch_occupancy",
                "serve.tick_host_ms_p50"} <= set(m)
        # the cell reports no TTFT end to end (PERF.md section 6, PR 32),
        # so none of the metrics that move it either
        assert not {"serve.queue_ms_p95", "serve.prefill_useful_share"} \
            & set(m)
        assert 0.0 < m["serve.chunk_share"]["value"] < 100.0
        assert 0.0 < m["serve.chunk_useful_share"]["value"] <= 100.0
        # shares of a peak are the chip's to report, never a rehearsal's
        assert not {k for k in m if "mfu" in k or "roofline" in k}
        spans = next(n for n in notes if n.get("phase") == "program_spans")
        assert spans["by_action"].get("chunk", 0) > 0


@pytest.mark.parametrize("trace", [0, 1])
def test_prefill_heavy_rehearsal(capsys, trace):
    line, _ = _rehearse(capsys, PREFILL_HEAVY, trace)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == {"served_logit_gap", "sampled_topk_gap"}
    if trace:
        assert {"serve.prefill_share", "serve.prefill_useful_share",
                "serve.batch_occupancy"} <= set(line["metrics"])


def test_prefill_heavy_is_closed40_under_other_lengths():
    heavy = harness.load_json("benchmark", "workloads",
                              PREFILL_HEAVY + ".json")
    closed = harness.load_json("benchmark", "workloads",
                               "gpt2-large.serve.closed40.json")
    for key in ("config", "kind", "chips", "engine", "clients", "sampled",
                "limits", "pool_requests", "check_requests"):
        assert heavy[key] == closed[key], key
    assert heavy["prompt"] == {"median": 448, "sigma": 0.1, "min": 384,
                               "max": 512}
    assert heavy["output"] == {"median": 16, "sigma": 0.5, "min": 8,
                               "max": 32}


# -------------------------------------------------------- planted faults
def _never_decayed(real):
    def rule(q, k, v, log_alpha, beta, state, *a, **kw):
        return real(q, k, v, jnp.zeros_like(log_alpha), beta, state,
                    *a, **kw)
    return rule


def _beta_not_doubled(real):
    def rule(q, k, v, log_alpha, beta, state, *a, **kw):
        return real(q, k, v, log_alpha, 0.5 * beta, state, *a, **kw)
    return rule


@pytest.mark.parametrize("fault", [_never_decayed, _beta_not_doubled],
                         ids=["state_never_decayed", "beta_not_doubled"])
def test_planted_fault_reads_not_correct(capsys, monkeypatch, fault):
    """The timed path with a fault in the delta rule (its prefill pieces
    and its decode steps alike): tokens are still served, and the
    reference finds them wrong under the cell's own limits."""
    import jax
    from ray_lightning_tpu.models import olmo_hybrid
    for name in ("gated_delta_chunk", "gated_delta_step"):
        monkeypatch.setattr(olmo_hybrid, name,
                            fault(getattr(olmo_hybrid, name)))
    # the engine's programs are jitted on the model, which an earlier
    # test of this process may have traced sound
    jax.clear_caches()
    try:
        line, _ = _rehearse(capsys, LONGDOC, 0)
    finally:
        jax.clear_caches()
    assert line["failed"] == 0 and line["attempted"] > 0
    assert not line["correct"]
    assert any(v > lim for v, lim in line["compared"].values())


def test_control_reads_well_above_a_sound_run():
    """At nano width: the reference in fp8, in the program's place, puts
    tokens first that the float32 reference ranks well below its best."""
    w = harness.load_json("benchmark", "workloads", LONGDOC + ".json")
    shape, seed = {**OLMO, **w["rehearse"]["shape"]}, 2 ** 31 + 77
    family = serve_driver.family_of(shape)
    key = family.seed_key(seed)
    ref = family.make_reference(shape, key, "f32")
    rng = np.random.default_rng([seed, 9])
    records = []
    for _ in range(2):
        seq = rng.integers(0, shape["vocab_size"], size=40).tolist()
        for _ in range(48):
            seq.append(int(np.argmax(np.asarray(
                ref(seq, [len(seq) - 1]))[0])))
        records.append(dict(prompt=seq[:40], tokens=seq[40:], greedy=True))
    got = serve_driver.served_gaps(
        types.SimpleNamespace(shape=shape, seed=seed), key, records,
        w["sampled"], control_mode="fp8")
    assert got["greedy"] < 1e-3
    assert got["control_greedy"] > w["limits"]["served_logit_gap"]
