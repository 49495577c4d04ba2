"""``deepseek_v2_work.py`` against counts made by hand for DeepSeek-V2 as
the benchmark runs it (5 of 60 layers, published widths, routing group 0
of 8 held, an eighth of the vocabulary), the figures the configuration
file and the cell's sizing state; and the new cell rehearsed at nano
width, where ``correct`` comes out false when the block is broken."""
import json

import jax
import numpy as np
import pytest

from benchmark import deepseek_v2_work as work
from benchmark import harness, run, serve_driver

SHAPE = harness.load_json("benchmark", "configs", "deepseek-v2.json")
CELL = "deepseek-v2.serve.ragdoc40"
BENCH = harness.load_json("BENCHMARK.json")

# W_dq 5120 x 1536, W_uq 1536 x 128 (128 + 64), W_dkv 5120 x (512 + 64),
# W_o 128 x 128 x 5120; W_uk and W_uv 512 x 128 x 128 each
PROJ = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 128 * 128 * 5120
UP = 512 * 128 * 256
DENSE = 3 * 5120 * 12288
EXPERT = 3 * 5120 * 1536
SHARED = 3 * 5120 * 3072
ROUTER = 5120 * 160
HEAD = 5120 * 12800
FIXED = 5 * (PROJ + UP) + DENSE + 4 * (ROUTER + SHARED) + HEAD
ABSORBED = 2 * 128 * (576 + 512)          # a live position and layer
EXPANDED = 2 * 128 * 192 + 2 * 128 * 128   # a key and query and layer


def test_parameters_by_hand():
    assert work.projection_params(SHAPE) == PROJ == 132_448_256
    assert work.attention_params(SHAPE) == PROJ + UP == 149_225_472
    assert work.dense_mlp_params(SHAPE) == DENSE
    assert work.expert_params(SHAPE) == EXPERT == 23_592_960
    assert work.shared_params(SHAPE) == SHARED
    assert work.router_params(SHAPE) == ROUTER
    assert work.fixed_params(SHAPE) == FIXED == 1_192_427_520
    # the configuration file's 3.145 B, 6.29 GB in bfloat16: the gains
    # are two a layer, the two latents' norms and the final norm
    gains = 5 * (2 * 5120 + 1536 + 512) + 5120
    total = FIXED + 4 * 20 * EXPERT + HEAD + gains
    assert work.total_params(SHAPE) == total == 3_145_466_880
    assert round(2 * total / 1e7) == 629
    # a layer as the issue counts it: the dense one 338.0 M, an expert
    # layer outside its routed experts 197.2 M
    assert round((PROJ + UP + DENSE) / 1e5) == 3380
    assert round((PROJ + UP + SHARED + ROUTER) / 1e5) == 1972
    assert work.experts_per_token(SHAPE) == 6 * 20 / 160 == 0.75


def test_flops_by_hand():
    assert work.absorbed_flops_per_position(SHAPE) == ABSORBED == 278_528
    assert work.expanded_flops_per_key(SHAPE) == EXPANDED == 81_920
    token = 2 * (FIXED - 5 * UP + 4 * 0.75 * EXPERT)
    assert work.token_flops(SHAPE) == token
    # a decode step's token: the weights, W_uk / W_uv folded into the
    # query and after the weighted sum, the absorbed read of 7000 positions
    assert work.decode_flops(SHAPE, 7000) \
        == token + 2 * 5 * UP + 5 * ABSORBED * 7000
    # a piece of 2048 at offset 4096: its 6144 positions expanded once,
    # keys 4097 .. 6144, no head
    keys = sum(range(4097, 6145))
    assert work.piece_flops(SHAPE, 4096, 2048) == (
        2048 * (token - 2 * HEAD)
        + 5 * (2 * UP * 6144 + EXPANDED * keys))
    # a whole prompt is one piece from 0, and the head
    assert work.prefill_flops(SHAPE, 1500) \
        == work.piece_flops(SHAPE, 0, 1500) + 2 * HEAD


def test_cache_bytes_by_hand():
    # 512 + 64 bf16 values a position and layer: 1152 B (heads' K and V
    # would take 2 x 128 x 128 x 2 = 64 KB a position and layer)
    assert work.cache_bytes_per_position(SHAPE) == 1152
    assert work.live_cache_bytes(SHAPE, 16384) == 5 * 1152 * 16384
    # a slot of 16384 positions: 94.4 MB; 40 of them 3.77 GB; with the
    # weights 10.07 GB of arguments
    slot = work.live_cache_bytes(SHAPE, 16384)
    assert round(slot / 1e5) == 944
    assert round(40 * slot / 1e7) == 377
    assert round((40 * slot + 2 * work.total_params(SHAPE)) / 1e7) == 1007


def test_decode_step_bytes_and_operations_by_hand():
    """37 rows at 7500 positions and 64 of the 80 held (layer, expert)
    pairs hit: 2.38 GB of weights outside the routed experts, 3.02 GB of
    the hit experts, 1.60 GB of latent — and 0.39 TFLOP of absorbed
    attention, which is about at the ridge (bytes / 819 GB/s and
    operations / 197 TFLOP/s are both ~2 ms)."""
    got = work.decode_step_bytes(SHAPE, [7500] * 37, 64)
    assert got == 2 * (FIXED + 64 * EXPERT) + 37 * 5 * 1152 * 7500
    assert round(2 * FIXED / 1e7) == 238
    assert round(2 * 64 * EXPERT / 1e7) == 302
    latent = 37 * 5 * 1152 * 7500
    assert round(latent / 1e7) == 160
    attend = 37 * 5 * ABSORBED * 7500
    assert round(attend / 1e10) == 39
    assert 0.9 < (latent / 819e9) / (attend / 197e12) < 1.1
    # the kernel alone, one layer: the same latent and its queries
    assert work.mla_decode_flops(SHAPE, [7500] * 37) == attend / 5
    assert work.mla_decode_bytes(SHAPE, [7500] * 37) \
        == latent / 5 + 37 * 128 * (576 * 2 + 512 * 4)


def test_prefill_kernel_counts_by_hand():
    """A dispatch of two rows: 2048 tokens at offset 4096 and 1500 at 0.
    The first expands 6144 positions and scores keys 4097 .. 6144 a
    query; the second 1500 positions, keys 1 .. 1500."""
    pieces = [(4096, 2048), (0, 1500)]
    expand = 2 * UP * (6144 + 1500)
    attend = EXPANDED * (sum(range(4097, 6145)) + sum(range(1, 1501)))
    assert work.mla_prefill_flops(SHAPE, pieces) == expand + attend
    # ~1.2 TFLOP a layer: at the bf16 peak ~6 ms, while its bytes are
    # 9 MB of latent, 0.41 GB of queries and float32 outputs and 34 MB
    # of W_uk / W_uv, ~0.55 ms at the memory's speed: compute bound
    assert round((expand + attend) / 1e11) == 12
    got = work.mla_prefill_bytes(SHAPE, pieces)
    assert got == 2 * UP + 1152 * 7644 \
        + 3548 * 128 * (192 * 2 + 128 * 4)
    assert round(got / 1e7) == 45
    assert (expand + attend) / 197e12 > 10 * got / 819e9


# ------------------------------------------------------------ rehearsal
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
    on_device = {"serve.deepseek_v2_step_mfu",
                 "serve.deepseek_v2_decode_step_roofline",
                 "serve.deepseek_v2_chunk_roofline", "mla_decode_roofline",
                 "mla_prefill_roofline", "serve.peak_hbm_gb"}
    assert set(line["metrics"]) == listed - on_device
    # the cell reports no TTFT end to end, so nothing that moves it
    assert "serve_ttft_p95_ms" not in line["metrics"]
    assert 0.0 < line["metrics"]["serve.chunk_share"]["value"] < 100.0
    assert line["metrics"]["serve.moe_experts_hit_share"]["value"] > 0.0
    spans = next(n for n in notes if n.get("phase") == "program_spans")
    assert spans["by_action"].get("chunk", 0) > 0
    margins = [n for n in notes if n.get("phase") == "router_margins"]
    assert margins and all(n["left_out"] for n in margins)


def _no_group_limit(monkeypatch):
    from ray_lightning_tpu.models import deepseek_v2 as program
    held = program.held_experts
    monkeypatch.setattr(program, "held_experts", lambda *a, **k: held(
        *a, **{**k, "n_group": 1, "topk_group": 1}))


def _no_mscale(monkeypatch):
    from ray_lightning_tpu.models import deepseek_v2 as program
    monkeypatch.setattr(program.DeepseekV2Config, "softmax_scale",
                        property(lambda cfg: (cfg.qk_nope_head_dim
                                              + cfg.qk_rope_head_dim)
                                 ** -0.5))


@pytest.mark.parametrize("plant", [_no_group_limit, _no_mscale],
                         ids=["no_group_limit", "no_mscale"])
def test_planted_fault_reads_incorrect(capsys, monkeypatch, plant):
    """The router choosing among all 160 (no group limit), and the softmax
    scale without YaRN's ``mscale^2`` (the ``transformers`` port's
    omission): each makes ``correct`` false under the rehearsal limits."""
    plant(monkeypatch)
    jax.clear_caches()      # the sound run's traces must not be reused
    try:
        line, _ = drive(capsys)
    finally:
        jax.clear_caches()
    assert line["failed"] == 0 and line["attempted"] > 0
    assert not line["correct"], line["compared"]


def test_the_control_reads_above_a_sound_run():
    """At nano width, greedy continuations of the float32 reference read
    0 against it; the reference one precision down puts tokens first that
    it ranks well below its best."""
    w = harness.load_json("benchmark", "workloads", CELL + ".json")
    shape = {**SHAPE, **w["rehearse"]["shape"]}
    seed = 3000000029
    family = serve_driver.family_of(shape)
    key = family.seed_key(seed)
    ref = family.make_reference(shape, key, "f32")
    rng = np.random.default_rng([seed, 9])
    records = []
    for _ in range(2):
        seq = rng.integers(0, shape["vocab_size"], size=40).tolist()
        for _ in range(30):
            seq.append(int(np.argmax(np.asarray(
                ref(seq, [len(seq) - 1]))[0])))
        records.append(dict(prompt=seq[:40], tokens=seq[40:], greedy=True))
    got = serve_driver.served_gaps(
        type("Ctx", (), dict(shape=shape, seed=seed)), key, records,
        w["sampled"], control_mode="fp8")
    assert got["greedy"] < 1e-4
    assert got["control_greedy"] > \
        w["rehearse"]["workload"]["limits"]["served_logit_gap"]
