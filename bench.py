"""Benchmark: training throughput per chip, with honesty guards.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras"}.
The primary metric stays samples/sec/chip on the MNIST classifier train step
(BASELINE.json "metric"); extras carry BERT-base and GPT-2-small (the
flagship) numbers with MFU, the pallas-flash long-seq comparison, the
virtual-mesh scaling proxy, real-chip batch scaling, and the native
data-pipeline measurement.

Measurement design (the round-1 bench silently clamped a collapsed
differential to 1e-9 s and recorded 2e14 samples/s — see VERDICT.md):

- Differential timing: ``rate = extra_samples / (t(n_large) - t(n_small))``
  where ``t(n)`` runs ``n`` chained train steps inside one compiled
  ``fori_loop`` and ends with a host *fetch* of a value derived from the
  final state. The chained state makes every timed call unique (nothing is
  cacheable); the fetch defeats async dispatch. This removes the fixed
  per-dispatch cost from the measurement.
- Loud failure: the differential must be positive and exceed a floor far
  above the clock resolution. If not, ``n_large`` doubles (bounded) and the
  measurement retries; when retries run out a ``MeasurementError`` with a
  diagnostic is raised — no number is ever printed from a collapsed timing.
- Physical sanity: measured FLOP/s is bounded against the chip's peak
  (device-kind table below); exceeding ~1.5x peak means the timing is wrong
  and the bench fails. MFU is reported alongside samples/s.
- ``BENCH_REFERENCE.json`` is written on the first valid run so
  ``vs_baseline`` tracks progress across rounds.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time
from functools import partial

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE_FILE = os.path.join(HERE, "BENCH_REFERENCE.json")

# Peak bf16 matmul FLOP/s per chip by device kind (public spec sheets /
# jax-ml.github.io/scaling-book). Used for the sanity bound and MFU.
PEAK_BF16_FLOPS = {
    "v2": 46e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
    "trillium": 918e12,
}

# HBM bandwidth (bytes/s) per chip by device kind, same public sources as
# PEAK_BF16_FLOPS. Used for the decode honesty floor — must track the
# generation actually running, or a faster chip (v6e ~1.6 TB/s) would
# legitimately beat a v5e-calibrated floor and be misflagged.
HBM_BANDWIDTH = {
    "v2": 700e9,
    "v3": 900e9,
    "v4": 1228e9,
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
    "trillium": 1640e9,
}


class MeasurementError(RuntimeError):
    """A throughput measurement that cannot be trusted. Never clamped."""


def _fetch_scalar(tree) -> float:
    """Host-fetch one element of ``tree``: ends a timed section on data
    derived from its output, so the clock stops only after the device
    has finished (jax returns from a dispatch before the work is done).
    Every timed section ends with this helper — don't hand-roll it.
    """
    import jax

    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(jax.device_get(leaf.ravel()[0]))


def _lookup_by_kind(table: dict, device) -> float:
    """Single device-kind → spec-table matcher, shared by the FLOP and
    HBM-bandwidth bounds so new generations get added in one shape. A
    device that is not in the table is an error, never a default: a
    bound or an MFU against an assumed peak would be a made-up number."""
    kind = getattr(device, "device_kind", "").lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise MeasurementError(
        f"unknown device_kind {device.device_kind!r} (platform "
        f"{device.platform!r}): no published peak to measure against — "
        f"known kinds: {sorted(table)}")


def _chip_peak_flops(device) -> float:
    return _lookup_by_kind(PEAK_BF16_FLOPS, device)


def _hbm_bandwidth(device) -> float:
    return _lookup_by_kind(HBM_BANDWIDTH, device)


def _device_fields(device=None) -> dict:
    """The device a number was taken on, as jax reports it — every child
    process stamps its own into the JSON it prints."""
    import jax

    device = device or jax.devices()[0]
    return {"platform": device.platform, "device_kind": device.device_kind}


def _step_flops(step, state, batch) -> float | None:
    """Per-step FLOPs from XLA's compiled cost analysis (None when the
    backend's analysis reports no flops).

    Caveat: loop bodies (``lax.scan``/``fori_loop``) are counted ONCE, so
    scanned-layer transformers undercount by ~n_layers — those benches pass
    an analytic count instead (``_transformer_train_flops``).
    """
    cost = step.lower(state, batch).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    return flops if flops > 0 else None


def _transformer_train_flops(state, tokens_per_step: int) -> float:
    """Standard analytic train-step FLOPs: 6 * params * tokens
    (fwd 2NT + bwd 4NT; attention O(T^2) term negligible at short seq)."""
    import jax

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    return 6.0 * n_params * tokens_per_step


def _assemble_step(strategy, model, tx, loss_fn, init_batch, batch):
    """Shared builder tail: sharded init + compiled train step + batch
    placement (identical across the MNIST/BERT/GPT-2 benches)."""
    import jax

    from ray_lightning_tpu.core.train_state import TrainState

    def init_fn(rng):
        params = model.init(rng, init_batch)["params"]
        return TrainState.create(params, tx.init(params))

    state_shardings = jax.tree_util.tree_map(
        lambda _: strategy.scalar_sharding(),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)))
    state = jax.jit(init_fn, out_shardings=state_shardings)(
        jax.random.PRNGKey(0))
    step = strategy.make_train_step(loss_fn, tx, state_shardings,
                                    strategy.batch_sharding())
    batch = jax.device_put(batch, strategy.batch_sharding())
    return step, state, batch


def _build_anchor_step():
    """FROZEN cross-round anchor workload — raw jax, zero framework code.

    DO NOT MODIFY (recorded round 5): the headline's cross-session
    comparability rests on this exact computation. An absolute samples/s
    number inherits whatever run-to-run jitter the session has; this
    anchor rides the *same* session as the headline measurement, so the
    ratio headline/anchor cancels the shared jitter; ``vs_baseline``
    compares anchored ratios across rounds instead of raw rates.

    Same shapes as the headline (784→128→256→10 MLP, batch 8192) so the
    two workloads stress the chip and the host identically; plain
    handwritten SGD so no library change can drift it.
    """
    import jax
    import jax.numpy as jnp
    from typing import NamedTuple

    class AnchorState(NamedTuple):
        params: tuple

    rng = np.random.default_rng(1234)
    dims = [784, 128, 256, 10]
    params = tuple(
        (jnp.asarray(rng.standard_normal((i, o)) * (1.0 / math.sqrt(i)),
                     jnp.float32), jnp.zeros((o,), jnp.float32))
        for i, o in zip(dims[:-1], dims[1:]))
    x = jnp.asarray(rng.standard_normal((8192, 784)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(8192,)), jnp.int32)

    def loss_fn(params, batch):
        bx, by = batch
        h = bx
        for w, b in params[:-1]:
            h = jnp.maximum(h @ w + b, 0.0)
        w, b = params[-1]
        logits = h @ w + b
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.mean(logz - jnp.take_along_axis(
            logits, by[:, None], axis=-1)[:, 0])

    def step(state, batch):
        grads = jax.grad(loss_fn)(state.params, batch)
        new = jax.tree_util.tree_map(
            lambda p, g: p - 1e-3 * g, state.params, grads)
        return AnchorState(new), {}

    return step, AnchorState(params), (x, y)


def bench_headline_interleaved(pairs: int = 8) -> tuple[dict, dict]:
    """Headline MNIST measurement interleaved with the frozen anchor.

    Alternates full ``_measure_rate`` passes A/B/A/B… in one session so
    both workloads see the same host noise field; best-of each
    side is the least-interfered pass. Returns (headline, anchor) dicts;
    headline carries ``vs_anchor`` — the jitter-cancelled number the
    scoreboard compares across rounds.
    """
    import jax

    from ray_lightning_tpu import RayStrategy

    n_chips = len(jax.devices())
    strategy = RayStrategy(num_workers=n_chips, use_tpu=True)
    fw_step, fw_state, fw_batch = _build_mnist_step(strategy,
                                                    batch_size=8192)
    an_step, an_state, an_batch = _build_anchor_step()
    fw_flops = _step_flops(fw_step, fw_state, fw_batch)
    an_flops = _step_flops(jax.jit(an_step), an_state, an_batch)
    chip_peak = _chip_peak_flops(jax.devices()[0])
    fw_peak = chip_peak * n_chips

    fw_best = an_best = None
    pair_ratios = []
    for _ in range(pairs):
        # floor_s=1.0 (4x the default): the pair ratio inherits the
        # differential's relative noise (spread on the current machine:
        # not measured)
        fw = _measure_rate(fw_step, fw_state, fw_batch, 8192, fw_flops,
                           fw_peak, floor_s=1.0)
        an = _measure_rate(an_step, an_state, an_batch, 8192, an_flops,
                           chip_peak, floor_s=1.0)
        # the ratio statistic is per-PAIR (adjacent measurements share
        # the same instantaneous session conditions), then median across
        # pairs: best-of-fw over best-of-anchor broke the pairing — the
        # two bests can come from different moments, re-admitting the
        # drift the interleave exists to cancel (observed: fw stable to
        # 0.45% across sessions while best-of anchors moved 2.6%)
        pair_ratios.append(fw["samples_per_sec"]
                           / (an["samples_per_sec"] * n_chips))
        if fw_best is None or fw["samples_per_sec"] > \
                fw_best["samples_per_sec"]:
            fw_best = fw
        if an_best is None or an["samples_per_sec"] > \
                an_best["samples_per_sec"]:
            an_best = an
    fw_best["samples_per_sec_per_chip"] = (
        fw_best["samples_per_sec"] / n_chips)
    fw_best["n_chips"] = n_chips
    fw_best["device_kind"] = jax.devices()[0].device_kind
    fw_best["vs_anchor"] = float(np.median(pair_ratios))
    fw_best["pair_ratio_spread"] = round(
        (max(pair_ratios) - min(pair_ratios)) / min(pair_ratios), 4)
    return fw_best, an_best


def _build_mnist_step(strategy, batch_size: int):
    import optax

    from ray_lightning_tpu.data.synthetic import synthetic_mnist
    from ray_lightning_tpu.models.mnist import MNISTNet

    model = MNISTNet()
    tx = optax.adam(1e-3)
    x, y = synthetic_mnist(batch_size, seed=0)

    def loss_fn(params, model_state, batch, rng):
        bx, by = batch
        logits = model.apply({"params": params}, bx)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean()
        return loss, ({}, model_state)

    return _assemble_step(strategy, model, tx, loss_fn, x[:1], (x, y))


def _build_bert_step(strategy, batch_size: int, seq_len: int,
                     remat_policy: str =
                     "dots_with_no_batch_dims_save_attn"):
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.models.bert import (BertClassifier, bert_config,
                                               _synthetic_classification_tokens)

    # save_attn (round 4): +1.0-1.2% over dots_nb in interleaved pairs
    # (1688/1745 vs 1708/1763 sps) — attention is only ~3% of BERT's
    # flops at T=128, so the recompute skip is small but consistent;
    # round-5 re-sweep under the upgraded runtime kept it (see
    # docs/performance.md)
    cfg = bert_config("base", vocab_size=30522, max_seq_len=seq_len,
                      dtype=jnp.bfloat16, remat=True,
                      remat_policy=remat_policy)
    model = BertClassifier(cfg, num_classes=2)
    tx = optax.adamw(5e-5, weight_decay=0.01)
    x, y = _synthetic_classification_tokens(batch_size, seq_len,
                                            cfg.vocab_size, 2, seed=0)

    def loss_fn(params, model_state, batch, rng):
        tokens, labels = batch
        logits = model.apply({"params": params}, tokens, deterministic=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, ({}, model_state)

    return _assemble_step(strategy, model, tx, loss_fn, x[:1], (x, y))


def _build_vit_step(strategy, batch_size: int, image_size: int = 224,
                    patch_size: int = 16, **cfg_overrides):
    """ViT-base classification train step (round-5 sweep winner: bs 32
    with the remat+save_attn defaults vit_config now ships — +30% over
    no-remat, tools/ab_sweep.py)."""
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.core.optim import make_optimizer
    from ray_lightning_tpu.models.vit import ViTClassifier, vit_config

    opt_name = cfg_overrides.pop("optimizer", "adamw")
    cfg = vit_config("base", image_size=image_size, patch_size=patch_size,
                     dtype=jnp.bfloat16, **cfg_overrides)
    model = ViTClassifier(cfg, num_classes=1000, patch_size=patch_size)
    tx = make_optimizer(opt_name, learning_rate=1e-3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (batch_size, image_size, image_size, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, size=(batch_size,)), jnp.int32)

    def loss_fn(params, model_state, batch, rng):
        bx, by = batch
        logits = model.apply({"params": params}, bx)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean()
        return loss, ({}, model_state)

    return _assemble_step(strategy, model, tx, loss_fn, x[:1], (x, y))


def _build_moe_step(strategy, batch_size: int, seq_len: int = 512,
                    **cfg_overrides):
    """MoE LM train step (8 layers / d512 / 8 experts top-1; round-5
    sweep winner: bs 16 + adafactor, +15.6% over adamw — the optimizer
    updates every expert param while routing runs 1/k of the FLOPs)."""
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.core.optim import make_optimizer
    from ray_lightning_tpu.models.moe import MoeTransformerLM, moe_config

    opt_name = cfg_overrides.pop("optimizer", "adafactor")
    cfg = moe_config("small", vocab_size=50304, max_seq_len=seq_len,
                     d_model=512, n_heads=8, n_layers=8, d_ff=2048,
                     n_experts=8, dtype=jnp.bfloat16, **cfg_overrides)
    model = MoeTransformerLM(cfg)
    tx = make_optimizer(opt_name, learning_rate=1e-3)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 50257,
                                    size=(batch_size, seq_len + 1)),
                       jnp.int32)
    x, y = toks[:, :-1], toks[:, 1:]

    def loss_fn(params, model_state, batch, rng):
        bx, by = batch
        logits, aux = model.apply({"params": params}, bx, False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean() + cfg.aux_loss_weight * aux
        return loss, ({}, model_state)

    return _assemble_step(strategy, model, tx, loss_fn, x[:1], (x, y))


def _build_gpt2_step(strategy, batch_size: int, seq_len: int,
                     size: str = "small", optimizer: str = "adamw",
                     scan_unroll: int = 1, chunk_size: int = 2048,
                     remat_policy: str = "dots_with_no_batch_dims"):
    """Flagship model (GPT-2-small, the ``entry()`` model) train step.

    Config from the round-3 v5e sweep + HLO trace: bs 8 / seq 512 / bf16 /
    UNROLLED layers / remat(dots_with_no_batch_dims) / fused bf16-logit
    cross-entropy (``lm_head_xent``), vocab padded 50257→50304 (x128
    multiple keeps the LM-head matmul MXU-aligned: +9% measured).
    Round-3 sweep (samples/s at bs8@512): scanned+f32-xent 237 → unrolled
    248 → unrolled+fused-xent 265-279. Larger batches LOSE on this chip
    (bs16 249, bs32 230 — the per-layer emitters degrade and the LM-head
    adamw fusion doubles); no-remat and policy 'dots' both lose to
    dots_nb (saved-activation HBM traffic > recompute). Flash attention
    loses to XLA dot inside the step at T=512 (kernel opacity blocks
    neighboring fusions) while winning standalone — measured, not
    assumed.
    """
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.ops.lm_head_loss import lm_head_xent

    # small fits comfortably: unrolled layers + direct fused xent is the
    # measured optimum. medium (355M) only fits the 16 GB chip with
    # scanned layers + the chunked loss (unrolled OOMs even at full
    # remat; direct loss OOMs) — single-chip medium is memory-bound by
    # design; BASELINE's medium config is multi-host FSDP (v4-32).
    scan = size != "small"
    # bf16 softmax: the (B,H,T,T) score tensors dominate attention HBM
    # traffic; storing + reducing them bf16 measured +13% on this step
    # (300 vs 265 sps same-session). ~1% attention-weight rounding —
    # training-quality parity pinned by test_models.py
    # (test_bf16_softmax_training_parity).
    cfg = gpt2_config(size, vocab_size=50304, max_seq_len=seq_len,
                      dtype=jnp.bfloat16, scan_layers=scan,
                      scan_unroll=scan_unroll if scan else 1,
                      remat=remat_policy != "none",
                      remat_policy=None if remat_policy in ("none", "full")
                      else remat_policy,
                      attention_softmax_dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    from ray_lightning_tpu.core.optim import make_optimizer
    tx = make_optimizer(optimizer, 3e-4, weight_decay=0.1)
    toks = np.random.default_rng(0).integers(
        0, 50257, size=(batch_size, seq_len + 1)).astype(np.int32)

    def loss_fn(params, model_state, batch, rng):
        x, y = batch[:, :-1], batch[:, 1:]
        hidden = model.apply({"params": params}, x, return_hidden=True)
        if scan and chunk_size > 0:
            from ray_lightning_tpu.ops.lm_head_loss import (
                chunked_lm_head_xent)
            loss = chunked_lm_head_xent(hidden,
                                        params["wte"]["embedding"], y,
                                        chunk_size=chunk_size)
        else:
            loss = lm_head_xent(hidden, params["wte"]["embedding"], y)
        return loss, ({}, model_state)

    return _assemble_step(strategy, model, tx, loss_fn, toks[:1, :-1],
                          toks)


def _measure_rate(step, state, batch, samples_per_step: int,
                  flops_per_step: float | None, peak_flops: float | None,
                  floor_s: float = 0.25, max_doublings: int = 8,
                  repeats: int = 3) -> dict:
    """Trustworthy samples/s via differential chained-chunk timing.

    Raises :class:`MeasurementError` instead of ever returning a value from
    a collapsed or physically impossible timing.
    """
    import jax

    resolution = time.get_clock_info("perf_counter").resolution
    floor = max(floor_s, 1000.0 * resolution)

    @partial(jax.jit, static_argnames="n")
    def run_chunk(s, b, n):
        def body(_, acc):
            nxt, _logs = step(acc, b)
            return nxt
        return jax.lax.fori_loop(0, n, body, s)

    cell = {"state": state}
    compiled: set = set()

    def fetch():
        leaf = jax.tree_util.tree_leaves(cell["state"].params)[0]
        return float(jax.device_get(leaf.ravel()[0]))

    def timed(n: int) -> float:
        if n not in compiled:
            cell["state"] = run_chunk(cell["state"], batch, n)
            fetch()  # compile + execute outside the clock
            compiled.add(n)
        fetch()  # drain any pending work before the clock starts
        t0 = time.perf_counter()
        cell["state"] = run_chunk(cell["state"], batch, n)
        fetch()
        return time.perf_counter() - t0

    # Size the chunk from the model's FLOPs so the differential dwarfs
    # dispatch noise on the first try: assume >= 10% of peak (or a slow
    # CPU) and target ~2x the floor of pure device compute.
    assumed = 0.10 * peak_flops if peak_flops else 2e9
    if flops_per_step:
        n_est = int(math.ceil(2.0 * floor * assumed / flops_per_step))
    else:
        n_est = 64
    n_large = max(16, min(1 << (n_est - 1).bit_length(), 1 << 16))
    n_small = max(2, n_large // 8)

    history = []
    for _ in range(max_doublings):
        dt_small = min(timed(n_small) for _ in range(repeats))
        dt_large = min(timed(n_large) for _ in range(repeats))
        diff = dt_large - dt_small
        history.append((n_small, n_large, dt_small, dt_large))
        if diff > floor:
            rate = samples_per_step * (n_large - n_small) / diff
            flops_rate = (flops_per_step or 0.0) * rate / samples_per_step
            if peak_flops and flops_rate > 1.5 * peak_flops:
                raise MeasurementError(
                    f"measured {flops_rate:.3e} FLOP/s exceeds 1.5x chip "
                    f"peak {peak_flops:.3e}; timing is wrong "
                    f"(history={history})")
            return {
                "samples_per_sec": rate,
                "steps_timed": n_large - n_small,
                "dt": diff,
                "mfu": (flops_rate / peak_flops
                        if peak_flops and flops_per_step else None),
                "flops_per_step": flops_per_step,
            }
        if n_large >= 1 << 20:
            break
        n_large *= 2
    raise MeasurementError(
        f"differential timing never exceeded the {floor:.3f}s floor after "
        f"{len(history)} attempts (clock resolution {resolution:.1e}s); "
        f"either the device elides work or dispatch noise dominates. "
        f"history={history}")


def bench_model(build, samples_per_step: int, analytic_tokens: int = 0,
                best_of: int = 1, **build_kwargs) -> dict:
    import jax

    from ray_lightning_tpu import RayStrategy

    n_chips = len(jax.devices())
    strategy = RayStrategy(num_workers=n_chips, use_tpu=True)
    step, state, batch = build(strategy, **build_kwargs)
    if analytic_tokens:  # scanned-layer models: cost_analysis undercounts
        flops = _transformer_train_flops(state, analytic_tokens)
    else:
        flops = _step_flops(step, state, batch)
    # The step runs over the whole mesh: the sanity bound and MFU must use
    # the mesh's aggregate peak, not one chip's, or any multi-chip host
    # fails the bound at >1.5/n_chips per-chip utilization.
    peak = _chip_peak_flops(jax.devices()[0]) * n_chips
    # best-of-N full measurements: the fastest clean measurement is the
    # least-interfered one and stays sanity-bounded.
    out = _measure_rate(step, state, batch, samples_per_step, flops, peak)
    for _ in range(best_of - 1):
        cand = _measure_rate(step, state, batch, samples_per_step, flops,
                             peak)
        if cand["samples_per_sec"] > out["samples_per_sec"]:
            out = cand
    out["samples_per_sec_per_chip"] = out["samples_per_sec"] / n_chips
    out["n_chips"] = n_chips
    out["device_kind"] = jax.devices()[0].device_kind
    return out


# --------------------------------------------------------------------- #
# child processes. The parent has touched jax and holds the chip, and a
# chip belongs to one process: no child may need it. The three children
# below are CPU-by-design (a virtual CPU mesh, or no jax at all); each
# stamps the platform and device kind IT ran on into the JSON it prints
# (`_device_fields`), and the parent refuses a child that did not — a
# child's number must never pass for the chip's.
# --------------------------------------------------------------------- #
def _run_child(mode: str, cpu_devices: int | None = None) -> dict:
    """Re-exec this file in ``_TL_BENCH_MODE=mode`` and return the JSON
    object it printed last. ``cpu_devices=N`` pins the child to an
    N-device virtual CPU platform; ``None`` is for a child that never
    imports jax."""
    env = dict(os.environ)
    env["_TL_BENCH_MODE"] = mode
    if cpu_devices is not None:
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={cpu_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    if proc.returncode != 0:
        raise MeasurementError(
            f"{mode} child failed rc={proc.returncode}: "
            f"{proc.stderr[-500:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            out = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not {"platform", "device_kind"} <= set(out):
            raise MeasurementError(
                f"{mode} child did not say which device it ran on: {out}")
        return out
    raise MeasurementError(f"{mode} child printed no JSON")


# scaling proxy: dp=8 vs dp=1 on a virtual CPU mesh
def _scaling_child(dp: int) -> None:
    import jax

    from ray_lightning_tpu import RayStrategy

    per_device_batch = 512
    strategy = RayStrategy(num_workers=dp, use_tpu=False)
    step, state, batch = _build_mnist_step(strategy,
                                           per_device_batch * dp)
    flops = _step_flops(step, state, batch)
    out = _measure_rate(step, state, batch, per_device_batch * dp, flops,
                        peak_flops=None, floor_s=0.15)
    print(json.dumps({"dp": dp, "rate": out["samples_per_sec"],
                      "devices": len(jax.devices()), **_device_fields()}))


def _bench_decode(batch: int = 8, prompt: int = 16,
                  new_tokens: int = 256, short_tokens: int = 64,
                  prefill_len: int = 512,
                  prefill_short: int = 128) -> dict:
    """KV-cache autoregressive decode + prefill throughput (GPT-2-small,
    greedy).

    Generation is TWO jitted programs (models/generate.py): a batched
    prompt prefill and a tokens-only decode scan with donated
    cache/tokens buffers — this measures each side separately, the
    serving-side analog of the training headline. Params are served in
    bf16 (standard inference practice): each decode step reads every
    weight, so f32 masters would double the per-step HBM traffic that
    bounds small-batch decode.

    Round-6 protocol (ADVICE round 5 + the prefill split):

    - ``device_ms_per_token_step`` is the **per-pair median** of the
      interleaved long/short differentials — ``min(long) - min(short)``
      took its two minima from different moments, which can understate
      the marginal step or go negative under jitter and trip
      MeasurementError on a healthy device (the same pairing break the
      headline's interleave already fixed).
    - ``fixed_dispatch_ms`` is clamped at 0: a negative residual means
      the attribution is not meaningful for this session, not that
      dispatch has negative cost.
    - ``prefill_tokens_per_sec`` (wall, P=512) and
      ``device_prefill_tokens_per_sec`` (per-pair 512/128 differential —
      dispatch cancels) report the single-pass prompt fill;
      ``prefill_speedup_vs_sequential`` compares the differential
      per-position prefill cost against ``device_ms_per_token_step``,
      the cost the same prompt would pay fed token-by-token.
    - ``host_sync_ms`` / ``enqueue_ms`` (round 13) split the
      ``fixed_dispatch_ms`` residual via a sync-every-call vs
      chained-with-one-fetch differential: the sync share is what the
      async serve pipeline hides, the enqueue share is irreducible.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.models.generate import generate, prefill

    total = prompt + new_tokens
    # scan_layers=False: under the round-5 runtime the nested loop
    # (token scan over a layer scan) compiles ~1.9x slower per decode
    # step than unrolled layers (2.16 vs 1.14 ms/step interleaved A/B;
    # the device trace shows the whole regression inside while.62, the
    # inner layer loop). Serving configs should unroll — recompile cost
    # is paid once per shape.
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.bfloat16,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(batch, prompt)), jnp.int32)
    params = jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, toks)["params"]))(jax.random.PRNGKey(0))
    dec = TransformerLM(gpt2_config("small", decode=True,
                                    param_dtype=jnp.bfloat16, **base))

    # params/toks are jit ARGUMENTS, not closure constants: greedy
    # sampling ignores rng, so a closure-constant generation is a
    # constant function and XLA may fold the whole scan at compile time —
    # the param-bandwidth floor caught exactly that (2.7e-5 s for 271
    # steps) when the 256-token variant crossed the folding threshold.
    params = jax.device_put(params)
    toks = jax.device_put(toks)

    def make_runner(n: int):
        # generate() is itself two jitted programs (prefill + donated
        # decode scan); wrapping it in ANOTHER jit would inline both into
        # one program and silently drop the buffer donation, so the
        # runner stays plain python — the long/short differential
        # cancels the extra dispatch the same way it cancels the first.
        def run(params, toks, rng):
            return generate(dec, params, toks, max_new_tokens=n,
                            rng=rng, temperature=0.0)
        # warm up twice, each ending in a host fetch of output data: the
        # first call compiles, the second drains residual
        # first-dispatch cost out of the timed reps
        for k in (1, 99):
            _fetch_scalar(run(params, toks, jax.random.PRNGKey(k)))
        return run

    run_long = make_runner(new_tokens)
    run_short = make_runner(short_tokens)

    def timed(runner, rep: int) -> float:
        # vary the prompt per rep so no layer of the stack can reuse a
        # prior execution; fetch the last column as the completion proof
        t_in = (toks + rep) % 50257
        t0 = time.perf_counter()
        out = runner(params, t_in, jax.random.PRNGKey(2 + rep))
        _fetch_scalar(out)
        return time.perf_counter() - t0

    # Interleaved pairs (the round-4 A/B discipline): decode showed ±16%
    # session spread across rounds; alternating long/short gives both
    # lengths the same noise field. The differential statistic is
    # per-PAIR (adjacent measurements share the same instantaneous
    # session conditions), then the median across pairs — mirroring
    # bench_headline_interleaved's ratio statistic.
    longs, pair_diffs = [], []
    for i in range(4):
        t_long = timed(run_long, i)
        t_short = timed(run_short, 10 + i)
        longs.append(t_long)
        pair_diffs.append(t_long - t_short)
    best_long = min(longs)
    diff = float(np.median(pair_diffs))
    # the marginal cost of a generated token is one cached decode step;
    # the prefill program and its dispatch are identical on both sides
    # of the differential and cancel
    diff_steps = new_tokens - short_tokens
    # Honesty guard (same contract as _measure_rate): a collapsed timing
    # must raise, never print. The floor IS the physical bound: every
    # decode step reads at least all params, so the run cannot finish
    # faster than the bf16 param bytes cross HBM (1.5x slack for spec
    # optimism), nor faster than the clock can resolve.
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    hbm_bw = _hbm_bandwidth(jax.devices()[0])
    step_floor = (2 * n_params) / (1.5 * hbm_bw)
    resolution = 1000 * time.get_clock_info("perf_counter").resolution
    if best_long < max(new_tokens * step_floor, resolution):
        raise MeasurementError(
            f"decode timing collapsed: {best_long:.2e}s for {new_tokens} "
            f"generated tokens is below the param-bandwidth floor — "
            "device elided work or async dispatch leaked")
    if diff < max(diff_steps * step_floor, resolution):
        raise MeasurementError(
            f"decode differential collapsed: {diff:.2e}s median for "
            f"{diff_steps} marginal steps is below the param-bandwidth "
            "floor — the two lengths did not both execute "
            f"(pair_diffs={[round(d, 4) for d in pair_diffs]})")
    device_ms = 1e3 * diff / diff_steps

    # ------- prefill: one batched (B, P) prompt-fill program ---------- #
    # Own model instance: prefill needs max_seq_len >= P=512 and the
    # decode model above is sized to its generation. Interleaved 512/128
    # per-pair differential, same discipline as decode — the marginal
    # 384 positions are pure prefill compute, dispatch cancels.
    pf_base = dict(vocab_size=50304, max_seq_len=prefill_len,
                   dtype=jnp.bfloat16, scan_layers=False)
    pf_dec = TransformerLM(gpt2_config("small", decode=True,
                                       param_dtype=jnp.bfloat16,
                                       **pf_base))
    pf_params = jax.device_put(jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            TransformerLM(gpt2_config("small", **pf_base)).init(
                r, toks)["params"]))(jax.random.PRNGKey(0)))
    pf_toks = jax.device_put(jnp.asarray(
        np.random.default_rng(1).integers(
            0, 50257, size=(batch, prefill_len)), jnp.int32))

    def pf_timed(P: int, rep: int) -> float:
        t_in = (pf_toks[:, :P] + rep) % 50257
        t0 = time.perf_counter()
        _cache, last = prefill(pf_dec, pf_params, t_in)
        _fetch_scalar(last)
        return time.perf_counter() - t0

    for P in (prefill_len, prefill_short):  # compile + drain, fetched
        for rep in (90, 91):
            pf_timed(P, rep)
    pf_longs, pf_diffs = [], []
    for i in range(4):
        t_long = pf_timed(prefill_len, i)
        t_short = pf_timed(prefill_short, 20 + i)
        pf_longs.append(t_long)
        pf_diffs.append(t_long - t_short)
    pf_best = min(pf_longs)
    pf_diff = float(np.median(pf_diffs))
    # prefill reads params once per CALL (not per token), so the only
    # floors with teeth are one param pass and the clock
    if pf_best < max(step_floor, resolution):
        raise MeasurementError(
            f"prefill timing collapsed: {pf_best:.2e}s for a "
            f"(B={batch}, P={prefill_len}) forward is below one param "
            "pass over HBM — execution was elided")
    if pf_diff <= resolution:
        raise MeasurementError(
            f"prefill differential collapsed: {pf_diff:.2e}s median for "
            f"{prefill_len - prefill_short} marginal positions "
            f"(pair_diffs={[round(d, 4) for d in pf_diffs]})")
    # per-position marginal prefill cost vs the per-token decode step the
    # same positions would cost fed sequentially (both cover `batch` rows)
    pf_pos_ms = 1e3 * pf_diff / (prefill_len - prefill_short)

    # ------- dispatch-cost split: host_sync vs enqueue (round 13) ----- #
    # `fixed_dispatch_ms` is one opaque residual; the async-dispatch
    # work needs it split into the part depth-2 pipelining can hide
    # (HOST SYNC: the device→host copy + the blocking wait the sync
    # driver serializes between a dispatch landing and the next one
    # launching) and the part it cannot (ENQUEUE: trace/dispatch issue
    # cost, paid per call regardless). Differential leg: K generations
    # fetched after EVERY call vs the same K chained with ONE final
    # fetch — the per-call difference is the sync cost pipelining
    # removes, and the chained leg's issue-only time is the enqueue
    # cost. The chain is device-side DEPENDENT (call i+1's prompt is a
    # function of call i's output tokens), so executions serialize and
    # backend concurrency cannot deflate the chained leg.
    K_split = 3

    def _dep(out):
        # next prompt from the previous output: device-side dependency
        # + per-call variety (no layer can reuse a prior execution)
        return (out[:, :prompt] + 1) % 50257

    def _split_pair(rep: int):
        t_in = (toks + 50 + rep) % 50257
        t0 = time.perf_counter()
        for i in range(K_split):
            out = run_short(params, t_in, jax.random.PRNGKey(40 + i))
            _fetch_scalar(out)
            t_in = _dep(out)
        t_sync = (time.perf_counter() - t0) / K_split
        t_in = (toks + 70 + rep) % 50257
        t0 = time.perf_counter()
        for i in range(K_split):
            out = run_short(params, t_in, jax.random.PRNGKey(60 + i))
            t_in = _dep(out)
        t_issue = (time.perf_counter() - t0) / K_split
        _fetch_scalar(out)
        t_chain = (time.perf_counter() - t0) / K_split
        return t_sync, t_issue, t_chain

    split = [_split_pair(r) for r in range(3)]
    # the sync leg pays K_split fetches, the chained leg ONE — so the
    # per-call difference captures (K_split-1)/K_split of the true
    # sync cost; rescale so host_sync_ms is the full per-call figure
    host_sync_ms = 1e3 * max(0.0, float(np.median(
        [s - c for s, _i, c in split]))) * K_split / (K_split - 1)
    enqueue_ms = 1e3 * float(np.median([i for _s, i, _c in split]))

    return {
        "model": "gpt2_small (bf16 serving params)", "batch": batch,
        "prompt": prompt, "new_tokens": new_tokens,
        "generated_tokens_per_sec": round(
            batch * new_tokens / best_long, 0),
        # decode-only wall cost per generated token (prefill + both
        # program dispatches amortized in)
        "ms_per_token_step": round(1e3 * best_long / new_tokens, 3),
        "device_ms_per_token_step": round(device_ms, 3),
        "device_token_steps_per_sec": round(
            batch * 1e3 / device_ms, 0),
        # residual after attributing every generated token its marginal
        # device step; clamped — negative residuals mean the attribution
        # is not meaningful under this session's jitter, not that
        # dispatch has negative cost
        "fixed_dispatch_ms": round(
            max(0.0, 1e3 * best_long - device_ms * new_tokens), 1),
        # the split of that residual (per generate() call: prefill +
        # decode-scan programs): host_sync_ms = what async double-
        # buffering can take off the critical path, enqueue_ms = the
        # issue cost every dispatch pays regardless — the floor behind
        # extras["serve"]["async_dispatch"]'s overlap claim
        "host_sync_ms": round(host_sync_ms, 2),
        "enqueue_ms": round(enqueue_ms, 2),
        "prefill_len": prefill_len,
        "prefill_tokens_per_sec": round(
            batch * prefill_len / pf_best, 0),
        "device_prefill_tokens_per_sec": round(
            batch * (prefill_len - prefill_short) / pf_diff, 0),
        "prefill_speedup_vs_sequential": round(device_ms / pf_pos_ms, 1),
    }


def _param_stream_floor_s(params) -> float:
    """Seconds one param-streaming pass cannot beat: the engine's
    at-rest parameter bytes (``models/quant.py param_bytes`` — exact
    for plain AND weight-quantized trees) over 1.5x the device's HBM
    bandwidth. The shared denominator of every serve honesty floor.

    Kernel-awareness: for a quantized tree the at-rest bytes are the
    codes+scales, and that is the floor charged in BOTH matmul-kernel
    modes. Under ``matmul_kernel="xla"`` the real per-dispatch stream
    is LARGER (the materialized dequant tree is written and re-read as
    dispatch scratch), so the floor is a deliberately loose lower
    bound there; under ``matmul_kernel="pallas"`` no dequantized
    arena exists and the codes+scales floor IS the per-dispatch param
    stream — the shrunken floor a fused-kernel leg must genuinely
    respect (``_bench_weight_quant``'s fused legs enforce exactly
    this)."""
    import jax

    from ray_lightning_tpu.models.quant import param_bytes

    return param_bytes(params) / (1.5 * _hbm_bandwidth(jax.devices()[0]))


def _bench_serve(num_slots: int = 8, n_requests: int = 16,
                 prompt: int = 64, new_tokens: int = 64,
                 spread: float = 1.5,
                 steps_per_dispatch: int = 8) -> dict:
    """Continuous-batching engine vs static-batch generate() on one
    deterministic staggered arrival trace (GPT-2-small, bf16 serving
    params, greedy).

    The trace: ``n_requests`` ragged prompts with HETEROGENEOUS token
    budgets (``new_tokens/4 .. new_tokens``, seeded rng) arriving at a
    fixed inter-arrival gap sized so the arrival window spans ``spread``
    x the measured static generation time — the regime continuous
    batching is built for. Both sides serve the SAME requests:

    - **engine**: ``serve/`` slot pool, ``steps_per_dispatch`` decode
      steps per program call (multi-step scheduling — token-granularity
      dispatch pays the fixed per-call overhead ONCE PER TOKEN — its
      size is not measured on the current machine);
      requests join mid-flight and retire at their own budgets. Makespan
      = first arrival -> last completion.
    - **static**: one-shot ragged ``generate()`` in waves of
      ``num_slots``. A wave starts at max(its own LAST arrival, previous
      wave done) — earlier waves do run during the arrival window — and
      every row pays the wave's LONGEST budget (``generate``'s scan
      length is one static number per batch: the static batch waits for
      its slowest member in both arrival time and length).

    Both rates count the same useful tokens (each request's own budget).
    ``serve_tokens_per_sec`` is the tracked rate;
    ``serve_vs_static_batch`` > 1 is the schedule-level win (early
    start + mid-flight backfill + per-request budgets); it shrinks as
    the arrival spread -> 0 and budgets equalize, where the one-shot
    static batch is the right tool.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.models.generate import generate
    from ray_lightning_tpu.serve import ServeClient

    total = prompt + new_tokens
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.bfloat16,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, toks0)["params"]))(jax.random.PRNGKey(0)))
    dec = TransformerLM(gpt2_config("small", decode=True,
                                    param_dtype=jnp.bfloat16, **base))

    rng = np.random.default_rng(1)
    prompts, budgets = [], []
    for _ in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        prompts.append([int(t) for t in rng.integers(0, 50257, size=L)])
        budgets.append(int(rng.integers(new_tokens // 4, new_tokens + 1)))
    useful_tokens = sum(budgets)

    # ---- static side: waves of num_slots through one-shot generate ----
    waves = [list(range(i, min(i + num_slots, n_requests)))
             for i in range(0, n_requests, num_slots)]

    def run_wave(ids, key):
        batch = np.zeros((len(ids), prompt), np.int32)
        lengths = np.array([len(prompts[i]) for i in ids], np.int32)
        for r, i in enumerate(ids):
            batch[r, :len(prompts[i])] = prompts[i]
        out = generate(dec, params, jnp.asarray(batch),
                       max_new_tokens=max(budgets[i] for i in ids),
                       rng=jax.random.PRNGKey(key), temperature=0.0,
                       prompt_lengths=jnp.asarray(lengths))
        _fetch_scalar(out)

    for k, ids in enumerate(waves):  # compile + drain, fetched
        run_wave(ids, 90 + k)
    wave_walls = []
    for k, ids in enumerate(waves):
        t0 = time.perf_counter()
        run_wave(ids, k)
        wave_walls.append(time.perf_counter() - t0)
    static_gen_wall = sum(wave_walls)

    # ---- the shared trace: arrivals spread over spread x static time ---
    gap = spread * static_gen_wall / max(1, n_requests - 1)
    last_arrival = gap * (n_requests - 1)
    trace = [(i * gap,
              dict(prompt=prompts[i], max_new_tokens=budgets[i]))
             for i in range(n_requests)]

    # engine warmup on a throwaway client: compiles the prefill+inject
    # and step programs (jit-cached by model identity for the timed run)
    warm = ServeClient(dec, params, num_slots=num_slots,
                       prefill_len=prompt,
                       steps_per_dispatch=steps_per_dispatch,
                       clock=time.perf_counter)
    for i in range(2):
        warm.submit(prompts[i], max_new_tokens=2)
    warm.run_until_idle()

    client = ServeClient(dec, params, num_slots=num_slots,
                         prefill_len=prompt,
                         steps_per_dispatch=steps_per_dispatch,
                         clock=time.perf_counter)
    out = client.serve_trace(trace)
    makespan = max(c.finish_time for c in out.values())
    tokens_total = sum(len(c.tokens) for c in out.values())
    if tokens_total != useful_tokens:
        raise MeasurementError(
            f"engine emitted {tokens_total} tokens, expected "
            f"{useful_tokens}")

    # honesty floor (same contract as _bench_decode): every model
    # token-step reads all at-rest param bytes once, so the busy time
    # cannot beat those bytes over HBM x the number of executed
    # sub-steps. Bytes come from param_bytes() — the exact storage
    # accounting — NOT dtype arithmetic: a weight-quantized engine's
    # floor must shrink with its codes (stale 2*n_params math would
    # hand quantized legs a floor they could legitimately beat)
    step_floor = _param_stream_floor_s(client.engine.params)
    substeps = (client.engine.decode_substeps + client.engine.prefills)
    if makespan < max(substeps * step_floor,
                      1000 * time.get_clock_info("perf_counter").resolution):
        raise MeasurementError(
            f"serve timing collapsed: {makespan:.2e}s makespan for "
            f"{substeps} engine token-steps is below the param-bandwidth "
            "floor — device elided work or async dispatch leaked")

    # quantiles through the SAME Histogram production serving reports
    # from (obs.metrics — exact-sample mode at this n matches
    # np.percentile's linear interpolation bit-for-bit)
    from ray_lightning_tpu.obs.metrics import Histogram
    lat_h = Histogram("serve_latency_ms")
    ttft_h = Histogram("serve_ttft_ms")
    for c in out.values():
        lat_h.observe(1e3 * c.latency)
        ttft_h.observe(1e3 * c.time_to_first_token)
    # fair static schedule: each wave starts at max(previous wave done,
    # its OWN last arrival) — earlier waves may run during the arrival
    # window; charging every wave for the global last arrival would
    # inflate the engine's win
    finish = 0.0
    for ids, wall in zip(waves, wave_walls):
        finish = max(finish, ids[-1] * gap) + wall
    static_makespan = finish
    serve_tps = tokens_total / makespan
    static_tps = tokens_total / static_makespan
    return {
        "model": "gpt2_small (bf16 serving params)",
        "num_slots": num_slots, "requests": n_requests,
        "prompt_len": prompt, "max_new_tokens": new_tokens,
        "useful_tokens": useful_tokens,
        "steps_per_dispatch": steps_per_dispatch,
        "arrival_window_s": round(last_arrival, 3),
        "serve_tokens_per_sec": round(serve_tps, 0),
        "p50_latency_ms": round(lat_h.quantile(0.50), 1),
        "p99_latency_ms": round(lat_h.quantile(0.99), 1),
        "ttft_p50_ms": round(ttft_h.quantile(0.50), 1),
        "static_batch_tokens_per_sec": round(static_tps, 0),
        "serve_vs_static_batch": round(serve_tps / static_tps, 2),
        "engine_dispatches": client.engine.steps,
        "engine_prefills": client.engine.prefills,
    }


def _bench_paged(num_slots: int = 8, prompt: int = 64,
                 new_tokens: int = 64, page_size: int = 16,
                 prefill_chunk: int = 64, long_prompt: int = 384,
                 n_prefix: int = 8) -> dict:
    """Paged-KV serving additions to ``extras["serve"]`` (ROADMAP item 1).

    Three measurements, one per lever:

    - ``paged_concurrent_capacity``: co-resident admissions at the SAME
      KV byte budget as the static slot pool, on the pinned mixed-length
      request set (same rng as ``_bench_serve``'s trace). Pure allocator
      accounting — :class:`PagePool` builds its arena lazily, so this
      measures the admission math the real engine runs, without device
      memory. A short request holds ``ceil((prompt+budget)/page_size)``
      pages instead of a ``max_seq_len`` row; >= 2x expected at this mix.
    - ``prefix_cache_hit_rate``: fraction of adoptable prompt-prefix
      pages actually served from cache on a shared-system-prompt trace
      (``n_prefix`` requests, one ``prompt``-token system prefix plus
      distinct tails) through the REAL chunked+prefix engine.
    - ``decode_stall_p99_ms``: the Sarathi bound. Three short requests
      decode while a ``long_prompt``-token prompt arrives; the stall is
      the wall gap between consecutive decode dispatches around the
      injection. Monolithic prefill pays the whole prompt in one gap;
      chunked prefill alternates chunk/decode dispatches, bounding the
      p99 gap near ONE chunk's compute. Both sides run the paged engine
      (same gather/scatter tax), isolating the scheduling policy.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.obs.metrics import Histogram
    from ray_lightning_tpu.serve import PagePool, Request, ServeEngine
    from ray_lightning_tpu.serve.engine import SlotPoolFull

    max_len = long_prompt + prefill_chunk * 2
    base = dict(vocab_size=50304, max_seq_len=max_len, dtype=jnp.bfloat16,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(2, 8)), jnp.int32)
    params = jax.device_put(jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, toks0)["params"]))(jax.random.PRNGKey(0)))
    dec = TransformerLM(gpt2_config("small", decode=True,
                                    param_dtype=jnp.bfloat16, **base))

    # ---- capacity: same arena bytes as num_slots static rows ----------
    pages_per_row = max_len // page_size
    pool = PagePool(dec, num_slots=num_slots * pages_per_row,
                    page_size=page_size,
                    num_pages=num_slots * pages_per_row)
    rng = np.random.default_rng(1)  # the _bench_serve request mix
    admitted = 0
    for i in range(pool.num_slots):
        L = int(rng.integers(prompt // 2, prompt + 1))
        budget = int(rng.integers(new_tokens // 4, new_tokens + 1))
        try:
            pool.acquire(Request(id=i, prompt=[1] * L,
                                 max_new_tokens=budget, seed=i))
        except SlotPoolFull:
            break
        admitted += 1
    capacity = admitted / num_slots

    # ---- prefix hit rate: shared system prompt through the engine -----
    sys_prompt = [int(t) for t in
                  np.random.default_rng(2).integers(0, 50257, size=prompt)]
    eng = ServeEngine(dec, params, num_slots=4, prefill_len=prefill_chunk,
                      page_size=page_size, prefill_chunk=prefill_chunk,
                      prefix_cache=True)
    tails = np.random.default_rng(3).integers(0, 50257,
                                              size=(n_prefix, 8))
    for i in range(n_prefix):
        eng.prefill([Request(id=i,
                             prompt=sys_prompt + [int(t) for t in tails[i]],
                             max_new_tokens=4, seed=i)])
        while eng.chunk_pending:
            eng.prefill_chunk_step()
        while eng.active_count:
            eng.step()
    hit_rate = eng.prefix.hit_rate
    eng.shutdown()

    # ---- decode stall: monolithic vs chunked long-prompt injection ----
    shorts = [Request(id=100 + i, prompt=[3 + i] * 16, max_new_tokens=48,
                      seed=100 + i) for i in range(3)]
    long_toks = [int(t) for t in np.random.default_rng(4).integers(
        0, 50257, size=long_prompt)]

    def stall_run(chunked: bool) -> Histogram:
        eng = ServeEngine(
            dec, params, num_slots=4,
            prefill_len=(prefill_chunk if chunked else max_len),
            prefill_batch=4, page_size=page_size,
            prefill_chunk=(prefill_chunk if chunked else None))
        eng.prefill([Request(id=r.id, prompt=list(r.prompt),
                             max_new_tokens=r.max_new_tokens, seed=r.seed)
                     for r in shorts])
        for _ in range(4):   # warm the step program + settle
            eng.step()
        gaps = Histogram("decode_gap_ms")
        long_req = Request(id=999, prompt=long_toks, max_new_tokens=4,
                           seed=999)
        last = time.perf_counter()
        eng.prefill([long_req])
        while eng.chunk_pending or eng.active_count:
            if eng.chunk_pending:
                eng.prefill_chunk_step()
            if eng.active_count:
                eng.step()
                now = time.perf_counter()
                gaps.observe(1e3 * (now - last))
                last = now
        eng.shutdown()
        return gaps

    stall_run(True)   # compile both program sets outside the timing
    stall_run(False)
    chunked_gaps = stall_run(True)
    mono_gaps = stall_run(False)
    return {
        "page_size": page_size,
        "prefill_chunk": prefill_chunk,
        "paged_concurrent_capacity": round(capacity, 2),
        "paged_admissions": admitted,
        "static_admissions": num_slots,
        "prefix_cache_hit_rate": round(hit_rate, 3),
        "decode_stall_p99_ms": round(chunked_gaps.quantile(0.99), 1),
        "decode_stall_p99_ms_monolithic": round(
            mono_gaps.quantile(0.99), 1),
        "decode_stall_p50_ms": round(chunked_gaps.quantile(0.50), 1),
        "long_prompt_len": long_prompt,
    }


def _zero_residual_blocks(params):
    """Zero every transformer block's residual-output projections
    (attn ``out`` and mlp ``down``, kernels AND biases): each block
    becomes an EXACT identity on the residual stream, so two models
    sharing embeddings + ln_f produce bit-identical logits regardless
    of depth. The acceptance-friendly surgery behind ``_bench_spec``'s
    pinned trace — the compute still executes (zeros multiply at full
    cost), only the numbers are rigged for 100% draft agreement."""
    import jax

    def walk(tree, path):
        if not isinstance(tree, dict):
            zero = (("attn" in path and "out" in path)
                    or ("mlp" in path and "down" in path))
            return jax.tree_util.tree_map(np.zeros_like, tree) if zero \
                else tree
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(params, ())


def _bench_spec(num_slots: int = 2, n_requests: int = 6,
                prompt: int = 16, new_tokens: int = 32,
                spec_k: int = 4, steps_per_dispatch: int = 4) -> dict:
    """Speculative decoding on the pinned acceptance-friendly trace:
    the bandwidth-amortization CEILING, honestly labeled.

    Decode at small batch is parameter-bandwidth-bound (this repo's own
    measured claim, docs/performance.md): every single-token step
    streams all target params once. That is the regime speculative
    decoding multiplies — a ``(B, k+1)`` verify reads the params ONCE
    for k+1 tokens' worth of scoring, so it costs ~one step, not k+1
    (measured here: a 5-token verify is ~1.1x a step at the pinned
    8-layer/d512 shape — THIS host is genuinely bandwidth-bound there;
    shrink the model below cache-resident and the CPU turns
    compute-bound and spec honestly loses, which is why the shape is
    part of the pin). To pin the CEILING — machinery cost at ~100%
    acceptance, not draft quality — both models get their residual
    blocks zeroed (exact identity blocks) and share embeddings, so the
    1-layer draft agrees with the 8-layer target on every token
    (``spec_accept_rate`` is reported; a real deployment's speedup
    scales this ceiling by its measured acceptance). Greedy
    ``spec_token_mismatches`` vs the plain-engine leg is ENFORCED 0
    (fp32 — margins are real, flips would mean the accept/rollback
    machinery is broken). Legs run sequentially and alone: this CPU
    host jitters ±10%, interleaving would alias it.

    Also runs the chaos seat: a pinned ``serve.verify`` crash schedule
    through the supervisor (rebuild + replay) must lose no requests and
    flip no tokens; its recovery cost is mirrored into
    ``extras["chaos"]``.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
    from ray_lightning_tpu.serve import FINISH_FAILED, ServeClient

    max_len = prompt + new_tokens + spec_k
    # the pinned bandwidth-bound shape: 8 unrolled layers at d512 put
    # ~26M f32 params (~103 MB) well past cache, so a decode step's
    # cost IS the param stream and the widened verify amortizes it
    base = dict(vocab_size=1024, max_seq_len=max_len,
                dtype=jnp.float32, scan_layers=False, d_model=512,
                n_heads=8, d_ff=2048, n_layers=8)
    tcfg = gpt2_config("nano", decode=True, **base)
    dec = TransformerLM(tcfg)
    params = _zero_residual_blocks(jax.device_get(TransformerLM(
        gpt2_config("nano", **base)).init(
        jax.random.PRNGKey(0),
        np.zeros((2, 8), np.int32))["params"]))
    dcfg = dataclasses.replace(tcfg, n_layers=1)          # 1-layer draft
    draft = TransformerLM(dcfg)
    dparams = _zero_residual_blocks(jax.device_get(TransformerLM(
        dataclasses.replace(dcfg, decode=False)).init(
        jax.random.PRNGKey(1),
        np.zeros((2, 8), np.int32))["params"]))
    # share the logit-determining leaves: zero blocks make both models
    # pure functions of these, hence bit-identical logits
    for name in ("wte", "wpe", "ln_f"):
        dparams[name] = params[name]

    rng = np.random.default_rng(5)
    trace = []
    for _ in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.0, dict(
            prompt=[int(t) for t in rng.integers(0, 1024, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)))))
    useful = sum(t[1]["max_new_tokens"] for t in trace)

    def leg(spec: bool, plan=None, retry=False):
        # prefill_len covers prompt + full budget: the supervisor
        # replays prompt + emitted tokens through ONE prefill pass
        # (the docs/reliability.md sizing rule, same as _bench_chaos)
        kw = dict(num_slots=num_slots,
                  prefill_len=prompt + new_tokens,
                  steps_per_dispatch=steps_per_dispatch,
                  clock=time.perf_counter)
        if spec:
            kw.update(draft_model=draft, draft_params=dparams,
                      spec_k=spec_k)
        if retry:
            kw["retry_policy"] = RetryPolicy(max_attempts=3,
                                             base_delay=0.0)
        client = ServeClient(dec, params, **kw)
        if plan is None:
            out = client.serve_trace(trace)
        else:
            with plan.armed():
                out = client.serve_trace(trace)
        makespan = max(c.finish_time for c in out.values())
        return client, out, makespan

    # sequential A/B, each leg warmed then timed alone; every client
    # released so earlier legs' KV pools and draft caches don't sit on
    # the later legs' memory/timing
    leg(False)[0].shutdown()
    base_client, base_out, base_makespan = leg(False)
    base_client.shutdown()
    leg(True)[0].shutdown()
    spec_client, spec_out, spec_makespan = leg(True)

    mismatches = sum(1 for rid, comp in base_out.items()
                     if spec_out[rid].tokens != comp.tokens)
    if mismatches:
        raise MeasurementError(
            f"speculative decoding flipped {mismatches}/{n_requests} "
            "greedy streams vs the plain engine — the accept/rollback "
            "machinery is broken (fp32: no rounding excuse)")
    if sum(len(c.tokens) for c in spec_out.values()) != useful:
        raise MeasurementError("spec leg lost tokens")

    eng = spec_client.engine
    judged = eng.spec_accepted_tokens + eng.spec_rejected_tokens
    accept_rate = eng.spec_accepted_tokens / max(1, judged)
    spec_stats = dict(rounds=eng.spec_rounds, dispatches=eng.steps,
                      refills=eng.spec.refills)
    spec_client.shutdown()

    # chaos seat: pinned serve.verify crashes through the supervisor
    # (ticks sized to land inside this trace's ~6 spec dispatches)
    plan = FaultPlan.at("serve.verify", [1, 3])
    chaos_client, chaos_out, _ = leg(True, plan=plan, retry=True)
    sup = chaos_client.engine
    chaos_client.shutdown()
    chaos_mism = sum(1 for rid, comp in spec_out.items()
                     if chaos_out[rid].tokens != comp.tokens)
    failed = sum(1 for c in chaos_out.values()
                 if c.finish_reason == FINISH_FAILED)
    if plan.fired < 2 or failed or chaos_mism:
        raise MeasurementError(
            f"serve.verify chaos leg broke: fired={plan.fired}/2, "
            f"failed={failed}, mismatches={chaos_mism} — spec-path "
            "recovery is not replay-exact")

    spec_tps = useful / spec_makespan
    base_tps = useful / base_makespan
    return {
        "model": "8L/d512/v1024 f32 target + 1L draft, zero-block "
                 "acceptance-friendly trace",
        "spec_k": spec_k, "steps_per_dispatch": steps_per_dispatch,
        "num_slots": num_slots, "requests": n_requests,
        "useful_tokens": useful,
        "spec_accept_rate": round(accept_rate, 3),
        "spec_generated_tokens_per_sec": round(spec_tps, 0),
        "nonspec_tokens_per_sec": round(base_tps, 0),
        "spec_vs_nonspec": round(spec_tps / base_tps, 2),
        "spec_token_mismatches": mismatches,
        "spec_rounds": spec_stats["rounds"],
        "spec_dispatches": spec_stats["dispatches"],
        "draft_refills": spec_stats["refills"],
        "spec_verify_faults_injected": plan.fired,
        "spec_verify_recovery_ms": round(
            1e3 * sup.recovery_s_total / max(1, sup.recoveries), 1),
        "spec_verify_token_mismatches": chaos_mism,
        "note": "ceiling: ~100% acceptance by construction (zero-block "
                "models share logits) on a measured bandwidth-bound "
                "shape; real speedup = this param-stream amortization "
                "x measured acceptance",
    }


def _bench_kv_int8(num_slots: int = 8, prompt: int = 64,
                   new_tokens: int = 64, page_size: int = 16) -> dict:
    """Int8 KV storage: capacity at equal arena bytes + greedy identity.

    - ``int8_concurrent_capacity_vs_bf16``: admissions at the SAME
      at-rest byte budget (``PagePool.bytes_per_page`` accounting —
      lazy arenas, no device memory), pinned request mix from
      ``_bench_serve``. Int8 pages cost half the bf16 bytes plus the
      per-page-per-head f32 scale tax, so the arena holds ~2x the pages
      and admits ~2x the mix; ENFORCED >= 1.8x (pure accounting — a
      miss means the byte math regressed).
    - ``int8_token_mismatches``: greedy outputs of a REAL
      bf16-compute/int8-storage nano engine vs its bf16-storage twin on
      a pinned trace, ENFORCED 0 (absmax per-page-per-head error is
      ~amax/254, below these argmax margins; a flip means the
      quantize/dequantize path corrupted KV, not that int8 is noisy).
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.serve import PagePool, Request, ServeClient
    from ray_lightning_tpu.serve.engine import SlotPoolFull

    # ---- capacity at equal bytes: gpt2-small shapes, accounting only --
    total = prompt + new_tokens
    big = TransformerLM(gpt2_config(
        "small", vocab_size=50304, max_seq_len=total,
        dtype=jnp.bfloat16, decode=True, scan_layers=False))

    def admissions(kv_dtype, budget_bytes):
        probe = PagePool(big, num_slots=1, page_size=page_size,
                         num_pages=1, kv_dtype=kv_dtype)
        pages = int(budget_bytes // probe.bytes_per_page)
        pool = PagePool(big, num_slots=pages, page_size=page_size,
                        num_pages=pages, kv_dtype=kv_dtype)
        rng = np.random.default_rng(1)   # the _bench_serve mix
        n = 0
        for i in range(pages):
            L = int(rng.integers(prompt // 2, prompt + 1))
            budget = int(rng.integers(new_tokens // 4, new_tokens + 1))
            try:
                pool.acquire(Request(id=i, prompt=[1] * L,
                                     max_new_tokens=budget, seed=i))
            except SlotPoolFull:
                break
            n += 1
        return n, pages

    bf16_probe = PagePool(big, num_slots=1, page_size=page_size,
                          num_pages=1)
    budget_bytes = num_slots * (total // page_size) \
        * bf16_probe.bytes_per_page   # num_slots static bf16 rows
    bf16_n, bf16_pages = admissions(None, budget_bytes)
    int8_n, int8_pages = admissions("int8", budget_bytes)
    capacity = int8_n / max(1, bf16_n)
    if capacity < 1.8:
        raise MeasurementError(
            f"int8 arena admitted only {capacity:.2f}x the bf16 mix at "
            "equal bytes — the page byte accounting regressed")

    # ---- greedy identity: real bf16-compute nano engine, int8 vs bf16 -
    base = dict(vocab_size=512, max_seq_len=64, dtype=jnp.bfloat16,
                scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **base))
    params = TransformerLM(gpt2_config("nano", **base)).init(
        jax.random.PRNGKey(0), np.zeros((2, 8), np.int32))["params"]
    rng = np.random.default_rng(7)
    trace = [(0.0, dict(
        prompt=[int(t) for t in rng.integers(0, 512, size=12)],
        max_new_tokens=16)) for _ in range(4)]

    def run(kv_dtype):
        client = ServeClient(dec, params, num_slots=4, prefill_len=16,
                             page_size=8, kv_dtype=kv_dtype)
        out = client.serve_trace(trace)
        client.shutdown()
        return out

    ref = run(None)
    out8 = run("int8")
    mism = sum(1 for rid, c in ref.items()
               if out8[rid].tokens != c.tokens)
    if mism:
        raise MeasurementError(
            f"int8 KV flipped {mism}/4 greedy streams vs bf16 storage "
            "on the pinned nano trace — the quantize/dequantize path "
            "corrupted KV")
    return {
        "page_size": page_size,
        "int8_concurrent_capacity_vs_bf16": round(capacity, 2),
        "int8_admissions": int8_n, "bf16_admissions": bf16_n,
        "int8_pages_at_equal_bytes": int8_pages,
        "bf16_pages_at_equal_bytes": bf16_pages,
        "bytes_per_page_bf16": bf16_probe.bytes_per_page,
        "bytes_per_page_int8": PagePool(
            big, num_slots=1, page_size=page_size, num_pages=1,
            kv_dtype="int8").bytes_per_page,
        "int8_token_mismatches": mism,
    }


def _bench_weight_quant(num_slots: int = 2, n_requests: int = 6,
                        prompt: int = 16, new_tokens: int = 32,
                        steps_per_dispatch: int = 4) -> dict:
    """Weight-only int8/int4 quantization A/B on the pinned
    bandwidth-bound shape (the 8L/d512 f32 target of ``_bench_spec`` —
    ~103 MB of params, well past cache, so a decode step's cost IS the
    param stream).

    Three sequential legs (fp32, int8, int4), each warmed and run
    alone, clients released. ENFORCED gates (``MeasurementError``):

    - **param bytes** via ``param_bytes()`` (exact codes+scales
      accounting, never dtype arithmetic): int8 <= 0.55x fp, int4
      <= 0.35x fp. These are the bytes the honesty floor charges the
      quantized legs — the floor shrinks with the codes.
    - **top-1 agreement** vs the fp leg, teacher-forced: the quantized
      model re-scores the fp leg's exact streams position-by-position
      (prompt + fp tokens in, argmax out), so one early flip cannot
      cascade — the honest "weight quant perturbs logits" metric.
      int8 >= 0.95, int4 >= 0.60 (measured 0.99 / 0.74 on this
      UNTRAINED random net — trained weights agree far more; token
      identity is deliberately NOT the gate, unlike int8 KV / spec /
      page-native which are exact by construction).
    - each leg emits the full token budget (no lost tokens).

    Decode throughput per leg is RECORDED, not gated: on this CPU host
    XLA materializes the dequantized f32 tree once per dispatch (no
    convert-into-GEMM fusion on the oneDNN path), so quantized decode
    honestly LOSES wall-clock here (~0.4x measured) — the same
    host-regime honesty note as ``_bench_spec``'s cache-resident
    caveat. The tracked claim is the byte stream (floor-backed); the
    wall-clock win requires a backend that feeds codes to the MXU/GEMM
    without a materialized temp (TPU convert fusion, or the pallas
    endgame in ``docs/serving.md``).
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.models.quant import (dequantize_params,
                                                param_bytes)
    from ray_lightning_tpu.serve import ServeClient

    max_len = prompt + new_tokens
    base = dict(vocab_size=1024, max_seq_len=max_len,
                dtype=jnp.float32, scan_layers=False, d_model=512,
                n_heads=8, d_ff=2048, n_layers=8)
    tcfg = gpt2_config("nano", decode=True, **base)
    dec = TransformerLM(tcfg)
    params = jax.device_get(TransformerLM(
        gpt2_config("nano", **base)).init(
        jax.random.PRNGKey(0), np.zeros((2, 8), np.int32))["params"])

    rng = np.random.default_rng(5)
    trace = []
    for _ in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.0, dict(
            prompt=[int(t) for t in rng.integers(0, 1024, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)))))
    useful = sum(t[1]["max_new_tokens"] for t in trace)

    def leg(weight_dtype, matmul_kernel=None):
        kw = dict(num_slots=num_slots, prefill_len=prompt + new_tokens,
                  steps_per_dispatch=steps_per_dispatch,
                  clock=time.perf_counter, weight_dtype=weight_dtype,
                  matmul_kernel=matmul_kernel)
        warm = ServeClient(dec, params, **kw)
        for i in range(2):
            warm.submit(trace[i][1]["prompt"], max_new_tokens=2)
        warm.run_until_idle()
        warm.shutdown()
        client = ServeClient(dec, params, **kw)
        out = client.serve_trace(list(trace))
        makespan = max(c.finish_time for c in out.values())
        if sum(len(c.tokens) for c in out.values()) != useful:
            raise MeasurementError(
                f"{weight_dtype or 'fp'} leg lost tokens")
        # the floor each leg must respect charges ITS at-rest bytes —
        # for a fused-kernel leg that IS the per-dispatch param stream
        # (no materialized dequant arena; _param_stream_floor_s)
        floor = _param_stream_floor_s(client.engine.params)
        substeps = client.engine.decode_substeps + client.engine.prefills
        if makespan < substeps * floor:
            raise MeasurementError(
                f"{weight_dtype or 'fp'} leg beat its own "
                "param-bandwidth floor — work elided")
        stored = client.engine.params
        client.shutdown()
        return out, makespan, stored

    # sequential legs, each run alone (this host jitters +-10%)
    out_fp, mk_fp, p_fp = leg(None)
    out_i8, mk_i8, p_i8 = leg("int8")
    out_i4, mk_i4, p_i4 = leg("int4")

    # fused-kernel legs: the SAME quantized codes, streamed into the
    # pallas dequant-matmul kernel instead of a per-dispatch
    # materialized dequant. ENFORCED: the kernel actually arms (a
    # fresh trace instantiates it), the engine holds codes+scales only
    # (no dequantized tree anywhere — the at-rest bytes ARE the
    # per-dispatch stream, gated by the byte ratios below), and the
    # tokens are IDENTICAL to the materialized-dequant legs (the
    # interpret-mode bitwise contract, docs/serving.md).
    from ray_lightning_tpu.models.pallas_matmul import kernel_calls
    from ray_lightning_tpu.models.quant import is_quantized
    calls0 = kernel_calls()
    out_f8, mk_f8, p_f8 = leg("int8", matmul_kernel="pallas")
    out_f4, mk_f4, p_f4 = leg("int4", matmul_kernel="pallas")
    # the witness binds on the FIRST in-process run only: a warm
    # process-wide jit cache legitimately skips retracing on reruns
    # (the structural gates below — pallas config + still-quantized
    # params — cover those)
    if kernel_calls() == calls0 and calls0 == 0:
        raise MeasurementError(
            "fused legs never traced the pallas dequant-matmul kernel "
            "— matmul_kernel='pallas' is not reaching the projections")
    if not (is_quantized(p_f8) and is_quantized(p_f4)):
        raise MeasurementError(
            "fused legs hold a dequantized parameter tree — the "
            "codes+scales byte-stream claim is void")
    fused_mismatches = sum(
        int(out_f8[r].tokens != out_i8[r].tokens) for r in out_i8) + sum(
        int(out_f4[r].tokens != out_i4[r].tokens) for r in out_i4)
    if fused_mismatches:
        raise MeasurementError(
            f"fused-kernel legs diverged from the materialized-dequant "
            f"legs on {fused_mismatches} request streams — the "
            "interpret-mode bitwise identity contract is broken")

    bytes_fp = param_bytes(p_fp)
    ratio_i8 = param_bytes(p_i8) / bytes_fp
    ratio_i4 = param_bytes(p_i4) / bytes_fp
    if ratio_i8 > 0.55 or ratio_i4 > 0.35:
        raise MeasurementError(
            f"weight-quant byte accounting regressed: int8 {ratio_i8:.3f}x "
            f"(must be <= 0.55), int4 {ratio_i4:.3f}x (<= 0.35)")
    # the fused legs' per-dispatch param stream is ENFORCED at the
    # codes+scales floor: same stored bytes as the materialized-dequant
    # legs (which they are gated against above), and — unlike those —
    # nothing else ever materializes, so these ratios ARE the stream
    if param_bytes(p_f8) != param_bytes(p_i8) \
            or param_bytes(p_f4) != param_bytes(p_i4):
        raise MeasurementError(
            "fused legs' at-rest bytes drifted from the quantized "
            "legs' — they must hold the identical codes+scales")

    # teacher-forced top-1 agreement: re-score the fp streams with the
    # quantized weights; every position conditions on the SAME (fp)
    # context, so agreement reads per-position flip probability
    cache0 = dec.init(jax.random.PRNGKey(0),
                      np.zeros((1, 1), np.int32),
                      positions=np.zeros((1, 1), np.int32))["cache"]

    def agreement(stored):
        deq = dequantize_params(stored)
        agree = total = 0
        for comp in out_fp.values():
            seq = list(comp.prompt) + list(comp.tokens)
            L = len(seq)
            batch = np.asarray(seq, np.int32)[None, :]
            logits, _ = dec.apply(
                {"params": deq, "cache": cache0}, jnp.asarray(batch),
                positions=jnp.arange(L)[None, :], deterministic=True,
                mutable=["cache"])
            pred = np.asarray(logits[0]).argmax(-1)[
                len(comp.prompt) - 1:L - 1]
            ref = np.asarray(comp.tokens)
            agree += int((pred == ref).sum())
            total += len(ref)
        return agree / total

    agree_i8 = agreement(p_i8)
    agree_i4 = agreement(p_i4)
    if agree_i8 < 0.95 or agree_i4 < 0.60:
        raise MeasurementError(
            f"weight-quant top-1 agreement collapsed: int8 "
            f"{agree_i8:.3f} (>= 0.95), int4 {agree_i4:.3f} (>= 0.60) "
            "— quantization is corrupting weights beyond rounding")

    return {
        "model": "8L/d512/v1024 f32 target (the _bench_spec "
                 "bandwidth-bound shape)",
        "num_slots": num_slots, "requests": n_requests,
        "useful_tokens": useful,
        "steps_per_dispatch": steps_per_dispatch,
        "param_bytes_fp": bytes_fp,
        "param_bytes_int8": param_bytes(p_i8),
        "param_bytes_int4": param_bytes(p_i4),
        "param_bytes_int8_vs_fp": round(ratio_i8, 3),
        "param_bytes_int4_vs_fp": round(ratio_i4, 3),
        "top1_agreement_int8": round(agree_i8, 4),
        "top1_agreement_int4": round(agree_i4, 4),
        "fp_tokens_per_sec": round(useful / mk_fp, 1),
        "int8_tokens_per_sec": round(useful / mk_i8, 1),
        "int4_tokens_per_sec": round(useful / mk_i4, 1),
        "int8_vs_fp_decode": round(mk_fp / mk_i8, 2),
        "int4_vs_fp_decode": round(mk_fp / mk_i4, 2),
        # fused dequant-matmul kernel legs (matmul_kernel="pallas"):
        # byte stream ENFORCED at the codes+scales floor with no
        # materialized dequant arena, tokens ENFORCED identical to the
        # materialized legs; wall-clock RECORDED under the interpret
        # caveat (the PR 12 precedent — off-TPU the kernel executes
        # under the pallas interpreter and honestly loses time; the
        # per-dispatch byte stream is the floor-backed claim, the time
        # win needs the Mosaic lowering on a real TPU)
        "fused_token_mismatches": 0,
        "int8_fused_tokens_per_sec": round(useful / mk_f8, 1),
        "int4_fused_tokens_per_sec": round(useful / mk_f4, 1),
        "int8_fused_vs_fp_decode": round(mk_fp / mk_f8, 2),
        "int4_fused_vs_fp_decode": round(mk_fp / mk_f4, 2),
        "note": "byte + agreement gates ENFORCED; decode ratios "
                "recorded honestly — this CPU host materializes the "
                "per-dispatch dequant (no convert-into-GEMM fusion), "
                "so quantized decode loses wall-clock here, and the "
                "fused legs additionally pay the pallas interpret tax "
                "off-TPU; the byte stream is the floor-backed claim "
                "(docs/performance.md rounds 11 + 14)",
    }


def _page_native_pin(num_slots: int, prompt: int, new_tokens: int,
                     page_size: int, max_seq_len: int):
    """The ONE pinned KV-dominated page-native A/B setup, shared by
    ``_bench_page_native`` and ``_bench_pallas`` so their "same shape,
    same trace" comparability is structural, not copy-paste: the
    8L/d512 f32 decode model (+ its params) and the rng(5) staggered
    trace. Returns ``(dec, params, trace, pages_needed, useful)``."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM

    base = dict(vocab_size=1024, max_seq_len=max_seq_len,
                dtype=jnp.float32, scan_layers=False, d_model=512,
                n_heads=8, d_ff=2048, n_layers=8)
    dec = TransformerLM(gpt2_config("nano", decode=True, **base))
    params = jax.device_get(TransformerLM(
        gpt2_config("nano", **base)).init(
        jax.random.PRNGKey(0), np.zeros((2, 8), np.int32))["params"])

    rng = np.random.default_rng(5)
    trace = []
    pages_needed = 0
    for _ in range(num_slots):
        L = int(rng.integers(prompt // 2, prompt + 1))
        budget = int(rng.integers(new_tokens // 2, new_tokens + 1))
        trace.append((0.0, dict(
            prompt=[int(t) for t in rng.integers(0, 1024, size=L)],
            max_new_tokens=budget)))
        pages_needed += -(-(L + budget) // page_size)
    useful = sum(t[1]["max_new_tokens"] for t in trace)
    return dec, params, trace, pages_needed, useful


def _bench_page_native(num_slots: int = 8, prompt: int = 32,
                       new_tokens: int = 32, page_size: int = 64,
                       max_seq_len: int = 512,
                       steps_per_dispatch: int = 4) -> dict:
    """Page-native attention vs dense-gather on a pinned KV-dominated
    shape: both engines serve the SAME trace on identical page arenas;
    the only difference is whether each decode dispatch materializes
    the dense ``(num_slots, max_seq_len)`` KV view (gather → step →
    scatter) or reads/writes K/V straight through the page table
    inside the attention.

    The shape pins the regime the lever targets: 8 slots x 512
    positions x 8 layers of d512 f32 KV = a ~134 MB view per dispatch
    against ~16 MB of actually-occupied pages (the trace's requests
    hold 1 page each → <= 25% arena occupancy, asserted from the same
    ``bytes_per_page`` accounting the capacity benches use — never
    dtype arithmetic). ENFORCED: ``page_native_token_mismatches`` == 0
    (the path is exact — same scores, same masks, one exact softmax;
    only final-accumulation rounding differs, below these f32 argmax
    margins) and speedup >= 1.2x (measured ~3x on this host; the win
    scales with 1/occupancy).
    """
    from ray_lightning_tpu.serve import ServeClient

    dec, params, trace, pages_needed, useful = _page_native_pin(
        num_slots, prompt, new_tokens, page_size, max_seq_len)

    def leg(page_native):
        kw = dict(num_slots=num_slots, prefill_len=prompt,
                  page_size=page_size,
                  steps_per_dispatch=steps_per_dispatch,
                  clock=time.perf_counter, page_native=page_native)
        warm = ServeClient(dec, params, **kw)
        for i in range(2):
            warm.submit(trace[i][1]["prompt"], max_new_tokens=2)
        warm.run_until_idle()
        warm.shutdown()
        client = ServeClient(dec, params, **kw)
        out = client.serve_trace(list(trace))
        makespan = max(c.finish_time for c in out.values())
        if sum(len(c.tokens) for c in out.values()) != useful:
            raise MeasurementError(
                f"page_native={page_native} leg lost tokens")
        pool = client.engine.pool
        bpp = pool.bytes_per_page
        pages_per_slot = pool.pages_per_slot
        total_pages = pool.num_pages
        client.shutdown()
        return out, makespan, bpp, pages_per_slot, total_pages

    # sequential A/B, each leg warmed and run alone
    out_d, mk_d, bpp, pages_per_slot, total_pages = leg(False)
    out_n, mk_n, _, _, _ = leg(True)

    occupancy = pages_needed / total_pages
    if occupancy > 0.25:
        raise MeasurementError(
            f"page-native pin broken: trace occupies {occupancy:.2f} of "
            "the arena (the claim is gated at <= 0.25 — at high "
            "occupancy the dense view approaches the occupied bytes "
            "and the lever flattens by design)")
    mismatches = sum(1 for rid in out_d
                     if out_n[rid].tokens != out_d[rid].tokens)
    if mismatches:
        raise MeasurementError(
            f"page-native flipped {mismatches}/{num_slots} greedy "
            "streams vs dense-gather (f32: no rounding excuse) — the "
            "page-table read/write path is broken")
    speedup = mk_d / mk_n
    if speedup < 1.2:
        raise MeasurementError(
            f"page-native decode only {speedup:.2f}x dense-gather at "
            f"{occupancy:.2f} occupancy — the dense-view bytes are not "
            "being skipped")

    return {
        "model": "8L/d512/v1024 f32, max_seq_len=512 (KV-dominated)",
        "num_slots": num_slots, "page_size": page_size,
        "steps_per_dispatch": steps_per_dispatch,
        "useful_tokens": useful,
        "arena_occupancy": round(occupancy, 3),
        # byte claims from bytes_per_page accounting, not dtype math
        "dense_view_bytes_per_dispatch": num_slots * pages_per_slot
        * bpp,
        "occupied_page_bytes": pages_needed * bpp,
        "dense_gather_tokens_per_sec": round(useful / mk_d, 1),
        "page_native_tokens_per_sec": round(useful / mk_n, 1),
        "page_native_vs_dense_gather": round(speedup, 2),
        "page_native_token_mismatches": mismatches,
        "note": "exact page-table-direct attention (no per-dispatch "
                "dense view); bytes touched scale with occupied pages "
                "— the win grows as occupancy falls",
    }


def _bench_pallas(num_slots: int = 8, prompt: int = 32,
                  new_tokens: int = 32, page_size: int = 64,
                  max_seq_len: int = 512,
                  steps_per_dispatch: int = 4) -> dict:
    """The pallas paged-attention kernel vs the XLA page-native path,
    on the SAME pinned KV-dominated shape as ``_bench_page_native``
    (8L/d512 f32, <= 25% occupancy) plus an int8-arena leg.

    ENFORCED, backend-independent: ``pallas_token_mismatches`` == 0 on
    both the f32 and int8 legs (under interpret mode the kernel's read
    side is bitwise the XLA page-native math — exact tiled softmax, no
    online approximation, pinned by tests/test_pallas_attention.py),
    and the per-dispatch byte floor cited from ``bytes_per_page`` /
    ``param_bytes()`` accounting: the kernel's ONLY K/V operands are
    the arena leaves themselves, so a decode dispatch streams
    ``occupied_pages x bytes_per_page`` KV bytes (each occupied page
    crosses HBM→VMEM once per score pass and once per output pass —
    the page the index map parks on between phases is not re-fetched)
    plus one ``param_bytes()`` pass. On int8 arenas those operands are
    the CODES + per-page-per-head scales — the int8 floor must come in
    under 0.55x the f32 floor, which is the accounting-backed witness
    that no dense dequantized K/V arena exists on this path (dequant
    happens per (page_size, H, D) VMEM block inside the kernel).

    RECORDED honestly, not gated: wall-clock. This host runs the
    kernel under **pallas interpret mode** (no TPU), which pays an
    interpretation tax per grid step — CPU interpret loses wall-clock
    to the fused XLA path, the byte floor is the claim (the PR 9/11
    precedent: the time win needs the real Mosaic lowering, where the
    fused kernel removes the XLA path's page-sized score/output
    temporaries and the int8 dequant pass).
    """
    from ray_lightning_tpu.models.quant import param_bytes
    from ray_lightning_tpu.serve import ServeClient

    dec, params, trace, pages_needed, useful = _page_native_pin(
        num_slots, prompt, new_tokens, page_size, max_seq_len)

    def leg(kernel, kv_dtype=None):
        kw = dict(num_slots=num_slots, prefill_len=prompt,
                  page_size=page_size, page_native=True,
                  steps_per_dispatch=steps_per_dispatch,
                  kv_dtype=kv_dtype, attention_kernel=kernel,
                  clock=time.perf_counter)
        warm = ServeClient(dec, params, **kw)
        for i in range(2):
            warm.submit(trace[i][1]["prompt"], max_new_tokens=2)
        warm.run_until_idle()
        warm.shutdown()
        client = ServeClient(dec, params, **kw)
        out = client.serve_trace(list(trace))
        makespan = max(c.finish_time for c in out.values())
        if sum(len(c.tokens) for c in out.values()) != useful:
            raise MeasurementError(
                f"pallas bench leg ({kernel}, kv={kv_dtype}) lost "
                "tokens")
        bpp = client.engine.pool.bytes_per_page
        total_pages = client.engine.pool.num_pages
        client.shutdown()
        return {r: c.tokens for r, c in out.items()}, makespan, bpp, \
            total_pages

    out_x, mk_x, bpp_fp, total_pages = leg("xla")
    out_p, mk_p, _, _ = leg("pallas")
    out_xi, _, bpp_i8, _ = leg("xla", kv_dtype="int8")
    out_pi, mk_pi, _, _ = leg("pallas", kv_dtype="int8")

    occupancy = pages_needed / total_pages
    mismatches = sum(1 for rid in out_x if out_p[rid] != out_x[rid])
    mismatches_i8 = sum(1 for rid in out_xi
                        if out_pi[rid] != out_xi[rid])
    if mismatches or mismatches_i8:
        raise MeasurementError(
            f"pallas kernel flipped {mismatches} (f32) / "
            f"{mismatches_i8} (int8) greedy streams vs the XLA "
            "page-native path — interpret mode is bitwise-exact, a "
            "mismatch means the kernel read path is broken")
    if bpp_i8 > 0.55 * bpp_fp:
        raise MeasurementError(
            f"int8 bytes_per_page ({bpp_i8}) is not under 0.55x the "
            f"f32 page ({bpp_fp}) — the kernel's per-dispatch floor "
            "is supposed to stream codes + scales, not a dequantized "
            "arena")

    return {
        "model": "8L/d512/v1024 f32, max_seq_len=512 (KV-dominated, "
                 "the page_native shape)",
        "num_slots": num_slots, "page_size": page_size,
        "steps_per_dispatch": steps_per_dispatch,
        "useful_tokens": useful,
        "arena_occupancy": round(occupancy, 3),
        # byte floors from bytes_per_page / param_bytes accounting —
        # never dtype arithmetic (the serve honesty rule)
        "kv_bytes_per_dispatch_fp32": pages_needed * bpp_fp,
        "kv_bytes_per_dispatch_int8": pages_needed * bpp_i8,
        "int8_vs_fp32_kv_bytes": round(bpp_i8 / bpp_fp, 3),
        "param_bytes_per_pass": param_bytes(params),
        "pallas_token_mismatches": mismatches + mismatches_i8,
        "xla_page_native_tokens_per_sec": round(useful / mk_x, 1),
        "pallas_interpret_tokens_per_sec": round(useful / mk_p, 1),
        "pallas_interpret_int8_tokens_per_sec": round(useful / mk_pi,
                                                      1),
        "pallas_vs_xla_page_native": round(mk_x / mk_p, 2),
        "note": "identity + byte floors ENFORCED; timing RECORDED "
                "honestly — this host runs the kernel under pallas "
                "INTERPRET mode (no TPU), which loses wall-clock to "
                "the fused XLA path by design; the byte floor (codes+"
                "scales in-kernel, no dense dequantized arena, no "
                "dense view) is the claim "
                "(docs/performance.md round 12)",
    }


def _bench_async_dispatch(num_slots: int = 8, n_requests: int = 8,
                          prompt: int = 32, new_tokens: int = 48,
                          decode_split: Optional[dict] = None) -> dict:
    """Depth-2 pipelined dispatch (``async_dispatch=True``) vs the sync
    driver on a pinned decode-dominated trace (GPT-2-small bf16 serving
    params, greedy): an all-at-once burst that admits in ONE prefill
    barrier and then runs a pure decode chain — the regime where the
    pipeline stays armed and every dispatch's host round-trip either
    sits on the critical path (sync) or overlaps the next dispatch
    (async). Sequential interleaved A/B pairs, per-pair ratio, median —
    the headline discipline.

    ENFORCED, backend-independent: ``async_token_mismatches`` == 0 vs
    the sync driver at steps_per_dispatch ∈ {1, 4} (pipelining must
    not move a single token); the **deferral witness** — the median
    ``step_enqueue()`` wall must come in under half a full sync
    ``step()`` (a blocking enqueue would read ~one device step, so
    this is the structural proof the handle really defers the host
    sync); and, pipeline armed, ``replay_token_mismatches`` == 0 under
    a pinned ``serve.dispatch`` crash (the in-flight dispatch is
    discarded and regenerated by replay) plus
    ``failover_token_mismatches`` == 0 under a pinned
    ``serve.replica`` kill on a 2-replica async fleet — both on an f32
    config where greedy argmax margins sit above rounding, so identity
    is CHECKABLE (the ``_bench_chaos`` bf16 caveat).

    Throughput is ENFORCED only as "pipelining is ~free" (>= 0.9x at
    both widths, outside session noise) and otherwise RECORDED: the
    >= 1.15x overlap target needs a host-side blocking sync per
    dispatch that is large against the device step (not measured on the
    current machine). This host's CPU backend barely overlaps a DEPENDENT
    dispatch chain at all (measured here: independent dispatches
    overlap host work 1.28x, the carry-chained equivalent 1.04x — the
    chained launch needs the TPU runtime's event-chained async
    dispatch), so the time win is honestly not demonstrable on this
    tier; the hideable share is bounded by ``host_sync_ms`` out of
    ``host_sync_ms + enqueue_ms + device step`` (the
    ``dispatch_split`` field, from ``_bench_decode``'s differential) —
    the PR 9/11 precedent: the contract claims are gated, the time win
    is cited against its floor.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
    from ray_lightning_tpu.serve import ReplicaFleet, ServeClient

    total = prompt + new_tokens
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.bfloat16,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, toks0)["params"]))(jax.random.PRNGKey(0)))
    dec = TransformerLM(gpt2_config("small", decode=True,
                                    param_dtype=jnp.bfloat16, **base))

    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 50257, size=prompt)]
               for _ in range(n_requests)]
    # uniform budgets, t=0 burst: one admission barrier, then the
    # pipeline never drains until the trailing no-op dispatch
    trace = [(0.0, dict(prompt=p, max_new_tokens=new_tokens))
             for p in prompts]
    useful = n_requests * new_tokens

    def leg(spd: int, async_: bool):
        client = ServeClient(dec, params, num_slots=num_slots,
                             prefill_len=prompt, steps_per_dispatch=spd,
                             async_dispatch=async_,
                             clock=time.perf_counter)
        try:
            out = client.serve_trace(list(trace))
            makespan = max(c.finish_time for c in out.values())
            if sum(len(c.tokens) for c in out.values()) != useful:
                raise MeasurementError(
                    f"async-dispatch leg (spd={spd}, async={async_}) "
                    "lost tokens")
            # the serve honesty floor: busy time cannot beat the
            # executed sub-steps' param bytes over HBM — async overlap
            # hides host work, never device work, so the floor binds
            # both drivers
            floor = _param_stream_floor_s(client.engine.params)
            substeps = (client.engine.decode_substeps
                        + client.engine.prefills)
            if makespan < max(substeps * floor,
                              1000 * time.get_clock_info(
                                  "perf_counter").resolution):
                raise MeasurementError(
                    f"async-dispatch timing collapsed: {makespan:.2e}s "
                    f"for {substeps} sub-steps is below the param-"
                    "bandwidth floor — device elided work or a sync "
                    "leaked")
            return ({r: c.tokens for r, c in out.items()},
                    useful / makespan)
        finally:
            # a failing check must not pin this engine's KV/params
            # through every later bench leg (the PR 9 release rule)
            client.shutdown()

    results = {}
    mismatches = 0       # async-vs-sync within a pair: the async claim
    baseline_drift = 0   # sync-vs-sync across reps: baseline health
    for spd in (1, 4):
        leg(spd, False)  # warmup: compiles this spd's step program
        pairs = []
        ref_tokens = None
        for _rep in range(2):
            sync_toks, sync_tps = leg(spd, False)
            async_toks, async_tps = leg(spd, True)
            pairs.append((sync_tps, async_tps))
            ref_tokens = ref_tokens or sync_toks
            mismatches += sum(1 for r in sync_toks
                              if async_toks[r] != sync_toks[r])
            baseline_drift += sum(1 for r in sync_toks
                                  if sync_toks[r] != ref_tokens[r])
        results[spd] = {
            "sync_tokens_per_sec": round(
                float(np.median([s for s, _a in pairs])), 1),
            "async_tokens_per_sec": round(
                float(np.median([a for _s, a in pairs])), 1),
            "async_vs_sync": round(float(np.median(
                [a / s for s, a in pairs])), 3),
        }
    if baseline_drift:
        # separate verdicts so a broken BASELINE is not misdiagnosed
        # as (and does not double-count into) a pipelining defect
        raise MeasurementError(
            f"sync driver is nondeterministic across reps: "
            f"{baseline_drift} greedy streams drifted between "
            "identical sync runs — fix the baseline before reading "
            "the async comparison")
    if mismatches:
        raise MeasurementError(
            f"async dispatch flipped {mismatches} greedy streams vs "
            "the sync driver — the pipelined carry chain must be "
            "token-identical by construction")
    for spd in (1, 4):
        ratio = results[spd]["async_vs_sync"]
        # DELIBERATELY 0.9, not the acceptance sketch's 1.0: the
        # measured median on this backend is a coin-flip around 1.00
        # (per-pair spread ±5% — 0.97..1.09 observed across shapes
        # while tokens stayed identical), because the CPU client
        # barely chain-overlaps (docstring). A hard 1.0 gate on that
        # distribution fails healthy sessions ~half the time — exactly
        # the flaky-measurement class the integrity rules exist to
        # kill. 0.9 is outside the observed spread, so it still trips
        # on a REAL pipelining tax; the honest ratio is recorded.
        if ratio < 0.9:
            raise MeasurementError(
                f"async dispatch REGRESSED at steps_per_dispatch="
                f"{spd}: {ratio}x vs the sync driver — pipelining must "
                "be ~free even on a backend that cannot chain-overlap "
                "(0.9x floor = outside the measured ±5% pair spread)")

    # --- sync-frontier legs: crash replay + failover, pipeline armed ---
    mk = dict(vocab_size=512, max_seq_len=96, dtype=jnp.float32,
              scan_layers=False)
    f_dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    f_params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(3), np.zeros((2, 8), np.int32))["params"]
    f_prompts = [[int(t) for t in rng.integers(0, 512, size=8)]
                 for _ in range(4)]
    f_trace = [(float(i), dict(prompt=p, max_new_tokens=24))
               for i, p in enumerate(f_prompts)]

    # deferral witness: step_enqueue must RETURN without paying the
    # device step + host sync a full step() serializes — a blocking
    # enqueue would read ~one sync step and the "pipeline" would be a
    # rename. Measured on a warm engine with live rows.
    from ray_lightning_tpu.serve import Request, ServeEngine
    w_eng = ServeEngine(f_dec, f_params, num_slots=2, prefill_len=16)
    try:
        for i, p in enumerate(f_prompts[:2]):
            w_eng.prefill([Request(id=i, prompt=p, max_new_tokens=60)])
        for _ in range(4):
            w_eng.step()  # warm
        step_walls, enq_walls = [], []
        for _ in range(8):
            t0 = time.perf_counter()
            w_eng.step()
            step_walls.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            h = w_eng.step_enqueue()
            enq_walls.append(time.perf_counter() - t0)
            w_eng.step_sync(h)
    finally:
        w_eng.shutdown()
    step_ms = 1e3 * float(np.median(step_walls))
    enq_ms = 1e3 * float(np.median(enq_walls))
    if enq_ms > 0.5 * step_ms:
        raise MeasurementError(
            f"async enqueue is not deferred: median step_enqueue wall "
            f"{enq_ms:.2f} ms vs full sync step {step_ms:.2f} ms — the "
            "handle must launch without paying the device step + host "
            "sync")

    def f_run(client_kwargs=None, fleet_kwargs=None, plan=None):
        if fleet_kwargs is not None:
            target = ReplicaFleet(f_dec, f_params, num_slots=3,
                                  prefill_len=16, async_dispatch=True,
                                  **fleet_kwargs)
        else:
            target = ServeClient(f_dec, f_params, num_slots=3,
                                 prefill_len=16, async_dispatch=True,
                                 **(client_kwargs or {}))
        try:
            if plan is not None:
                with plan.armed():
                    out = target.serve_trace(list(f_trace))
            else:
                out = target.serve_trace(list(f_trace))
            rebuilds = getattr(getattr(target, "engine", None),
                               "rebuilds", 0)
            failovers = getattr(target, "failovers", 0)
            return out, rebuilds, failovers
        finally:
            target.shutdown()

    ref, _, _ = f_run()
    chaos, rebuilds, _ = f_run(
        client_kwargs=dict(retry_policy=RetryPolicy(max_attempts=3,
                                                    base_delay=0.0)),
        plan=FaultPlan.at("serve.dispatch", [6]))
    failover, _, failovers = f_run(
        fleet_kwargs=dict(num_replicas=2, num_standby=1),
        plan=FaultPlan.at("serve.replica", [7]))
    replay_mm = sum(1 for r in ref if chaos[r].tokens != ref[r].tokens
                    or chaos[r].finish_reason == "failed")
    failover_mm = sum(1 for r in ref
                      if failover[r].tokens != ref[r].tokens
                      or failover[r].finish_reason == "failed")
    if rebuilds < 1 or failovers < 1:
        raise MeasurementError(
            f"async chaos legs did not exercise recovery (rebuilds="
            f"{rebuilds}, failovers={failovers}) — the pinned fault "
            "ticks no longer land with the pipeline armed")
    if replay_mm or failover_mm:
        raise MeasurementError(
            f"async sync-frontier recovery lost/flipped streams: "
            f"replay={replay_mm}, failover={failover_mm} — an in-flight "
            "dispatch must be discarded and regenerated by replay, "
            "never committed twice or dropped")

    split = {k: decode_split[k]
             for k in ("fixed_dispatch_ms", "host_sync_ms", "enqueue_ms")
             if isinstance(decode_split, dict) and k in decode_split}
    return {
        "model": "gpt2_small (bf16 serving params), t=0 burst, "
                 "uniform budgets (decode-dominated)",
        "num_slots": num_slots, "requests": n_requests,
        "prompt_len": prompt, "max_new_tokens": new_tokens,
        "useful_tokens": useful,
        "steps_per_dispatch_1": results[1],
        "steps_per_dispatch_4": results[4],
        "async_token_mismatches": mismatches,
        "sync_baseline_drift": baseline_drift,
        "replay_token_mismatches": replay_mm,
        "failover_token_mismatches": failover_mm,
        "async_chaos_rebuilds": rebuilds,
        "async_failovers": failovers,
        # the deferral witness: an enqueue returns in a fraction of a
        # full sync step (ENFORCED < 0.5x) — the structural proof the
        # handle defers the host sync instead of renaming it
        "sync_step_ms": round(step_ms, 2),
        "step_enqueue_ms": round(enq_ms, 2),
        # the overlap claim's floor: what the pipeline can hide per
        # dispatch (host_sync_ms) vs what it cannot (enqueue_ms), from
        # _bench_decode's differential attribution on this same host
        "dispatch_split": split,
        "note": "identity + lossless recovery + enqueue deferral "
                "ENFORCED; throughput ENFORCED only as ~free (>= 0.9x)"
                " and RECORDED — this CPU backend barely overlaps a "
                "dependent dispatch chain (independent 1.28x vs "
                "carry-chained 1.04x host-work overlap, measured), so "
                "the >= 1.15x target stays unmeasured, "
                "bounded by dispatch_split's host_sync_ms "
                "(docs/performance.md round 13)",
    }


def _bench_tenancy(num_slots: int = 2, prefill_len: int = 8,
                   bulk_requests: int = 10, fast_requests: int = 4,
                   bulk_new: int = 24, fast_new: int = 8) -> dict:
    """Multi-tenant SLO isolation (``tenant_classes=``) on a pinned
    mixed-class burst: a saturating batch flood (``bulk_requests`` x
    ``bulk_new`` tokens, all at t=0, several times the slot pool) with
    interactive requests trickling in while the backlog drains — the
    exact regime the tiered scheduler exists for. Tick clock
    throughout, so every latency below is a deterministic dispatch
    count, not wall noise.

    ENFORCED (``MeasurementError``):

    - **Interactive p99 TTFT bounded vs its solo run**: the mixed-run
      interactive p99 must come in under ``solo p99 + bulk_new +
      slack`` — the structural bound (a fast arrival waits at most one
      in-flight bulk request's remaining budget for a slot, never the
      backlog: tiers jump the queue, they don't preempt a slot).
      The same trace under plain FIFO is measured alongside and the
      tiered p99 must beat it by 2x — the isolation is real, not a
      bound both policies meet.
    - **Batch no-starvation**: every bulk request retires with
      ``finish_reason != "failed"`` (nothing starves behind the
      interactive tier — the starvation-credit escape hatch plus
      bounded interactive service guarantee drain).
    - **Per-class token identity**: every request's tokens — both
      classes, greedy — are identical to its solo run on an untenanted
      engine (0 mismatches; scheduling is ordering-only,
      docs/serving.md#multi-tenant-scheduling).

    Clients are released via try/finally (the PR 9 release rule).
    Untracked — the gates are the claim, the tick counts are recorded
    for trend visibility.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.serve import ServeClient, TenantClass

    mk = dict(vocab_size=512, max_seq_len=prefill_len + bulk_new,
              dtype=jnp.float32, scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(5),
        np.zeros((2, prefill_len), np.int32))["params"]
    classes = [TenantClass("fast", weight=4.0, tier="interactive"),
               TenantClass("bulk", weight=1.0, tier="batch")]

    rng = np.random.default_rng(7)
    # t=0 batch flood: bulk_requests x bulk_new tokens over num_slots
    # slots saturates the pool for ~bulk_requests*bulk_new/num_slots
    # ticks; the interactive arrivals land inside that window
    mixed = [(0.0, dict(prompt=[int(t) for t in rng.integers(
                            0, 512, size=prefill_len)],
                        max_new_tokens=bulk_new, tenant="bulk"))
             for _ in range(bulk_requests)]
    fast_at = [float(5 + 20 * i) for i in range(fast_requests)]
    fast_kw = [dict(prompt=[int(t) for t in rng.integers(
                        0, 512, size=prefill_len // 2)],
                    max_new_tokens=fast_new, tenant="fast")
               for _ in range(fast_requests)]
    mixed += [(t, kw) for t, kw in zip(fast_at, fast_kw)]
    fast_ids = list(range(bulk_requests,
                          bulk_requests + fast_requests))

    def run(trace, tenant_classes):
        client = ServeClient(dec, params, num_slots=num_slots,
                             prefill_len=prefill_len,
                             tenant_classes=tenant_classes)
        try:
            return client.serve_trace(
                [(t, dict(kw)) for t, kw in trace])
        finally:
            # a failing gate must not pin this engine's KV/params
            # through every later bench leg (the PR 9 release rule)
            client.shutdown()

    def p99(out, ids):
        ttfts = [out[r].time_to_first_token for r in ids]
        if any(t is None for t in ttfts):
            raise MeasurementError(
                f"tenancy bench: interactive request never streamed "
                f"a token (ttfts={ttfts})")
        return float(np.percentile(ttfts, 99))

    out = run(mixed, classes)
    # FIFO contrast: the same trace, classes stripped, tenancy off
    fifo = run([(t, {k: v for k, v in kw.items() if k != "tenant"})
                for t, kw in mixed], None)
    # interactive solo: only the fast requests, same arrival ticks
    solo_fast = run(list(zip(fast_at, fast_kw)), classes)
    solo_ids = list(range(fast_requests))

    fast_p99 = p99(out, fast_ids)
    fifo_p99 = p99(fifo, fast_ids)
    solo_p99 = p99(solo_fast, solo_ids)
    slack = 4.0  # prefill dispatch + alternation ticks
    if fast_p99 > solo_p99 + bulk_new + slack:
        raise MeasurementError(
            f"tenancy SLO isolation failed: mixed interactive p99 TTFT "
            f"{fast_p99} ticks vs solo {solo_p99} exceeds the "
            f"structural bound (+{bulk_new + slack} — one in-flight "
            "bulk budget of slot wait) — the interactive tier is not "
            "jumping the batch backlog")
    if fast_p99 * 2.0 > fifo_p99:
        raise MeasurementError(
            f"tenancy SLO isolation is not real: tiered interactive "
            f"p99 TTFT {fast_p99} ticks vs FIFO {fifo_p99} is under "
            "2x — the pinned saturating flood should separate the "
            "policies decisively")
    starved = [r for r in range(bulk_requests)
               if r not in out or out[r].finish_reason == "failed"]
    if starved:
        raise MeasurementError(
            f"tenancy batch starvation: bulk requests {starved} never "
            "retired cleanly under interactive pressure — the "
            "no-starvation bound is broken")

    # per-class token identity vs solo runs on ONE untenanted engine,
    # one request at a time (seed pinned to the mixed run's id-seed —
    # tokens are a pure function of (engine seed, request seed, step),
    # so a drained engine between runs is exactly a fresh one)
    mismatches = 0
    solo = ServeClient(dec, params, num_slots=num_slots,
                       prefill_len=prefill_len)
    try:
        for rid, (_t, kw) in enumerate(mixed):
            sid = solo.submit(
                prompt=kw["prompt"], max_new_tokens=kw["max_new_tokens"],
                seed=rid)
            ref = solo.run_until_idle()[sid]
            if out[rid].tokens != ref.tokens:
                mismatches += 1
    finally:
        solo.shutdown()
    if mismatches:
        raise MeasurementError(
            f"tenancy flipped {mismatches} greedy streams vs solo "
            "runs — scheduling must be ordering-only")

    return {
        "model": "gpt2_nano f32 (tick clock — deterministic counts)",
        "num_slots": num_slots,
        "bulk": {"requests": bulk_requests, "max_new_tokens": bulk_new,
                 "class": "bulk (batch, w=1)"},
        "fast": {"requests": fast_requests, "max_new_tokens": fast_new,
                 "class": "fast (interactive, w=4)"},
        "interactive_p99_ttft_ticks": fast_p99,
        "interactive_p99_ttft_ticks_solo": solo_p99,
        "interactive_p99_ttft_ticks_fifo": fifo_p99,
        "batch_starved": 0,
        "tenancy_token_mismatches": 0,
        "note": "interactive p99 bounded vs solo (one bulk budget of "
                "slot wait, ENFORCED) and >= 2x under FIFO's "
                "(ENFORCED); batch no-starvation + per-class token "
                "identity ENFORCED; tick clock, so every count is "
                "deterministic",
    }


def _bench_lora(num_slots: int = 6, prefill_len: int = 8,
                new_tokens: int = 24, rank: int = 8,
                reps: int = 2) -> dict:
    """Batched multi-LoRA serving (``adapters=`` + per-row bank gather)
    on a pinned mixed trace: six greedy requests landing at t=0, two
    bound to adapter ``a``, two to ``b``, two to the null adapter — one
    engine, one dispatch stream — against the pre-bank deployment
    shape: one engine PER adapter (plus a bankless one for base
    traffic) serving the same rows sequentially. Fixed-shape dispatch
    cost is batch-size-invariant, so the mixed batch runs ~one
    program's dispatch stream where the solo fleet runs three; the
    recorded ratio is that dispatch-amortization statement (host/CPU
    regime — not a TPU number; engine builds excluded, which favors
    the solo side, it builds 3x the engines).

    ENFORCED (``MeasurementError``):

    - **Per-row token identity**: every mixed-batch request — adapter
      rows AND null rows — emits exactly its solo engine's tokens
      (``lora_token_mismatches`` must be 0; batching adapters is an
      ordering/residency concern only,
      docs/serving.md#multi-lora-serving).
    - **Bank byte floor**: ``engine.adapter_bank_bytes()`` equals
      ``capacity * adapter_bytes(params)`` exactly — the resident bank
      is the accounted arena, no hidden per-adapter copies.
    - **Eviction determinism, twice over**: the same registry
      admit/bind script replayed on two fresh
      :class:`~ray_lightning_tpu.serve.adapters.AdapterRegistry`
      instances yields identical (index, victim) sequences matching
      the pinned expectation, and a hot ``load_adapter`` into the
      full, drained engine evicts exactly the least-recently-bound
      resident ("a": the trace binds it first).

    Clients are released via try/finally (the PR 9 release rule).
    Untracked — the gates are the claim.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.lora import (LoraConfig, adapter_bytes,
                                               extract_adapter,
                                               install_lora_bank)
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.serve import AdapterRegistry, ServeClient

    mk = dict(vocab_size=512, max_seq_len=prefill_len + new_tokens,
              dtype=jnp.float32, scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(5),
        np.zeros((2, prefill_len), np.int32))["params"]

    def rand_adapter(seed):
        # a publishable adapter with non-trivial weights: graft a
        # 1-slot bank, slice it out, fill it with seeded noise
        tree = extract_adapter(install_lora_bank(
            params, LoraConfig(rank=rank, num_adapters=1)), 0)

        def rnd(t, key):
            out = {}
            for k, v in sorted(t.items()):
                key, sub = jax.random.split(key)
                out[k] = (rnd(v, sub) if isinstance(v, dict) else
                          0.3 * jax.random.normal(sub, v.shape, v.dtype))
            return out
        return rnd(tree, jax.random.PRNGKey(seed))

    adapters = {"a": rand_adapter(1), "b": rand_adapter(2)}
    armed = dict(num_slots=num_slots, prefill_len=prefill_len,
                 max_resident_adapters=2, lora_rank=rank)
    rng = np.random.default_rng(11)
    names = ["a", "a", "b", "b", None, None]
    trace = [(0.0, dict(prompt=[int(t) for t in rng.integers(
                            0, 512, size=prefill_len)],
                        max_new_tokens=new_tokens, seed=rid,
                        **({"adapter": nm} if nm else {})))
             for rid, nm in enumerate(names)]
    total_tokens = len(trace) * new_tokens

    mixed = ServeClient(dec, params, adapters=adapters, **armed)
    solo = {nm: ServeClient(
                dec, params,
                **(dict(armed, adapters={nm: adapters[nm]}) if nm else
                   dict(num_slots=num_slots, prefill_len=prefill_len)))
            for nm in ("a", "b", None)}
    try:
        def run_mixed():
            return mixed.serve_trace([(t, dict(kw)) for t, kw in trace])

        def run_solo():
            out = {}
            for nm, client in solo.items():
                ids = {}
                for rid, (_t, kw) in enumerate(trace):
                    if kw.get("adapter") != nm:
                        continue
                    ids[client.submit(**dict(kw))] = rid
                done = client.run_until_idle()
                out.update({rid: done[sid] for sid, rid in ids.items()})
            return out

        def timed(fn):
            best, result = float("inf"), None
            for _ in range(reps):
                t0 = time.perf_counter()
                result = fn()
                best = min(best, time.perf_counter() - t0)
            return best, result

        run_mixed(), run_solo()  # warmup: compiles paid off-clock
        t_mixed, out = timed(run_mixed)
        t_solo, ref = timed(run_solo)

        mismatches = sum(out[rid].tokens != ref[rid].tokens
                         for rid in range(len(trace)))
        if mismatches:
            raise MeasurementError(
                f"multi-LoRA batching flipped {mismatches} greedy "
                "streams vs solo single-adapter engines — the per-row "
                "bank gather must be exact")

        eng = mixed.engine
        per = adapter_bytes(eng.params)
        bank = eng.adapter_bank_bytes()
        if per <= 0 or bank != 2 * per:
            raise MeasurementError(
                f"adapter bank byte accounting broke its floor: bank "
                f"{bank} B vs capacity 2 x {per} B/adapter — "
                "adapter_bank_bytes() must be exactly capacity * "
                "adapter_bytes(params)")

        # eviction determinism #1: same registry script, two fresh
        # instances, one pinned answer
        def script(reg):
            steps = [reg.admit("a"), reg.admit("b")]
            reg.bind("a"); reg.unbind("a")
            steps += [reg.admit("c"), reg.admit("d")]
            return steps, reg.residents
        first, second = script(AdapterRegistry(2)), script(AdapterRegistry(2))
        pinned = ([(0, None), (1, None), (1, "b"), (0, "a")], ["c", "d"])
        if first != second or first != pinned:
            raise MeasurementError(
                f"registry eviction is not deterministic: replayed "
                f"script gave {first} then {second}, pinned {pinned}")

        # eviction determinism #2: hot load into the full, drained
        # engine — the trace binds "a" before "b", so "a" is the
        # least-recently-bound resident and must be the victim
        evicted = mixed.load_adapter("c", rand_adapter(3))
        if evicted != "a" or eng.resident_adapters != ["b", "c"]:
            raise MeasurementError(
                f"hot-load eviction picked {evicted!r} (residents now "
                f"{eng.resident_adapters}) — the pinned trace binds "
                "'a' first, so LRU eviction must take 'a'")
    finally:
        mixed.shutdown()
        for client in solo.values():
            client.shutdown()

    return {
        "model": "gpt2_nano f32 (host/CPU regime — dispatch-count "
                 "statement, not a TPU number)",
        "num_slots": num_slots,
        "lora_rank": rank,
        "trace": "6 greedy rows at t=0: 2x adapter a, 2x b, 2x null",
        "mixed_tokens_per_sec": total_tokens / t_mixed,
        "solo_fleet_tokens_per_sec": total_tokens / t_solo,
        "mixed_vs_solo_speedup": t_solo / t_mixed,
        "lora_token_mismatches": 0,
        "adapter_bytes_per_adapter": per,
        "adapter_bank_bytes": bank,
        "eviction_victim": "a",
        "note": "per-row token identity vs solo engines ENFORCED; bank "
                "bytes ENFORCED at capacity * adapter_bytes(); "
                "eviction determinism ENFORCED (registry replay + "
                "pinned hot-load victim); speedup is one dispatch "
                "stream vs three engines' — fixed shapes make dispatch "
                "cost batch-invariant, which is the whole point of "
                "batching adapters",
    }


def _bench_chaos(num_slots: int = 4, n_requests: int = 8,
                 prompt: int = 32, new_tokens: int = 32,
                 steps_per_dispatch: int = 4) -> dict:
    """Serving under a pinned fault plan: throughput tax + recovery cost.

    The same continuous-batching setup as ``_bench_serve`` (GPT-2-small,
    bf16 serving params, greedy), driven twice over one deterministic
    all-at-once burst: once clean, once with a PINNED
    ``FaultPlan.random(seed=0)`` injecting 3 dispatch crashes that the
    :class:`ServeSupervisor` must absorb (rebuild engine, replay every
    in-flight prompt + emitted tokens, continue). Recovery must lose no
    requests; token flips (possible here because bf16 + untrained
    weights put greedy argmax margins below rounding — see the inline
    note) are recorded as ``replay_token_mismatches``.

    ``extras["chaos"]``: ``serve_tokens_per_sec`` under faults,
    ``recovery_ms`` (mean wall per recovery: rebuild + replay prefills),
    and ``chaos_slowdown`` vs the clean run. NOT in ``tracked_extras``
    (no regression gate yet): recovery cost is dominated by engine
    rebuild/compile behavior that varies across environments — recorded
    for trend visibility first.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.reliability import FaultPlan, RetryPolicy
    from ray_lightning_tpu.serve import FINISH_FAILED, ServeClient

    total = prompt + new_tokens
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.bfloat16,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, toks0)["params"]))(jax.random.PRNGKey(0)))
    dec = TransformerLM(gpt2_config("small", decode=True,
                                    param_dtype=jnp.bfloat16, **base))

    rng = np.random.default_rng(2)
    trace = []
    for _ in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.0, dict(
            prompt=[int(t) for t in rng.integers(0, 50257, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)))))

    def run(plan=None):
        # prefill_len covers prompt + full budget: the supervisor replays
        # a request as prompt + emitted tokens through ONE prefill pass,
        # so a window sized to prompts alone would shed mid-decode
        # requests as unreplayable (the docs/reliability.md sizing rule)
        client = ServeClient(
            dec, params, num_slots=num_slots, prefill_len=total,
            steps_per_dispatch=steps_per_dispatch,
            clock=time.perf_counter,
            retry_policy=RetryPolicy(max_attempts=3, base_delay=0.0))
        if plan is None:
            out = client.serve_trace(trace)
        else:
            with plan.armed():
                out = client.serve_trace(trace)
        makespan = max(c.finish_time for c in out.values())
        return client, out, makespan

    run()  # warmup: compiles prefill+inject and the K-step program
    _, base_out, base_makespan = run()

    # ~3 crashes into a run of this size: horizon sized to land inside
    # the burst's dispatch count at these knobs (seed 0 -> ticks 5/6/8)
    plan = FaultPlan.random(0, 3, sites=("serve.dispatch",), horizon=10)
    sup_client, out, makespan = run(plan)
    sup = sup_client.engine  # the ServeSupervisor
    if plan.fired < 3:
        raise MeasurementError(
            f"fault plan fired {plan.fired}/3 — horizon no longer "
            "matches the dispatch count; retune _bench_chaos knobs")
    # Replay token-identity is pinned EXACTLY in fp32 by
    # tests/test_reliability.py. This bench runs bf16 with UNTRAINED
    # random weights, where greedy top-1 margins over a 50k vocab sit
    # below bf16 rounding — a replayed prefill's last-bit KV differences
    # (batched matmul vs step-by-step accumulation order) can then flip
    # a token. Record the flip count; fail only on the signals that mean
    # recovery itself broke (failed requests / wholesale divergence).
    mismatched = sum(1 for rid, comp in base_out.items()
                     if out[rid].tokens != comp.tokens)
    failed = sum(1 for c in out.values()
                 if c.finish_reason == FINISH_FAILED)
    if failed or mismatched > n_requests // 2:
        raise MeasurementError(
            f"recovery lost work ({failed} failed, {mismatched}/"
            f"{n_requests} diverged) — replay is broken, timing numbers "
            "would be meaningless")

    tokens_total = sum(len(c.tokens) for c in out.values())
    return {
        "model": "gpt2_small (bf16 serving params)",
        "num_slots": num_slots, "requests": n_requests,
        "steps_per_dispatch": steps_per_dispatch,
        "faults_injected": plan.fired,
        "recoveries": sup.recoveries,
        "engine_rebuilds": sup.rebuilds,
        "replay_token_mismatches": mismatched,
        "serve_tokens_per_sec": round(tokens_total / makespan, 0),
        "faultfree_tokens_per_sec": round(
            tokens_total / base_makespan, 0),
        "chaos_slowdown": round(makespan / base_makespan, 2),
        "recovery_ms": round(
            1e3 * sup.recovery_s_total / max(1, sup.recoveries), 1),
    }


def _bench_chaos_poison(num_replicas: int = 3, n_requests: int = 9,
                        prompt: int = 32, new_tokens: int = 24,
                        steps_per_dispatch: int = 4,
                        max_request_failovers: int = 3) -> dict:
    """Poison containment under load: bounded blast radius, innocents
    exact (PR 18).

    A ``num_replicas`` in-process :class:`ReplicaFleet` (GPT-2-small,
    **fp32** — innocents must be checkable token-for-token, the
    ``_bench_fleet`` rule) serves a pinned mixed trace twice: once
    clean, once with one request turned into a poison pill
    (``FaultPlan(poison=...)`` — it kills every engine that seats it,
    every time). Containment is ENFORCED, not just recorded: the poison
    must retire ``finish_reason="failed"`` having consumed at most
    ``max_request_failovers`` replica kills, and every innocent request
    must finish with **zero** token mismatches against the clean run —
    a violation raises :class:`MeasurementError` because every other
    number in ``extras["chaos"]`` presumes recovery works.

    ``extras["chaos"]["poison"]``: ``poison_tokens_per_sec`` (innocent
    tokens only, under containment), ``containment_slowdown`` vs clean,
    ``replicas_lost`` (== failovers consumed by containment), and the
    enforced invariants echoed as numbers."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.reliability import FaultPlan
    from ray_lightning_tpu.serve import (FINISH_FAILED, FleetConfig,
                                         ReplicaFleet)

    total = prompt + new_tokens
    num_slots = 4  # per replica
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.float32,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(
        model.init(jax.random.PRNGKey(0), toks0)["params"])
    dec = TransformerLM(gpt2_config("small", decode=True, **base))

    rng = np.random.default_rng(18)
    trace = []
    for i in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.02 * i, dict(
            prompt=[int(t) for t in rng.integers(0, 50257, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)))))
    poison_id = n_requests // 2  # mid-trace: lands on a warm fleet

    kw = dict(num_slots=num_slots, prefill_len=total,
              steps_per_dispatch=steps_per_dispatch)
    cfg = FleetConfig(max_request_failovers=max_request_failovers,
                      probation_after=2)

    def run_fleet(plan=None):
        fleet = ReplicaFleet(dec, params, num_replicas=num_replicas,
                             num_standby=1, clock=time.perf_counter,
                             fleet_config=cfg, **kw)
        if plan is None:
            out = fleet.serve_trace(trace)
        else:
            with plan.armed():
                out = fleet.serve_trace(trace)
        makespan = max(c.finish_time for c in out.values())
        fleet.shutdown()
        return fleet, out, makespan

    run_fleet()  # warmup: compiles prefill+inject and the K-step program
    _, clean_out, clean_makespan = run_fleet()

    fleet, out, makespan = run_fleet(FaultPlan(poison=(poison_id,)))
    if out[poison_id].finish_reason != FINISH_FAILED \
            or fleet.poison_failed != 1:
        raise MeasurementError(
            f"poison request {poison_id} finished "
            f"{out[poison_id].finish_reason!r} (poison_failed="
            f"{fleet.poison_failed}) — containment never retired it")
    if fleet.failovers > max_request_failovers:
        raise MeasurementError(
            f"poison consumed {fleet.failovers} replicas, budget is "
            f"{max_request_failovers} — the failover budget leaked")
    innocents = [rid for rid in clean_out if rid != poison_id]
    mismatched = sum(1 for rid in innocents
                     if out[rid].tokens != clean_out[rid].tokens)
    failed = sum(1 for rid in innocents
                 if out[rid].finish_reason == FINISH_FAILED)
    if failed or mismatched:
        raise MeasurementError(
            f"containment harmed innocents ({failed} failed, "
            f"{mismatched}/{len(innocents)} diverged in fp32) — "
            "isolation is broken, timing numbers would be meaningless")

    innocent_tokens = sum(len(out[rid].tokens) for rid in innocents)
    return {
        "model": "gpt2_small (fp32 serving params)",
        "replicas": num_replicas, "slots_per_replica": num_slots,
        "requests": n_requests, "poison_id": poison_id,
        "max_request_failovers": max_request_failovers,
        "replicas_lost": fleet.failovers,
        "poison_failed": fleet.poison_failed,
        "innocent_token_mismatches": mismatched,
        "poison_tokens_per_sec": round(innocent_tokens / makespan, 0),
        "containment_slowdown": round(makespan / clean_makespan, 2),
    }


def _bench_driver_restart(num_slots: int = 4, prompt: int = 24,
                          new_tokens: int = 24,
                          steps_per_dispatch: int = 4,
                          kill_tick: int = 5) -> dict:
    """Driver-death survival: journal write tax + warm-restart cost (PR 20).

    A ``num_slots`` all-at-once burst (GPT-2-small, **fp32** — restart
    identity must be checkable token-for-token, the ``_bench_fleet``
    rule; greedy AND sampled rows) served three ways: disarmed
    (``journal=None`` baseline), journal-armed at maximum durability
    (``sync_every=1`` — every record fsync'd, the worst-case write
    tax recorded as ``journal_overhead_pct``), and journal-armed under
    a seeded mid-decode driver kill (``FaultPlan.at("serve.driver",
    [kill_tick])`` — the in-process stand-in for SIGKILL; the real-kill
    path is pinned by ``tests/test_journal.py``). The kill leg then
    warm-restarts via :meth:`ServeClient.restore` and decomposes the
    cost: ``restore_rebuild_ms`` (fold the WAL + build the cold engine
    + re-admit) vs ``restore_replay_ms`` (re-feed every journaled
    ``prompt + frontier`` through prefill until each replayed request
    is back at its kill-point frontier).

    ENFORCED, not just recorded — a violation raises
    :class:`MeasurementError`: the merged pre-kill + post-restore
    output must have **zero** token mismatches against the clean run,
    the dead driver's completions and the restored driver's must not
    overlap (no double emission), and the final journal must fold with
    **zero** duplicate retirements. Untracked (restore cost is
    dominated by engine rebuild/compile behavior, the
    ``_bench_chaos`` rule)."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.reliability import FaultPlan
    from ray_lightning_tpu.reliability.faults import InjectedFault
    from ray_lightning_tpu.serve import (Journal, ServeClient,
                                         read_journal)

    total = prompt + new_tokens
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.float32,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(
        model.init(jax.random.PRNGKey(0), toks0)["params"])
    dec = TransformerLM(gpt2_config("small", decode=True, **base))

    rng = np.random.default_rng(20)
    trace = []
    for i in range(num_slots):  # one burst, everything seats at tick 1
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.0, dict(
            prompt=[int(t) for t in rng.integers(0, 50257, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)),
            temperature=0.0 if i % 2 == 0 else 0.8,
            top_k=None if i % 2 == 0 else 20,
            seed=100 + i)))

    # prefill_len covers prompt + full budget: restart replays a
    # request as prompt + journaled frontier through ONE prefill pass
    # (the docs/reliability.md replay-window sizing rule)
    kw = dict(num_slots=num_slots, prefill_len=total,
              steps_per_dispatch=steps_per_dispatch,
              clock=time.perf_counter)

    def run(journal=None):
        client = ServeClient(dec, params, journal=journal, **kw)
        out = client.serve_trace(trace)
        return client, out, max(c.finish_time for c in out.values())

    run()  # warmup: compiles prefill+inject and the K-step program
    _, clean_out, clean_makespan = run()

    wal = os.path.join(tempfile.mkdtemp(prefix="tl_bench_wal_"), "j.wal")
    armed_j = Journal(wal + ".overhead", sync_every=1)
    armed_client, armed_out, armed_makespan = run(journal=armed_j)
    armed_client.shutdown()
    if any(armed_out[r].tokens != clean_out[r].tokens for r in clean_out):
        raise MeasurementError(
            "journal-armed run diverged from disarmed — journaling "
            "must never touch tokens")

    # the kill leg: seeded mid-decode driver death, then warm restart
    journal = Journal(wal, sync_every=1)
    kill_client = ServeClient(dec, params, journal=journal, **kw)
    plan = FaultPlan.at("serve.driver", [kill_tick])
    try:
        with plan.armed():
            kill_client.serve_trace(trace)
        raise MeasurementError(
            f"driver kill at tick {kill_tick} never fired — the burst "
            "drained first; retune _bench_driver_restart knobs")
    except InjectedFault:
        pass
    pre = dict(kill_client.completions)  # already in the caller's hands
    need = {req.id: len(toks)
            for req, toks in read_journal(wal).pending()}
    if not pre or not need:
        raise MeasurementError(
            f"kill tick {kill_tick} split nothing ({len(pre)} retired, "
            f"{len(need)} mid-flight) — retune _bench_driver_restart "
            "knobs so the kill lands mid-decode")

    t0 = time.perf_counter()
    restored = ServeClient.restore(wal, dec, params, sync_every=1, **kw)
    t1 = time.perf_counter()
    while True:  # replay done: every journaled frontier re-established
        flight = {req.id: len(toks) for req, toks
                  in restored.engine.snapshot_in_flight()}
        if all(rid in restored.completions or flight.get(rid, -1) >= k
               for rid, k in need.items()):
            break
        restored.tick()
    t2 = time.perf_counter()
    post = restored.run_until_idle()
    restored.shutdown()

    if set(pre) & set(post):
        raise MeasurementError(
            f"requests {sorted(set(pre) & set(post))} emitted by BOTH "
            "the dead and the restored driver — exactly-once broke")
    merged = dict(pre)
    merged.update(post)
    mismatched = sum(1 for rid in clean_out
                     if merged[rid].tokens != clean_out[rid].tokens)
    final = read_journal(wal)
    if mismatched or final.duplicate_retires:
        raise MeasurementError(
            f"warm restart broke the contract ({mismatched} token "
            f"mismatches in fp32, {final.duplicate_retires} duplicate "
            "retirements) — timing numbers would be meaningless")

    return {
        "model": "gpt2_small (fp32 serving params)",
        "num_slots": num_slots, "requests": len(trace),
        "steps_per_dispatch": steps_per_dispatch,
        "sync_every": 1,
        "journal_records": armed_j.records,
        "journal_syncs": armed_j.syncs,
        "journal_overhead_pct": round(
            100.0 * (armed_makespan / clean_makespan - 1.0), 1),
        "kill_tick": kill_tick,
        "retired_before_kill": len(pre),
        "replayed_requests": len(need),
        "restore_rebuild_ms": round(1e3 * (t1 - t0), 1),
        "restore_replay_ms": round(1e3 * (t2 - t1), 1),
        "restore_ms": round(1e3 * (t2 - t0), 1),
        "replay_token_mismatches": mismatched,
        "duplicate_retirements": final.duplicate_retires,
    }


def _bench_fleet(num_replicas: int = 3, n_requests: int = 12,
                 prompt: int = 32, new_tokens: int = 32,
                 steps_per_dispatch: int = 4) -> dict:
    """Replica-fleet serving under a seeded replica kill (ROADMAP item 2).

    A ``num_replicas`` :class:`ReplicaFleet` (GPT-2-small, **fp32**
    serving params — failover replay must be checkable token-for-token,
    and bf16 greedy margins on untrained weights sit below rounding,
    see ``_bench_chaos``) serves the same pinned staggered trace three
    ways: one clean fleet pass, one with a pinned
    ``FaultPlan.at("serve.replica", ...)`` killing a replica mid-run
    (its in-flight requests re-admit to survivors via replay, a warm
    standby is promoted), and one single-engine :class:`ServeClient`
    with the fleet's total slot count for the scaling reference.

    ``extras["fleet"]`` (untracked — failover cost is dominated by
    engine construction/compile behavior, recorded for trend
    visibility): ``fleet_tokens_per_sec`` (under the kill) /
    ``fleet_clean_tokens_per_sec`` / ``single_engine_tokens_per_sec``
    and their ratio, ``fleet_failover_ms`` (snapshot + teardown +
    replay re-admission + standby promotion, from the fleet's own
    ``failover_s_total``), and ``readmitted_token_mismatches`` — which
    MUST be 0 in fp32: a non-zero count means failover replay broke and
    every other number here is meaningless (enforced)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.reliability import FaultPlan
    from ray_lightning_tpu.serve import (FINISH_FAILED, ReplicaFleet,
                                         ServeClient)

    total = prompt + new_tokens
    num_slots = 4  # per replica
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.float32,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(
        model.init(jax.random.PRNGKey(0), toks0)["params"])
    dec = TransformerLM(gpt2_config("small", decode=True, **base))

    rng = np.random.default_rng(4)
    trace = []
    for i in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.02 * i, dict(
            prompt=[int(t) for t in rng.integers(0, 50257, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)))))

    # prefill_len covers prompt + full budget: the replay window rule
    # (docs/reliability.md) — a mid-decode victim re-feeds prompt +
    # emitted through ONE prefill pass on its new replica
    kw = dict(num_slots=num_slots, prefill_len=total,
              steps_per_dispatch=steps_per_dispatch)

    def run_fleet(plan=None):
        fleet = ReplicaFleet(dec, params, num_replicas=num_replicas,
                             num_standby=1, clock=time.perf_counter, **kw)
        if plan is None:
            out = fleet.serve_trace(trace)
        else:
            with plan.armed():
                out = fleet.serve_trace(trace)
        makespan = max(c.finish_time for c in out.values())
        fleet.shutdown()
        return fleet, out, makespan

    run_fleet()  # warmup: compiles prefill+inject and the K-step program
    _, clean_out, clean_makespan = run_fleet()

    # the kill lands a few rounds in: with num_replicas live replicas
    # firing per fleet tick, tick 3*num_replicas+1 is replica 1 on
    # fleet round 3 — mid-run, slots occupied
    plan = FaultPlan.at("serve.replica", [3 * num_replicas + 1])
    fleet, out, makespan = run_fleet(plan)
    if plan.fired != 1 or fleet.failovers != 1:
        raise MeasurementError(
            f"fault plan fired {plan.fired}, failovers "
            f"{fleet.failovers} — the kill tick no longer lands inside "
            "the run; retune _bench_fleet knobs")
    mismatched = sum(1 for rid, comp in clean_out.items()
                     if out[rid].tokens != comp.tokens)
    failed = sum(1 for c in out.values()
                 if c.finish_reason == FINISH_FAILED)
    if failed or mismatched:
        raise MeasurementError(
            f"fleet failover lost work ({failed} failed, {mismatched}/"
            f"{n_requests} diverged in fp32) — replay is broken, timing "
            "numbers would be meaningless")

    def run_single():
        client = ServeClient(dec, params, clock=time.perf_counter,
                             **{**kw, "num_slots":
                                num_slots * num_replicas})
        single_out = client.serve_trace(trace)
        makespan = max(c.finish_time for c in single_out.values())
        client.shutdown()
        return makespan

    # the 12-slot shapes compile fresh (the fleet warmup only built the
    # per-replica 4-slot programs): warm this leg too or its makespan
    # eats the XLA compile and flatters the fleet ratio
    run_single()
    single_makespan = run_single()

    tokens_total = sum(len(c.tokens) for c in out.values())
    return {
        "model": "gpt2_small (fp32 serving params)",
        "replicas": num_replicas, "slots_per_replica": num_slots,
        "requests": n_requests,
        "steps_per_dispatch": steps_per_dispatch,
        "fleet_tokens_per_sec": round(tokens_total / makespan, 0),
        "fleet_clean_tokens_per_sec": round(
            tokens_total / clean_makespan, 0),
        "single_engine_tokens_per_sec": round(
            tokens_total / single_makespan, 0),
        "fleet_vs_single_engine": round(
            single_makespan / clean_makespan, 2),
        "fleet_failover_ms": round(1e3 * fleet.failover_s_total, 1),
        "readmitted_requests": fleet.readmitted,
        "readmitted_token_mismatches": mismatched,
    }


def _bench_gang() -> dict:
    """Gang kill-and-restart cost on the process backend: cold vs warm.

    One OS-process worker fits a BoringModel (3 epochs x 4 batches)
    under :class:`GangSupervisor`: clean, then with a pinned
    ``worker.exit`` fault hard-killing the worker at batch tick 9 of 12
    — inside the final epoch (``os._exit``, the OOM/preemption death).
    The supervisor detects the dead actor, tears the gang down,
    re-launches on a fresh rendezvous, and resumes from the step-8
    (end-of-epoch) checkpoint, re-running only the last epoch.
    ``gang_recovery_ms`` is the extra wall the faulted run pays over the
    clean one — detection + teardown + respawn (interpreter/jax cold
    start dominates) + the ~1-epoch resume.

    The **warm** pair repeats both runs with a prefilled
    :class:`StandbyPool` (2 standbys — the restart's rank slot is
    guaranteed a warm promotion, no refill race): the recovery path
    pays promotion instead of actor spawn, so ``gang_recovery_warm_ms``
    should be bounded by detection + teardown + the 1-epoch resume —
    the "no actor-spawn on the critical path" claim (the background
    refill overlaps the resumed epoch and is excluded by stopping the
    timer before pool shutdown). Untracked (no regression gate): spawn
    cost is environment noise; recorded for trend visibility.
    """
    import shutil
    import tempfile

    from ray_lightning_tpu import (GangConfig, GangSupervisor,
                                   ModelCheckpoint, RayStrategy,
                                   RetryPolicy, Trainer)
    from ray_lightning_tpu.launchers.process_backend import ProcessRay
    from ray_lightning_tpu.launchers.ray_launcher import (ExecutorBase,
                                                          RayLauncher)
    from ray_lightning_tpu.models import BoringModel
    from ray_lightning_tpu.reliability import FaultPlan, StandbyPool

    # the worker is CPU by design: this leg times detection + respawn +
    # resume (host work), and the parent holds the chip
    worker_env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }

    def worker_device() -> dict:
        """What a worker spawned under ``worker_env`` reports as ITS
        device — asked of a real worker process, not assumed here."""
        ray_mod = ProcessRay(worker_env=dict(worker_env))
        ray_mod.init()
        try:
            actor = ray_mod.remote(ExecutorBase).options().remote()
            return ray_mod.get(actor.execute.remote(_device_fields))
        finally:
            ray_mod.shutdown()

    def run(plan, num_standby=0):
        root = tempfile.mkdtemp(prefix="tl_bench_gang_")
        ray_mod = ProcessRay(worker_env=dict(worker_env))
        ray_mod.init()
        pool = None
        if num_standby:
            pool = StandbyPool(ray_mod, num_standby=num_standby)
            pool.fill(lambda: ray_mod.remote(
                ExecutorBase).options().remote())

        def make_trainer():
            strategy = RayStrategy(num_workers=1)
            trainer = Trainer(
                strategy=strategy, max_epochs=3, seed=0,
                limit_train_batches=4, limit_val_batches=0,
                callbacks=[ModelCheckpoint(
                    dirpath=os.path.join(root, "ck"))],
                default_root_dir=root)
            trainer._launcher = RayLauncher(
                strategy, ray_module=ray_mod,
                gang=GangConfig(heartbeat_timeout=120.0), standby=pool)
            return trainer

        sup = GangSupervisor(make_trainer,
                             RetryPolicy(max_attempts=3, base_delay=0.0),
                             sleep=lambda s: None, standby=pool)
        t0 = time.perf_counter()
        try:
            if plan is None:
                sup.fit(BoringModel)
            else:
                with plan.armed():
                    sup.fit(BoringModel)
            elapsed = time.perf_counter() - t0  # refill tail excluded
        finally:
            if pool is not None:
                pool.shutdown()
            ray_mod.shutdown()
            shutil.rmtree(root, ignore_errors=True)
        return elapsed, sup, pool

    plan = lambda: FaultPlan.at("worker.exit", [9], mode="exit")  # noqa: E731
    clean_s, _, _ = run(None)
    fault_s, sup, _ = run(plan())
    if sup.restarts != 1 or not sup.failures:
        raise MeasurementError(
            f"gang scenario expected exactly 1 restart, saw "
            f"{sup.restarts} (failures: {len(sup.failures)}) — the "
            "pinned fault tick no longer lands past the last "
            "epoch-boundary checkpoint")
    warm_clean_s, _, _ = run(None, num_standby=2)
    warm_fault_s, warm_sup, warm_pool = run(plan(), num_standby=2)
    if warm_sup.restarts != 1 or warm_pool.promotions < 2:
        raise MeasurementError(
            f"warm gang scenario expected 1 restart with a warm "
            f"promotion, saw restarts={warm_sup.restarts} "
            f"promotions={warm_pool.promotions}")
    out = {
        "backend": "process (1 OS-process worker)",
        **worker_device(),
        "fault": "worker.exit tick 9 of 12 (os._exit in the final epoch)",
        "restarts": sup.restarts,
        "attempts": sup.attempts,
        "failure_reason": sup.failures[0].reason,
        "faultfree_fit_s": round(clean_s, 2),
        "faulted_fit_s": round(fault_s, 2),
        "gang_recovery_ms": round(1e3 * max(0.0, fault_s - clean_s), 1),
        "standby_promotions": warm_pool.promotions,
        "warm_faultfree_fit_s": round(warm_clean_s, 2),
        "warm_faulted_fit_s": round(warm_fault_s, 2),
        "gang_recovery_warm_ms": round(
            1e3 * max(0.0, warm_fault_s - warm_clean_s), 1),
    }
    out["elastic"] = _run_gang_elastic_child()
    return out


def _gang_elastic_child() -> None:
    """N→M elastic-resume cost, in a forced-8-CPU-device child.

    A 4-way FSDP fit (params + optimizer state sharded over ``fsdp=4``)
    saves an epoch-boundary checkpoint; losing half the capacity is then
    simulated by resuming the SAME checkpoint at world size 2 — build
    trainer, re-shard-restore, re-run the final epoch.
    ``gang_recovery_elastic_ms`` is that resume's wall; the 4-way resume
    of the identical checkpoint is the same-size baseline, so the
    difference isolates what shrinking the world actually costs
    (re-shard placement + the smaller mesh's step). Restored params are
    verified element-identical to the checkpoint before timing counts.
    """
    import shutil
    import tempfile

    import jax

    from flax import serialization
    from ray_lightning_tpu import FSDPStrategy, ModelCheckpoint, Trainer
    from ray_lightning_tpu.core.checkpoint import (find_resume_candidates,
                                                   load_sharded_checkpoint)
    from ray_lightning_tpu.models import BoringModel

    root = tempfile.mkdtemp(prefix="tl_bench_elastic_")
    ck = os.path.join(root, "ck")

    def make(world, max_epochs):
        return Trainer(strategy=FSDPStrategy(num_workers=world,
                                             use_tpu=False),
                       max_epochs=max_epochs, seed=0,
                       limit_train_batches=4, limit_val_batches=0,
                       callbacks=[ModelCheckpoint(dirpath=ck,
                                                  save_format="orbax")],
                       default_root_dir=root)

    try:
        make(4, 2).fit(BoringModel())
        path = find_resume_candidates(ck)[0]
        host = load_sharded_checkpoint(path)

        def resume(world):
            t0 = time.perf_counter()
            trainer = make(world, 3)
            trainer.fit(BoringModel(), ckpt_path=path)
            jax.block_until_ready(trainer.train_state.params)
            return time.perf_counter() - t0, trainer

        # honesty gate FIRST, on a pure restore (no epochs left to
        # train): the 2-way re-shard must hold the checkpoint's exact
        # values before its resume time means anything
        chk = make(2, 2)
        chk.fit(BoringModel(), ckpt_path=path)
        restored = serialization.to_state_dict(
            jax.device_get(chk.train_state))["params"]
        saved = host["state"]["params"]
        mism = sum(
            int(not np.array_equal(a, b))
            for a, b in zip(jax.tree_util.tree_leaves(saved),
                            jax.tree_util.tree_leaves(restored)))
        same_s, _ = resume(4)
        elastic_s, _t2 = resume(2)
        print(json.dumps({
            "world": "save 4-way, resume 2-way (+1 epoch)",
            "gang_recovery_elastic_ms": round(1e3 * elastic_s, 1),
            "same_size_resume_ms": round(1e3 * same_s, 1),
            "reshard_param_leaves_mismatched": mism,
            **_device_fields(),
        }))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _run_gang_elastic_child() -> dict:
    out = _run_child("gang_elastic", cpu_devices=8)
    if out.get("reshard_param_leaves_mismatched", 1) != 0:
        raise MeasurementError(
            "elastic resume did not restore the checkpoint "
            f"element-identically: {out}")
    return out


def _bench_obs(num_slots: int = 4, n_requests: int = 8,
               prompt: int = 32, new_tokens: int = 32,
               steps_per_dispatch: int = 4, repeats: int = 3) -> dict:
    """Telemetry overhead: the armed and disarmed cost of the obs layer.

    Serve side (the gated claim): one pinned burst trace (same model
    family and knobs as ``_bench_chaos``) served with ``telemetry=None``
    (the production default — every instrumentation point is one
    attribute read + None check) and with a fully armed
    :class:`~ray_lightning_tpu.obs.Telemetry` (events + JSONL sink +
    metrics + spans + global activation). Best-of-``repeats`` tokens/sec
    each. ``obs_overhead_pct`` is armed vs disarmed;
    ``disarmed_overhead_pct`` compares two independent disarmed
    measurements — the pre-telemetry code path no longer exists, so the
    disarmed claim is pinned as "indistinguishable from itself"
    (repeat-run variance bounds the None-check cost). The tracing leg
    (``tracing_overhead_pct``) serves the same armed trace and then
    runs the PR 19 post-hoc fold — ``request_traces()`` assembly plus
    the stitched Chrome export — pricing end-to-end request tracing
    inside the same few-percent armed budget (the fold is offline; its
    cost is reported separately as ``trace_assembly_ms`` /
    ``trace_export_ms``).

    Train side (reported, not gated): median batch-to-batch interval of
    a BoringModel fit with a bare timing probe vs
    ``StepStatsCallback(telemetry)``. BoringModel's step is
    host-dominated (µs scale), so this percentage is a hard UPPER bound
    on real-model overhead.

    NOT in ``tracked_extras``: overhead ratios this small sit inside
    environment noise; recorded for trend visibility.
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.obs import Telemetry
    from ray_lightning_tpu.serve import ServeClient

    total = prompt + new_tokens
    base = dict(vocab_size=50304, max_seq_len=total, dtype=jnp.bfloat16,
                scan_layers=False)
    model = TransformerLM(gpt2_config("small", **base))
    toks0 = jnp.asarray(np.random.default_rng(0).integers(
        0, 50257, size=(num_slots, prompt)), jnp.int32)
    params = jax.device_put(jax.jit(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, toks0)["params"]))(jax.random.PRNGKey(0)))
    dec = TransformerLM(gpt2_config("small", decode=True,
                                    param_dtype=jnp.bfloat16, **base))

    rng = np.random.default_rng(3)
    trace = []
    for _ in range(n_requests):
        L = int(rng.integers(prompt // 2, prompt + 1))
        trace.append((0.0, dict(
            prompt=[int(t) for t in rng.integers(0, 50257, size=L)],
            max_new_tokens=int(rng.integers(new_tokens // 2,
                                            new_tokens + 1)))))

    def run(tel) -> float:
        client = ServeClient(dec, params, num_slots=num_slots,
                             prefill_len=total,
                             steps_per_dispatch=steps_per_dispatch,
                             clock=time.perf_counter, telemetry=tel)
        if tel is None:
            out = client.serve_trace(trace)
        else:
            with tel.activated():
                out = client.serve_trace(trace)
            tel.flush()
        makespan = max(c.finish_time for c in out.values())
        return sum(len(c.tokens) for c in out.values()) / makespan

    run(None)  # compile warmup (same jit cache for armed: model identity)
    tmp = tempfile.mkdtemp(prefix="bench_obs_")
    events_recorded = 0

    def armed() -> Telemetry:
        return Telemetry(clock=time.perf_counter,
                         jsonl_path=os.path.join(tmp, "serve.jsonl"))

    tps_disarmed = max(run(None) for _ in range(repeats))
    tps_disarmed_b = max(run(None) for _ in range(repeats))
    armed_tels = [armed() for _ in range(repeats)]
    tps_armed = max(run(t) for t in armed_tels)
    events_recorded = armed_tels[0].bus.tick

    # --- tracing leg: armed serve + per-request span-tree assembly ------
    # the serve loop is byte-for-byte the armed one (tracing adds only
    # the per-event t/sync payload fields already measured above); what
    # this leg prices is the OFFLINE fold — request_traces() + the
    # stitched Chrome export — which must stay post-hoc, never on the
    # dispatch path
    traced_tels = [armed() for _ in range(repeats)]
    tps_traced = max(run(t) for t in traced_tels)
    t0 = time.perf_counter()
    req_traces = traced_tels[0].request_traces()
    trace_assembly_ms = (time.perf_counter() - t0) * 1e3
    from ray_lightning_tpu.obs.tracing import export_fleet_chrome_trace
    t0 = time.perf_counter()
    export_fleet_chrome_trace(os.path.join(tmp, "trace.json"),
                              traced_tels[0], req_traces)
    trace_export_ms = (time.perf_counter() - t0) * 1e3

    # --- train side: bare probe vs StepStatsCallback --------------------
    from ray_lightning_tpu import (RayStrategy, StepStatsCallback, Trainer)
    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.models import BoringModel

    class _Probe(Callback):
        def __init__(self):
            self.marks = []

        def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                               batch_idx):
            self.marks.append(time.perf_counter())

    def train_run(extra_cbs, tel=None) -> float:
        probe = _Probe()
        tr = Trainer(strategy=RayStrategy(num_workers=1), max_epochs=1,
                     limit_train_batches=40, seed=0,
                     default_root_dir=tempfile.mkdtemp(
                         prefix="bench_obs_train_"),
                     callbacks=[probe] + extra_cbs, telemetry=tel)
        tr.fit(BoringModel())
        return float(np.median(np.diff(probe.marks[3:]))) * 1e3

    train_plain_ms = train_run([])
    tel_train = Telemetry(clock=time.perf_counter)
    train_armed_ms = train_run([StepStatsCallback(tel_train)], tel_train)

    return {
        "model": "gpt2_small (bf16 serving params)",
        "num_slots": num_slots, "requests": n_requests,
        "steps_per_dispatch": steps_per_dispatch,
        "serve_tokens_per_sec_disarmed": round(tps_disarmed, 0),
        "serve_tokens_per_sec_armed": round(tps_armed, 0),
        "obs_overhead_pct": round(
            100.0 * (tps_disarmed / tps_armed - 1.0), 2),
        "serve_tokens_per_sec_traced": round(tps_traced, 0),
        "tracing_overhead_pct": round(
            100.0 * (tps_disarmed / tps_traced - 1.0), 2),
        "traces_assembled": len(req_traces),
        "trace_assembly_ms": round(trace_assembly_ms, 3),
        "trace_export_ms": round(trace_export_ms, 3),
        "disarmed_overhead_pct": round(
            100.0 * (tps_disarmed / tps_disarmed_b - 1.0), 2),
        "events_recorded": int(events_recorded),
        "train_step_interval_plain_ms": round(train_plain_ms, 4),
        "train_step_interval_stepstats_ms": round(train_armed_ms, 4),
        "train_obs_overhead_pct": round(
            100.0 * (train_armed_ms / train_plain_ms - 1.0), 2),
    }


def _bench_flash_long_seq(T: int = 8192) -> dict:
    """Pallas flash vs XLA fused attention, train step (fwd+bwd) at long
    sequence — the regime the hand kernel exists for (XLA materializes the
    scores and stops scaling ~T^2 memory)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.attention import dot_product_attention
    from ray_lightning_tpu.ops.pallas_flash import pallas_flash_attention

    B, H, D = 1, 12, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(x, (B, T, H, D), dtype=jnp.bfloat16)
                   for x in ks)

    # HBM floor for one fwd+bwd: the four (B,T,H,D) bf16 tensors must
    # each cross HBM at least once; clock floor covers the rest. Catches
    # elided/deduped executions the way decode's param floor did.
    tensor_bytes = 4 * q.size * 2
    call_floor = max(tensor_bytes / _hbm_bandwidth(jax.devices()[0]),
                     1000 * time.get_clock_info("perf_counter").resolution)

    def timed(attn) -> float:
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32)
                * do.astype(jnp.float32)),
            argnums=(0, 1, 2)))

        _fetch_scalar(g(q, k, v))  # compile + execute
        best = float("inf")
        for _ in range(3):
            drain = g(q, k, v)
            _fetch_scalar(drain)  # drain before the clock
            # chain the drain's dq into the FIRST timed call too — every
            # timed dispatch (not just calls 2-5) has inputs no earlier
            # dispatch ever saw
            qi = drain[0].astype(jnp.bfloat16)
            t0 = time.perf_counter()
            for _ in range(5):
                out = g(qi, k, v)
                # chain: next query is this call's dq — a data dependency
                # that also makes every dispatch's inputs distinct, so no
                # layer of the stack can elide or dedupe repeats
                qi = out[0].astype(jnp.bfloat16)
            _fetch_scalar(out)
            best = min(best, (time.perf_counter() - t0) / 5)
        if best < call_floor:
            raise MeasurementError(
                f"flash timing collapsed: {best:.2e}s/call is under the "
                f"HBM floor {call_floor:.2e}s — executions were elided")
        return best

    flash_s = timed(lambda q, k, v: pallas_flash_attention(
        q, k, v, causal=True))
    xla_s = timed(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True))
    return {
        "seq_len": T,
        "flash_ms": round(flash_s * 1e3, 2),
        "xla_dot_ms": round(xla_s * 1e3, 2),
        "speedup": round(xla_s / flash_s, 2),
    }


def _load_multiproc_nojax():
    """Import ``ray_lightning_tpu.data.multiproc`` + ``_native`` standalone
    — never the package ``__init__`` (whose strategy imports pull in jax).
    Keeps this child truly jax-free so the forked producers cross no XLA
    runtime state (the hazard ``default_mp_context`` guards against)."""
    import importlib.util
    import types

    pkg_root = os.path.join(HERE, "ray_lightning_tpu")

    def load(name, path):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    for pkg in ("ray_lightning_tpu", "ray_lightning_tpu.data"):
        if pkg not in sys.modules:
            stub = types.ModuleType(pkg)
            stub.__path__ = []
            sys.modules[pkg] = stub
    load("ray_lightning_tpu._native",
         os.path.join(pkg_root, "_native", "__init__.py"))
    return load("ray_lightning_tpu.data.multiproc",
                os.path.join(pkg_root, "data", "multiproc.py"))


class _AugmentedBatches:
    """Plain-numpy loader with per-batch host work (normalize + flip +
    pad), the decode/augment stand-in the native path exists to overlap.
    Module-level so either mp start method could pickle it."""

    def __init__(self, n=32768, bs=512, seed=0):
        rng = np.random.default_rng(seed)
        self.x = rng.standard_normal((n, 32, 32, 3)).astype(np.float32)
        self.y = rng.integers(0, 10, size=(n,)).astype(np.int32)
        self.bs = bs

    def __len__(self):
        return len(self.x) // self.bs

    def __iter__(self):
        for i in range(len(self)):
            bx = self.x[i * self.bs:(i + 1) * self.bs]
            by = self.y[i * self.bs:(i + 1) * self.bs]
            bx = (bx - bx.mean(axis=(1, 2, 3), keepdims=True)) / (
                bx.std(axis=(1, 2, 3), keepdims=True) + 1e-6)
            bx = bx[:, :, ::-1, :]
            bx = np.pad(bx, ((0, 0), (2, 2), (2, 2), (0, 0)))
            yield bx.copy(), by


def _bench_data_pipeline() -> dict:
    """Native shm-ring multiprocess loader vs in-process loader.

    Host-side only (no device). The timed pass is one full epoch
    INCLUDING producer fork + ring setup — the loader re-forks each
    epoch, so that is the per-epoch cost a user actually pays; 64
    batches amortize it.
    """
    assert "jax" not in sys.modules, (
        "data bench must stay jax-free for fork safety")
    multiproc = _load_multiproc_nojax()

    def rate(loader) -> float:
        t0 = time.perf_counter()
        count = 0
        for bx, _ in loader:
            count += bx.shape[0]
        return count / (time.perf_counter() - t0)

    cores = os.cpu_count() or 1
    workers = max(1, min(4, cores - 1))
    # ONE dataset instance shared by every loader under test: separate
    # instances are ~400 MB of arrays each, and three of them cycling
    # through a small host cache penalized whichever loader ran at the
    # wrong phase (read as a spurious 0.89x fallback "overhead")
    base_loader = _AugmentedBatches()
    # default path: auto_fallback picks ring vs in-process by core count,
    # so this speedup is the one a user actually gets (never < ~1.0 by
    # construction — round-2 VERDICT weak #3)
    mp = multiproc.MultiprocessDataLoader(
        base_loader, num_workers=workers, mp_context="fork")
    # Interleaved best-of (round-3 VERDICT weak #3): a single
    # base-then-wrapped ordering read the fallback at 0.66-0.87x on this
    # 1-core host purely from host-load drift between the two
    # measurements — falsifying the wrapper's own never-slower design
    # claim. Alternating reps give every loader the same noise field;
    # best-of keeps the least-interfered pass of each. The forced-ring
    # diagnostic (starved hosts only) rides the same loop for the same
    # reason.
    forced = None
    if not mp.uses_ring and mp.native:
        forced = multiproc.MultiprocessDataLoader(
            base_loader, num_workers=workers, mp_context="fork",
            auto_fallback=False)
    for _ in base_loader:  # one warm pass pages in the shared arrays
        pass
    base = mp_rate = forced_rate = 0.0
    for _ in range(3):
        base = max(base, rate(base_loader))
        mp_rate = max(mp_rate, rate(mp))
        if forced is not None:
            forced_rate = max(forced_rate, rate(forced))
    out = {
        "inproc_samples_per_sec": round(base, 0),
        "default_samples_per_sec": round(mp_rate, 0),
        "workers": mp.num_workers,
        "host_cores": cores,
        "speedup": round(mp_rate / base, 2),
        "native_ring": mp.native,
        "ring_active": mp.uses_ring,
    }
    if forced is not None:
        out["forced_ring_samples_per_sec"] = round(forced_rate, 0)
        out["forced_ring_transport_ratio"] = round(forced_rate / base, 2)
        out["note"] = (
            "host has too few cores for producer overlap, so the default "
            "path is in-process (ring auto-fallback); forced_ring_* "
            "tracks pure shm transport overhead")
    return out


def _run_data_child() -> dict:
    """Run the data-pipeline bench in a subprocess that never imports
    jax, so the forked producer processes cross no XLA runtime state."""
    return _run_child("data")


def bench_scaling() -> dict:
    """SPMD overhead proxy on a virtual 8-device CPU mesh (weak scaling).

    With fewer host cores than mesh devices the virtual devices time-slice,
    so the ideal dp=8 speedup is min(8, cores). This measures what the
    framework *adds* (partitioning + collective overhead at equal compute
    capacity) — the regressable part; real ICI scaling needs real chips.

    Presentation (round-2 VERDICT weak #4): the raw dp8/dp1 ratio can
    exceed the nominal ideal on a time-sliced host (per-device batch-size
    economics, not scaling), so it is reported as
    ``collective_overhead_proxy`` — values >= 1 mean "no measurable
    framework overhead at this core count" — and the bounded
    ``efficiency`` (<= 1.0 by construction) is what the scoreboard may
    compare across rounds.
    """
    cores = os.cpu_count() or 1
    r1 = _run_child("scaling:1", cpu_devices=8)
    r8 = _run_child("scaling:8", cpu_devices=8)
    ideal = float(min(8, cores))
    raw = r8["rate"] / (r1["rate"] * ideal)
    return {
        "proxy": "virtual 8-device CPU mesh, weak scaling (512 samples/dev)",
        "platform": r8["platform"], "device_kind": r8["device_kind"],
        "host_cores": cores,
        "dp1_samples_per_sec": r1["rate"],
        "dp8_samples_per_sec": r8["rate"],
        "ideal_speedup": ideal,
        "collective_overhead_proxy": raw,
        "efficiency": min(1.0, raw),
    }


class _Legs:
    """The secondary legs of one bench run: results under ``extras``, and
    the names of the legs that raised under ``failed`` — a non-empty
    ``failed`` makes ``main`` exit non-zero. The other legs still run,
    but a failed phase is never just a note."""

    def __init__(self):
        self.extras: dict = {}
        self.failed: list = []

    def run(self, key: str, fn, into: dict | None = None) -> bool:
        """Run ``fn`` into ``into[key]`` (default: ``extras``); True when
        it returned, False (and ``{"error": ...}`` recorded) when it
        raised."""
        into = self.extras if into is None else into
        try:
            into[key] = fn()
        except Exception as exc:  # tl-lint: allow-broad-except — recorded + exit 1
            self.failed.append(key)
            into[key] = {"error": f"{type(exc).__name__}: {exc}"}
            return False
        return True


def main() -> None:
    mode = os.environ.get("_TL_BENCH_MODE", "")
    if mode.startswith("scaling:"):
        _scaling_child(int(mode.split(":", 1)[1]))
        return
    if mode == "data":
        # host-only child: no jax in this process, so no device
        print(json.dumps({**_bench_data_pipeline(), "platform": "host",
                          "device_kind": "none (child never imports jax)"}))
        return
    if mode == "gang_elastic":
        _gang_elastic_child()
        return

    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()

    legs = _Legs()
    extras, failed, leg = legs.extras, legs.failed, legs.run

    # Interleaved A/B vs the frozen raw-jax anchor (round 5, VERDICT #2):
    # 8 alternating pairs in one session — the anchored ratio vs_anchor is
    # what the scoreboard compares across rounds, cancelling session
    # jitter. Batch sweep re-verified 8192 as the throughput plateau
    # (16384 equal, 32k/64k regress).
    mnist, anchor = bench_headline_interleaved(pairs=8)
    value = mnist["samples_per_sec_per_chip"]
    extras["mnist"] = {
        "samples_per_sec_per_chip": round(value, 1),
        "mfu": round(mnist["mfu"], 4) if mnist["mfu"] else None,
        "flops_per_step": mnist["flops_per_step"],
        "device_kind": mnist["device_kind"],
        "anchor_samples_per_sec": round(anchor["samples_per_sec"], 1),
        "vs_anchor": round(mnist["vs_anchor"], 4),
        "pair_ratio_spread": mnist["pair_ratio_spread"],
    }

    def bert_leg() -> dict:
        # batch 128 + remat(dots_with_no_batch_dims) measured fastest on
        # v5e: the policy saves weight-matmul outputs so backward skips
        # their recompute — 1710 sps / MFU 0.728 vs 1572 / 0.669 for full
        # remat (sweep: bs 32→1027, 64→1340, 128 full-remat→1629,
        # 128 dots_nb→1710, 160/192/256 dots_nb regress). MFU counts only
        # required model FLOPs (6NT), not the remat recompute — the
        # standard MFU convention.
        bert_batch = 128
        bert = bench_model(_build_bert_step, samples_per_step=bert_batch,
                           analytic_tokens=bert_batch * 128,
                           batch_size=bert_batch, seq_len=128, best_of=2)
        return {
            "samples_per_sec_per_chip": round(
                bert["samples_per_sec_per_chip"], 2),
            "mfu": round(bert["mfu"], 4) if bert["mfu"] else None,
            "flops_per_step": bert["flops_per_step"],
            "batch": bert_batch, "seq_len": 128,
        }

    leg("bert_base", bert_leg)

    def gpt_extra(key: str, size: str, best_of: int,
                  gpt_bs: int = 8, **build_kw) -> None:
        gpt_seq = 512

        def run() -> dict:
            gpt = bench_model(_build_gpt2_step, samples_per_step=gpt_bs,
                              analytic_tokens=gpt_bs * gpt_seq,
                              batch_size=gpt_bs, seq_len=gpt_seq,
                              size=size, best_of=best_of, **build_kw)
            return {
                "samples_per_sec_per_chip": round(
                    gpt["samples_per_sec_per_chip"], 2),
                "tokens_per_sec_per_chip": round(
                    gpt["samples_per_sec_per_chip"] * gpt_seq, 0),
                "mfu": round(gpt["mfu"], 4) if gpt["mfu"] else None,
                "batch": gpt_bs, "seq_len": gpt_seq,
                **build_kw,  # provenance: every layout knob
            }

        leg(key, run)

    # round-5: the runtime/compiler upgrade flipped round 4's winner —
    # save_attn (+9.6% then) now LOSES to plain dots_nb by 6.5%
    # (interleaved sweep: dots_nb 334.9, save_attn 314.4, no-remat 305.2,
    # full 304.6 sps; tools/ab_sweep.py gpt2). Re-sweep on runtime drift,
    # don't trust stale winners.
    gpt_extra("gpt2_small", "small", 3,
              remat_policy="dots_with_no_batch_dims")

    def vit_leg() -> dict:
        # round-5 sweep winner config (vit_config's own defaults carry
        # remat+save_attn); analytic 6NT flops — the stack is scanned,
        # so cost_analysis undercounts by ~n_layers
        vit_bs = 32
        vit = bench_model(_build_vit_step, samples_per_step=vit_bs,
                          analytic_tokens=vit_bs * 197,
                          batch_size=vit_bs, best_of=2)
        return {
            "samples_per_sec_per_chip": round(
                vit["samples_per_sec_per_chip"], 2),
            "mfu": round(vit["mfu"], 4) if vit["mfu"] else None,
            "batch": vit_bs, "image_size": 224,
        }

    leg("vit_base", vit_leg)

    def moe_leg() -> dict:
        # round-5 sweep winner: bs 16 + adafactor; layers are a python
        # loop (no scan), so cost_analysis counts the sparse expert
        # einsums at their true dims — no analytic override needed
        moe_bs, moe_seq = 16, 512
        moe = bench_model(_build_moe_step, samples_per_step=moe_bs,
                          batch_size=moe_bs, seq_len=moe_seq, best_of=2)
        return {
            "samples_per_sec_per_chip": round(
                moe["samples_per_sec_per_chip"], 2),
            "tokens_per_sec_per_chip": round(
                moe["samples_per_sec_per_chip"] * moe_seq, 0),
            "batch": moe_bs, "seq_len": moe_seq, "optimizer": "adafactor",
        }

    leg("moe_lm", moe_leg)
    leg("flash_attention_t8192", _bench_flash_long_seq)
    decode_ok = leg("decode", _bench_decode)

    # continuous-batching engine vs static batches, staggered arrivals;
    # its sub-legs (all untracked, each ENFORCING its own identity /
    # floor in-bench) hang off the same dict and need it to exist
    if leg("serve", _bench_serve):
        serve = extras["serve"]
        try:
            # paged-KV additions: capacity per arena byte, prefix reuse,
            # chunked-prefill decode-stall bound
            serve.update(_bench_paged())
        except Exception as exc:  # tl-lint: allow-broad-except — recorded + exit 1
            failed.append("serve.paged")
            serve["paged_error"] = f"{type(exc).__name__}: {exc}"
        leg("spec", _bench_spec, serve)
        leg("kv_int8", _bench_kv_int8, serve)
        leg("weight_quant", _bench_weight_quant, serve)
        leg("page_native", _bench_page_native, serve)
        leg("pallas", _bench_pallas, serve)
        # depth-2 pipelined dispatch vs the sync driver; cites
        # _bench_decode's host_sync/enqueue split as the overlap floor
        leg("async_dispatch", lambda: _bench_async_dispatch(
            decode_split=extras["decode"] if decode_ok else None), serve)
        leg("tenancy", _bench_tenancy, serve)
        leg("lora", _bench_lora, serve)

    # serving under a pinned fault plan: recovery cost, untracked
    if leg("chaos", _bench_chaos):
        chaos = extras["chaos"]
        spec = extras.get("serve", {}).get("spec", {})
        if "spec_verify_recovery_ms" in spec:
            # the spec path's chaos seat measured in _bench_spec: mirror
            # its recovery cost next to the other chaos numbers
            chaos["spec_verify_recovery_ms"] = \
                spec["spec_verify_recovery_ms"]
        # PR 18 containment leg (seeded poison pill, 3-replica trace) and
        # PR 20 driver-death leg (journal tax + mid-decode kill): both
        # ENFORCE token exactness or raise MeasurementError
        leg("poison", _bench_chaos_poison, chaos)
        leg("driver_restart", _bench_driver_restart, chaos)
    # replica-fleet serving under a seeded serve.replica kill: failover
    # cost + fleet-vs-single-engine throughput, untracked. This IS the
    # fleet leg of the chaos bench, so mirror the failover cost there.
    if leg("fleet", _bench_fleet) and "error" not in extras["chaos"]:
        extras["chaos"]["fleet_failover_ms"] = \
            extras["fleet"]["fleet_failover_ms"]
    if "error" not in extras["chaos"]:
        # gang kill-and-restart on the process backend (CPU worker by
        # design; stamps the worker's own platform), untracked
        leg("gang", _bench_gang, extras["chaos"])

    # telemetry layer overhead, armed vs disarmed, untracked
    leg("obs", _bench_obs)

    def batch_scaling_leg() -> dict:
        # batch scaling on the chip: utilization growth small -> large
        small = bench_model(_build_mnist_step, samples_per_step=1024,
                            batch_size=1024)
        return {
            "batch_1024_samples_per_sec": round(
                small["samples_per_sec_per_chip"], 1),
            "batch_8192_samples_per_sec": round(value, 1),
            "speedup_8x_batch": round(
                value / small["samples_per_sec_per_chip"], 3),
        }

    leg("batch_scaling", batch_scaling_leg)

    # medium (355M) brushes the 16 GB HBM ceiling by design — an OOM here
    # poisons subsequent allocations in this backend (observed: flash +
    # batch_scaling inherited RESOURCE_EXHAUSTED), so it runs AFTER every
    # other on-chip section. Round-4 config: factored optimizer states
    # (adafactor) free ~2.1 GB vs plain adamw, which buys bs 12 (adamw
    # OOMs at 12) + the save_attn remat policy — interleaved A/B:
    # 86.8 -> 95.1 sps (MFU 0.480 -> 0.525), see docs/performance.md
    gpt_extra("gpt2_medium", "medium", 2, gpt_bs=12,
              optimizer="adafactor",
              remat_policy="dots_with_no_batch_dims_save_attn")

    # CPU-by-design children (virtual CPU mesh / no jax): each result
    # carries the platform its child reported
    leg("scaling", bench_scaling)
    leg("data_pipeline", _run_data_child)

    # Extras with their own reference anchor (round-3 VERDICT weak #4:
    # decode had no tracking, so a regression would be silent). Each gets
    # a vs_reference ratio next to its value — loud like the headline.
    # decode tracks the device-differential rate (round 5): the wall rate
    # changed meaning when new_tokens went 64→256 (less dispatch per
    # step), so comparing it against a 64-token anchor would fabricate a
    # win; the device number is protocol-independent.
    tracked_extras = {
        "decode": "device_token_steps_per_sec",
        # serve tracks the trace-level rate: the trace (prompts, arrival
        # spread, slot count) is pinned, so the ratio is meaningful
        "serve": "serve_tokens_per_sec",
        "data_pipeline": "speedup",
        "gpt2_small": "mfu",
        "gpt2_medium": "mfu",
        "vit_base": "mfu",
        "moe_lm": "samples_per_sec_per_chip",
    }
    vs_baseline = 1.0
    if os.path.exists(REFERENCE_FILE):
        try:
            with open(REFERENCE_FILE) as f:
                ref = json.load(f)
            # Anchored comparison (round 5): both sides of the ratio are
            # normalized by the frozen raw-jax anchor measured in their
            # OWN session, so session jitter cancels instead of reading
            # as regression. Falls back to the raw-rate ratio when the
            # reference predates the anchor.
            ref_vs_anchor = ref.get("headline_vs_anchor")
            if ref_vs_anchor and extras["mnist"].get("vs_anchor"):
                vs_baseline = (extras["mnist"]["vs_anchor"]
                               / float(ref_vs_anchor))
            elif ref.get("value"):
                vs_baseline = value / float(ref["value"])
            raw_ratio = (value / float(ref["value"])
                         if ref.get("value") else None)
            if (not ref_vs_anchor and extras["mnist"].get("vs_anchor")
                    and raw_ratio is not None
                    and 0.93 <= raw_ratio <= 1.10):
                # one-time upgrade: record this session's anchored pair so
                # every later run compares jitter-free. Gated on the raw
                # ratio sitting inside the assumed jitter band — a
                # genuinely regressed (or miraculous) session must NOT
                # become the permanent baseline; it stays on the loud raw
                # comparison and the next healthy session re-anchors.
                ref["headline_vs_anchor"] = extras["mnist"]["vs_anchor"]
                ref["anchor_recorded"] = "round 5 re-anchor"
                with open(REFERENCE_FILE, "w") as f:
                    json.dump(ref, f, indent=2)
            ref_extras = ref.get("extras", {})
            # re-anchor the (possibly fresh) extras dict INTO the
            # reference before any dump: a loaded reference that lacks an
            # 'extras' key would otherwise take the first recordings into
            # a detached dict and silently drop them on write
            ref["extras"] = ref_extras
            ref_dirty = False
            for key, field in tracked_extras.items():
                cur = extras.get(key, {}).get(field)
                ref_val = ref_extras.get(key, {}).get(field)
                if cur is not None and ref_val:
                    extras[key]["vs_reference"] = round(
                        float(cur) / float(ref_val), 3)
                elif cur is not None and 0.93 <= vs_baseline <= 1.10:
                    # protocol gained a field (or a whole workload) the
                    # anchor predates: record the first valid measurement
                    # so later runs compare against it — but only from a
                    # session whose headline sits inside the known jitter
                    # band, so a degraded (or miraculous) session never
                    # becomes a new metric's permanent baseline
                    ref_extras.setdefault(key, {})[field] = cur
                    ref_extras[key][f"{field}_recorded"] = (
                        "auto-recorded on first valid measurement "
                        "(protocol addition)")
                    ref_dirty = True
            if ref_dirty:
                with open(REFERENCE_FILE, "w") as f:
                    json.dump(ref, f, indent=2)
        except (json.JSONDecodeError, KeyError, ValueError):
            pass
    else:
        with open(REFERENCE_FILE, "w") as f:
            json.dump({
                "metric": "samples/sec/chip (MNIST MLP train step)",
                "value": round(value, 1),
                "recorded": "first valid run",
                "headline_vs_anchor": extras["mnist"].get("vs_anchor"),
                "extras": extras,
            }, f, indent=2)

    import jax

    print(json.dumps({
        "metric": "samples/sec/chip (MNIST MLP train step)",
        "value": round(value, 1),
        "unit": "samples/s/chip",
        "vs_baseline": round(vs_baseline, 3),
        "device": {**_device_fields(), "count": len(jax.devices())},
        "failed_legs": failed,
        "extras": extras,
    }))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
