"""Interleaved A/B sweep harness for model-zoo levers on the real chip.

The measurement discipline proven in round 4 on GPT-2 (docs/performance.md
"Measurement integrity"), packaged: candidate configs are measured in
alternating full passes within ONE session — A/B/A/B… — so session
jitter hits every candidate equally and the RATIO between bests is
trustworthy even when absolute rates drift.

Round-5 use (VERDICT #6): sweep ``save_attn`` remat and the
``make_optimizer`` presets over the ViT and MoE-LM families; results in
docs/performance.md, winning defaults shipped in the examples.

Usage (real chip) — one sweep per model family in ``SWEEPS``:
    python tools/ab_sweep.py vit      # remat space + adafactor
    python tools/ab_sweep.py moe      # remat space + optimizer presets
    python tools/ab_sweep.py gpt2     # flagship remat space (drift check)
    python tools/ab_sweep.py bert     # save_attn vs dots_nb (drift check)
    python tools/ab_sweep.py seq2seq  # encoder-decoder remat space

Prints one JSON line per candidate: {"name", "samples_per_sec", "best_of"}
plus a final {"winner": ...} line with ratios vs the first (baseline)
candidate.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _build_seq2seq_step(strategy, batch_size: int, src_len: int = 256,
                        tgt_len: int = 256, **cfg_overrides):
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_tpu.core.optim import make_optimizer
    from ray_lightning_tpu.models.seq2seq import Seq2SeqTransformer
    from ray_lightning_tpu.models.transformer import TransformerConfig

    opt_name = cfg_overrides.pop("optimizer", "adamw")
    cfg = TransformerConfig(vocab_size=50304, max_seq_len=max(src_len,
                                                              tgt_len),
                            d_model=512, n_heads=8, n_layers=6,
                            d_ff=2048, causal=True, dtype=jnp.bfloat16,
                            scan_layers=False, **cfg_overrides)
    model = Seq2SeqTransformer(cfg)
    tx = make_optimizer(opt_name, learning_rate=1e-3)
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(0, 50257, (batch_size, src_len)),
                      jnp.int32)
    tgt = jnp.asarray(rng.integers(0, 50257, (batch_size, tgt_len + 1)),
                      jnp.int32)

    def loss_fn(params, model_state, batch, rng):
        bsrc, btgt = batch
        logits = model.apply({"params": params}, bsrc, btgt[:, :-1])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, btgt[:, 1:]).mean()
        return loss, ({}, model_state)

    # init with 1-example shapes, measure at full batch
    return bench._assemble_step(
        strategy, _Seq2SeqInitAdapter(model), tx, loss_fn,
        (src[:1], tgt[:1]), (src, tgt))


class _Seq2SeqInitAdapter:
    """Adapts the two-input seq2seq model to _assemble_step's
    single-init-batch contract (init_batch arrives as an (src, tgt)
    tuple; flax wants them positional). Only ``init`` is consumed —
    the loss_fn closes over the real model for apply."""

    def __init__(self, model):
        self._model = model

    def init(self, rng, init_batch):
        src, tgt = init_batch
        return self._model.init(rng, src, tgt[:, :-1])


SWEEPS = {
    "seq2seq": {
        # encoder-decoder family: remat-space drift check (cross-attention
        # adds a third dot per decoder block — attention flop share is
        # higher than BERT's at the same T)
        "build": _build_seq2seq_step,
        "batch_size": 16,
        "candidates": [
            ("no_remat", {}),
            ("remat_dots_nb", {"remat": True,
                               "remat_policy":
                                   "dots_with_no_batch_dims"}),
            ("remat_save_attn", {"remat": True,
                                 "remat_policy":
                                     "dots_with_no_batch_dims_save_attn"}),
        ],
    },
    "vit": {
        "build": bench._build_vit_step,
        # 4 candidates' train states live simultaneously (interleaving
        # needs them all warm); bs 32 keeps the sum under the 16 GB chip
        "batch_size": 32,
        "candidates": [
            # explicit remat=False: vit_config ships remat+save_attn as
            # its default since this sweep measured the win, so an empty
            # override would silently measure the winner against itself
            ("no_remat", {"remat": False, "remat_policy": None}),
            ("remat_dots_nb", {"remat": True,
                               "remat_policy":
                                   "dots_with_no_batch_dims"}),
            ("remat_save_attn", {"remat": True,
                                 "remat_policy":
                                     "dots_with_no_batch_dims_save_attn"}),
            ("no_remat_adafactor", {"remat": False, "remat_policy": None,
                                    "optimizer": "adafactor"}),
        ],
    },
    "bert": {
        # same runtime-drift re-check as gpt2: bert shipped save_attn on
        # a +1.0-1.2% round-4 margin that the new compiler may have
        # reversed (it reversed gpt2-small's +9.6%)
        "build": lambda strategy, batch_size, **o: bench._build_bert_step(
            strategy, batch_size, 128, **o),
        "batch_size": 128,
        "candidates": [
            # explicit (not the builder default) so a future default flip
            # can't turn this into a self-comparison — same guard as vit
            ("save_attn", {"remat_policy":
                           "dots_with_no_batch_dims_save_attn"}),
            ("dots_nb", {"remat_policy": "dots_with_no_batch_dims"}),
        ],
    },
    "gpt2": {
        # flagship layout re-check under runtime/compiler drift: the
        # round-4 winner (save_attn) lost ~10% MFU across round-5
        # sessions while BERT gained — re-measure the remat space in one
        # session before attributing it to the environment
        "build": lambda strategy, batch_size, **o: bench._build_gpt2_step(
            strategy, batch_size, 512, size="small", **o),
        "batch_size": 8,
        "candidates": [
            ("save_attn", {"remat_policy":
                           "dots_with_no_batch_dims_save_attn"}),
            ("dots_nb", {"remat_policy": "dots_with_no_batch_dims"}),
            ("no_remat", {"remat_policy": "none"}),
            ("full_remat", {"remat_policy": "full"}),
        ],
    },
    "moe": {
        # bench's moe builder ships the sweep winner (adafactor) as its
        # default, so candidates name the optimizer EXPLICITLY — an empty
        # override would self-compare against the winner
        "build": bench._build_moe_step,
        "batch_size": 16,
        "candidates": [
            ("no_remat_adamw", {"optimizer": "adamw"}),
            ("remat_dots_nb_adamw", {"optimizer": "adamw", "remat": True,
                                     "remat_policy":
                                         "dots_with_no_batch_dims"}),
            ("remat_save_attn_adamw",
             {"optimizer": "adamw", "remat": True,
              "remat_policy": "dots_with_no_batch_dims_save_attn"}),
            ("no_remat_adafactor", {"optimizer": "adafactor"}),
        ],
    },
}


def run_sweep(which: str, pairs: int = 4) -> dict:
    import jax

    from ray_lightning_tpu import RayStrategy

    spec = SWEEPS[which]
    n_chips = len(jax.devices())
    strategy = RayStrategy(num_workers=n_chips, use_tpu=True)
    bs = spec["batch_size"]

    built = []
    for name, overrides in spec["candidates"]:
        try:
            step, state, batch = spec["build"](strategy, batch_size=bs,
                                               **dict(overrides))
            flops = bench._step_flops(step, state, batch)
            built.append((name, step, state, batch, flops))
        except Exception as exc:  # e.g. OOM at this layout: record, go on
            print(json.dumps({"name": name,
                              "error": f"{type(exc).__name__}: {exc}"}))
    chip_peak = bench._chip_peak_flops(jax.devices()[0])
    peak = chip_peak * n_chips if chip_peak else None

    best: dict = {}
    dead: set = set()
    for _ in range(pairs):  # interleave full passes across ALL candidates
        for name, step, state, batch, flops in built:
            if name in dead:
                continue
            try:
                out = bench._measure_rate(step, state, batch, bs, flops,
                                          peak)
            except Exception as exc:  # OOM at this layout: record, go on
                dead.add(name)
                print(json.dumps({"name": name,
                                  "error": f"{type(exc).__name__}: "
                                           f"{exc}"[:300]}))
                continue
            if name not in best or out["samples_per_sec"] > \
                    best[name]["samples_per_sec"]:
                best[name] = out
    if not best:
        print(json.dumps({"sweep": which, "batch_size": bs,
                          "error": "every candidate failed"}))
        return {}
    baseline = spec["candidates"][0][0]
    # a dead baseline (e.g. the memory-hungry no-remat candidate OOMs)
    # must not kill the report: fall back to the first surviving
    # candidate as the ratio base and say so
    if baseline not in best:
        baseline = next(n for n, *_ in built if n in best)
        print(json.dumps({"note": f"baseline dead; ratios vs {baseline}"}))
    report = {}
    for name, out in best.items():
        report[name] = {
            "samples_per_sec": round(out["samples_per_sec"], 2),
            "vs_baseline": round(out["samples_per_sec"]
                                 / best[baseline]["samples_per_sec"], 4),
        }
        print(json.dumps({"name": name, **report[name]}))
    winner = max(report, key=lambda k: report[k]["samples_per_sec"])
    print(json.dumps({"winner": winner, "sweep": which,
                      "batch_size": bs, "report": report}))
    return report


if __name__ == "__main__":
    run_sweep(sys.argv[1] if len(sys.argv) > 1 else "vit")
