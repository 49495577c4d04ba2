#!/usr/bin/env python3
"""On-chip readings the limits are set from — NOT part of a benchmark run.

One process, one cell: reads ``--seeds`` sound runs of the program (for a
train cell no measured window is needed; a serve cell gets a short one at
its own load) and, on the first ``--control-seeds`` of them, whatever is
put in the program's place: the control (the reference one precision down),
the planted faults (rows of the batch left out of the reference) and the
reference at the epsilon GPT-2 publishes. One JSON line per reading, each
number beside its limit as ``[value, limit]`` and with the verdict
``harness.compare`` gives it (``correct``: the control and the faults have
to read false); ``PERF.md`` records what they gave::

    python benchmark/control.py --workload <cell> --seeds 12 --control-seeds 3
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: LayerNorm's epsilon in OpenAI's GPT-2 config.json (the program runs 1e-6)
PUBLISHED_EPSILON = 1e-5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import harness, weights
    from ray_lightning_tpu.util import enable_compile_cache
    bench = harness.load_json("BENCHMARK.json")
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"wrong platform {platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    meter = harness.CompileMeter()

    def ctx_for(seed: int):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0,
                                rehearse=args.rehearse)
        ctx = harness.Ctx(ns, bench, time.perf_counter())
        ctx.devices, ctx.meter = jax.devices(), meter
        return ctx

    def reading(name: str, seed: int, compared: dict, **more) -> None:
        harness.note(reading=name, seed=seed,
                     correct=harness.compare(compared),
                     **{k: [float(v), float(lim)]
                        for k, (v, lim) in compared.items()}, **more)

    kind_name = ctx_for(0).workload["kind"]
    kind = harness.load_module("kinds", kind_name)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ctx = ctx_for(seed)
        if kind_name == "train_fit":
            ctx.seconds = 0.0           # the window closes on its first step
        t0 = time.perf_counter()
        outcome = kind.run(ctx)
        reading("program", seed, outcome["check"](),
                seconds=round(time.perf_counter() - t0, 1))
        if i >= args.control_seeds:
            continue
        w = ctx.workload
        published = harness.Ctx(ctx.args, bench, ctx.t_start)
        published.devices = ctx.devices
        published.shape = {**ctx.shape,
                           "layer_norm_epsilon": PUBLISHED_EPSILON}
        if kind_name == "train_fit":
            feed = kind.TokenFeed(seed, w["batch"], w["seq_len"],
                                  ctx.shape["vocab_size"],
                                  w["data_pool_batches"])
            ref = kind.run_reference(ctx, feed, "f32")
            n = int(w["reference"]["steps"])
            readings = {"control_fp8": dict(mode="fp8"),
                        "witness_bf16": dict(mode="bf16"),
                        "fault_half_batch": dict(rows=(0, w["batch"] // 2))}
            if ctx.chips > 1:
                readings["fault_no_exchange"] = dict(
                    rows=(0, w["batch"] // ctx.chips))
            for name, kw in readings.items():
                reading(name, seed, kind.judge(
                    kind.run_reference(ctx, feed, **kw), ref, w["limits"], n))
            # the program against the reference as GPT-2 publishes it
            reading("published_epsilon", seed, kind.judge(
                outcome["program"],
                kind.run_reference(published, feed, "f32"), w["limits"], n))
        else:
            sample = kind.pick_sample(outcome["records"], seed,
                                      int(w["check_requests"]))
            top_k, limits = int(w["sampled"]["top_k"]), w["limits"]
            key = weights.seed_key(seed)
            got = kind.served_gaps(ctx, key, sample, top_k,
                                   control_mode="fp8")
            reading("control_fp8", seed, {
                "served_logit_gap": (got["control_greedy"],
                                     limits["served_logit_gap"]),
                "sampled_topk_gap": (got["control_sampled"],
                                     limits["sampled_topk_gap"])},
                positions=got["positions"], requests=got["requests"],
                bars={k: v for k, v in got.items() if k.endswith("_bar")})
            # the program's tokens against the reference as GPT-2
            # publishes it
            pub = kind.served_gaps(published, key, sample, top_k)
            reading("published_epsilon", seed, {
                "served_logit_gap": (pub["greedy"],
                                     limits["served_logit_gap"]),
                "sampled_topk_gap": (pub["sampled"],
                                     limits["sampled_topk_gap"])})
    return 0


if __name__ == "__main__":
    sys.exit(main())
