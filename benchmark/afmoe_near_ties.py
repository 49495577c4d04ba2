#!/usr/bin/env python3
"""On-chip readings the ``afmoe`` family's near-tie rule and the new
cell's limits are set from — NOT part of a benchmark run
(``family_control.py``'s sibling, position by position).

One process, one cell: ``--seeds`` sound runs of the program through a
short window at the cell's own load; on each, the requests the judge
would pick are teacher-forced through the float32 reference (which also
gives, for every position, how far the nearest *held* expert's selection
score lies from the boundary of the top four, over the expert layers:
``afmoe_reference``'s ``last_margins``) and through what is put in the
program's place: the reference in fp8 (the control), with bfloat16
operands (the stated precision) and with a bfloat16 router (this
configuration's own witness). For every ``--bars`` value of the
near-tie bar, one JSON line a seed: the share of positions a bar leaves
out and the widest gap of each kind among the positions kept; then the
ten widest program gaps with their margins. ``--dump`` writes every
position to a file. About seven minutes a seed on a v5e (four references
over eight requests of up to 8192 positions; the first seed compiles
every padded length in every precision)::

    python benchmark/afmoe_near_ties.py --seeds 6
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "trinity-large-preview.serve.mixedlen32"
IN_PLACE = {"fp8": ("fp8", {}), "bf16": ("bf16", {}),
            "bf16_router": ("f32", {"router_dtype": "bfloat16"})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147400001)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--bars", default="0,2.5e-4,5e-4,1e-3,2e-3,4e-3")
    ap.add_argument("--dump", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import afmoe_reference, afmoe_weights, harness
    from benchmark import serve_driver
    from ray_lightning_tpu.util import enable_compile_cache
    bench = harness.load_json("BENCHMARK.json")
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"wrong platform {platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    meter = harness.CompileMeter()
    bars = [float(b) for b in args.bars.split(",")]
    dump = open(args.dump, "w") if args.dump else None

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ns = argparse.Namespace(workload=WORKLOAD, seed=seed,
                                seconds=args.seconds, trace=0,
                                rehearse=args.rehearse)
        ctx = harness.Ctx(ns, bench, time.perf_counter())
        ctx.devices, ctx.meter = jax.devices(), meter
        w, shape = ctx.workload, ctx.shape
        outcome = serve_driver.run(ctx)
        compared = outcome["check"]()       # frees the program first
        harness.note(reading="program", seed=seed,
                     correct=harness.compare(compared)
                     and outcome["failed"] == 0,
                     completed=outcome["attempted"],
                     **{k: [float(v), float(lim)]
                        for k, (v, lim) in compared.items()})
        sample = serve_driver.pick_sample(outcome["records"], seed,
                                          int(w["check_requests"]))
        del outcome
        gc.collect()
        weights = afmoe_weights.make_canonical(
            afmoe_weights.seed_key(seed), shape)
        pad = {"pad_multiple": 64} if args.rehearse else {}
        ref = afmoe_reference.make_logits_fn(shape, "f32", **pad)
        low = {name: afmoe_reference.make_logits_fn(
            shape, mode, **{k: jnp.dtype(v) for k, v in kw.items()}, **pad)
            for name, (mode, kw) in IN_PLACE.items()}
        top_k = int(w["sampled"]["top_k"])
        rows_out = []
        for r in sample:
            seq = r["prompt"] + r["tokens"]
            first = len(r["prompt"]) - 1
            rows = np.arange(first, first + len(r["tokens"]))
            at = np.arange(len(rows))
            lg = np.asarray(ref(weights, seq, rows), np.float64)
            margin = ref.last_margins.min(0)
            rank = 1 if r["greedy"] else top_k
            bar = np.partition(lg, -rank, axis=-1)[:, -rank]
            gaps = {"program": np.maximum(0.0, bar - lg[at, r["tokens"]])}
            for name, fn in low.items():
                lo = np.asarray(fn(weights, seq, rows), np.float64)
                pick = np.argpartition(lo, -rank, axis=-1)[:, -rank]
                gaps[name] = np.maximum(0.0, bar - lg[at, pick])
            for j in range(len(rows)):
                rows_out.append(dict(
                    rid=r["rid"], greedy=bool(r["greedy"]),
                    position=int(rows[j]), margin=float(margin[j]),
                    **{k: float(v[j]) for k, v in gaps.items()}))
        del weights, ref, low
        gc.collect()
        margins = np.array([x["margin"] for x in rows_out])
        greedy = np.array([x["greedy"] for x in rows_out])
        for near in bars:
            kept = margins >= near
            reading = {}
            for name in ["program"] + list(IN_PLACE):
                g = np.array([x[name] for x in rows_out])
                reading[name] = [
                    float(g[kept & greedy].max(initial=0.0)),
                    float(g[kept & ~greedy].max(initial=0.0))]
            harness.note(reading="bar", seed=seed, near_tie=near,
                         positions=len(rows_out),
                         left_out=round(float(1 - kept.mean()), 4),
                         **reading)
        worst = sorted(rows_out, key=lambda x: -x["program"])[:10]
        harness.note(reading="widest_program_gaps", seed=seed, rows=[
            [x["position"], x["greedy"], round(x["program"], 4),
             float(f"{x['margin']:.3g}")] for x in worst])
        if dump:
            for x in rows_out:
                dump.write(json.dumps(dict(seed=seed, **x)) + "\n")
            dump.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
