#!/usr/bin/env python3
"""On-chip readings the limits of a family-built serve cell are set from
— NOT part of a benchmark run (``control.py``'s sibling for the kinds of
``serve_driver.py``).

One process, one cell: ``--seeds`` sound runs of the program through a
short window at the cell's own load and, on each, whatever is put in the
program's place — the reference one precision down (``fp8``, the control
that has to read ``correct: false``), the reference with bf16 matmul
operands (a witness: what the configuration states) and, for a family
with recurrent state, the reference with that state held in bfloat16
(``--state-witness``). One JSON line per reading, each compared number as
``[value, limit]`` with ``harness.compare``'s verdict, the rest beside
them::

    python benchmark/family_control.py --workload <cell> --seeds 3
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--state-witness", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import harness, serve_driver
    from ray_lightning_tpu.util import enable_compile_cache
    bench = harness.load_json("BENCHMARK.json")
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"wrong platform {platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    meter = harness.CompileMeter()
    names = serve_driver.COMPARED

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0,
                                rehearse=args.rehearse)
        ctx = harness.Ctx(ns, bench, time.perf_counter())
        ctx.devices, ctx.meter = jax.devices(), meter
        w = ctx.workload
        t0 = time.perf_counter()
        outcome = serve_driver.run(ctx)
        compared = outcome["check"]()
        harness.note(reading="program", seed=seed,
                     correct=harness.compare(compared)
                     and outcome["failed"] == 0,
                     completed=outcome["attempted"],
                     **{k: [float(v), float(lim)]
                        for k, (v, lim) in compared.items()},
                     seconds=round(time.perf_counter() - t0, 1))
        sample = serve_driver.pick_sample(outcome["records"], seed,
                                          int(w["check_requests"]))
        key = serve_driver.family_of(ctx.shape).seed_key(seed)
        readings = {"control_fp8": ("fp8", {}), "witness_bf16": ("bf16", {})}
        if args.state_witness:
            readings["witness_bf16_state"] = (
                "f32", {"state_dtype": jnp.bfloat16})
        for name, (mode, kw) in readings.items():
            got = serve_driver.served_gaps(ctx, key, sample, w["sampled"],
                                           control_mode=mode, **kw)
            compared = {n: (1e30 if got.get("control_" + names[n]) is None
                            else got["control_" + names[n]], limit)
                        for n, limit in w["limits"].items()}
            harness.note(
                reading=name, seed=seed, correct=harness.compare(compared),
                **{k: [float(v), float(lim)]
                   for k, (v, lim) in compared.items()},
                positions=got["positions"], requests=got["requests"],
                sound={k: got[k] for k in ("greedy", "sampled", "sampled_z")},
                control={k: got["control_" + k]
                         for k in ("greedy", "sampled", "sampled_z")},
                bars={k: v for k, v in got.items() if k.endswith("_bar")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
