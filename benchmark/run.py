#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json::

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails at once, printing no result, when JAX finds no TPU or fewer devices
than the cell's ``chips``. ``--rehearse`` walks the same control flow at
nano widths on the CPU (four virtual devices for a four-chip cell), refuses
to run on a TPU, and never prints ``"platform": "tpu"``.

The last line of standard output is the result object; earlier lines carry
the set-up split, losses and counts. See ``benchmark/README.md``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="nano widths on the CPU; never a measurement")
    args = ap.parse_args(argv)

    from benchmark import harness
    bench = harness.load_json("BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    if not any(w["name"] == args.workload for w in bench["workloads"]):
        print(f"no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    ctx = harness.Ctx(args, bench, T_START)

    import ray_lightning_tpu  # noqa: F401  the system under test must be here
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if args.rehearse:
        if info["platform"] != "cpu":
            print(f"--rehearse is the CPU walk-through (found {info})",
                  file=sys.stderr)
            return 2
    elif info["platform"] != "tpu":
        print(f"benchmark/run.py needs a TPU; jax found {info}. Nothing was "
              "run and nothing is reported.", file=sys.stderr)
        return 2
    if len(devices) < ctx.chips:
        print(f"cell {args.workload} needs {ctx.chips} device(s); jax found "
              f"{info}", file=sys.stderr)
        return 2
    ctx.devices = devices

    from ray_lightning_tpu.util import enable_compile_cache
    cache_dir = enable_compile_cache()
    ctx.meter = harness.CompileMeter()
    harness.note(phase="start", cell=args.workload, seed=args.seed,
                 seconds=args.seconds, trace=args.trace,
                 rehearsal=args.rehearse, compile_cache_dir=cache_dir,
                 jax=jax.__version__, **info)
    kind = harness.load_module("kinds", ctx.workload["kind"])
    outcome = kind.run(ctx)
    return harness.finish(ctx, outcome)


if __name__ == "__main__":
    sys.exit(main())
