"""One traced run of a benchmark cell that KEEPS its profile, then the
report of ``tools/trace_report.py --profile`` over it.

``benchmark/run.py --trace 1`` reduces its profile to the result line and
deletes it; the line names device ops by instruction (``fusion.313``) and
gaps by the harness's own spans. This runs the same cell through the same
harness, copies the profile to ``--out`` before the harness removes it, and
prints device time by named scope and the idle gaps by the program's own
spans. ``--arm-trainer`` hands ``Trainer`` a wall-clock ``Telemetry`` (the
train kind arms none), so a train cell's ``trainer.*`` spans are in the
profile too, and ``--trace 0 --arm-trainer`` against ``--trace 0`` is what
the trainer's spans cost a step. Chip only (``--rehearse`` walks it on the
CPU)::

    python tools/chip_profile.py --workload gpt2-large.serve.closed40 \\
        --seed 11 --out chiprun_out/profile.serve
"""
from __future__ import annotations

import argparse
import functools
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None,
                    help="where the profile is kept (with --trace 1)")
    ap.add_argument("--arm-trainer", action="store_true")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness, run

    if args.trace and args.out:
        reduce = harness.Slice.reduce

        def keep_then_reduce(self):
            shutil.rmtree(args.out, ignore_errors=True)
            shutil.copytree(self.dir, args.out)
            return reduce(self)

        harness.Slice.reduce = keep_then_reduce
    if args.arm_trainer:
        import ray_lightning_tpu as rlt
        from ray_lightning_tpu.obs import Telemetry
        rlt.Trainer = functools.partial(
            rlt.Trainer, telemetry=Telemetry(clock=time.perf_counter))

    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", str(args.trace)]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        argv.append("--rehearse")
    rc = run.main(argv)
    if rc == 0 and args.trace and args.out:
        import trace_report  # tools/ is sys.path[0] when run as a script
        sys.stderr.write(trace_report.format_profile_report(
            trace_report.profile_report(args.out, args.depth, args.top))
            + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
