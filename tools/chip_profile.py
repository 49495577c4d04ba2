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
the trainer's spans cost a step (``--trace 0 --arm-serve`` against
``--trace 0`` is what a serve cell's armed ``Telemetry`` costs: the serve
kinds arm one only in a traced run, so this hands them a slice that
profiles nothing); an armed run also prints what the train step's or the
serve engine's prefill and step programs' attention seats decided while
they were traced (``obs/seats.py``: how many took a kernel, how many the
dense path and why), and the key blocks the step's per-row decode kernel
read against the slot pool's (``engine.step.call``). ``--census``
compiles a train cell's step once more after set-up and prints its
collectives (``obs/census.py``: kind, result type, bytes, inside the layer
loop or not, carrying the global batch or parameter-shaped). Chip only (``--rehearse`` walks it on the CPU)::

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


def print_census_at_setup() -> None:
    """Have ``Trainer._setup_state`` compile its train step for the batch it
    was set up with and write that program's collectives to stderr."""
    import jax

    from ray_lightning_tpu.core.trainer import Trainer
    from ray_lightning_tpu.obs.census import collective_census, format_census
    from ray_lightning_tpu.parallel.sharding import put_global_batch
    setup = Trainer._setup_state

    def setup_then_census(self, sample_batch, *args, **kwargs):
        state = setup(self, sample_batch, *args, **kwargs)
        batch = put_global_batch(self._cast_batch(sample_batch),
                                 self._batch_sharding)
        compiled = self._train_step.lower(state, batch).compile()
        rows = len(jax.tree_util.tree_leaves(batch)[0])
        sys.stderr.write(
            f"census of the train step (global batch {rows}):\n"
            + format_census(collective_census(compiled, batch=rows)) + "\n")
        return state

    Trainer._setup_state = setup_then_census


def arm_serve_without_profile(harness) -> None:
    """Hand every ``Ctx`` a slice that starts and stops no profile: the
    serve kinds build their ``Telemetry`` whenever ``ctx.slice`` is set,
    and an untraced line reports the end-to-end metrics alone."""
    class Unprofiled(harness.Slice):
        def start(self):
            self.t0 = time.perf_counter()

        def stop(self):
            self.t1 = time.perf_counter()

    init = harness.Ctx.__init__

    def armed_init(self, *a, **kw):
        init(self, *a, **kw)
        self.slice = Unprofiled(self.rehearse)

    harness.Ctx.__init__ = armed_init


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None,
                    help="where the profile is kept (with --trace 1)")
    ap.add_argument("--arm-trainer", action="store_true")
    ap.add_argument("--arm-serve", action="store_true",
                    help="with --trace 0: arm the serve kinds' Telemetry")
    ap.add_argument("--census", action="store_true",
                    help="print the collectives of the Trainer's step")
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
    if args.arm_serve and not args.trace:
        arm_serve_without_profile(harness)
    tel = None
    if args.arm_trainer:
        import ray_lightning_tpu as rlt
        from ray_lightning_tpu.obs import Telemetry
        tel = Telemetry(clock=time.perf_counter)
        rlt.Trainer = functools.partial(rlt.Trainer, telemetry=tel)

    if args.census:
        print_census_at_setup()

    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", str(args.trace)]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        argv.append("--rehearse")
    rc = run.main(argv)
    from ray_lightning_tpu.obs import last_telemetry
    armed = last_telemetry()    # --arm-trainer's, or a serve kind's own
    for name in ("trainer.train_step", "engine.prefill.call",
                 "engine.step.call"):
        for span in (armed.spans.spans(name) if armed is not None else ()):
            if "attn_kernel" in span.args:  # the call that traced it
                seat = {k: v for k, v in span.args.items()
                        if k.startswith("attn_")}
                sys.stderr.write(f"attention seats as traced ({name}): "
                                 f"{seat}\n")
    steps = [s.args for s in (armed.spans.spans("engine.step.call")
                              if armed is not None else ())
             if "kv_blocks_slot" in s.args]
    if steps:
        read = sum(a["kv_blocks_read"] for a in steps)
        slot = sum(a["kv_blocks_slot"] for a in steps)
        sys.stderr.write(f"decode key blocks read / slot blocks "
                         f"(engine.step.call, {len(steps)} dispatches): "
                         f"{read} / {slot} = {read / slot:.3f}\n")
    if rc == 0 and args.trace and args.out:
        import trace_report  # tools/ is sys.path[0] when run as a script
        sys.stderr.write(trace_report.format_profile_report(
            trace_report.profile_report(args.out, args.depth, args.top))
            + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
