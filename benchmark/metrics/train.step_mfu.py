"""Train step program: operations the forward and backward passes of the
steps in the traced slice require (``work.train_flops_per_token``;
recomputation under remat is not counted) over slice seconds x chips x the
device kind's bf16 peak."""
from benchmark import peaks, work

LAYER = "Train step program"
SOURCE = "host_clock"


def compute(run):
    if not run.get("slice_steps") or run["rehearse"]:
        return None
    flops = (work.train_flops_per_token(run["shape"], run["seq_len"])
             * run["batch"] * run["seq_len"] * run["slice_steps"])
    return 100.0 * flops / (run["slice_s"] * run["chips"]
                            * peaks.peak_flops(run["device_kind"]))
