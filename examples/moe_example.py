"""Mixture-of-experts training example: expert parallelism over ``ep``.

Net-new beyond the reference (no MoE story upstream): a sparse MoE
transformer LM whose expert banks shard across the ``ep`` mesh axis —
GSPMD inserts the dispatch all-to-alls from the sharding rule alone.

    python examples/moe_example.py --dp 2 --ep 4 --experts 8

Off-TPU, use the virtual mesh env (see mnist_ddp_example.py).
"""
import argparse

from ray_lightning_tpu import MeshStrategy, Trainer
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.models import (MoeModule, expert_parallel_rule,
                                      moe_config)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--ep", type=int, default=4,
                        help="Expert-parallel size (expert banks split).")
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--size", default="nano",
                        choices=["nano", "small"])
    parser.add_argument("--experts", type=int, default=8)
    parser.add_argument("--top-k", type=int, default=1)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--max-epochs", type=int, default=3)
    # adafactor measured +15.6% on-chip for MoE (expert params dominate
    # optimizer-state traffic; see docs/performance.md round-5 sweep)
    parser.add_argument("--optimizer", default="adafactor",
                        choices=["adamw", "adamw_bf16m", "adafactor"])
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    cfg = moe_config(args.size, n_experts=args.experts,
                     expert_top_k=args.top_k, max_seq_len=args.seq_len,
                     vocab_size=256)
    model = MoeModule(config=cfg, batch_size=args.batch_size,
                      seq_len=args.seq_len,
                      optimizer=args.optimizer,
                      num_samples=4 * args.batch_size if args.smoke_test
                      else 32 * args.batch_size)
    trainer = Trainer(
        strategy=MeshStrategy(axes={"dp": args.dp, "ep": args.ep},
                              param_rule=expert_parallel_rule,
                              use_tpu=args.use_tpu),
        max_epochs=1 if args.smoke_test else args.max_epochs,
        callbacks=[EpochStatsCallback()],
        enable_progress_bar=True,
        seed=42)
    trainer.fit(model)
    print("callback_metrics:",
          {k: round(float(v), 4) for k, v in trainer.callback_metrics.items()})


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
