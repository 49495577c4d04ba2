"""Explicit-allreduce (Horovod-parity) training example.

Parity with the reference's ``examples/ray_horovod_example.py``: the same
MNIST classifier trained with the allreduce-style strategy — per-rank
gradients explicitly all-reduced inside a ``shard_map`` step (the TPU-native
seat of ``hvd.DistributedOptimizer``) instead of sharding-derived psum. Run:

    python examples/allreduce_example.py --num-workers 2 --smoke-test

Use the virtual CPU mesh env (see mnist_ddp_example.py) off-TPU.
"""
import argparse

from ray_lightning_tpu import HorovodRayStrategy, Trainer
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.models import LightningMNISTClassifier


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=1,
                        help="Number of allreduce ranks (chips).")
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--max-epochs", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    model = LightningMNISTClassifier(
        config={"lr": args.lr, "batch_size": args.batch_size},
        num_samples=1024 if args.smoke_test else 8192)
    trainer = Trainer(
        strategy=HorovodRayStrategy(num_workers=args.num_workers,
                                    use_tpu=args.use_tpu),
        max_epochs=1 if args.smoke_test else args.max_epochs,
        callbacks=[EpochStatsCallback()],
        enable_progress_bar=True,
        seed=42)
    trainer.fit(model)
    print("callback_metrics:",
          {k: round(float(v), 4) for k, v in trainer.callback_metrics.items()})
    results = trainer.test(model)
    print("test results:", results)


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
