"""ResNet/CIFAR-style data-parallel training example.

Matches BASELINE.json's "ResNet-18/CIFAR-10, RayStrategy num_workers=8"
config: a residual CNN with BatchNorm state (carried through the compiled
step as mutable model state) trained data-parallel.

    python examples/resnet_example.py --num-workers 8 --depth 18

Off-TPU, use the virtual mesh env (see mnist_ddp_example.py).
"""
import argparse

from ray_lightning_tpu import RayStrategy, Trainer
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.models import ResNetModule


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=8)
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--depth", type=int, default=18, choices=[18, 50])
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--max-epochs", type=int, default=5)
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    model = ResNetModule(
        depth=args.depth,
        batch_size=32 if args.smoke_test else args.batch_size,
        num_samples=128 if args.smoke_test else 4096,
        lr=args.lr)
    trainer = Trainer(
        strategy=RayStrategy(num_workers=args.num_workers,
                             use_tpu=args.use_tpu),
        max_epochs=1 if args.smoke_test else args.max_epochs,
        callbacks=[EpochStatsCallback()],
        enable_progress_bar=True,
        seed=42)
    trainer.fit(model)
    results = trainer.test(model)
    print("callback_metrics:",
          {k: round(float(v), 4) for k, v in trainer.callback_metrics.items()})
    print("test results:", results)


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
