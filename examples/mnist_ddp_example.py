"""MNIST data-parallel training example.

Parity with the reference's ``examples/ray_ddp_example.py:118-173``: a small
classifier trained with ``RayStrategy`` via CLI flags. Run:

    python examples/mnist_ddp_example.py --num-workers 2 --smoke-test

On a machine without TPUs, set a virtual device mesh first:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mnist_ddp_example.py ...
"""
import argparse

from ray_lightning_tpu import RayStrategy, Trainer
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.data import MultiprocessDataLoader
from ray_lightning_tpu.models import LightningMNISTClassifier


class MNISTWithLoaderWorkers(LightningMNISTClassifier):
    """MNIST classifier feeding training through the native shm-ring
    multiprocess loader: N producer processes assemble batches GIL-free
    while the device steps — the parity seat of the reference example's
    torch ``DataLoader(num_workers=N)``."""

    def __init__(self, config=None, num_samples=8192, data_workers=2):
        super().__init__(config=config, num_samples=num_samples)
        self.data_workers = data_workers

    def train_dataloader(self):
        return MultiprocessDataLoader(super().train_dataloader(),
                                      num_workers=self.data_workers)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=1,
                        help="Number of data-parallel shards (chips).")
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--max-epochs", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--data-workers", type=int, default=0,
                        help="Multiprocess data-loader producers (0 = load "
                             "inline on the training process).")
    parser.add_argument("--use-ray", action="store_true", default=False,
                        help="Attach to (or start) a Ray cluster and run "
                             "workers as Ray actors — the reference's "
                             "deployment shape (ray_ddp_example.py).")
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    if args.use_ray:
        import ray
        if not ray.is_initialized():
            ray.init()

    num_samples = 1024 if args.smoke_test else 8192
    if args.data_workers > 0:
        model = MNISTWithLoaderWorkers(
            config={"lr": args.lr, "batch_size": args.batch_size},
            num_samples=num_samples, data_workers=args.data_workers)
    else:
        model = LightningMNISTClassifier(
            config={"lr": args.lr, "batch_size": args.batch_size},
            num_samples=num_samples)
    # CPU actors over real Ray: each worker forms its own 1-device XLA
    # world (TPU actors manage visibility via the launcher instead)
    runtime_env = None
    if args.use_ray and not args.use_tpu:
        runtime_env = {"env_vars": {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        }}
    trainer = Trainer(
        strategy=RayStrategy(num_workers=args.num_workers,
                             use_tpu=args.use_tpu,
                             use_ray=args.use_ray or None,
                             worker_runtime_env=runtime_env),
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
