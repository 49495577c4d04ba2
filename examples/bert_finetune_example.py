"""BERT-base fine-tune over multi-host data parallelism.

The BASELINE "BERT-base fine-tune, RayStrategy multi-host (v4-32, 4 Ray
actors)" config: one Ray actor per TPU host, each hosting an XLA process;
the `dp` mesh axis spans all 16 chips and XLA derives the gradient psum
over ICI. Reference seat: ``examples/ray_ddp_example.py`` scaled up — the
same user surface (`Trainer(strategy=RayStrategy(...)).fit(model)`), a
transformer instead of an MLP.

On a v4-32 pod (4 hosts x 4 chips), from the head node:

    python examples/bert_finetune_example.py --num-workers 4 --use-tpu

Smoke test on the virtual CPU mesh (what CI runs):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/bert_finetune_example.py \
        --smoke-test
"""
import argparse

import jax.numpy as jnp

from ray_lightning_tpu import EpochStatsCallback, RayStrategy, Trainer
from ray_lightning_tpu.models.bert import BertModule, bert_config


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=None,
                        help="Ray actors = TPU hosts (v4-32 has 4); "
                        "defaults to 4, or 2 with --smoke-test")
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--max-epochs", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=128,
                        help="global batch, split across the dp axis")
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--lr", type=float, default=5e-5)
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    if args.smoke_test:
        cfg = bert_config("tiny", vocab_size=1024, max_seq_len=64)
        module = BertModule(config=cfg, batch_size=32, seq_len=64,
                            num_samples=128, lr=args.lr)
        epochs, workers = 1, args.num_workers or 2
    else:
        # bf16 activations + remat: the BERT-base config the round-4/5
        # sweeps settled on (docs/performance.md; no cell measures it) —
        # full fp32 master weights in the opt state
        cfg = bert_config("base", vocab_size=30522,
                          max_seq_len=args.seq_len, dtype=jnp.bfloat16,
                          remat=True,
                          remat_policy="dots_with_no_batch_dims")
        module = BertModule(config=cfg, batch_size=args.batch_size,
                            seq_len=args.seq_len, num_samples=4096,
                            lr=args.lr)
        epochs, workers = args.max_epochs, args.num_workers or 4

    trainer = Trainer(
        strategy=RayStrategy(num_workers=workers, use_tpu=args.use_tpu),
        max_epochs=epochs,
        callbacks=[EpochStatsCallback()],
        enable_progress_bar=True,
        seed=42)
    trainer.fit(module)
    acc = trainer.callback_metrics.get("val_acc")
    print("final val_accuracy:", None if acc is None else float(acc))
    return trainer


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
