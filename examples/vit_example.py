"""Vision Transformer classification example.

Model-zoo breadth beyond the reference (its examples cover MLP/CNN/GPT
seats; see ``ray_lightning/examples/``): a ViT classifier on the shared
``TransformerStack``, data-parallel over the mesh. Ships the round-5
defaults — ``vit_config`` rematerializes with the ``save_attn`` policy
(why: ``docs/performance.md`` "Model-zoo lever sweep"; no cell of the
benchmark measures it).

    python examples/vit_example.py --num-workers 4 --max-epochs 3

Off-TPU, use the virtual mesh env (see mnist_ddp_example.py).
"""
import argparse

from ray_lightning_tpu import RayStrategy, Trainer
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.models.vit import ViTModule


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=2)
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--size", default="tiny",
                        choices=["tiny", "small", "base"])
    parser.add_argument("--image-size", type=int, default=32)
    parser.add_argument("--patch-size", type=int, default=4)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--max-epochs", type=int, default=3)
    parser.add_argument("--no-remat", action="store_true", default=False,
                        help="Opt out of the measured remat default "
                             "(saves compile time on tiny configs).")
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    from ray_lightning_tpu.models.vit import vit_config
    cfg = vit_config(args.size, image_size=args.image_size,
                     patch_size=args.patch_size,
                     **({"remat": False} if args.no_remat else {}))
    model = ViTModule(size=args.size, image_size=args.image_size,
                      patch_size=args.patch_size, config=cfg,
                      batch_size=args.batch_size,
                      num_samples=4 * args.batch_size if args.smoke_test
                      else 16 * args.batch_size)
    trainer = Trainer(
        strategy=RayStrategy(num_workers=args.num_workers,
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
