"""Sharded large-model training example.

Parity with the reference's ``examples/ray_ddp_sharded_example.py`` (ImageGPT
with ``RayShardedStrategy`` + epoch-time/peak-memory callback): a GPT-2 model
trained with ZeRO-1 optimizer-state sharding (or full FSDP with
``--fsdp``), reporting per-epoch wall time and device memory.

    python examples/gpt_sharded_example.py --num-workers 8 --size nano

Use the virtual CPU mesh env (see mnist_ddp_example.py) off-TPU.
"""
import argparse

from ray_lightning_tpu import (FSDPStrategy, RayShardedStrategy, Trainer)
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.models import GPTModule


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=2)
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--size", default="nano",
                        choices=["nano", "small", "medium", "large", "xl"])
    parser.add_argument("--fsdp", action="store_true", default=False,
                        help="Fully-sharded params (ZeRO-3) instead of "
                             "optimizer-state-only (ZeRO-1)")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--max-epochs", type=int, default=2)
    parser.add_argument("--generate", type=int, default=0, metavar="N",
                        help="after training, decode N tokens from the "
                             "trained weights with the KV-cache sampler")
    parser.add_argument("--optimizer", default="adamw",
                        choices=["adamw", "adamw_bf16m", "adafactor"],
                        help="memory-efficient presets free optimizer-"
                             "state HBM for bigger batches/models on a "
                             "chip (core/optim.py)")
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    strategy_cls = FSDPStrategy if args.fsdp else RayShardedStrategy
    model = GPTModule(size=args.size, batch_size=args.batch_size,
                      seq_len=args.seq_len, optimizer=args.optimizer,
                      num_samples=4 * args.batch_size if args.smoke_test
                      else 64 * args.batch_size)
    trainer = Trainer(
        strategy=strategy_cls(num_workers=args.num_workers,
                              use_tpu=args.use_tpu),
        max_epochs=1 if args.smoke_test else args.max_epochs,
        callbacks=[EpochStatsCallback()],
        enable_progress_bar=True,
        seed=42)
    trainer.fit(model)
    print("callback_metrics:",
          {k: round(float(v), 4) for k, v in trainer.callback_metrics.items()})

    if args.generate:
        import dataclasses

        import jax
        import numpy as np

        from ray_lightning_tpu.models import TransformerLM, generate
        from ray_lightning_tpu.models.transformer import unstack_scan_params

        # decode needs no remat (single-token steps store no activations)
        # and unrolled layers (scanned layers nest a loop inside the token
        # scan — ~2x slower per decode step; see models/generate.py);
        # unstack_scan_params converts the scanned training weights.
        # generate() runs the prefill/decode split: the prompt fills the
        # KV cache in one compiled pass, then a tokens-only scan samples.
        dec_cfg = dataclasses.replace(model.cfg, decode=True, remat=False,
                                      remat_policy=None, scan_layers=False,
                                      scan_unroll=1)
        if trainer.train_state is not None:  # local launch: live arrays
            params = trainer.train_state.params
        else:  # Ray launch: the driver recovered a host state dict
            params = trainer.train_state_dict["params"]
        if model.cfg.scan_layers:
            params = unstack_scan_params(params)
        prompt = np.asarray(
            [[1, 2, 3, 4]], dtype=np.int32)
        out = generate(TransformerLM(dec_cfg), params,
                       prompt, max_new_tokens=args.generate,
                       rng=jax.random.PRNGKey(0), temperature=0.8,
                       top_k=40)
        print("generated:", np.asarray(out)[0].tolist())


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
