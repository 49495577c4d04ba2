"""Long-context training example: ring attention over a dp×sp mesh.

Net-new beyond the reference (it has no long-context story): a GPT trained
with :class:`SequenceParallelStrategy` — the batch dim splits over ``dp``,
the *sequence* dim over ``sp``, and ``attention_impl="ring"`` rotates K/V
shards around the ICI ring (``lax.ppermute``) so no chip ever materializes
the full sequence. Per-chip activation memory scales O(seq_len / sp).

    python examples/long_context_example.py --dp 2 --sp 4 --seq-len 2048

``--impl ulysses`` switches to the all-to-all head-sharded variant
(DeepSpeed-Ulysses style): two GSPMD resharding collectives per attention
call instead of sp ring hops; needs n_heads divisible by sp.

Single-chip long context: ``--impl flash`` trains through the pallas
flash kernels instead of sharding the sequence — at T≥16384 the plain
XLA attention no longer even compiles on a 16 GiB chip (the f32 score
tensor alone exceeds HBM; see docs/performance.md), so past that point
flash (one chip) or ring/ulysses (many chips) are the only paths.

``--generate N`` runs the serving side after training: the trained
weights drive the prefill/decode split (models/generate.py) on a long
prompt (capped at 2k) — the whole prompt fills the KV cache in ONE
compiled forward instead of per-token steps. The serving path uses
plain dot attention, so prefill past a few thousand positions would
need a chunked/flash prefill (not plumbed into the cached path yet);
the cap keeps the demo inside what one chip compiles.

Off-TPU, use the virtual mesh env (see mnist_ddp_example.py).
"""
import argparse

from ray_lightning_tpu import SequenceParallelStrategy, Trainer
from ray_lightning_tpu.core.callbacks import EpochStatsCallback
from ray_lightning_tpu.models import GPTModule, gpt2_config


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dp", type=int, default=None,
                        help="Data-parallel size (batch split); defaults "
                             "to 2, or 1 in --impl flash (single-chip).")
    parser.add_argument("--sp", type=int, default=4,
                        help="Sequence-parallel size (sequence split).")
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--size", default="nano",
                        choices=["nano", "small", "medium", "large", "xl"])
    parser.add_argument("--impl", default="ring",
                        choices=["ring", "ulysses", "flash"],
                        help="Sequence-parallel attention variant, or "
                             "'flash' for single-chip long context "
                             "through the pallas kernels (no sequence "
                             "sharding; --sp is ignored).")
    parser.add_argument("--seq-len", type=int, default=2048)
    parser.add_argument("--batch-size", type=int, default=4)
    parser.add_argument("--max-epochs", type=int, default=2)
    parser.add_argument("--generate", type=int, default=0, metavar="N",
                        help="After training, prefill a long prompt in "
                             "one pass and decode N new tokens with the "
                             "trained weights (single-chip demo of the "
                             "prefill/decode serving split).")
    parser.add_argument("--smoke-test", action="store_true", default=False)
    args = parser.parse_args()

    seq_len = 256 if args.smoke_test else args.seq_len
    if args.dp is None:
        # flash is the single-chip long-context path (the whole sequence
        # stays on each chip, tiled through VMEM by the kernel), so its
        # default world is one worker
        args.dp = 1 if args.impl == "flash" else 2
    cfg = gpt2_config(args.size, max_seq_len=seq_len,
                      attention_impl=args.impl)
    model = GPTModule(config=cfg, batch_size=args.batch_size,
                      seq_len=seq_len,
                      num_samples=4 * args.batch_size if args.smoke_test
                      else 32 * args.batch_size)
    if args.impl == "flash":
        from ray_lightning_tpu import RayStrategy
        strategy = RayStrategy(num_workers=args.dp, use_tpu=args.use_tpu)
    else:
        strategy = SequenceParallelStrategy(dp=args.dp, sp=args.sp,
                                            use_tpu=args.use_tpu)
    trainer = Trainer(
        strategy=strategy,
        max_epochs=1 if args.smoke_test else args.max_epochs,
        callbacks=[EpochStatsCallback()],
        enable_progress_bar=True,
        seed=42)
    trainer.fit(model)
    print("callback_metrics:",
          {k: round(float(v), 4) for k, v in trainer.callback_metrics.items()})

    if args.generate:
        import dataclasses
        import time

        import jax
        import numpy as np

        from ray_lightning_tpu.models import TransformerLM, generate
        from ray_lightning_tpu.models.transformer import unstack_scan_params

        # Serving config: cached 'dot' attention (the sequence-parallel
        # impls shard the training sequence; decode attends a KV cache),
        # unrolled layers (~2x faster per decode step, models/generate.py)
        # and no remat (single-token steps store no activations).
        dec_cfg = dataclasses.replace(
            model.cfg, decode=True, remat=False, remat_policy=None,
            scan_layers=False, scan_unroll=1, attention_impl="dot")
        if trainer.train_state is not None:  # local launch: live arrays
            params = trainer.train_state.params
        else:  # Ray launch: the driver recovered a host state dict
            params = trainer.train_state_dict["params"]
        if model.cfg.scan_layers:
            params = unstack_scan_params(params)
        # a long prompt is exactly where the prefill split pays: the
        # whole prompt is ONE compiled forward into the KV cache instead
        # of prompt_len sequential single-token dispatches. Capped at 2k:
        # the serving path uses plain dot attention, whose prefill
        # materializes the O(P^2) score tensor — past a few thousand
        # positions that needs chunked/flash prefill, which the cached
        # decode path does not plumb yet
        prompt_len = max(8, min(seq_len, 2048) - args.generate)
        prompt = np.asarray(
            np.arange(prompt_len)[None, :] % model.cfg.vocab_size,
            dtype=np.int32)
        t0 = time.perf_counter()
        out = generate(TransformerLM(dec_cfg), params, prompt,
                       max_new_tokens=args.generate,
                       rng=jax.random.PRNGKey(0), temperature=0.0)
        tail = np.asarray(out)[0, prompt_len:].tolist()
        dt = time.perf_counter() - t0
        print(f"prefilled {prompt_len} prompt tokens in one pass + "
              f"decoded {args.generate} tokens in {dt:.2f}s "
              f"(incl. compile): {tail[:16]}...")


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
