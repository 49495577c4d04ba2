"""The train→serve LoRA adapter lifecycle, end to end.

Walks the whole loop the multi-LoRA serving subsystem exists for:

1. **Train** a LoRA adapter with the existing trainer: a nano GPT built
   with ``lora=LoraConfig(rank, num_adapters=1)`` fine-tunes on a
   successor-token task (``next = (tok + 1) % vocab``) with the base
   weights FROZEN — ``optax.multi_transform`` routes the optimizer to
   the ``lora_A``/``lora_B`` leaves and ``set_to_zero`` to everything
   else, so the artifact of training is the adapter alone.
2. **Publish** it through the checkpoint layer:
   :func:`~ray_lightning_tpu.models.lora.extract_adapter` slices the
   trained ``(A, B)`` pairs out of the bank and
   ``save_sharded_checkpoint`` commits them like any other artifact
   (meta records rank + targets for the load-side sanity check).
3. **Hot-load** it into a RUNNING engine next to the base model:
   a :class:`~ray_lightning_tpu.serve.ServeClient` armed with an empty
   two-slot bank serves base traffic, ``load_adapter()`` writes the
   published adapter into a bank slot with no recompilation, and
   adapter-bound requests batch in the same dispatches as base rows.

Self-checks (all hard failures):

- the base weights are bitwise untouched by fine-tuning (the freeze is
  real, so serving them under the adapter is exactly base + delta);
- hot-loading the adapter into a running engine is token-identical to
  building an engine with it resident from the start;
- the null-adapter row is token-identical to a bankless engine.

Off-TPU this runs on CPU (JAX_PLATFORMS=cpu) in under a minute:

    python examples/lora_lifecycle_example.py
"""
import argparse
import dataclasses
import os
import tempfile

import jax
import numpy as np


def _is_lora_leaf(path) -> bool:
    last = path[-1]
    key = getattr(last, "key", getattr(last, "name", last))
    return key in ("lora_A", "lora_B")


def _strip_lora(tree):
    """The base-weights view of a LoRA-armed param tree (what the serve
    engine takes as ``params`` — it grafts its own bank)."""
    if not isinstance(tree, dict):
        return tree
    return {k: _strip_lora(v) for k, v in tree.items()
            if k not in ("lora_A", "lora_B")}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rank", type=int, default=8,
                        help="LoRA rank r: the adapter is one (in, r) + "
                             "(r, out) pair per targeted projection.")
    parser.add_argument("--max-epochs", type=int, default=8,
                        help="adapter fine-tune epochs (8 is enough for "
                             "the successor rule to dominate the tuned "
                             "row's greedy continuation).")
    parser.add_argument("--publish-dir", default=None,
                        help="where to publish the adapter checkpoint "
                             "(default: a temp directory).")
    parser.add_argument("--max-new", type=int, default=16)
    args = parser.parse_args()

    import optax

    from ray_lightning_tpu import RayStrategy, Trainer
    from ray_lightning_tpu.core.checkpoint import (load_sharded_checkpoint,
                                                   save_sharded_checkpoint)
    from ray_lightning_tpu.data.loader import ArrayDataset, DataLoader
    from ray_lightning_tpu.models import (GPTModule, LoraConfig,
                                          TransformerLM, extract_adapter,
                                          gpt2_config)
    from ray_lightning_tpu.serve import ServeClient

    vocab, seq_len = 64, 32
    lora = LoraConfig(rank=args.rank, num_adapters=1)
    # unrolled layers: the bank helpers (and the serve engine) address
    # per-layer projections by name, not through a scanned stack
    cfg = gpt2_config("nano", vocab_size=vocab, max_seq_len=seq_len,
                      scan_layers=False, lora=lora)

    class LoraGPT(GPTModule):
        """GPTModule fine-tuning ONLY the adapter.

        The successor-token stream is the 'domain' being adapted to;
        the frozen base (random init here — in production, a trained
        checkpoint) is what every other adapter and the null row keep
        sharing.
        """

        def _loader(self, seed: int, shuffle: bool = False):
            rng = np.random.default_rng(seed)
            starts = rng.integers(0, vocab, size=self.num_samples)
            toks = (starts[:, None]
                    + np.arange(seq_len + 1)[None, :]) % vocab
            toks = toks.astype(np.int32)
            return DataLoader(ArrayDataset((toks[:, :-1], toks[:, 1:])),
                              batch_size=self.batch_size, shuffle=shuffle)

        def init_variables(self, model, rng, batch):
            variables = super().init_variables(model, rng, batch)
            # standard LoRA init: A ~ N(0, 0.02), B = 0 — the delta
            # starts at exactly zero (step 0 IS the base model) but
            # gradients flow, unlike the bank's unloaded-slot zero/zero
            # (crc32, not hash(): per-path keys must not depend on the
            # process's string-hash salt)
            import zlib
            akey = jax.random.PRNGKey(99)
            return jax.tree_util.tree_map_with_path(
                lambda p, leaf: 0.02 * jax.random.normal(
                    jax.random.fold_in(
                        akey,
                        zlib.crc32(jax.tree_util.keystr(p).encode())),
                    leaf.shape, leaf.dtype)
                if _is_lora_leaf(p) and p[-1].key == "lora_A" else leaf,
                variables)

        def configure_optimizers(self):
            labels = (lambda params: jax.tree_util.tree_map_with_path(
                lambda p, _: "adapter" if _is_lora_leaf(p) else "frozen",
                params))
            return optax.multi_transform(
                {"adapter": super().configure_optimizers(),
                 "frozen": optax.set_to_zero()}, labels)

    # 1) train: only the lora leaves move
    def fit(epochs):
        module = LoraGPT(config=cfg, batch_size=8, seq_len=seq_len,
                         num_samples=64, lr=2e-2, vocab_size=vocab)
        trainer = Trainer(strategy=RayStrategy(num_workers=1),
                          max_epochs=epochs, enable_progress_bar=False,
                          enable_checkpointing=False, seed=0)
        trainer.fit(module)
        return jax.device_get(trainer.train_state.params), trainer

    trained, trainer = fit(args.max_epochs)

    # the freeze self-check: two fits of different lengths share the
    # same seeded init, so a real freeze means bitwise-identical base
    # weights — while the adapter leaves keep moving with more steps
    short, _ = fit(1)
    frozen_ok = all(
        np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(_strip_lora(trained)),
            jax.tree_util.tree_leaves(_strip_lora(short))))
    adapter_moved = any(
        not np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(extract_adapter(trained, 0)),
            jax.tree_util.tree_leaves(extract_adapter(short, 0))))
    plain_cfg = dataclasses.replace(cfg, lora=None)
    print(f"base weights bitwise frozen through fine-tune: {frozen_ok} "
          f"(adapter kept training: {adapter_moved})")
    if not frozen_ok or not adapter_moved:
        raise SystemExit("optimizer mask leaked into base weights")

    # 2) publish: slice the adapter out of the bank, commit it through
    #    the checkpoint layer like any other artifact
    adapter = extract_adapter(trained, 0)
    publish_dir = args.publish_dir or os.path.join(
        tempfile.mkdtemp(prefix="lora_publish_"), "tuned")
    save_sharded_checkpoint(
        publish_dir,
        {"step": trainer.global_step, "lora_rank": args.rank,
         "lora_targets": list(lora.targets)}, adapter)
    ckpt = load_sharded_checkpoint(publish_dir)
    assert ckpt["lora_rank"] == args.rank
    published = ckpt["state"]
    n_leaves = len(jax.tree_util.tree_leaves(published))
    print(f"published adapter -> {publish_dir} "
          f"({n_leaves} low-rank leaves, rank {args.rank})")

    # 3) hot-load into a running engine next to the base model
    dec = TransformerLM(dataclasses.replace(plain_cfg, decode=True))
    base_params = _strip_lora(trained)
    prompt = [3, 4, 5, 6]
    kw = dict(max_new_tokens=args.max_new, seed=7)

    client = ServeClient(dec, base_params, num_slots=4, prefill_len=8,
                         max_resident_adapters=2, lora_rank=args.rank)
    rid_base = client.submit(prompt, **kw)            # base traffic...
    base_tok = client.run_until_idle()[rid_base].tokens
    client.load_adapter("tuned", published)           # ...then hot load
    rid_mix_b = client.submit(prompt, **kw)           # mixed batch:
    rid_mix_t = client.submit(prompt, adapter="tuned", **kw)
    mixed = client.run_until_idle()
    client.shutdown()
    tuned_tok = mixed[rid_mix_t].tokens

    hits = sum(t == (p + 1) % vocab for t, p in zip(
        tuned_tok, prompt[-1:] + tuned_tok[:-1]))
    print(f"base row: {base_tok}\ntuned row: {tuned_tok} "
          f"({hits}/{len(tuned_tok)} successor-rule tokens)")

    # identity self-checks: hot load ≡ build-time residency, and the
    # null row ≡ a bankless engine
    ref = ServeClient(dec, base_params, num_slots=4, prefill_len=8,
                      adapters={"tuned": published},
                      max_resident_adapters=2, lora_rank=args.rank)
    r0 = ref.submit(prompt, **kw)
    r1 = ref.submit(prompt, adapter="tuned", **kw)
    ref_out = ref.run_until_idle()
    ref.shutdown()
    bare = ServeClient(dec, base_params, num_slots=4, prefill_len=8)
    r2 = bare.submit(prompt, **kw)
    bare_tok = bare.run_until_idle()[r2].tokens
    bare.shutdown()

    ok = (mixed[rid_mix_t].tokens == ref_out[r1].tokens
          and mixed[rid_mix_b].tokens == ref_out[r0].tokens
          and base_tok == bare_tok == mixed[rid_mix_b].tokens)
    print(f"hot-load ≡ build-time residency, null row ≡ bankless: {ok}")
    if not ok:
        raise SystemExit("adapter lifecycle identity check failed")


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
