"""GPT-2 family modules — the flagship model (BASELINE.json: "GPT-2-medium,
RayShardedStrategy → FSDP on v4-32").

Causal LM built on the shared TPU-first transformer core; sizes mirror the
public GPT-2 family. Data is the synthetic Markov token stream (zero-egress
environment) — learnable, so loss visibly drops in tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.data.loader import ArrayDataset, DataLoader
from ray_lightning_tpu.data.synthetic import synthetic_tokens
from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)

GPT2_SIZES = {
    # name: (n_layers, d_model, n_heads)
    "nano": (2, 128, 4),          # test size
    "small": (12, 768, 12),       # 124M
    "medium": (24, 1024, 16),     # 350M
    "large": (36, 1280, 20),      # 774M
    "xl": (48, 1600, 25),         # 1.5B
}


def gpt2_config(size: str = "small",
                vocab_size: int = 50257,
                max_seq_len: int = 1024,
                **overrides) -> TransformerConfig:
    n_layers, d_model, n_heads = GPT2_SIZES[size]
    base = dict(
        vocab_size=vocab_size, max_seq_len=max_seq_len, d_model=d_model,
        n_heads=n_heads, n_layers=n_layers, d_ff=4 * d_model, causal=True)
    base.update(overrides)
    return TransformerConfig(**base)


class GPTModule(TpuModule):
    """Next-token LM training module over synthetic token streams."""

    def __init__(self,
                 config: Optional[TransformerConfig] = None,
                 size: str = "nano",
                 batch_size: int = 8,
                 seq_len: Optional[int] = None,
                 num_samples: int = 256,
                 lr: float = 3e-4,
                 weight_decay: float = 0.1,
                 vocab_size: int = 1024,
                 optimizer: str = "adamw"):
        super().__init__()
        if config is None:
            seq_len = 128 if seq_len is None else seq_len
            config = gpt2_config(size, vocab_size=vocab_size,
                                 max_seq_len=seq_len)
        self.cfg = config
        seq_len = config.max_seq_len if seq_len is None else seq_len
        if seq_len > config.max_seq_len:
            raise ValueError(
                f"seq_len={seq_len} exceeds config.max_seq_len="
                f"{config.max_seq_len}; positions would silently clamp")
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.num_samples = num_samples
        self.lr = lr
        self.weight_decay = weight_decay
        self.optimizer = optimizer

    def configure_model(self):
        return TransformerLM(self.cfg)

    def configure_optimizers(self):
        # memory-efficient presets ("adamw_bf16m", "adafactor") buy back
        # the optimizer-state HBM that forces large models into slow
        # layouts on one chip — see core/optim.py
        from ray_lightning_tpu.core.optim import make_optimizer
        # b2=0.95 applies to the adam presets; the factored branch runs
        # its own second-moment schedule and warns when b2 is forced on
        # it, so only pass it where it means something
        kwargs = {} if self.optimizer == "adafactor" else {"b2": 0.95}
        return make_optimizer(self.optimizer, self.lr,
                              weight_decay=self.weight_decay, **kwargs)

    def _loader(self, seed: int, shuffle: bool = False):
        toks = synthetic_tokens(self.num_samples, self.seq_len + 1,
                                self.cfg.vocab_size, seed=seed)
        # pre-split (inputs, targets): every batch leaf is (B, seq_len), so
        # sequence-dim sharding (SequenceParallelStrategy) divides evenly
        return DataLoader(ArrayDataset((toks[:, :-1], toks[:, 1:])),
                          batch_size=self.batch_size, shuffle=shuffle)

    def train_dataloader(self):
        return self._loader(0, shuffle=True)

    def val_dataloader(self):
        return self._loader(1)

    def init_variables(self, model, rng, batch):
        return model.init(rng, batch[0])

    def _loss(self, model, variables, batch, rng, deterministic):
        inputs, targets = batch
        rngs = {"dropout": rng} if self.cfg.dropout > 0 else None
        logits = model.apply(variables, inputs,
                             deterministic=deterministic, rngs=rngs)
        with jax.named_scope("xent"):
            loss = jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets))
        return loss, logits

    def training_step(self, model, variables, batch, rng):
        loss, _ = self._loss(model, variables, batch, rng,
                             deterministic=self.cfg.dropout == 0.0)
        self.log("train_ppl", jnp.exp(loss))
        return loss

    def validation_step(self, model, variables, batch, rng):
        loss, _ = self._loss(model, variables, batch, rng,
                             deterministic=True)
        return {"val_loss": loss, "val_ppl": jnp.exp(loss)}


def count_params(params) -> int:
    import jax
    return sum(int(np.prod(l.shape))
               for l in jax.tree_util.tree_leaves(params))
