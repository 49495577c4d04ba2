"""Vision Transformer family.

Model-zoo breadth beyond the reference (whose examples cover MLP/CNN/GPT
seats): a ViT classifier built from the same ``TransformerStack`` the
BERT/GPT families use, so every parallelism rule that works there
(tensor-parallel layouts, FSDP largest-dim sharding, remat, scanned
layers) applies to vision unchanged. Patch embedding is a single strided
conv — one big MXU matmul per image, no host-side patch extraction.
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.data.loader import ArrayDataset, DataLoader
from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                  TransformerStack)


def vit_config(size: str = "tiny", image_size: int = 32,
               patch_size: int = 4, **overrides) -> TransformerConfig:
    sizes = {
        "tiny": (4, 192, 3),
        "small": (12, 384, 6),
        "base": (12, 768, 12),   # ViT-B
    }
    if size not in sizes:
        raise ValueError(f"Unknown ViT size {size!r}; choose from "
                         f"{sorted(sizes)}")
    n_layers, d_model, n_heads = sizes[size]
    if image_size % patch_size != 0:
        raise ValueError(
            f"image_size={image_size} must be divisible by "
            f"patch_size={patch_size} (non-overlapping square patches)")
    n_patches = (image_size // patch_size) ** 2
    base = dict(vocab_size=1,  # unused: inputs are pixels, not tokens
                max_seq_len=n_patches + 1,  # +1 CLS
                d_model=d_model, n_heads=n_heads, n_layers=n_layers,
                d_ff=4 * d_model, causal=False,
                # remat + save_attn ships as the ViT default: saving
                # every activation costs more HBM write traffic than the
                # backward recompute (round-5 sweep at base/224/bs32:
                # docs/performance.md "Model-zoo lever sweep"; no cell
                # measures it) and is semantics-preserving. Override with
                # remat=False to trade throughput for compile simplicity.
                remat=True,
                remat_policy="dots_with_no_batch_dims_save_attn")
    base.update(overrides)
    if not base["remat"] and "remat_policy" not in overrides:
        # opting out via remat=False must not trip the config's
        # remat_policy-without-remat guard on the default policy
        base["remat_policy"] = None
    return TransformerConfig(**base)


class ViTClassifier(nn.Module):
    """ViT: conv patch embed + CLS token + bidirectional transformer."""
    cfg: TransformerConfig
    num_classes: int = 10
    patch_size: int = 4

    @nn.compact
    def __call__(self, images, deterministic: bool = True):
        cfg = self.cfg
        B = images.shape[0]
        p = self.patch_size
        x = nn.Conv(cfg.d_model, kernel_size=(p, p), strides=(p, p),
                    padding="VALID", dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    name="patch_embed")(images.astype(cfg.dtype))
        x = x.reshape(B, -1, cfg.d_model)  # (B, n_patches, D)
        cls = self.param("cls_token", nn.initializers.zeros,
                         (1, 1, cfg.d_model), cfg.param_dtype)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls, (B, 1, cfg.d_model)).astype(cfg.dtype),
             x], axis=1)
        T = x.shape[1]
        pos = self.param("pos_embed",
                         nn.initializers.normal(stddev=0.02),
                         (1, cfg.max_seq_len, cfg.d_model),
                         cfg.param_dtype)
        x = x + pos[:, :T].astype(cfg.dtype)
        x = TransformerStack(cfg, name="stack")(
            x, deterministic=deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="head_ln")(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x[:, 0])


def _synthetic_images(num_samples: int, image_size: int, num_classes: int,
                      seed: int = 0):
    """Class-conditioned noisy images so accuracy is learnable quickly.

    The class prototypes are drawn from a FIXED seed so train/val/test
    splits (different ``seed``) share one distribution and only differ in
    sampling noise — otherwise validation measures a different task.
    """
    protos = np.random.default_rng(1234).standard_normal(
        (num_classes, image_size, image_size, 3))
    rng = np.random.default_rng(seed)
    y = rng.integers(0, num_classes, size=(num_samples,)).astype(np.int32)
    x = protos[y] + 0.3 * rng.standard_normal(
        (num_samples, image_size, image_size, 3))
    return x.astype(np.float32), y


class ViTModule(TpuModule):
    """Image classification on synthetic class-prototype data."""

    def __init__(self,
                 size: str = "tiny",
                 image_size: int = 32,
                 patch_size: int = 4,
                 num_classes: int = 10,
                 batch_size: int = 32,
                 num_samples: int = 512,
                 lr: float = 1e-3,
                 config: Optional[TransformerConfig] = None):
        super().__init__()
        self.cfg = config or vit_config(size, image_size, patch_size)
        if image_size % patch_size != 0:
            raise ValueError(f"image_size={image_size} not divisible by "
                             f"patch_size={patch_size}")
        seq = (image_size // patch_size) ** 2 + 1  # patches + CLS
        if seq > self.cfg.max_seq_len:
            raise ValueError(
                f"config.max_seq_len={self.cfg.max_seq_len} is too small "
                f"for image_size={image_size}/patch_size={patch_size} "
                f"({seq} tokens incl. CLS) — build the config with "
                "vit_config(image_size=..., patch_size=...) matching the "
                "module arguments")
        self.image_size = image_size
        self.patch_size = patch_size
        self.num_classes = num_classes
        self.batch_size = batch_size
        self.num_samples = num_samples
        self.lr = lr

    def configure_model(self):
        return ViTClassifier(self.cfg, self.num_classes, self.patch_size)

    def configure_optimizers(self):
        return optax.adamw(self.lr, weight_decay=0.05)

    def _loader(self, seed: int, shuffle: bool = False):
        x, y = _synthetic_images(self.num_samples, self.image_size,
                                 self.num_classes, seed)
        return DataLoader(ArrayDataset(x, y), batch_size=self.batch_size,
                          shuffle=shuffle)

    def train_dataloader(self):
        return self._loader(seed=0, shuffle=True)

    def val_dataloader(self):
        return self._loader(seed=1)

    def test_dataloader(self):
        return self._loader(seed=2)

    def init_variables(self, model, rng, batch):
        return model.init(rng, batch[0])

    def training_step(self, model, variables, batch, rng):
        images, labels = batch
        deterministic = self.cfg.dropout == 0.0
        rngs = None if deterministic else {"dropout": rng}
        logits = model.apply(variables, images,
                             deterministic=deterministic, rngs=rngs)
        loss = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, labels))
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(
            jnp.float32))
        self.log("train_acc", acc)
        return loss

    def validation_step(self, model, variables, batch, rng):
        images, labels = batch
        logits = model.apply(variables, images, deterministic=True)
        loss = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, labels))
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(
            jnp.float32))
        return {"val_loss": loss, "val_acc": acc}

    def test_step(self, model, variables, batch, rng):
        logs = self.validation_step(model, variables, batch, rng)
        return {"test_loss": logs["val_loss"], "test_acc": logs["val_acc"]}
