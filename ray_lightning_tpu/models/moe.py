"""Mixture-of-Experts transformer with expert parallelism over the ``ep``
mesh axis (net-new beyond the reference — SURVEY.md §2.3 lists EP/MoE as
absent upstream; the multi-axis mesh makes it nearly free here).

TPU-first formulation: the classic GShard/Switch dense dispatch. Routing
produces **static-shape one-hot dispatch/combine tensors** (no gather /
dynamic shapes — XLA can tile everything onto the MXU), expert FFNs run as
one batched einsum over a leading experts dimension, and expert parallelism
is *pure sharding*: partition the experts dimension of the weights (and the
dispatched activations) along ``ep`` and GSPMD inserts the all-to-alls.
:func:`expert_parallel_rule` is the ready-made ``MeshStrategy`` param rule.

Capacity semantics: each expert processes at most
``capacity = ceil(top_k · tokens · capacity_factor / n_experts)`` tokens per
batch; overflow tokens are *dropped* for that expert slot (their combine
weight is 0, so they pass through the residual unchanged) — Switch
Transformer's behavior, and the price of static shapes. The router aux loss
(Switch eq. 4: ``E · Σ_e f_e · P_e``) pushes the load flat so drops stay
rare.

This is the **train-only capacity router**: a served token may not be
dropped, and ``(N, E, C)`` dispatch tensors do not fit a decode step. The
serve engine's expert layer is the dropless one of
``ops/grouped_experts.py`` (sorted and grouped, one ragged product a
projection, told which experts it holds), which ``models/afmoe.py`` runs
behind ``ServeClient``.
"""
from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.data.loader import ArrayDataset, DataLoader
from ray_lightning_tpu.models.transformer import (MultiHeadAttention,
                                                  kv_cache_leaf,
                                                  TransformerConfig,
                                                  maybe_remat)


@dataclasses.dataclass(frozen=True)
class MoeConfig(TransformerConfig):
    n_experts: int = 8
    expert_top_k: int = 1
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


def expert_parallel_rule(path, leaf):
    """``MeshStrategy(param_rule=...)`` rule: shard the experts dimension
    of MoE weights along ``ep``; everything else replicated (compose with
    your own rule for tp/fsdp hybrids)."""
    from ray_lightning_tpu.parallel.sharding import leading_dim_rule
    return leading_dim_rule("experts", "ep")(path, leaf)


def route_top_k(probs: jax.Array, capacity: int,
                top_k: int) -> tuple[jax.Array, jax.Array]:
    """Static-shape GShard/Switch routing: ``(dispatch, combine)``.

    Greedy top-k slot assignment: for each of the k slots, take the argmax
    over the not-yet-used experts, place the token at its expert's next
    free capacity position (cumsum trick), and zero that expert out for
    the next slot. Both outputs are ``(N, E, C)``; ``dispatch`` is 0/1,
    ``combine`` carries the router probability of the chosen expert.
    Pure function — unit-tested directly (combine mass per kept token ==
    sum of its top-k probs; per-expert load <= capacity).
    """
    N, E = probs.shape
    remaining = probs
    dispatch = jnp.zeros((N, E, capacity), dtype=jnp.float32)
    combine = jnp.zeros((N, E, capacity), dtype=jnp.float32)
    # position base: tokens claimed by earlier slots per expert
    claimed = jnp.zeros((E,), dtype=jnp.int32)
    for _ in range(top_k):
        expert_idx = jnp.argmax(remaining, axis=-1)        # (N,)
        onehot = jax.nn.one_hot(expert_idx, E,
                                dtype=jnp.float32)         # (N, E)
        gate = jnp.sum(probs * onehot, axis=-1)            # (N,)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # 0-based
        pos = pos + claimed[None, :].astype(jnp.float32) * onehot
        keep = (pos < capacity).astype(jnp.float32) * onehot
        pos_idx = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
        slot = keep[:, :, None] * jax.nn.one_hot(
            pos_idx, capacity, dtype=jnp.float32)          # (N, E, C)
        dispatch = dispatch + slot
        combine = combine + slot * gate[:, None, None]
        claimed = claimed + jnp.sum(onehot, axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    return dispatch, combine


class MoeMlp(nn.Module):
    """Top-k routed expert FFN bank. Returns ``(out, aux_loss)``."""
    cfg: MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, d = x.shape
        N = B * T
        E = cfg.n_experts
        k = cfg.expert_top_k
        capacity = max(1, int(np.ceil(k * N * cfg.capacity_factor / E)))

        tokens = x.reshape(N, d)
        router_logits = nn.Dense(E, dtype=jnp.float32,
                                 param_dtype=cfg.param_dtype,
                                 name="router")(tokens.astype(jnp.float32))
        probs = jax.nn.softmax(router_logits, axis=-1)        # (N, E) f32
        dispatch, combine = route_top_k(probs, capacity, k)

        # Switch aux loss: E * sum_e (fraction routed to e) * (mean prob e)
        frac = jnp.mean(
            jnp.sum(dispatch, axis=2), axis=0)                 # (E,)
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0)) / k

        w_up = self.param("experts_up", nn.initializers.lecun_normal(),
                          (E, d, cfg.d_ff), cfg.param_dtype)
        b_up = self.param("experts_up_bias", nn.initializers.zeros,
                          (E, 1, cfg.d_ff), cfg.param_dtype)
        w_down = self.param("experts_down", nn.initializers.lecun_normal(),
                            (E, cfg.d_ff, d), cfg.param_dtype)
        b_down = self.param("experts_down_bias", nn.initializers.zeros,
                            (E, 1, d), cfg.param_dtype)

        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(cfg.dtype),
                               tokens.astype(cfg.dtype))        # (E, C, d)
        h = jnp.einsum("ecd,edf->ecf", expert_in,
                       w_up.astype(cfg.dtype)) + b_up.astype(cfg.dtype)
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h,
                                w_down.astype(cfg.dtype)) \
            + b_down.astype(cfg.dtype)                          # (E, C, d)
        out = jnp.einsum("ecd,nec->nd", expert_out,
                         combine.astype(cfg.dtype))             # (N, d)
        return out.reshape(B, T, d), aux


class MoeTransformerBlock(nn.Module):
    cfg: MoeConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, kv_positions=None):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        x = x + MultiHeadAttention(cfg, name="attn")(
            h, mask=mask, deterministic=deterministic,
            kv_positions=kv_positions)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        moe_out, aux = MoeMlp(cfg, name="moe")(h)
        return x + moe_out, aux


class MoeTransformerLM(nn.Module):
    """Causal MoE LM. Returns ``(logits, total_aux_loss)`` — aux threaded
    functionally (layers are unrolled; MoE depth is small by design and
    routing differs per layer, so there is no scan win to chase)."""
    cfg: MoeConfig

    def cache_leaf(self, names):
        return kv_cache_leaf(self.cfg, names)

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, positions=None,
                 kv_positions=None):
        cfg = self.cfg
        B, T = tokens.shape
        wte = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="wte")
        x = wte(tokens)
        pos = positions if positions is not None else \
            jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        x = x + nn.Embed(cfg.max_seq_len, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="wpe")(pos)
        aux_total = 0.0
        # same remat seat as the dense stack (cfg.remat / cfg.remat_policy,
        # incl. save_attn): deterministic is arg 3 of the block's __call__
        block_cls = maybe_remat(MoeTransformerBlock, cfg,
                                deterministic_argnum=3)
        for i in range(cfg.n_layers):
            x, aux = block_cls(cfg, name=f"block_{i}")(
                x, None, deterministic, kv_positions)
            aux_total = aux_total + aux
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        logits = wte.attend(x)
        return logits.astype(jnp.float32), aux_total / cfg.n_layers


def moe_config(size: str = "nano", **overrides) -> MoeConfig:
    sizes = {
        "nano": (2, 64, 2, 4),      # layers, d_model, heads, experts
        "small": (4, 256, 4, 8),
    }
    n_layers, d_model, n_heads, n_experts = sizes[size]
    base = dict(d_model=d_model, n_heads=n_heads, n_layers=n_layers,
                d_ff=4 * d_model, n_experts=n_experts, causal=True,
                scan_layers=False)
    base.update(overrides)
    return MoeConfig(**base)


def _synthetic_lm_tokens(num_samples: int, seq_len: int, vocab_size: int,
                         seed: int):
    """Learnable synthetic LM data: next token = (token + 1) mod small
    period, with noise — a pattern a tiny LM drives loss down on fast."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab_size, size=(num_samples, 1))
    ramp = np.arange(seq_len + 1)[None, :]
    toks = ((start + ramp) % vocab_size).astype(np.int32)
    noise = rng.integers(0, vocab_size, size=toks.shape)
    toks = np.where(rng.random(toks.shape) < 0.05, noise, toks)
    return toks[:, :-1], toks[:, 1:]


class MoeModule(TpuModule):
    """MoE LM training module; pairs with
    ``MeshStrategy(axes={"dp": ..., "ep": ...},
    param_rule=expert_parallel_rule)`` for expert parallelism."""

    def __init__(self, config: MoeConfig | None = None, size: str = "nano",
                 batch_size: int = 8, seq_len: int = 64,
                 num_samples: int = 256, lr: float = 1e-3,
                 vocab_size: int = 256, optimizer: str = "adamw"):
        super().__init__()
        if config is None:
            config = moe_config(size, vocab_size=vocab_size,
                                max_seq_len=seq_len)
        self.cfg = config
        self.batch_size = batch_size
        self.seq_len = min(seq_len, config.max_seq_len)
        self.num_samples = num_samples
        self.lr = lr
        self.optimizer = optimizer

    def configure_model(self):
        return MoeTransformerLM(self.cfg)

    def configure_optimizers(self):
        # ``optimizer="adafactor"`` is the lever for MoE: top-k routing
        # touches 1/k of the expert FLOPs per step but the optimizer
        # updates EVERY expert param, so state traffic is a larger share
        # than on dense models (round-5 sweep: docs/performance.md
        # "Model-zoo lever sweep"; no cell measures it). Kept opt-in
        # (default adamw) because switching optimizer families is a
        # modeling decision — see core/optim.py.
        from ray_lightning_tpu.core.optim import make_optimizer
        return make_optimizer(self.optimizer, self.lr, weight_decay=0.01)

    def _loader(self, seed: int, shuffle: bool = False):
        x, y = _synthetic_lm_tokens(self.num_samples, self.seq_len,
                                    self.cfg.vocab_size, seed)
        return DataLoader(ArrayDataset((x, y)), batch_size=self.batch_size,
                          shuffle=shuffle)

    def train_dataloader(self):
        return self._loader(0, shuffle=True)

    def val_dataloader(self):
        return self._loader(1)

    def init_variables(self, model, rng, batch):
        return model.init(rng, batch[0])

    def _loss(self, model, variables, batch):
        tokens, targets = batch
        logits, aux = model.apply(variables, tokens)
        ce = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, targets))
        return ce, aux

    def training_step(self, model, variables, batch, rng):
        ce, aux = self._loss(model, variables, batch)
        self.log("train_ce", ce)
        self.log("train_aux", aux)
        return ce + self.cfg.aux_loss_weight * aux

    def validation_step(self, model, variables, batch, rng):
        ce, aux = self._loss(model, variables, batch)
        return {"val_ce": ce, "val_aux": aux}
