"""Olmo-Hybrid LM (``model_type: olmo_hybrid``): Gated-DeltaNet layers
with a matrix state per head, one full-attention layer in four, on the
serving protocol ``serve/engine.py`` drives (the one ``models/sambay.py``
is on).

Every layer ``l`` is ``x += RMSNorm(mixer_l(x)); x += RMSNorm(W_down(
silu(W_gate x) * (W_up x)))`` — the norm sits on each sub-layer's
*output* — then a final RMSNorm and an **untied** head. No bias and **no
positional encoding** anywhere. The mixer by ``cfg.layer_types[l]``:

- ``linear_attention`` — **Gated DeltaNet** (arXiv:2412.06464). ``q``,
  ``k`` (``H`` heads of ``d_k``) and ``v`` (``H`` heads of ``d_v``) come
  from one projection, go each through its own depthwise causal
  convolution of ``linear_conv_kernel_dim`` taps and ``silu``; per head
  ``q <- q / |q| * d_k^-1/2``, ``k <- k / |k|``; ``beta = 2 sigmoid(W_b
  x)`` (the 2 is ``linear_allow_neg_eigval``), ``alpha = exp(-exp(A_log)
  softplus(W_a x + dt_bias))``; the delta rule of
  ``ops/gated_delta.py`` on a ``(d_v, d_k)`` float32 state per head;
  ``y = W_o(RMSNorm_{d_v}(o) * silu(W_g x))``.
- ``full_attention`` — ``q = RMSNorm_h(W_q x)``, ``k = RMSNorm_h(W_k
  x)`` (QK-norm over the whole projection, before the split into heads),
  causal softmax attention at ``head_dim^-1/2``, ``y = W_o a``.

A slot's cache is two kinds of state, each leaf declared
(:meth:`OlmoHybridLM.cache_leaf`): *recurrent* ``delta_state``
``(B, H, d_v, d_k)`` and ``conv_state`` ``(B, taps - 1, 2 H d_k + H
d_v)``, both float32, and the full layers' *global* ``cached_key`` /
``cached_value`` ``(B, max_seq_len, heads, head_dim)``.

Call modes (``cfg.decode`` selects the cached ones):

- full forward (``decode=False``): every position's logits, no cache —
  what the CPU tests compare with the plain reference;
- **continue** (``decode=True``, ``kv_positions=None``): a ``(B, C)``
  piece at absolute ``offset`` (B,) with ``lengths`` (B,) valid tokens
  in it, *starting from the cache it is given*. Afterwards each row's
  recurrent state is as of its last valid token, its conv tail holds the
  last ``taps - 1`` inputs, its K/V holds positions ``offset .. offset +
  C - 1``, and the call returns the ``(B, 1, V)`` logits of the last
  valid token. A row of length 0 leaves its state as it was;
- prefill is continue from a zero cache at ``offset`` 0 (what
  ``generate._prefill_impl`` does: the length contract of
  ``generate.prefill``) — one implementation, not two. The model says so
  with :attr:`OlmoHybridLM.continues_prefill`, which is what lets
  ``ServeEngine(prefill_chunk=)`` stream a long prompt into a dense slot
  piece by piece;
- decode step (``kv_positions`` (B, 1)): one token a row at its own
  absolute position.

Matmul operands are ``cfg.dtype`` (bfloat16) with float32 accumulation;
the residual stream, norms, softmax, ``alpha``, ``beta``, the state, the
conv tail and the in-block solve are float32 (``cfg.state_dtype`` exists
so a test can show that a bfloat16 state fails the reference). Weights
are held in ``cfg.param_dtype``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.generate import CacheLeaf
from ray_lightning_tpu.models.sambay import (_dt_bias_init, _gather_rows,
                                             _Linear, _mask, _uniform)
from ray_lightning_tpu.ops.gated_delta import (gated_delta_chunk,
                                               gated_delta_step, hold)

LINEAR, FULL = "linear_attention", "full_attention"

#: keys a continue call's attention reads at a time (an online softmax
#: over blocks: a piece of 512 queries against a slot of 4608 positions
#: never holds more than one block of scores)
KEY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    # the published keys (config.json of Olmo-Hybrid-7B)
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 65536
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # how it is run
    max_seq_len: int = 4608          # positions one slot holds
    decode: bool = False
    dtype: Any = jnp.bfloat16        # matmul operands
    param_dtype: Any = jnp.bfloat16  # weights at rest
    state_dtype: Any = jnp.float32   # delta state, conv tail

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every layer: "
                             f"{len(self.layer_types)} entries for "
                             f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.tie_word_embeddings:
            raise ValueError("Olmo-Hybrid's head is not tied")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("the full-attention layers are written for "
                             "as many key-value heads as query heads")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the delta rule is written for as many key "
                             "heads as value heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
        if self.max_seq_len > self.max_position_embeddings:
            raise ValueError("max_seq_len exceeds the declared positions")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        return 2 * self.key_width + self.value_width


# ------------------------------------------------------------ pieces
def _linear(cfg: OlmoHybridConfig, features: int, name: str) -> _Linear:
    return _Linear(features, False, cfg.dtype, cfg.param_dtype, name=name)


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) \
            * scale


def _put_rows(cache, block, start):
    """Row ``b``'s ``block (T, H, D)`` into ``cache[b, start[b]:start[b]
    + T]``, in place on a donated buffer (a start past ``L - T`` clamps
    to it, as ``dynamic_update_slice`` does)."""
    return jax.vmap(lambda row, new, at: jax.lax.dynamic_update_slice_in_dim(
        row, new, at, axis=0))(cache, block.astype(cache.dtype), start)


def _attend(q, k, v, mask, dtype):
    """Softmax attention. ``q (B, T, H, D)``, ``k`` / ``v``
    ``(B, S, H, D)``, ``mask`` additive ``(B|1, T, S)`` -> ``(B, T, H
    D)`` float32."""
    B, T, H, D = q.shape
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(dtype), k.astype(dtype),
                        preferred_element_type=jnp.float32) * D ** -0.5
    probs = jax.nn.softmax(scores + mask[:, None], axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(dtype),
                     v.astype(dtype), preferred_element_type=jnp.float32)
    return out.reshape(B, T, H * D)


def _attend_cached(q, ck, cv, offset, dtype):
    """A continue call's attention: query ``t`` of row ``b`` sits at
    ``offset[b] + t`` and reads the cache's positions up to itself, a
    block of :data:`KEY_BLOCK` keys at a time under a running softmax.
    Only blocks some row still reads are visited."""
    B, T, H, D = q.shape
    S = ck.shape[1]
    blk = min(KEY_BLOCK, S)
    at = offset[:, None] + jnp.arange(T)[None, :]             # (B, T)
    n_live = jnp.minimum((jnp.max(offset) + T + blk - 1) // blk,
                         -(-S // blk))
    q = q.astype(dtype)

    def body(i, carry):
        m, l, acc = carry
        # the last block of a slot whose length is no multiple of the
        # block starts early; the keys it repeats are masked out below
        start = jnp.minimum(i * blk, S - blk)
        k = jax.lax.dynamic_slice_in_dim(ck, start, blk, axis=1)
        v = jax.lax.dynamic_slice_in_dim(cv, start, blk, axis=1)
        s = jnp.einsum("bthd,bshd->bhts", q, k.astype(dtype),
                       preferred_element_type=jnp.float32) * D ** -0.5
        key_at = start + jnp.arange(blk)
        ok = (key_at[None, None, :] <= at[:, :, None]) \
            & (key_at >= i * blk)[None, None, :]
        s = s + _mask(ok)[:, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None]) * ok[:, None]
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p.astype(dtype), v.astype(dtype),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((B, H, T), jnp.finfo(jnp.float32).min, jnp.float32),
            jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros((B, H, T, D), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_live, body, init)
    out = acc / l[..., None]
    return jnp.swapaxes(out, 1, 2).reshape(B, T, H * D)


class FullAttention(nn.Module):
    """QK-normed causal attention and this layer's K/V cache."""
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, offset, kv_positions):
        cfg = self.cfg
        B, T, d = x.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        qkv = _linear(cfg, 3 * d, "qkv")(x)
        with jax.named_scope("attn/qk_norm"):
            q = _RMSNorm(cfg.rms_norm_eps, name="q_norm")(qkv[..., :d])
            k = _RMSNorm(cfg.rms_norm_eps, name="k_norm")(qkv[..., d:2 * d])
        q, k = q.reshape(B, T, H, D), k.reshape(B, T, H, D)
        v = qkv[..., 2 * d:].reshape(B, T, H, D)

        def block_attend():
            with jax.named_scope("attn/attend"):
                causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
                return _attend(q, k, v, _mask(causal)[None], cfg.dtype)

        if not cfg.decode:
            out = block_attend()
        else:
            is_init = not self.has_variable("cache", "cached_key")
            shape = (B, cfg.max_seq_len, H, D)
            ck = self.variable("cache", "cached_key", jnp.zeros, shape,
                               cfg.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros, shape,
                               cfg.dtype)
            if is_init:
                out = jnp.zeros((B, T, d), jnp.float32)
            elif kv_positions is None:          # continue
                with jax.named_scope("attn/kv_write"):
                    ck.value = _put_rows(ck.value, k, offset)
                    cv.value = _put_rows(cv.value, v, offset)
                with jax.named_scope("attn/attend"):
                    out = _attend_cached(q, ck.value, cv.value, offset,
                                         cfg.dtype)
            else:                               # decode step
                pos = kv_positions[:, 0].astype(jnp.int32)
                with jax.named_scope("attn/kv_write"):
                    # not ops.cache_write.write_rows: its (B, H, D, L)
                    # view is a bitcast only where the TPU lays L out
                    # minor-most (heads x head_dim too small to tile).
                    # A head of 128 is a lane row: the view was a real
                    # transpose, and the step copied every K/V leaf
                    # whole, twice (PERF.md section 6, PR 32)
                    ck.value = _put_rows(ck.value, k, pos)
                    cv.value = _put_rows(cv.value, v, pos)
                with jax.named_scope("attn/attend"):
                    # the whole slot under a mask, at the memory's speed
                    # (1.5 ms a layer for 16 slots of 4608 on a v5e).
                    # The block loop of _attend_cached reads only what is
                    # live and still took twice as long for one query a
                    # row: its per-block ops are too small (PERF.md
                    # section 6, PR 32)
                    live = jnp.arange(cfg.max_seq_len)[None, None, :] \
                        <= pos[:, None, None]
                    out = _attend(q, ck.value, cv.value, _mask(live),
                                  cfg.dtype)
        return _linear(cfg, d, "out")(out)


class GatedDeltaNet(nn.Module):
    """The delta-rule mixer. State layout ``(B, H, d_v, d_k)``."""
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, lengths, kv_positions):
        cfg = self.cfg
        B, T, d = x.shape
        H, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        K, kw, cw = cfg.linear_conv_kernel_dim, cfg.key_width, cfg.conv_width
        qkv = _linear(cfg, cw, "qkv")(x)
        gate = _linear(cfg, cfg.value_width, "gate")(x)
        ab = _linear(cfg, 2 * H, "ab")(x)
        conv_w = self.param("conv_kernel", _uniform(K ** -0.5), (K, cw),
                            jnp.float32)
        a_log = self.param("A_log", nn.initializers.zeros, (H,), jnp.float32)
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1]: with A_log = 0
        # the decay alpha = exp(-dt) spans 0.905 .. 0.999
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
        o_scale = self.param("o_norm", nn.initializers.ones, (dv,),
                             jnp.float32)

        cached = step = False
        if cfg.decode:      # (not cached: the shape-building init pass)
            cached = self.has_variable("cache", "delta_state")
            s_var = self.variable("cache", "delta_state", jnp.zeros,
                                  (B, H, dv, dk), cfg.state_dtype)
            tail_var = self.variable("cache", "conv_state", jnp.zeros,
                                     (B, K - 1, cw), cfg.state_dtype)
            step = cached and kv_positions is not None

        with jax.named_scope("gdn/conv"):
            before = tail_var.value.astype(jnp.float32) if cached \
                else jnp.zeros((B, K - 1, cw), jnp.float32)
            xp = jnp.concatenate([before, qkv], axis=1)   # (B, T+K-1, cw)
            qkv = jax.nn.silu(sum(conv_w[i] * xp[:, i:i + T]
                                  for i in range(K)))
            q = qkv[..., :kw].reshape(B, T, H, dk)
            k = qkv[..., kw:2 * kw].reshape(B, T, H, dk)
            v = qkv[..., 2 * kw:].reshape(B, T, H, dv)
            # an all-zero head (a pad position) must stay finite
            unit = lambda z: z * jax.lax.rsqrt(                # noqa: E731
                jnp.sum(jnp.square(z), axis=-1, keepdims=True) + 1e-12)
            q, k = unit(q) * dk ** -0.5, unit(k)
            beta = jax.nn.sigmoid(ab[..., H:])
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            log_alpha = -jnp.exp(a_log) * jax.nn.softplus(
                ab[..., :H] + dt_bias)
        state = s_var.value.astype(jnp.float32) if cached \
            else jnp.zeros((B, H, dv, dk), jnp.float32)
        if step:
            with jax.named_scope("gdn/step"):
                o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                            log_alpha[:, 0], beta[:, 0],
                                            state)
                o, state = o[:, None], hold(state, cfg.state_dtype)
                tail = xp[:, T:]
        else:
            with jax.named_scope("gdn/chunk"):
                o, state = gated_delta_chunk(q, k, v, log_alpha, beta,
                                             state, lengths,
                                             cfg.state_dtype)
                # the inputs at L - (K-1) .. L - 1 of what came before
                # and the piece (a row of length 0 keeps its tail)
                tail = jax.vmap(
                    lambda row, i: jax.lax.dynamic_slice_in_dim(
                        row, i, K - 1, axis=0))(xp, lengths)
        if cached:
            s_var.value = state.astype(cfg.state_dtype)
            tail_var.value = tail.astype(cfg.state_dtype)
        with jax.named_scope("gdn/gate_norm"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True)
                                  + cfg.rms_norm_eps) * o_scale
            o = o.reshape(B, T, H * dv) * jax.nn.silu(gate)
        return _linear(cfg, d, "out")(o)


class GatedMLP(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gu = _linear(cfg, 2 * cfg.intermediate_size, "gate_up")(x)
        g, up = jnp.split(gu, 2, axis=-1)
        return _linear(cfg, cfg.hidden_size, "down")(jax.nn.silu(g) * up)


class OlmoHybridLM(nn.Module):
    """See the module docstring. ``positions`` is accepted and unused
    (the architecture has no positional encoding); ``lengths`` (B,) is
    the valid tokens of a continue call's piece (``None`` = all of it),
    ``offset`` (B,) the absolute position of its first token (``None`` =
    0)."""
    cfg: OlmoHybridConfig

    #: a slot holds state that is no K/V row at absolute positions: the
    #: prefill hands this model its row lengths and takes back
    #: last-position logits only (``generate._prefill_impl``), and
    #: ``ServeEngine.__init__`` refuses, by name, what it cannot put
    #: around such a model yet
    recurrent_state = True
    #: the prefill is a *continue* from the cache it is given (``offset``
    #: and ``lengths`` a row): ``ServeEngine(prefill_chunk=)`` may feed a
    #: prompt into a dense slot in pieces
    continues_prefill = True

    def cache_leaf(self, names: Tuple[str, ...]) -> CacheLeaf:
        """What one leaf of the ``cache`` collection is (by its path):
        every leaf here belongs to a slot, on axis 0."""
        return {
            "delta_state": CacheLeaf(0, "recurrent"),
            "conv_state": CacheLeaf(0, "recurrent"),
            "cached_key": CacheLeaf(0, "global", seq_axis=1),
            "cached_value": CacheLeaf(0, "global", seq_axis=1),
        }[names[-1]]

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, positions=None,
                 kv_positions=None, lengths=None, offset=None):
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.rms_norm_eps
        proceed = cfg.decode and kv_positions is None
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        offset = jnp.zeros((B,), jnp.int32) if offset is None \
            else jnp.asarray(offset, jnp.int32)
        embedding = self.param("embedding", nn.initializers.normal(0.02),
                               (cfg.vocab_size, cfg.hidden_size),
                               cfg.param_dtype)
        x = jnp.take(embedding, tokens, axis=0).astype(jnp.float32)
        for layer, kind in enumerate(cfg.layer_types):
            scope = f"layer_{layer}"
            if kind == LINEAR:
                out = GatedDeltaNet(cfg, name=scope + "_gdn")(
                    x, lengths, kv_positions)
            else:
                out = FullAttention(cfg, name=scope + "_attn")(
                    x, offset, kv_positions)
            x = x + _RMSNorm(eps, name=scope + "_mixer_norm")(out)
            x = x + _RMSNorm(eps, name=scope + "_mlp_norm")(
                GatedMLP(cfg, name=scope + "_mlp")(x))
        if proceed:
            # the head over each row's last valid token only
            x = _gather_rows(x, jnp.maximum(lengths - 1, 0))
        x = _RMSNorm(eps, name="norm_f")(x)
        return _linear(cfg, cfg.vocab_size, "lm_head")(x)
