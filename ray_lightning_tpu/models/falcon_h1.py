"""Falcon-H1 LM (``model_type: falcon_h1``): grouped-query attention and
a Mamba-2 mixer **side by side in every layer**, on the serving protocol
``serve/engine.py`` drives (the one ``models/olmo_hybrid.py`` and
``models/afmoe.py`` are on).

Every multiplier is a key of the configuration; where each one sits is
taken from the family's published ``falcon_h1`` modelling code. With
``RMSNorm`` (eps ``rms_norm_eps``) throughout::

    x_0 = E[t] * embedding_multiplier
    u   = RMSNorm_in(x)
    a   = Attn(u * attention_in_multiplier) * attention_out_multiplier
    m   = SSM(u * ssm_in_multiplier) * ssm_out_multiplier
    x  <- x + a + m
    x  <- x + MLP(RMSNorm_ff(x))
    logits = W_head RMSNorm_f(x) * lm_head_multiplier      (untied head)

- **Attn.** ``q = W_q u`` (``Hq`` heads of ``D``), ``k = (W_k u) *
  key_multiplier`` and ``v = W_v u`` (``Hkv`` heads; query head ``h``
  reads key-value head ``h // (Hq / Hkv)``). ``q`` and ``k`` are rotated
  (``models/afmoe.py::rope``: ``rope_theta``, all ``D`` dims, the
  half-split pairing, no scaling). Causal softmax at ``D^-1/2`` in
  float32, ``W_o``. No biases.
- **SSM (Mamba-2).** ``[z | x | B | C | dt] = (W_in u) * mu``, ``mu``
  the five ``ssm_multipliers`` over z (``mamba_d_ssm``), x
  (``mamba_d_ssm``), B and C (``mamba_n_groups x mamba_d_state`` each)
  and dt (``mamba_n_heads``). ``xBC <- silu(causal depthwise conv of
  mamba_d_conv taps, with bias)``; ``dt = softplus(dt + dt_bias)``, ``A
  = -exp(A_log)`` a head; head ``h`` uses group ``h // (H / G)`` of B and
  C; the SSD of ``ops/ssd.py`` on a ``(mamba_d_head, mamba_d_state)``
  float32 state a head; ``y = S C + D * x``; ``y <- RMSNorm`` over each
  of the ``G`` groups of ``(y * silu(z))`` (``mamba_rms_norm``,
  ``mamba_norm_before_gate`` false), then ``W_out``. ``mamba_d_ssm`` sets
  the width; ``mamba_expand`` is not used.
- **MLP.** ``W_down(silu(W_gate v * m_0) * W_up v) * m_1``
  (``mlp_multipliers``).

A slot's cache: every layer holds both kinds of state, each leaf
declared (:meth:`FalconH1LM.cache_leaf`) — *recurrent* ``ssm_state``
``(B, H, P, N)`` and ``conv_state`` ``(B, (taps - 1) x (mamba_d_ssm + 2
G N))`` (the conv's last inputs side by side, oldest first), both
float32, and *global* ``cached_key`` / ``cached_value``
``(B, Hkv, max_seq_len, D)`` (heads before positions, as
``models/afmoe.py`` holds them).

Call modes (``cfg.decode`` selects the cached ones), as
``models/olmo_hybrid.py`` has them:

- full forward (``decode=False``): every position's logits, no cache —
  what the CPU tests compare with the plain reference;
- **continue** (``decode=True``, ``kv_positions=None``): a ``(B, C)``
  piece at absolute ``offset`` (B,) with ``lengths`` (B,) valid tokens,
  from the cache it is given: the SSM state and the conv tail carried,
  the piece's K/V written at its positions and the row's keys read a
  block at a time; the ``(B, 1, V)`` logits of each row's last valid
  token come back. A row of length 0 is left as it was. Prefill is
  continue from zeros at ``offset`` 0 (:attr:`FalconH1LM.continues_prefill`),
  which is what lets ``ServeEngine(prefill_chunk=)`` stream a long prompt
  into a dense slot piece by piece;
- decode step (``kv_positions`` (B, 1)): one token a row at its own
  absolute position; the SSD step reads and writes the state once.

Matmul operands are ``cfg.dtype`` (bfloat16) with float32 accumulation;
the residual stream, norms, rotation, softmax, ``dt``, the decays, the
state, the conv tail and the scan are float32 (``cfg.state_dtype`` exists
so a test can show that a bfloat16 state fails the reference); K/V are
held in ``cfg.dtype``, weights in ``cfg.param_dtype``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.afmoe import (_put_positions, _read_blocks,
                                            _softmax_finish, _softmax_init,
                                            _softmax_update, rope)
from ray_lightning_tpu.models.generate import CacheLeaf
from ray_lightning_tpu.models.olmo_hybrid import _RMSNorm
from ray_lightning_tpu.models.sambay import (_dt_bias_init, _gather_rows,
                                             _Linear, _uniform)
from ray_lightning_tpu.ops.gated_delta import hold
from ray_lightning_tpu.ops.ssd import ssd_chunk, ssd_step


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    # the published keys (config.json of Falcon-H1-34B-Instruct)
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    #: z, x, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25,
                                          0.1767766952966369, 0.5,
                                          0.3535533905932738)
    #: gate, down
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    # how it is run
    max_seq_len: int = 3072          # positions one slot holds
    decode: bool = False
    dtype: Any = jnp.bfloat16        # matmul operands, K/V at rest
    param_dtype: Any = jnp.bfloat16  # weights at rest
    state_dtype: Any = jnp.float32   # SSM state, conv tail

    def __post_init__(self):
        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        # config.json gives 100000000000, past what an int32 holds
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers are five (z, x, B, C, dt), "
                             "mlp_multipliers two (gate, down)")
        if self.tie_word_embeddings:
            raise ValueError("Falcon-H1's head is not tied")
        if (self.attention_bias or self.mlp_bias or self.projectors_bias
                or self.mamba_proj_bias or not self.mamba_conv_bias):
            raise ValueError("written for no biases but the conv's")
        if not self.mamba_rms_norm or self.mamba_norm_before_gate:
            raise ValueError("written for the gated RMSNorm over groups, "
                             "gate before the norm")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_n_heads x mamba_d_head must be "
                             "mamba_d_ssm")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.head_dim % 2:
            raise ValueError("the rotation pairs dims: head_dim is even")
        if self.max_seq_len > self.max_position_embeddings:
            raise ValueError("max_seq_len exceeds the declared positions")

    @property
    def conv_width(self) -> int:
        """Channels of the conv: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.mamba_d_ssm + self.conv_width + self.mamba_n_heads

    def mup_vector(self):
        """``mu``: the five ``ssm_multipliers`` over the in-projection's
        columns (z, x, B, C, dt)."""
        gn = self.mamba_n_groups * self.mamba_d_state
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                  self.mamba_n_heads)
        return jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m
                                in zip(widths, self.ssm_multipliers)])


# ------------------------------------------------------------ pieces
def _linear(cfg: FalconH1Config, features: int, name: str) -> _Linear:
    return _Linear(features, False, cfg.dtype, cfg.param_dtype, name=name)


def _gated_norm(y, gate, weight, groups: int, eps: float):
    """``RMSNorm`` over each of ``groups`` equal parts of ``y * silu(gate)``
    (the gate before the norm), times ``weight``."""
    B, T, di = y.shape
    y = (y * jax.nn.silu(gate)).reshape(B, T, groups, di // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return y.reshape(B, T, di) * weight


def _a_log_init(key, shape, dtype):
    # Mamba-2: A uniform in [1, 16] a head
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Attention(nn.Module):
    """Grouped-query attention with rotary positions and this layer's
    full-length K/V."""
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, u, offset, kv_positions):
        cfg = self.cfg
        B, T, d = u.shape
        Hq, K, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        G, L, dtype = Hq // K, cfg.max_seq_len, cfg.dtype
        step = kv_positions is not None
        at = kv_positions.astype(jnp.int32) if step \
            else offset[:, None] + jnp.arange(T)[None, :]       # (B, T)
        with jax.named_scope("hybrid/attn"):
            qkv = _linear(cfg, (Hq + 2 * K) * D, "qkv")(
                u * cfg.attention_in_multiplier)
            q = qkv[..., :Hq * D].reshape(B, T, Hq, D)
            k = qkv[..., Hq * D:(Hq + K) * D].reshape(B, T, K, D) \
                * cfg.key_multiplier
            v = qkv[..., (Hq + K) * D:].reshape(B, T, K, D)
            q = rope(q, at, cfg.rope_theta)
            k = rope(k, at, cfg.rope_theta)
            # heads before positions, as the cache holds them: query
            # head k G + g reads key-value head k
            q = jnp.moveaxis((q * D ** -0.5).reshape(B, T, K, G, D), 1, 3)
            k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
            carry = _softmax_init(B, K, G, T, D)
            if not cfg.decode:
                causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
                out = _softmax_finish(_softmax_update(
                    carry, q, k, v, causal[None], dtype))
            else:
                is_init = not self.has_variable("cache", "cached_key")
                ck = self.variable("cache", "cached_key", jnp.zeros,
                                   (B, K, L, D), dtype)
                cv = self.variable("cache", "cached_value", jnp.zeros,
                                   (B, K, L, D), dtype)
                if is_init:
                    out = jnp.zeros((B, T, Hq * D), jnp.float32)
                elif step:
                    pos = at[:, 0]
                    # in place on the donated pool (models/afmoe.py)
                    ck.value = _put_positions(ck.value, k, pos)
                    cv.value = _put_positions(cv.value, v, pos)
                    # the whole row under a mask, at the memory's speed
                    live = jnp.arange(L)[None, :] <= pos[:, None]
                    out = _softmax_finish(_softmax_update(
                        carry, q, ck.value, cv.value, live[:, None, :],
                        dtype))
                else:                           # continue
                    ck.value = _put_positions(ck.value, k, offset)
                    cv.value = _put_positions(cv.value, v, offset)
                    out = _softmax_finish(_read_blocks(
                        carry, q, ck.value, cv.value, jnp.max(offset) + T,
                        lambda index: index[None, None, :]
                        <= at[:, :, None], dtype))
            return _linear(cfg, d, "out")(out)


class Mamba2(nn.Module):
    """The Mamba-2 mixer. State layout ``(B, H, P, N)``."""
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, u, lengths, kv_positions):
        cfg = self.cfg
        B, T, d = u.shape
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        G, K, di = cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_d_ssm
        cw = cfg.conv_width
        zxbcdt = _linear(cfg, cfg.in_proj_width, "in_proj")(
            u * cfg.ssm_in_multiplier) * cfg.mup_vector()
        conv_w = self.param("conv_kernel", _uniform(K ** -0.5), (K, cw),
                            jnp.float32)
        conv_b = self.param("conv_bias", _uniform(K ** -0.5), (cw,),
                            jnp.float32)
        a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        norm_g = self.param("norm", nn.initializers.ones, (di,),
                            jnp.float32)

        cached = step = False
        if cfg.decode:      # (not cached: the shape-building init pass)
            cached = self.has_variable("cache", "ssm_state")
            s_var = self.variable("cache", "ssm_state", jnp.zeros,
                                  (B, H, P, N), cfg.state_dtype)
            # the conv's inputs at the K - 1 positions before, oldest
            # first, side by side on one axis: a tap is a lane-aligned
            # slice, so the step reads and writes the leaf as it lies
            # ((B, K - 1, cw) is relaid out whole, every step)
            tail_var = self.variable("cache", "conv_state", jnp.zeros,
                                     (B, (K - 1) * cw), cfg.state_dtype)
            step = cached and kv_positions is not None

        with jax.named_scope("ssd/conv"):
            tail = tail_var.value.astype(jnp.float32) if cached \
                else jnp.zeros((B, (K - 1) * cw), jnp.float32)
            new = zxbcdt[..., di:di + cw]
            if step:
                taps = [tail[:, i * cw:(i + 1) * cw] for i in range(K - 1)]
                taps.append(new[:, 0])
                xbc = sum(w * tap for w, tap in zip(conv_w, taps))[:, None]
                tail = jnp.concatenate(taps[1:], axis=-1)
            else:
                xp = jnp.concatenate([tail.reshape(B, K - 1, cw), new],
                                     axis=1)
                xbc = sum(conv_w[i] * xp[:, i:i + T] for i in range(K))
                # the inputs at L - (K-1) .. L - 1 of what came before
                # and the piece (a row of length 0 keeps its tail)
                tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
                    row, n, K - 1, axis=0))(xp, lengths).reshape(B, -1)
            xbc = jax.nn.silu(xbc + conv_b)
            x = xbc[..., :di].reshape(B, T, H, P)
            b = xbc[..., di:di + G * N].reshape(B, T, G, N)
            c = xbc[..., di + G * N:].reshape(B, T, G, N)
            dt = jax.nn.softplus(zxbcdt[..., di + cw:] + dt_bias)
            a = -jnp.exp(a_log)
        state = s_var.value.astype(jnp.float32) if cached \
            else jnp.zeros((B, H, P, N), jnp.float32)
        if step:
            with jax.named_scope("ssd/step"):
                y, state = ssd_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                    state)
                y, state = y[:, None], hold(state, cfg.state_dtype)
        else:
            with jax.named_scope("ssd/chunk"):
                y, state = ssd_chunk(x, dt, a, b, c, state, lengths,
                                     cfg.mamba_chunk_size, cfg.state_dtype)
        if cached:
            s_var.value = state.astype(cfg.state_dtype)
            tail_var.value = tail.astype(cfg.state_dtype)
        with jax.named_scope("ssd/norm"):
            y = _gated_norm((y + skip[:, None] * x).reshape(B, T, di),
                            zxbcdt[..., :di], norm_g, G, cfg.rms_norm_eps)
        return _linear(cfg, d, "out_proj")(y)


class GatedMLP(nn.Module):
    cfg: FalconH1Config

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        f = cfg.intermediate_size
        gate, down = cfg.mlp_multipliers
        with jax.named_scope("hybrid/mlp"):
            gu = _linear(cfg, 2 * f, "gate_up")(h)
            return _linear(cfg, cfg.hidden_size, "down")(
                jax.nn.silu(gu[..., :f] * gate) * gu[..., f:]) * down


class FalconH1LM(nn.Module):
    """See the module docstring. ``positions`` is accepted and unused
    (a decode step's positions are ``kv_positions``, a piece's are
    ``offset + arange``); ``lengths`` (B,) is the valid tokens of a
    continue call's piece (``None`` = all of it), ``offset`` (B,) the
    absolute position of its first token (``None`` = 0)."""
    cfg: FalconH1Config

    #: a slot holds state that is no K/V row at absolute positions: the
    #: prefill hands this model its row lengths and takes back
    #: last-position logits only (``generate._prefill_impl``), and
    #: ``ServeEngine.__init__`` refuses, by name, what it cannot put
    #: around such a model yet
    recurrent_state = True
    #: the prefill is a *continue* from the cache it is given:
    #: ``ServeEngine(prefill_chunk=)`` may feed a prompt into a dense
    #: slot in pieces
    continues_prefill = True

    def cache_leaf(self, names: Tuple[str, ...]) -> CacheLeaf:
        """What one leaf of the ``cache`` collection is (by its path):
        every leaf belongs to a slot, on axis 0; each layer holds both
        kinds."""
        return {
            "ssm_state": CacheLeaf(0, "recurrent"),
            "conv_state": CacheLeaf(0, "recurrent"),
            "cached_key": CacheLeaf(0, "global", seq_axis=2),
            "cached_value": CacheLeaf(0, "global", seq_axis=2),
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
        x = jnp.take(embedding, tokens, axis=0).astype(jnp.float32) \
            * cfg.embedding_multiplier
        for layer in range(cfg.num_hidden_layers):
            scope = f"layer_{layer}"
            u = _RMSNorm(eps, name=scope + "_in_norm")(x)
            a = Attention(cfg, name=scope + "_attn")(u, offset, kv_positions)
            m = Mamba2(cfg, name=scope + "_ssm")(u, lengths, kv_positions)
            x = x + a * cfg.attention_out_multiplier \
                + m * cfg.ssm_out_multiplier
            x = x + GatedMLP(cfg, name=scope + "_mlp")(
                _RMSNorm(eps, name=scope + "_ff_norm")(x))
        if proceed:
            # the head over each row's last valid token only
            x = _gather_rows(x, jnp.maximum(lengths - 1, 0))
        x = _RMSNorm(eps, name="norm_f")(x)
        return _linear(cfg, cfg.vocab_size, "lm_head")(x) \
            * cfg.lm_head_multiplier
