"""SambaY decoder-hybrid-decoder LM (Phi-4-mini-flash-reasoning,
arXiv:2507.06607) on the serving protocol ``serve/engine.py`` drives.

Every layer ``l`` of ``0 .. n-1`` is ``x += mixer_l(LN(x)); x +=
W_down(silu(g) * u)`` with ``[g, u] = W_gate_up LN'(x)``; LayerNorm with
bias, a final LayerNorm, a tied head, **no positional encoding**. The
mixer by layer index (``n`` layers, ``half = n // 2``):

- ``l < half``, even — **Mamba-1** (selective scan, ``d_state`` 16,
  ``d_conv`` 4); ``l == half`` is Mamba too and keeps its scan output
  ``y`` (before the gate) as the position's **memory**;
- ``l < half``, odd — **differential attention over a causal window**
  of ``sliding_window`` keys (the query's own included);
- ``l == half + 1`` — the same attention with **no window**: its K/V is
  the model's only full-length cache;
- ``l > half + 1``, even — **gated memory unit**:
  ``W_out(memory * silu(W_in u))``;
- ``l > half + 1``, odd — **cross attention**: a query projection only,
  differential attention over layer ``half + 1``'s K and V.

A slot's cache is therefore three kinds of state, and every leaf
declares which (:meth:`SambaYLM.cache_leaf`): constant-size *recurrent*
state (``ssm_state`` float32, ``conv_state``), a *window* ring of
``sliding_window`` positions (``ring_key`` / ``ring_value``; position
``p`` lives at index ``p % window``), and one *global* K/V
(``cached_key`` / ``cached_value``) that the cross layers read.

Three call modes (``cfg.decode`` selects the cached ones):

- full forward (``decode=False``): every position's logits, no cache —
  what the CPU tests compare with the plain reference;
- prefill (``decode=True``, ``kv_positions=None``, ``lengths`` (B,)):
  rows are left-aligned and padded to a common P. A recurrence and a
  ring *would* eat the pad tail, so the row lengths reach the model:
  ``dt`` is zeroed past ``L - 1`` (``h`` freezes there), the conv tail
  is cut at ``L - 3 .. L - 1``, the ring holds positions
  ``max(0, L - window) .. L - 1``. Layers past ``half + 1`` run on each
  row's last valid position only — the architecture's own prefill
  saving — so the call returns ``(B, 1, V)`` logits;
- decode step (``kv_positions`` (B, 1)): one token a row at its own
  absolute position.

Matmul operands are ``cfg.dtype`` (bfloat16) with float32 accumulation;
the residual stream, norms, softmax, ``dt``, ``A`` and the recurrent
state are float32 (``cfg.state_dtype`` exists so a test can show that a
bfloat16 state fails the reference). Weights are held in
``cfg.param_dtype`` (bfloat16: float32 weights of the published size do
not fit one 16 GB chip).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.generate import CacheLeaf
from ray_lightning_tpu.ops.cache_write import write_rows

MAMBA, SWA, FULL, GMU, CROSS = "mamba", "swa", "full", "gmu", "cross"

#: positions of the prefill scan unrolled into one loop body
SCAN_UNROLL = 8


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    # the published keys (config.json of Phi-4-mini-flash-reasoning)
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 262144
    # sizes config.json does not carry: the released model's defaults
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None     # None = ceil(hidden / 16)
    # how it is run
    max_seq_len: int = 2048          # positions one slot holds
    decode: bool = False
    dtype: Any = jnp.bfloat16        # matmul operands
    param_dtype: Any = jnp.bfloat16  # weights at rest
    state_dtype: Any = jnp.float32   # recurrent state (h, conv tail)

    def __post_init__(self):
        if not self.tie_word_embeddings:
            raise ValueError("SambaY ties its head to the embedding")
        if self.mb_per_layer != 2:
            raise ValueError("the layer layout is written for "
                             "mb_per_layer == 2 (a Mamba every other layer)")
        if self.num_hidden_layers < 4 or self.num_hidden_layers % 4:
            raise ValueError("num_hidden_layers must be a multiple of 4 "
                             "(the memory layer n // 2 is a Mamba layer)")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs heads: "
                             "num_key_value_heads must be even and divide "
                             "num_attention_heads")
        if self.max_seq_len > self.max_position_embeddings:
            raise ValueError("max_seq_len exceeds the declared positions")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    def layer_kind(self, layer: int) -> str:
        half = self.memory_layer
        if layer <= half:
            return MAMBA if layer % 2 == 0 else SWA
        if layer == half + 1:
            return FULL
        return GMU if layer % 2 == 0 else CROSS

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ------------------------------------------------------------ pieces
class _Linear(nn.Module):
    """``x @ kernel (+ bias)``: operands in ``dtype``, float32 out."""
    features: int
    use_bias: bool
    dtype: Any
    param_dtype: Any
    kernel_init: Any = nn.initializers.normal(0.02)

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (x.shape[-1], self.features), self.param_dtype)
        y = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros,
                               (self.features,), jnp.float32)
        return y


def _linear(cfg: SambaYConfig, features: int, name: str, bias: bool = False,
            **kw) -> _Linear:
    return _Linear(features, bias, cfg.dtype, cfg.param_dtype, name=name,
                   **kw)


class _LayerNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias


def _gather_rows(x, index):
    """``x (B, T, ...)`` at one position a row, ``index (B,)`` ->
    ``(B, 1, ...)``."""
    return jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(
        row, i, 1, axis=0))(x, index)


def _mask(allowed):
    return jnp.where(allowed, 0.0, jnp.finfo(jnp.float32).min)


def diff_attend(q, k, v, mask, lam, lambda_init: float, subln, eps: float,
                dtype):
    """Differential attention. ``q (B, T, Hq, D)``, ``k`` / ``v``
    ``(B, S, Hkv, D)``, ``mask`` additive ``(B|1, T, S)``. Heads pair up
    (``2i``, ``2i + 1``): 2 query pairs share one key pair, the value
    heads of a pair lie side by side as one head of ``2 D``.
    ``out = RMSNorm_2D(a_1 - lam * a_2) * (1 - lambda_init)``."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    pairs, group = Hkv // 2, Hq // Hkv
    q = q.reshape(B, T, pairs, group, 2, D).astype(dtype)
    k = k.reshape(B, S, pairs, 2, D).astype(dtype)
    v = v.reshape(B, S, pairs, 2 * D).astype(dtype)
    scores = jnp.einsum("btkgcd,bskcd->bkgcts", q, k,
                        preferred_element_type=jnp.float32) * D ** -0.5
    probs = jax.nn.softmax(scores + mask[:, None, None, None], axis=-1)
    a = jnp.einsum("bkgcts,bske->btkgce", probs.astype(dtype), v,
                   preferred_element_type=jnp.float32)
    out = a[..., 0, :] - lam * a[..., 1, :]           # (B, T, pairs, g, 2D)
    out = out * jax.lax.rsqrt(
        jnp.mean(jnp.square(out), axis=-1, keepdims=True) + eps)
    out = out * subln * (1.0 - lambda_init)
    return out.reshape(B, T, Hq * D)


class _DiffLambda(nn.Module):
    """``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` and the
    sub-layer norm's gain."""
    head_dim: int
    lambda_init: float

    @nn.compact
    def __call__(self):
        init = nn.initializers.normal(0.1)
        lq1, lk1, lq2, lk2 = (
            self.param(n, init, (self.head_dim,), jnp.float32)
            for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        subln = self.param("subln", nn.initializers.ones,
                           (2 * self.head_dim,), jnp.float32)
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + self.lambda_init)
        return lam, subln


class SelfAttention(nn.Module):
    """Layers ``SWA`` and ``FULL``: fused QKV, differential attention,
    and this layer's cache — a ring of ``window`` positions or the
    full-length K/V. Returns ``(out, (k_all, v_all))``; the second is
    what a cross layer attends in the same call (the cache in the cached
    modes, the call's own K/V in a full forward)."""
    cfg: SambaYConfig
    layer: int

    @nn.compact
    def __call__(self, u, lengths, kv_positions):
        cfg = self.cfg
        B, T, _ = u.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        windowed = cfg.layer_kind(self.layer) == SWA
        W = cfg.sliding_window
        attend_scope = "swa/attend" if windowed else "yoco/attend"
        write_scope = "swa/ring_write" if windowed else "yoco/kv_write"
        qkv = _linear(cfg, (Hq + 2 * Hkv) * D, "qkv", bias=True)(u)
        q = qkv[..., :Hq * D].reshape(B, T, Hq, D)
        k = qkv[..., Hq * D:(Hq + Hkv) * D].reshape(B, T, Hkv, D)
        v = qkv[..., (Hq + Hkv) * D:].reshape(B, T, Hkv, D)
        lam, subln = _DiffLambda(D, cfg.lambda_init(self.layer),
                                 name="diff")()
        attend = lambda k_, v_, mask: diff_attend(          # noqa: E731
            q, k_, v_, mask, lam, cfg.lambda_init(self.layer), subln,
            cfg.layer_norm_eps, cfg.dtype)

        def block_mask():       # queries and keys of this call's block
            t = jnp.arange(T)[:, None]
            s = jnp.arange(T)[None, :]
            ok = s <= t
            if windowed:
                ok = ok & (t - s < W)
            return _mask(ok)[None]

        kv_all = (k, v)
        if not cfg.decode:
            with jax.named_scope(attend_scope):
                out = attend(k, v, block_mask())
        else:
            length = W if windowed else cfg.max_seq_len
            names = ("ring_key", "ring_value") if windowed \
                else ("cached_key", "cached_value")
            is_init = not self.has_variable("cache", names[0])
            ck = self.variable("cache", names[0], jnp.zeros,
                               (B, length, Hkv, D), cfg.dtype)
            cv = self.variable("cache", names[1], jnp.zeros,
                               (B, length, Hkv, D), cfg.dtype)
            if is_init:
                out = jnp.zeros((B, T, Hq * D), jnp.float32)
            elif kv_positions is None:          # prefill
                with jax.named_scope(attend_scope):
                    out = attend(k, v, block_mask())
                with jax.named_scope(write_scope):
                    if windowed:
                        # index j holds the newest position <= L - 1 that
                        # is congruent to j (older ones it overwrote)
                        last = (lengths - 1)[:, None]
                        j = jnp.arange(W)[None, :]
                        src = jnp.clip(last - (last - j) % W, 0, T - 1)
                        take = jax.vmap(lambda x, i: jnp.take(x, i, axis=0))
                        ck.value = take(k, src).astype(cfg.dtype)
                        cv.value = take(v, src).astype(cfg.dtype)
                    else:
                        ck.value = jax.lax.dynamic_update_slice_in_dim(
                            ck.value, k.astype(cfg.dtype), 0, axis=1)
                        cv.value = jax.lax.dynamic_update_slice_in_dim(
                            cv.value, v.astype(cfg.dtype), 0, axis=1)
                kv_all = (ck.value, cv.value)
            else:                               # decode step
                pos = kv_positions[:, 0].astype(jnp.int32)
                with jax.named_scope(write_scope):
                    ck.value, cv.value = write_rows(
                        (ck.value, cv.value), (k, v),
                        pos % W if windowed else pos)
                with jax.named_scope(attend_scope):
                    # a ring index j <= pos has been written by this
                    # request (all of them once pos >= W - 1)
                    live = jnp.arange(length)[None, None, :] \
                        <= pos[:, None, None]
                    out = attend(ck.value, cv.value, _mask(live))
                kv_all = (ck.value, cv.value)
        return _linear(cfg, cfg.hidden_size, "out", bias=True)(out), kv_all


class CrossAttention(nn.Module):
    """A query projection and differential attention over the ``FULL``
    layer's K/V; writes no cache. ``mask`` is additive ``(B|1, T, S)``."""
    cfg: SambaYConfig
    layer: int

    @nn.compact
    def __call__(self, u, kv_all, mask):
        cfg = self.cfg
        B, T, _ = u.shape
        Hq, D = cfg.num_attention_heads, cfg.head_dim
        q = _linear(cfg, Hq * D, "q", bias=True)(u).reshape(B, T, Hq, D)
        lam, subln = _DiffLambda(D, cfg.lambda_init(self.layer),
                                 name="diff")()
        with jax.named_scope("yoco/cross_attend"):
            out = diff_attend(q, kv_all[0], kv_all[1], mask, lam,
                              cfg.lambda_init(self.layer), subln,
                              cfg.layer_norm_eps, cfg.dtype)
        return _linear(cfg, cfg.hidden_size, "out", bias=True)(out)


def _a_log_init(key, shape, dtype):
    # mamba_ssm's S4D-real: A = -(1 .. d_state) on every channel
    n = shape[0]
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
        shape).astype(dtype)


def _dt_bias_init(key, shape, dtype, dt_min=1e-3, dt_max=1e-1):
    # softplus(bias) is log-uniform in [dt_min, dt_max]
    dt = jnp.exp(jax.random.uniform(key, shape) * (
        math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    dt = jnp.maximum(dt, 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _uniform(bound: float):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class Mamba(nn.Module):
    """Mamba-1. State layout ``(B, d_state, d_inner)`` (the wide axis
    last). Returns ``(out, memory)``: ``memory`` is the scan output ``y``
    before the gate."""
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u, lengths, kv_positions):
        cfg = self.cfg
        B, T, _ = u.shape
        di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                       cfg.dt_rank)
        xz = _linear(cfg, 2 * di, "in_proj")(u)
        x, z = xz[..., :di], xz[..., di:]
        conv_w = self.param("conv_kernel", _uniform(K ** -0.5), (K, di),
                            jnp.float32)
        conv_b = self.param("conv_bias", _uniform(K ** -0.5), (di,),
                            jnp.float32)
        a_log = self.param("A_log", _a_log_init, (N, di), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (di,), jnp.float32)

        cached = step = False
        if cfg.decode:      # (not cached: the shape-building init pass)
            cached = self.has_variable("cache", "ssm_state")
            h_var = self.variable("cache", "ssm_state", jnp.zeros,
                                  (B, N, di), cfg.state_dtype)
            tail_var = self.variable("cache", "conv_state", jnp.zeros,
                                     (B, K - 1, di), cfg.state_dtype)
            step = cached and kv_positions is not None

        with jax.named_scope("ssm/conv"):
            before = tail_var.value.astype(jnp.float32) if step \
                else jnp.zeros((B, K - 1, di), jnp.float32)
            xp = jnp.concatenate([before, x], axis=1)     # (B, T+K-1, di)
            xc = sum(conv_w[i] * xp[:, i:i + T] for i in range(K)) + conv_b
            xc = jax.nn.silu(xc)
        dbc = _linear(cfg, R + 2 * N, "x_proj")(xc)
        dt = jax.nn.softplus(
            _linear(cfg, di, "dt_proj", bias=True,
                    kernel_init=_uniform(R ** -0.5))(dbc[..., :R]))
        Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
        A = -jnp.exp(a_log)                               # (N, di)

        def advance(h, dt_t, x_t, b_t, c_t):
            # h (B, N, di) in float32; one position of every row
            h = (jnp.exp(dt_t[:, None, :] * A) * h
                 + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
            # the state is *held* in state_dtype between positions
            # (reduce_precision: a cast there and back may be elided)
            info = jnp.finfo(cfg.state_dtype)
            if info.bits < 32:
                h = jax.lax.reduce_precision(h, info.nexp, info.nmant)
            return h, jnp.sum(h * c_t[:, :, None], axis=1)

        if step:
            with jax.named_scope("ssm/step"):
                h, y = advance(h_var.value.astype(jnp.float32), dt[:, 0],
                               xc[:, 0], Bm[:, 0], Cm[:, 0])
                y = y[:, None]
                h_var.value = h.astype(cfg.state_dtype)
                tail_var.value = xp[:, T:].astype(cfg.state_dtype)
        else:
            with jax.named_scope("ssm/scan"):
                # a row's state freezes at its last valid position:
                # dt = 0 gives exp(0) * h + 0
                valid = jnp.arange(T)[None, :] < lengths[:, None]
                dt = jnp.where(valid[..., None], dt, 0.0)

                def body(h, xs):
                    return advance(h, *xs)

                h, y = jax.lax.scan(
                    body, jnp.zeros((B, N, di), jnp.float32),
                    tuple(jnp.swapaxes(a, 0, 1)
                          for a in (dt, xc, Bm, Cm)),
                    unroll=min(SCAN_UNROLL, T))
                y = jnp.swapaxes(y, 0, 1)
            if cached:
                h_var.value = h.astype(cfg.state_dtype)
                # the inputs at L - (K-1) .. L - 1 (zeros before 0)
                tail_var.value = jax.vmap(
                    lambda row, i: jax.lax.dynamic_slice_in_dim(
                        row, i, K - 1, axis=0))(xp, lengths).astype(
                            cfg.state_dtype)
        y = y + d_skip * xc
        out = _linear(cfg, cfg.hidden_size, "out_proj")(y * jax.nn.silu(z))
        return out, y


class GatedMemoryUnit(nn.Module):
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u, memory):
        cfg = self.cfg
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(_linear(cfg, cfg.d_inner, "in_proj")(u))
            return _linear(cfg, cfg.hidden_size, "out_proj")(memory * gate)


class GatedMLP(nn.Module):
    cfg: SambaYConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        gu = _linear(cfg, 2 * cfg.intermediate_size, "gate_up")(u)
        g, up = jnp.split(gu, 2, axis=-1)
        return _linear(cfg, cfg.hidden_size, "down")(jax.nn.silu(g) * up)


class _Embed(nn.Module):
    cfg: SambaYConfig

    def setup(self):
        cfg = self.cfg
        self.embedding = self.param(
            "embedding", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)

    def __call__(self, tokens):
        return jnp.take(self.embedding, tokens, axis=0).astype(jnp.float32)

    def attend(self, x):
        dt = self.cfg.dtype
        return jax.lax.dot_general(
            x.astype(dt), self.embedding.astype(dt),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


class SambaYLM(nn.Module):
    """See the module docstring. ``positions`` is accepted and unused
    (the architecture has no positional encoding); ``lengths`` (B,) is
    the prefill's row lengths (``None`` = every row is whole)."""
    cfg: SambaYConfig

    #: a slot holds state that is no K/V row at absolute positions: the
    #: prefill hands this model its row lengths and takes back
    #: last-position logits only (``generate._prefill_impl``), and
    #: ``ServeEngine.__init__`` refuses, by name, what it cannot put
    #: around such a model yet
    recurrent_state = True

    def cache_leaf(self, names: Tuple[str, ...]) -> CacheLeaf:
        """What one leaf of the ``cache`` collection is (by its path):
        every leaf here belongs to a slot, on axis 0."""
        return {
            "ssm_state": CacheLeaf(0, "recurrent"),
            "conv_state": CacheLeaf(0, "recurrent"),
            "ring_key": CacheLeaf(0, "window", seq_axis=1),
            "ring_value": CacheLeaf(0, "window", seq_axis=1),
            "cached_key": CacheLeaf(0, "global", seq_axis=1),
            "cached_value": CacheLeaf(0, "global", seq_axis=1),
        }[names[-1]]

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, positions=None,
                 kv_positions=None, lengths=None):
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.layer_norm_eps
        half = cfg.memory_layer
        step = cfg.decode and kv_positions is not None
        prefill = cfg.decode and kv_positions is None
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        embed = _Embed(cfg, name="embed")
        x = embed(tokens)
        memory = kv_all = cross_mask = None
        for layer in range(cfg.num_hidden_layers):
            kind = cfg.layer_kind(layer)
            scope = f"layer_{layer}"
            if layer == half + 2:
                # the cross-decoder: at prefill, each row's last valid
                # position is all it has to compute
                if prefill:
                    last = lengths - 1
                    x = _gather_rows(x, last)
                    memory = _gather_rows(memory, last)
                    S = kv_all[0].shape[1]
                    cross_mask = _mask(jnp.arange(S)[None, None, :]
                                       <= last[:, None, None])
                elif step:
                    pos = kv_positions[:, 0].astype(jnp.int32)
                    S = kv_all[0].shape[1]
                    cross_mask = _mask(jnp.arange(S)[None, None, :]
                                       <= pos[:, None, None])
                else:
                    cross_mask = _mask(jnp.arange(T)[None, :]
                                       <= jnp.arange(T)[:, None])[None]
            u = _LayerNorm(eps, name=scope + "_ln1")(x)
            if kind == MAMBA:
                out, y = Mamba(cfg, name=scope + "_mamba")(
                    u, lengths, kv_positions)
                if layer == half:
                    memory = y
            elif kind in (SWA, FULL):
                out, kv = SelfAttention(cfg, layer, name=scope + "_attn")(
                    u, lengths, kv_positions)
                if kind == FULL:
                    kv_all = kv
            elif kind == GMU:
                out = GatedMemoryUnit(cfg, name=scope + "_gmu")(u, memory)
            else:
                out = CrossAttention(cfg, layer, name=scope + "_cross")(
                    u, kv_all, cross_mask)
            x = x + out
            x = x + GatedMLP(cfg, name=scope + "_mlp")(
                _LayerNorm(eps, name=scope + "_ln2")(x))
        x = _LayerNorm(eps, name="ln_f")(x)
        return embed.attend(x)
