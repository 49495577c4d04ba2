"""Trinity / AFMoE LM (``model_type: afmoe``): gated grouped-query
attention — sliding-window layers with rotary positions, one
full-attention layer in four with **no** positions — over a sigmoid-routed
expert layer with a shared expert, on the serving protocol
``serve/engine.py`` drives (the one ``models/sambay.py`` and
``models/olmo_hybrid.py`` are on).

``h = E[token] * sqrt(hidden_size)`` (``mup_enabled``). Every layer is a
*sandwich*: ``h += RMSNorm(Attn(RMSNorm(h))); h += RMSNorm(FFN(
RMSNorm(h)))`` — four gains a layer —, then a final RMSNorm and an
**untied** head. No bias anywhere. ``FFN`` is a dense SwiGLU in the first
``num_dense_layers`` layers and the expert layer after them.

**Attention.** ``q`` (``Hq`` heads of ``D``), ``k``, ``v`` (``Hkv``
heads; ``Hq / Hkv`` query heads share a key-value head) and the gate
``g`` (``Hq D``) come from one projection. ``q`` and ``k`` are RMS-normed
per head over ``D`` (one gain each a layer). On a ``sliding_attention``
layer both are rotated (:func:`rope`: ``rope_theta``, all ``D`` dims, the
half-split pairing ``(i, i + D / 2)``) and a query sees the last
``sliding_window`` positions, its own included; on a ``full_attention``
layer nothing is rotated and a query sees every earlier position.
``s = q . k / sqrt(D)``, causal softmax in float32, ``o = W_o(sigmoid(g)
* concat_h(sum_j p_h v_j))``.

**The cache**, each leaf declared (:meth:`AfmoeLM.cache_leaf`): a window
layer holds K and V as *rings* — ``ring_key`` / ``ring_value`` ``(B,
Hkv, window, D)``; position ``p`` lives at index ``p % window``, keys
are stored rotated, so an entry needs no position of its own —, a full
layer ``cached_key`` / ``cached_value`` ``(B, Hkv, max_seq_len, D)``.
Heads come **before** positions: ``Hkv`` = 8 beside ``D`` would be padded
to a bfloat16 tile's 16 sublanes and the cache doubled, and ``(B, L,
Hkv D)`` is transposed whole, every step, for the scores' product (the
compiler's verdict for a v5e: ``tests/test_chip_compile.py``).

**Expert layer.** Router logits ``W_r x`` in float32 over **all**
``num_experts``; ``ops/grouped_experts.py`` takes it from there: sigmoid
scores, the top ``num_experts_per_tok`` of ``s + b``, weights without
``b``, ``route_norm``, ``route_scale``; ``y = E_shared(x) + sum_k w_k
E_{e_k}(x)``. The layer is **told which experts it holds**
(``cfg.experts_held`` from ``cfg.expert_offset``, the share of an
expert-parallel group): it adds only its own experts' terms and the
shared expert, dropless, sorted and grouped. Each expert layer leaves the
rows each held expert took in its last call in the cache collection
(``expert_load (experts_held,)``, a ``"counter"`` leaf no slot owns),
which ``ServeEngine`` reads when telemetry is armed.

Call modes (``cfg.decode`` selects the cached ones):

- full forward (``decode=False``): every position's logits, no cache —
  what the CPU tests compare with the plain reference;
- **continue** (``decode=True``, ``kv_positions=None``): a ``(B, C)``
  piece at absolute ``offset`` (B,) with ``lengths`` (B,) valid tokens,
  *reading the cache it is given* below ``offset``. A window layer reads
  the ring **before** the piece's own keys overwrite the oldest entries
  its first queries still see, then writes the piece's *valid* positions
  only (a pad tail would overwrite live ones); a full layer writes the
  piece and reads the row up to each query. It returns the ``(B, 1, V)``
  logits of each row's last valid token. Prefill is continue from
  ``offset`` 0 — one implementation (:attr:`AfmoeLM.continues_prefill`),
  which is what lets ``ServeEngine(prefill_chunk=)`` stream a long prompt
  into a dense slot piece by piece;
- decode step (``kv_positions`` (B, 1)): one token a row at its own
  absolute position.

Matmul operands are ``cfg.dtype`` (bfloat16) with float32 accumulation;
the residual stream, norms, rotation, softmax, router logits, sigmoid and
top-k are float32 (the router's product at ``Precision.HIGHEST``: the TPU
would otherwise round its operands); K/V are held in ``cfg.dtype``;
logits float32. Weights are held in ``cfg.param_dtype``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.generate import CacheLeaf
from ray_lightning_tpu.models.olmo_hybrid import _RMSNorm
from ray_lightning_tpu.models.sambay import _gather_rows, _Linear
from ray_lightning_tpu.ops.grouped_experts import held_experts

SLIDING, FULL = "sliding_attention", "full_attention"

#: keys a continue call's attention reads at a time (an online softmax
#: over blocks: a piece of 512 queries against a slot of 8192 positions
#: never holds more than one block of scores)
KEY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    # the published keys (config.json of Trinity-Large-Preview)
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288       # the dense layers' width
    moe_intermediate_size: int = 3072    # an expert's width
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 15
    sliding_window: int = 4096
    num_experts: int = 256               # the router's outputs
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # the share of an expert-parallel group this chip holds
    experts_held: Optional[int] = None   # None: every expert
    expert_offset: int = 0
    # how it is run
    max_seq_len: int = 8192          # positions one slot holds
    decode: bool = False
    dtype: Any = jnp.bfloat16        # matmul operands, K/V at rest
    param_dtype: Any = jnp.bfloat16  # weights at rest

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every layer: "
                             f"{len(self.layer_types)} entries for "
                             f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.tie_word_embeddings:
            raise ValueError("the head is not tied")
        if self.score_func != "sigmoid":
            raise ValueError("written for the sigmoid router")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("written for no group limit (n_group and "
                             "topk_group 1)")
        if self.num_shared_experts != 1:
            raise ValueError("written for one shared expert")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.head_dim % 2:
            raise ValueError("the rotation pairs dims: head_dim is even")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers is a count of the layers")
        held = self.held
        if held < 1 or self.expert_offset < 0 \
                or self.expert_offset + held > self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + held}"
                f" are not among the router's {self.num_experts}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than the router has")
        if self.max_seq_len > self.max_position_embeddings:
            raise ValueError("max_seq_len exceeds the declared positions")

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def ring_len(self) -> int:
        """Positions a window layer's ring holds (a slot shorter than the
        window holds every position it has)."""
        return min(self.sliding_window, self.max_seq_len)


# ------------------------------------------------------------ pieces
def _linear(cfg: AfmoeConfig, features: int, name: str) -> _Linear:
    return _Linear(features, False, cfg.dtype, cfg.param_dtype, name=name)


def rope(x, positions, theta: float):
    """Rotate ``x (B, T, H, D)`` by ``positions (B, T)``: the pair ``(i,
    i + D / 2)`` turns by ``pos * theta^(-2 i / D)`` (the half-split
    pairing; float32 in, float32 out)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(jnp.float32)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def _softmax_init(B, K, G, T, D):
    """An online softmax that has read no key: running maximum, sum and
    weighted values of ``K x G`` query heads of ``T`` queries a row."""
    return (jnp.full((B, K, G, T), jnp.finfo(jnp.float32).min, jnp.float32),
            jnp.zeros((B, K, G, T), jnp.float32),
            jnp.zeros((B, K, G, T, D), jnp.float32))


def _softmax_update(carry, q, k, v, ok, dtype):
    """One block of keys into a running softmax. ``q (B, K, G, T, D)``
    already scaled, ``k`` / ``v (B, K, S, D)``, ``ok (B|1, T, S)`` bool:
    which keys each query reads."""
    m, l, acc = carry
    s = jnp.einsum("bkgtd,bksd->bkgts", q.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32)
    ok = ok[:, None, None]
    s = jnp.where(ok, s, jnp.finfo(jnp.float32).min)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None]) * ok
    scale = jnp.exp(m - m_new)
    l = l * scale + jnp.sum(p, axis=-1)
    acc = acc * scale[..., None] + jnp.einsum(
        "bkgts,bksd->bkgtd", p.astype(dtype), v.astype(dtype),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def _softmax_finish(carry):
    """-> ``(B, T, K G D)`` float32; a query that read no key (a pad
    position) gives zeros."""
    _, l, acc = carry
    B, K, G, T, D = acc.shape
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(B, T, K * G * D)


def _read_blocks(carry, q, ck, cv, live, key_ok, dtype):
    """The cache's keys into the running softmax, a block of
    :data:`KEY_BLOCK` at a time: the blocks that hold the first ``live``
    entries (a traced count: only what some row still reads is visited).
    ``ck`` / ``cv (B, K, S, D)``; ``key_ok(index (blk,)) -> (B, T,
    blk)`` says which of the entries at those indexes each query
    reads."""
    S = ck.shape[2]
    blk = min(KEY_BLOCK, S)
    n_blocks = (jnp.minimum(live, S) + blk - 1) // blk

    def body(i, carry):
        # the last block of a length that is no multiple of the block
        # starts early; the entries it repeats are masked out
        start = jnp.minimum(i * blk, S - blk)
        index = start + jnp.arange(blk)
        k = jax.lax.dynamic_slice_in_dim(ck, start, blk, axis=2)
        v = jax.lax.dynamic_slice_in_dim(cv, start, blk, axis=2)
        ok = key_ok(index) & (index >= i * blk)[None, None, :]
        return _softmax_update(carry, q, k, v, ok, dtype)

    return jax.lax.fori_loop(0, n_blocks, body, carry)


def _ring_positions(index, newest, ring: int):
    """The position a ring entry holds once every position up to
    ``newest (B,)`` has been written: the newest one congruent to its
    index — negative where none has been. ``index (n,)`` -> ``(B, n)``."""
    newest = newest[:, None]
    return newest - (newest - index[None, :]) % ring


def _put_positions(cache, block, start):
    """Row ``b``'s ``block (K, T, D)`` into ``cache[b, :, start[b]:
    start[b] + T]``, in place on a donated buffer (a start past ``L - T``
    clamps to it, as ``dynamic_update_slice`` does)."""
    return jax.vmap(lambda row, new, at: jax.lax.dynamic_update_slice_in_dim(
        row, new, at, axis=1))(cache, block.astype(cache.dtype), start)


class GatedAttention(nn.Module):
    """One attention layer and its cache: a ring of ``window`` positions
    (``windowed``, rotated) or the slot's full length (no positions)."""
    cfg: AfmoeConfig
    windowed: bool

    @nn.compact
    def __call__(self, x, offset, lengths, kv_positions):
        cfg = self.cfg
        B, T, d = x.shape
        Hq, K, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        G, W, dtype = Hq // K, cfg.sliding_window, cfg.dtype
        step = kv_positions is not None
        at = kv_positions.astype(jnp.int32) if step \
            else offset[:, None] + jnp.arange(T)[None, :]       # (B, T)
        with jax.named_scope("attn/qkv"):
            qkvg = _linear(cfg, 2 * (Hq + K) * D, "qkvg")(x)
            q = _RMSNorm(cfg.rms_norm_eps, name="q_norm")(
                qkvg[..., :Hq * D].reshape(B, T, Hq, D))
            k = _RMSNorm(cfg.rms_norm_eps, name="k_norm")(
                qkvg[..., Hq * D:(Hq + K) * D].reshape(B, T, K, D))
            v = qkvg[..., (Hq + K) * D:(Hq + 2 * K) * D].reshape(B, T, K, D)
            gate = qkvg[..., (Hq + 2 * K) * D:]
        if self.windowed:
            with jax.named_scope("attn/rope"):
                q = rope(q, at, cfg.rope_theta)
                k = rope(k, at, cfg.rope_theta)
        # heads before positions, as the cache holds them: query head
        # k G + g reads key-value head k
        q = jnp.moveaxis((q * D ** -0.5).reshape(B, T, K, G, D), 1, 3)
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # (B, K, T, D)
        attend_scope = "attn/attend_window" if self.windowed \
            else "attn/attend_full"

        def own_keys(carry):
            """The call's own keys: causal, and inside the window."""
            t = jnp.arange(T)[:, None]
            s = jnp.arange(T)[None, :]
            ok = s <= t
            if self.windowed:
                ok = ok & (t - s < W)
            return _softmax_update(carry, q, k, v, ok[None], dtype)

        carry = _softmax_init(B, K, G, T, D)
        if not cfg.decode:
            with jax.named_scope(attend_scope):
                out = _softmax_finish(own_keys(carry))
        else:
            length = cfg.ring_len if self.windowed else cfg.max_seq_len
            names = ("ring_key", "ring_value") if self.windowed \
                else ("cached_key", "cached_value")
            is_init = not self.has_variable("cache", names[0])
            ck = self.variable("cache", names[0], jnp.zeros,
                               (B, K, length, D), dtype)
            cv = self.variable("cache", names[1], jnp.zeros,
                               (B, K, length, D), dtype)
            if is_init:
                out = jnp.zeros((B, T, Hq * D), jnp.float32)
            elif step:
                pos = at[:, 0]
                with jax.named_scope("attn/ring_write" if self.windowed
                                     else "attn/kv_write"):
                    # a vmapped dynamic_update_slice, in place on the
                    # donated pool (ops.cache_write.write_rows' (B, H, D,
                    # L) view is a real transpose where a head is a lane
                    # row of 128: models/olmo_hybrid.py)
                    index = pos % length if self.windowed else pos
                    ck.value = _put_positions(ck.value, k, index)
                    cv.value = _put_positions(cv.value, v, index)
                with jax.named_scope(attend_scope):
                    # the whole row under a mask, at the memory's speed:
                    # what this request has written (an index up to pos;
                    # all of a ring once pos has passed its length) and,
                    # of a ring, what is inside the window
                    index = jnp.arange(length)
                    if self.windowed:
                        held = _ring_positions(index, pos, length)
                        live = (held >= 0) & (held > (pos - W)[:, None])
                    else:
                        live = index[None, :] <= pos[:, None]
                    out = _softmax_finish(_softmax_update(
                        carry, q, ck.value, cv.value, live[:, None, :],
                        dtype))
            elif self.windowed:                 # continue, a ring
                with jax.named_scope(attend_scope):
                    # FIRST the ring as the pieces before left it: entry
                    # j holds the newest position below the offset that
                    # is congruent to j; a query reads it while it is
                    # inside its window
                    def ring_ok(index):
                        held = _ring_positions(index, offset - 1, length)
                        return (held >= 0)[:, None, :] \
                            & (held[:, None, :] > at[:, :, None] - W)

                    carry = _read_blocks(
                        carry, q, ck.value, cv.value,
                        jnp.where(jnp.max(offset) > 0, length, 0), ring_ok,
                        dtype)
                    out = _softmax_finish(own_keys(carry))
                with jax.named_scope("attn/ring_write"):
                    # THEN the piece's valid positions: entry j takes
                    # the newest of them congruent to j, or stays
                    held = _ring_positions(jnp.arange(length),
                                           offset + lengths - 1, length)
                    src = held - offset[:, None]                # (B, ring)
                    new = (src >= 0)[:, None, :, None]
                    src = jnp.clip(src, 0, T - 1)[:, None, :, None]
                    ck.value = jnp.where(new, jnp.take_along_axis(
                        k.astype(dtype), src, axis=2), ck.value)
                    cv.value = jnp.where(new, jnp.take_along_axis(
                        v.astype(dtype), src, axis=2), cv.value)
            else:                               # continue, the full row
                with jax.named_scope("attn/kv_write"):
                    ck.value = _put_positions(ck.value, k, offset)
                    cv.value = _put_positions(cv.value, v, offset)
                with jax.named_scope(attend_scope):
                    out = _softmax_finish(_read_blocks(
                        carry, q, ck.value, cv.value, jnp.max(offset) + T,
                        lambda index: index[None, None, :]
                        <= at[:, :, None], dtype))
        with jax.named_scope("attn/gate"):
            out = jax.nn.sigmoid(gate) * out
        return _linear(cfg, d, "out")(out)


class GatedMLP(nn.Module):
    """The dense SwiGLU of the leading layers."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        f = cfg.intermediate_size
        with jax.named_scope("mlp/dense"):
            gu = _linear(cfg, 2 * f, "gate_up")(x)
            return _linear(cfg, cfg.hidden_size, "down")(
                jax.nn.silu(gu[..., :f]) * gu[..., f:])


class ExpertLayer(nn.Module):
    """The routed experts held here and the shared expert."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x, valid):
        cfg = self.cfg
        B, T, d = x.shape
        f, held = cfg.moe_intermediate_size, cfg.held
        init = nn.initializers.normal(0.02)
        w_router = self.param("router", init, (d, cfg.num_experts),
                              cfg.param_dtype)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        w_gate_up = self.param("experts_gate_up", init, (held, d, 2 * f),
                               cfg.param_dtype)
        w_down = self.param("experts_down", init, (held, f, d),
                            cfg.param_dtype)
        flat = x.reshape(B * T, d)
        # the shape-building init pass of a cache computes nothing of the
        # experts: its zero cache must not wait for weights to be drawn
        is_init = cfg.decode and not self.has_variable("cache",
                                                       "expert_load")
        if is_init:
            routed = jnp.zeros((B * T, d), jnp.float32)
            load = jnp.zeros((held,), jnp.int32)
        else:
            with jax.named_scope("moe/router"):
                logits = jnp.dot(flat.astype(jnp.float32),
                                 w_router.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
            routed, load = held_experts(
                flat, logits, bias, w_gate_up, w_down,
                top_k=cfg.num_experts_per_tok, offset=cfg.expert_offset,
                normalise=cfg.route_norm, scale=cfg.route_scale,
                valid=None if valid is None else valid.reshape(B * T),
                dtype=cfg.dtype)
        if cfg.decode:
            # the rows each held expert took in this call (the leaf of a
            # "counter": no slot owns it, the engine hands it on)
            count = self.variable("cache", "expert_load", jnp.zeros,
                                  (held,), jnp.int32)
            count.value = load
        with jax.named_scope("moe/shared"):
            gu = _linear(cfg, 2 * f, "shared_gate_up")(x)
            shared = _linear(cfg, d, "shared_down")(
                jax.nn.silu(gu[..., :f]) * gu[..., f:])
        return routed.reshape(B, T, d) + shared


class AfmoeLM(nn.Module):
    """See the module docstring. ``positions`` is accepted and unused
    (a decode step's positions are ``kv_positions``, a piece's are
    ``offset + arange``); ``lengths`` (B,) is the valid tokens of a
    continue call's piece (``None`` = all of it), ``offset`` (B,) the
    absolute position of its first token (``None`` = 0)."""
    cfg: AfmoeConfig

    #: the prefill is a *continue* from the cache it is given (``offset``
    #: and ``lengths`` a row, last-position logits back):
    #: ``ServeEngine(prefill_chunk=)`` may feed a prompt into a dense
    #: slot in pieces. A ring is no K/V row at absolute positions, which
    #: pages, int8 storage, the prefix cache, draft verification and the
    #: LoRA bank do not know: ``ServeEngine.__init__`` refuses them by
    #: name
    continues_prefill = True

    def cache_leaf(self, names: Tuple[str, ...]) -> CacheLeaf:
        """What one leaf of the ``cache`` collection is (by its path)."""
        return {
            "ring_key": CacheLeaf(0, "window", seq_axis=2),
            "ring_value": CacheLeaf(0, "window", seq_axis=2),
            "cached_key": CacheLeaf(0, "global", seq_axis=2),
            "cached_value": CacheLeaf(0, "global", seq_axis=2),
            "expert_load": CacheLeaf(None, "counter"),
        }[names[-1]]

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, positions=None,
                 kv_positions=None, lengths=None, offset=None):
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.rms_norm_eps
        proceed = cfg.decode and kv_positions is None
        valid = None
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        else:
            lengths = jnp.asarray(lengths, jnp.int32)
            valid = jnp.arange(T)[None, :] < lengths[:, None]
        offset = jnp.zeros((B,), jnp.int32) if offset is None \
            else jnp.asarray(offset, jnp.int32)
        embedding = self.param("embedding", nn.initializers.normal(0.02),
                               (cfg.vocab_size, cfg.hidden_size),
                               cfg.param_dtype)
        x = jnp.take(embedding, tokens, axis=0).astype(jnp.float32)
        if cfg.mup_enabled:
            x = x * cfg.hidden_size ** 0.5
        for layer, kind in enumerate(cfg.layer_types):
            scope = f"layer_{layer}"
            out = GatedAttention(cfg, kind == SLIDING, name=scope + "_attn")(
                _RMSNorm(eps, name=scope + "_attn_norm_in")(x), offset,
                lengths, kv_positions)
            x = x + _RMSNorm(eps, name=scope + "_attn_norm_out")(out)
            h = _RMSNorm(eps, name=scope + "_mlp_norm_in")(x)
            if layer < cfg.num_dense_layers:
                out = GatedMLP(cfg, name=scope + "_mlp")(h)
            else:
                out = ExpertLayer(cfg, name=scope + "_moe")(h, valid)
            x = x + _RMSNorm(eps, name=scope + "_mlp_norm_out")(out)
        if proceed:
            # the head over each row's last valid token only
            x = _gather_rows(x, jnp.maximum(lengths - 1, 0))
        x = _RMSNorm(eps, name="norm_f")(x)
        return _linear(cfg, cfg.vocab_size, "lm_head")(x)
