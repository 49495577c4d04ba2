"""Shared transformer core for the GPT/BERT model families.

TPU-first choices baked in:

- **bf16 compute, f32 params** (`TransformerConfig.dtype`): matmuls hit the
  MXU at bf16 throughput; master weights and softmax stay f32.
- **`nn.scan` over layers** (`scan_layers=True`): one compiled block program
  reused L times — compile time stays flat as depth grows, and XLA pipelines
  the layer loop.
- **`nn.remat`** (`remat=True`): rematerialize block activations in backward,
  trading MXU FLOPs for HBM — the standard memory lever for long sequences.
- **Pluggable attention impl** (``attention_impl``): 'dot' (the default:
  the seat reads its own call — :func:`attention_seat` — and runs the
  pallas blockwise kernel for causal self-attention with no mask and no
  dropout from ``FLASH_MIN_LEN`` positions up on a TPU, per device under a
  mesh, and the dense XLA path for everything else), 'flash' (the
  blockwise kernel asked for by name), 'ring' (sequence-parallel ring
  attention over the ``sp`` mesh axis), 'ulysses' (all-to-all head-sharded
  sequence parallelism over the same axis).

Parameter-path naming is stable and load-bearing: tensor-parallel sharding
rules (``MeshStrategy(param_rule=...)``) match on these names.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.models.quant import (kv_dequantize, kv_quantize,
                                            kv_scales)
from ray_lightning_tpu.obs import seats
from ray_lightning_tpu.ops.attention import dot_product_attention
from ray_lightning_tpu.ops.cache_write import write_rows
from ray_lightning_tpu.ops.pallas_flash import (head_lanes,
                                                pallas_flash_attention)
from ray_lightning_tpu.parallel import sharding as shardlib
from ray_lightning_tpu.parallel.sharding import constrain_batch


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    max_seq_len: int = 1024
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.float32   # master weights
    causal: bool = True
    scan_layers: bool = True
    # unroll factor for the layer scan: XLA optimizes across unrolled
    # block boundaries (better fusion/overlap) while the scan keeps
    # compile time and HLO size bounded — the middle ground between
    # scan_layers=True (1) and False (n_layers). Caveat, measured on a
    # 16 GB v5e: unrolling raises peak memory sharply (longer live
    # ranges) — GPT-2-medium fits at unroll=1 (8.3 GB) and OOMs at 2+;
    # use it only with memory headroom.
    scan_unroll: int = 1
    remat: bool = False
    # None = rematerialize everything; "dots" saves matmul outputs and
    # recomputes only elementwise ops (less recompute, more memory);
    # "dots_with_no_batch_dims" saves weight-only matmuls
    remat_policy: Optional[str] = None
    # autoregressive decode mode: attention keeps a KV cache sized
    # max_seq_len in the "cache" variable collection and consumes ONE
    # token per call (see models/generate.py)
    decode: bool = False
    # "dot" adapts: attention_seat (below) picks the blockwise pallas
    # kernel or the dense XLA path from the call it traces. Attention
    # dropout > 0 in training keeps the dense path (the kernel has no
    # dropout). "flash" asks for the blockwise path by name.
    attention_impl: str = "dot"      # dot | flash | ring | ulysses
    # kernel for the PAGE-NATIVE cached-attention read side (serving
    # engines with page_native=True; inert everywhere else): "xla" =
    # the pure-XLA blockwise path, "pallas" = the hand-tiled paged
    # attention kernel (models/pallas_attention.py — page-table-indexed
    # block loads, in-kernel int8 dequant, tiled exact softmax; runs
    # under pallas interpret mode off-TPU). Selected via
    # ServeEngine/ServeClient(attention_kernel=...).
    attention_kernel: str = "xla"    # xla | pallas
    # kernel for weight-QUANTIZED matmuls (params holding QTensor
    # leaves — models/quant.py; inert on plain trees): "xla" =
    # dequantize the whole tree once at program entry (the PR 11
    # materialized-dequant path, quant.materialize_for_program), then
    # plain XLA matmuls; "pallas" = stream the int8/int4 codes + group
    # scales INTO a fused dequant-matmul kernel per projection
    # (models/pallas_matmul.py — nibble unpack and codes x scales on
    # VMEM tiles, no dense dequantized weight arena anywhere, so the
    # per-dispatch param byte stream drops to the codes+scales floor).
    # Selected via ServeEngine/ServeClient(matmul_kernel=...); runs
    # under pallas interpret mode off-TPU, compiled on a TPU
    # (docs/serving.md for the contract).
    matmul_kernel: str = "xla"       # xla | pallas
    # f32 (default) is the numerically-safe softmax; bf16 halves the
    # (B,H,T,T) score-tensor HBM traffic (round-3 history:
    # docs/performance.md) at ~1% attention-weight rounding. Only the 'dot'
    # and 'ulysses' impls consume it; flash/ring keep f32 accumulators
    # by construction (their running max/denominator live in registers,
    # not HBM, so there is nothing to save).
    attention_softmax_dtype: Any = jnp.float32
    tie_embeddings: bool = True
    num_segments: int = 0            # >0 adds segment embeddings (BERT)
    # multi-LoRA arming (models/lora.py LoraConfig, hashable; None =
    # stock model, byte-for-byte the pre-LoRA family): targeted
    # projections swap for their bank-delegating siblings and every
    # *Block call accepts per-row ``adapter_ids`` — each batch row
    # gathers its own (A, B) pair from a resident
    # (num_adapters, r, d) bank and adds the low-rank delta OUTSIDE
    # the (possibly quantized) base matmul. Selected via
    # ServeEngine/ServeClient(adapters=, max_resident_adapters=,
    # lora_rank=) on the serve side; trained directly by building the
    # model with lora=LoraConfig(rank, num_adapters=1).
    lora: Any = None

    def __post_init__(self):
        if self.scan_unroll < 1:
            raise ValueError(
                f"scan_unroll must be >= 1, got {self.scan_unroll}")
        if self.scan_unroll > 1 and not self.scan_layers:
            raise ValueError(
                "scan_unroll is set but scan_layers=False — the unroll "
                "factor would be silently ignored (the python loop is "
                "already fully unrolled); drop it or use scan_layers=True")
        if self.attention_kernel not in ("xla", "pallas"):
            raise ValueError(
                f"attention_kernel must be 'xla' or 'pallas', got "
                f"{self.attention_kernel!r}")
        if self.matmul_kernel not in ("xla", "pallas"):
            raise ValueError(
                f"matmul_kernel must be 'xla' or 'pallas', got "
                f"{self.matmul_kernel!r}")
        if self.remat_policy is not None:
            if not self.remat:
                raise ValueError(
                    "remat_policy is set but remat=False — the policy "
                    "would be silently ignored; pass remat=True (or drop "
                    "the policy)")
            valid = ("dots", "dots_with_no_batch_dims",
                     "dots_with_no_batch_dims_save_attn",
                     "dots_with_no_batch_dims_save_attn_mlp")
            if self.remat_policy not in valid:
                raise ValueError(
                    f"remat_policy must be one of {valid} or None, got "
                    f"{self.remat_policy!r}")
        if self.lora is not None:
            from ray_lightning_tpu.models.lora import LoraConfig
            if not isinstance(self.lora, LoraConfig):
                raise ValueError(
                    f"lora must be a models.lora.LoraConfig or None, "
                    f"got {type(self.lora).__name__}")

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def tensor_parallel_rule(path, leaf):
    """Megatron-style tensor-parallel PartitionSpec rule for this module
    family, for ``MeshStrategy(axes={"dp": ..., "tp": ...},
    param_rule=tensor_parallel_rule)``.

    Column-parallel up-projections (attention qkv over the heads dim, MLP
    ``up`` over d_ff) and row-parallel down-projections (attention ``out``
    and MLP ``down`` over their input dim) — so each block needs exactly
    one all-reduce in forward, which GSPMD inserts from these specs.
    Negative dim indexing makes the same rule cover scanned stacks (the
    leading ``layers`` dim the ``nn.scan`` adds) and unrolled blocks.
    Embeddings/layernorms replicate.
    """

    names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
    shape = tuple(getattr(leaf, "shape", ()))
    if not shape:
        return P()

    def at(dim):
        spec = [None] * len(shape)
        spec[dim] = "tp"
        return P(*spec)

    leafname = names[-1]
    if "attn" in names and "qkv" in names:
        # kernel (..., d_model, 3, H, Dh), bias (..., 3, H, Dh): heads dim
        return at(-2)
    if "attn" in names and "out" in names:
        # kernel (..., H*Dh, d_model) row-parallel; bias replicated
        return at(-2) if leafname == "kernel" and len(shape) >= 2 else P()
    if "mlp" in names and "up" in names:
        # kernel (..., d_model, d_ff), bias (..., d_ff): d_ff dim
        return at(-1)
    if "mlp" in names and "down" in names:
        # kernel (..., d_ff, d_model) row-parallel; bias replicated
        return at(-2) if leafname == "kernel" and len(shape) >= 2 else P()
    return P()


# --------------------------------------------------------- quant layers
# Drop-in projections/embeddings that consume weight-QUANTIZED param
# leaves (models/quant.py QTensor) in place. The plain-param path
# DELEGATES to the stock flax module through nn.share_scope — same
# param names/paths (tensor_parallel_rule and un/stack_scan_params
# keep matching), same initializers, bitwise-identical apply — so
# every unquantized model in the family is byte-for-byte unchanged.
# When the bound leaf is a QTensor (matmul_kernel="pallas" lets
# quant.materialize_for_program pass codes through the jit boundary):
#
# - cfg.matmul_kernel == "pallas": the matmul dispatches the fused
#   dequant-matmul kernel (models/pallas_matmul.py) — codes + scales
#   stream straight into the dot, no dense weight materializes.
# - otherwise (a direct caller handed codes to an "xla" model): the
#   leaf dequantizes layer-locally — same tokens, dispatch-scoped
#   dequant scratch — instead of failing flax's param shape check.
#
# Embedding LOOKUPS gather codes + scales row-wise and dequantize the
# gathered rows (element-wise dequant commutes with gather: bitwise
# the dequantize-then-take path at a fraction of the bytes).

def _raw_qtensor(mod: nn.Module, name: str):
    """The bound param leaf iff it is a QTensor — read raw (bypassing
    ``self.param``'s structural check, which would flatten the QTensor
    into its two children and refuse); None during init and on plain
    trees (the delegation path)."""
    from ray_lightning_tpu.models.quant import QTensor
    if mod.is_initializing() or not mod.has_variable("params", name):
        return None
    leaf = mod.get_variable("params", name)
    return leaf if isinstance(leaf, QTensor) else None


def _quant_matmul(x, qt, matmul_kernel: str, transpose: bool = False):
    """One quantized-leaf contraction in compute dtype: the fused
    kernel under "pallas", a layer-local dequantize + the identical
    XLA dot otherwise. Both branches return the FLATTENED ``(..., N)``
    form — callers reshape to their feature dims."""
    if matmul_kernel == "pallas":
        from ray_lightning_tpu.models.pallas_matmul import quantized_matmul
        return quantized_matmul(x, qt, transpose=transpose)
    w = qt.dequantize().astype(x.dtype)
    if transpose:
        return jnp.dot(x, w.T)
    return jax.lax.dot_general(
        x, w.reshape(w.shape[0], -1),
        (((x.ndim - 1,), (0,)), ((), ())))


class QuantDenseGeneral(nn.Module):
    """``nn.DenseGeneral(axis=-1)`` that also consumes QTensor kernels
    (module comment above). ``features`` may be an int or a tuple."""
    features: Any
    matmul_kernel: str = "xla"
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        self._dense = nn.DenseGeneral(
            features=self.features, axis=-1, use_bias=self.use_bias,
            dtype=self.dtype, param_dtype=self.param_dtype)
        nn.share_scope(self, self._dense)

    def __call__(self, x):
        qt = _raw_qtensor(self, "kernel")
        if qt is None:
            return self._dense(x)
        y = _quant_matmul(x.astype(self.dtype), qt, self.matmul_kernel)
        feats = (self.features if isinstance(self.features, tuple)
                 else (self.features,))
        y = y.reshape(*x.shape[:-1], *feats)
        if self.use_bias:
            y = y + jnp.asarray(self.get_variable("params", "bias"),
                                self.dtype)
        return y


class QuantDense(nn.Module):
    """``nn.Dense`` that also consumes QTensor kernels."""
    features: int
    matmul_kernel: str = "xla"
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        self._dense = nn.Dense(
            self.features, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype)
        nn.share_scope(self, self._dense)

    def __call__(self, x):
        qt = _raw_qtensor(self, "kernel")
        if qt is None:
            return self._dense(x)
        y = _quant_matmul(x.astype(self.dtype), qt, self.matmul_kernel)
        y = y.reshape(*x.shape[:-1], self.features)
        if self.use_bias:
            y = y + jnp.asarray(self.get_variable("params", "bias"),
                                self.dtype)
        return y


class QuantEmbed(nn.Module):
    """``nn.Embed`` that also consumes a QTensor embedding table: the
    lookup gathers codes (+ int4 group scales) row-wise and dequantizes
    the gathered rows; ``attend`` — the tied LM head — contracts the
    codes through the fused kernel's transpose orientation (the scales
    ride the contraction axis there; see ``quant.matmul_view``)."""
    num_embeddings: int
    features: int
    matmul_kernel: str = "xla"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        self._embed = nn.Embed(
            self.num_embeddings, self.features, dtype=self.dtype,
            param_dtype=self.param_dtype)
        nn.share_scope(self, self._embed)

    def __call__(self, ids):
        qt = _raw_qtensor(self, "embedding")
        if qt is None:
            return self._embed(ids)
        if qt.bits == 8:
            rows = jnp.take(qt.q, ids, axis=0).astype(jnp.float32)
            w = rows * qt.scale[0]              # (1, d) scale -> (d,)
        else:
            from ray_lightning_tpu.models.quant import unpack_int4
            packed = jnp.take(qt.q, ids, axis=0)
            s = jnp.take(qt.scale, ids, axis=0)     # (..., d/gs, 1)
            codes = unpack_int4(packed).astype(jnp.float32)
            grouped = codes.reshape(*codes.shape[:-1], -1,
                                    qt.group_size)
            w = (grouped * s).reshape(codes.shape)
        return w.astype(qt.dtype).astype(self.dtype)

    def attend(self, query):
        qt = _raw_qtensor(self, "embedding")
        if qt is None:
            return self._embed.attend(query)
        return _quant_matmul(query.astype(self.dtype), qt,
                             self.matmul_kernel, transpose=True)


def _projection(cfg: TransformerConfig, *, features, in_features: int,
                name: str, dense: bool = False):
    """One named block projection as a call closure ``f(x, adapter_ids)``:
    the stock quant layer (adapter_ids ignored — the module graph is
    byte-for-byte the pre-LoRA family), or its bank-delegating LoRA
    sibling when ``cfg.lora`` targets this name (models/lora.py)."""
    if cfg.lora is not None and name in cfg.lora.targets:
        from ray_lightning_tpu.models.lora import LoraDense, LoraDenseGeneral
        cls = LoraDense if dense else LoraDenseGeneral
        mod = cls(features=features, in_features=in_features,
                  lora=cfg.lora, matmul_kernel=cfg.matmul_kernel,
                  dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        return lambda x, adapter_ids: mod(x, adapter_ids)
    cls = QuantDense if dense else QuantDenseGeneral
    mod = cls(features=features, matmul_kernel=cfg.matmul_kernel,
              dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
    return lambda x, adapter_ids: mod(x)


#: Shortest causal self-attention the default seat hands to the blockwise
#: kernel: the medium train cell's step program, timed at 1024, 512 and 256
#: positions a row with the tokens of a step held, is faster on the kernel
#: at all three (PERF.md section 6, PR 33); nothing shorter was timed.
FLASH_MIN_LEN = 256


def attention_seat(q_shape, k_shape, *, decode: bool, causal: bool, mask,
                   dropout: bool, backend: Optional[str] = None):
    """Which program computes the attention call being traced, from the
    call itself and the ambient mesh: ``(kernel, how)``.

    ``(True, "local")`` is the blockwise kernel as a plain call (no mesh,
    one device's worth of data axes, or a region that is already manual);
    ``(True, "sharded")`` the same kernel nested in a ``shard_map`` over
    the mesh, batch rows split over its data axes (a bare ``pallas_call``
    is opaque to the partitioner, which would gather the global batch onto
    every chip). ``(False, reason)`` is the dense path; ``reason`` names
    the first test the call fails: ``decode``, ``non_causal``, ``mask``,
    ``dropout``, ``length`` (query and key lengths differ or are under
    ``FLASH_MIN_LEN``), ``head_dim``, ``backend``, ``sequence_cut`` (an
    ``sp`` axis: ring / ulysses own that case), ``batch_indivisible``.
    """
    if decode:
        return False, "decode"
    if not causal:
        return False, "non_causal"
    if mask is not None:
        return False, "mask"
    if dropout:
        return False, "dropout"
    if q_shape[1] != k_shape[1] or q_shape[1] < FLASH_MIN_LEN:
        return False, "length"
    if head_lanes(q_shape[-1]) is None:
        return False, "head_dim"
    if (backend or jax.default_backend()) != "tpu":
        return False, "backend"
    mesh = shardlib.ambient_mesh()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return True, "local"
    if mesh.shape.get("sp", 1) > 1:
        return False, "sequence_cut"
    if _mesh_cut(mesh, q_shape) == (None, None):
        return True, "local"
    if q_shape[0] % shardlib.data_axis_size(mesh):
        return False, "batch_indivisible"
    return True, "sharded"


def _mesh_cut(mesh, q_shape):
    """The axes a nested kernel call splits batch rows and heads over."""
    rows = shardlib.data_axis_names(mesh) \
        if shardlib.data_axis_size(mesh) > 1 else None
    tp = mesh.shape.get("tp", 1)
    heads = "tp" if tp > 1 and q_shape[2] % tp == 0 else None
    return rows, heads


def _blockwise_attention(q, k, v, how: str):
    """The causal blockwise kernel on ``(B, T, H, D)``; ``"sharded"`` runs
    it per device (the pattern of ``ring_attention.sp_sharded_attention``),
    heads kept on ``tp`` where such an axis divides them."""
    if how == "local":
        return pallas_flash_attention(q, k, v, causal=True)
    mesh = shardlib.ambient_mesh()
    rows, heads = _mesh_cut(mesh, q.shape)
    spec = P(rows, None, heads)
    return jax.shard_map(
        lambda a, b, c: pallas_flash_attention(a, b, c, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def _attention_fn(cfg: TransformerConfig):
    if cfg.attention_impl == "dot":
        return functools.partial(_adaptive_attention, cfg.decode)
    if cfg.attention_impl == "flash":
        from ray_lightning_tpu.ops.flash_attention import flash_attention
        return flash_attention
    if cfg.attention_impl == "ring":
        from ray_lightning_tpu.parallel.ring_attention import (
            sp_sharded_attention)
        return sp_sharded_attention
    if cfg.attention_impl == "ulysses":
        from ray_lightning_tpu.parallel.ulysses import ulysses_attention
        return ulysses_attention
    raise ValueError(f"Unknown attention_impl {cfg.attention_impl!r}")


def _adaptive_attention(decode, q, k, v, *, causal, mask, dropout_rate,
                        dropout_rng, **kw):
    """The default seat: the blockwise kernel where :func:`attention_seat`
    says so, ``dot_product_attention`` exactly as ever elsewhere."""
    kernel, how = attention_seat(
        q.shape, k.shape, decode=decode, causal=causal, mask=mask,
        dropout=dropout_rate > 0.0 and dropout_rng is not None)
    seats.note(kernel, how)
    if kernel:
        return _blockwise_attention(q, k, v, how)
    return dot_product_attention(
        q, k, v, causal=causal, mask=mask, dropout_rate=dropout_rate,
        dropout_rng=dropout_rng, **kw)


class MultiHeadAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, kv_positions=None,
                 page_table=None, adapter_ids=None):
        cfg = self.cfg
        B, T, _ = x.shape
        qkv = _projection(
            cfg, features=(3, cfg.n_heads, cfg.head_dim),
            in_features=cfg.d_model, name="qkv")(x, adapter_ids)
        # static index slices, not moveaxis: the 3-to-front transpose
        # materializes a layout-changing copy of the whole qkv tensor on
        # TPU (376us/step at GPT-2-small bs8 in the v5e trace); slices
        # fuse into the attention consumers instead
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cfg.decode and page_table is not None:
            # page-native cached attention: K/V live in the serving
            # engine's page arena and are read/written THROUGH the page
            # table — no dense (B, max_seq_len) view ever materializes
            out = self._page_native_attention(q, k, v, kv_positions,
                                              page_table)
            from jax.ad_checkpoint import checkpoint_name
            out = checkpoint_name(out, "attn_out")
            out = out.reshape(B, T, cfg.n_heads * cfg.head_dim)
            return _projection(
                cfg, features=cfg.d_model,
                in_features=cfg.n_heads * cfg.head_dim,
                name="out")(out, adapter_ids)
        causal = cfg.causal
        if cfg.decode:
            k, v, cache_mask = self._decode_cache(k, v, kv_positions)
            if cache_mask is not None:
                # combine with any caller mask (e.g. left-pad masking for
                # batched prompts) — both are additive 0/-inf biases
                mask = cache_mask if mask is None else mask + cache_mask
            causal = False  # the cache mask already encodes causality
        drop_rng = None
        if cfg.dropout > 0.0 and not deterministic:
            drop_rng = self.make_rng("dropout")
        attn = _attention_fn(cfg)
        kw = {}
        if cfg.attention_softmax_dtype != jnp.float32 and \
                cfg.attention_impl in ("dot", "ulysses"):
            kw["softmax_dtype"] = cfg.attention_softmax_dtype
        out = attn(q, k, v, causal=causal, mask=mask,
                   dropout_rate=cfg.dropout if not deterministic else 0.0,
                   dropout_rng=drop_rng, **kw)
        # named checkpoint seat for the "...save_attn" remat policies:
        # saving this one (B,T,H,D) tensor lets backward skip recomputing
        # the whole attention chain (scores, softmax, AV) at the cost of
        # seq*d_model bf16 bytes per layer — the right trade once HBM
        # headroom exists (memory-efficient optimizer states)
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "attn_out")
        out = out.reshape(B, T, cfg.n_heads * cfg.head_dim)
        return _projection(
            cfg, features=cfg.d_model,
            in_features=cfg.n_heads * cfg.head_dim,
            name="out")(out, adapter_ids)

    def _decode_cache(self, k, v, kv_positions=None):
        """KV-cache update (flax decode pattern): the "cache" collection
        holds keys/values for all ``max_seq_len`` positions. Two write
        modes:

        - ``kv_positions=None`` — write a block of ``T >= 1`` new
          positions at the shared ``cache_index`` (T=1 is the classic
          per-token decode step; T>1 is the prefill path writing the whole
          prompt in one ``dynamic_update_slice``). The returned additive
          mask is intra-block causal over the cache buffer: query ``q`` of
          the block attends positions ``<= cache_index + q``.
        - ``kv_positions`` (B, T) — per-row block write of ``T >= 1``
          tokens at each row's own absolute positions (ragged decode:
          rows sit at different sequence lengths; T=1 is the classic
          per-row decode step, T>1 is the speculative-decode verify
          program scoring a row's draft block in one pass). Positions
          must be the contiguous run ``kv_positions[row, 0] + 0..T-1``
          — the write is :func:`ops.cache_write.write_rows` at that
          start (in place: one kernel call a position for K and V
          together); the mask is per-row, per-query
          ``key <= kv_positions[row, q]`` (block-causal over the
          cache, the ragged sibling of the shared-index block mode).

        The scalar ``cache_index`` advances by ``T`` either way; in the
        per-row mode it is bookkeeping only (positions come from the
        caller).
        """
        cfg = self.cfg
        B, T, H, D = k.shape
        is_init = not self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (B, cfg.max_seq_len, H, D), k.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (B, cfg.max_seq_len, H, D), v.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        if is_init:  # shape-building init pass: no cache semantics yet
            return k, v, None
        key_pos = jax.lax.broadcasted_iota(jnp.int32,
                                           (1, 1, 1, cfg.max_seq_len), 3)
        big_neg = jnp.finfo(jnp.float32).min
        if kv_positions is not None:
            pos = kv_positions.astype(jnp.int32)                # (B, T)
            with jax.named_scope("kv_write"):
                ck.value, cv.value = write_rows(
                    (ck.value, cv.value), (k, v), pos[:, 0])
            ci.value = ci.value + T
            # per-row, per-query: query q of the block attends keys at
            # positions <= pos[row, q] — block-causal, covering the
            # block's own just-written K/V up to each query
            mask = jnp.where(key_pos <= pos[:, None, :, None], 0.0,
                             big_neg)                           # (B,1,T,S)
            return ck.value, cv.value, mask
        idx = ci.value
        with jax.named_scope("kv_write"):
            ck.value = jax.lax.dynamic_update_slice(ck.value, k,
                                                    (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v,
                                                    (0, idx, 0, 0))
        ci.value = idx + T
        if T == 1:
            mask = jnp.where(key_pos <= idx, 0.0, big_neg)      # (1,1,1,S)
        else:
            q_off = jax.lax.broadcasted_iota(jnp.int32, (1, 1, T, 1), 2)
            mask = jnp.where(key_pos <= idx + q_off, 0.0,
                             big_neg)                           # (1,1,T,S)
        return ck.value, cv.value, mask

    def _page_native_attention(self, q, k, v, kv_positions, page_table):
        """Cached attention straight through the serving engine's page
        arena — the gather-fusion half of the pallas endgame, in pure
        XLA (see ``docs/serving.md``).

        The ``cache`` collection holds the arena leaves themselves
        (``(num_pages, page_size, H, D)``; int8 arenas put the codes
        here and their absmax scales in a parallel ``kvscale``
        collection), and ``page_table`` (B, pages_per_slot) maps each
        row's logical pages to arena pages (−1 = unmapped). Instead of
        materializing the dense ``(B, max_seq_len)`` per-slot view every
        dispatch (the ``gather_pages``/``scatter_pages`` round trip,
        whose bytes scale with ``num_slots x max_seq_len`` regardless of
        occupancy), this path:

        - **writes** the block's T tokens' K/V directly into the owning
          pages at ``kv_positions`` (unmapped / write-masked rows drop;
          int8 pages are read-modify-requantized one page at a time);
        - **reads** K blockwise, one page column per iteration — scores
          for all ``pages_per_slot`` columns are concatenated into the
          SAME ``(B, H, T, max_seq_len)`` logits tensor the dense path
          builds (tiny: no V-sized buffer), masked with the identical
          per-row block-causal ``key <= kv_positions[row, q]`` rule, and
          softmaxed in one exact f32 pass — no online-softmax
          approximation, so outputs match the dense-gather path up to
          reduction-order rounding in the final V accumulation;
        - **accumulates** the output blockwise over V page columns in
          f32.

        ``cfg.attention_kernel == "pallas"`` swaps the read side (the
        three bullets above) for the hand-tiled pallas kernel
        (:func:`ray_lightning_tpu.models.pallas_attention.paged_attention`)
        — same blockwise plan, but the page loads, int8 dequant,
        masked scores, exact softmax, and f32 output accumulation all
        happen inside ONE kernel with VMEM-resident tiles (interpret
        mode off-TPU). The write half below is shared by both kernels.

        Unmapped (−1) entries clamp to page 0 — finite stale bytes the
        position mask never admits, the same argument as
        ``gather_pages`` — and repeated clamped reads stay cache-hot:
        the bytes actually streamed scale with *occupied* pages.
        """
        cfg = self.cfg
        if kv_positions is None:
            raise ValueError(
                "page-native attention is a serving-engine mode and "
                "needs per-row kv_positions (each row's absolute "
                "sequence positions)")
        B, T, H, D = k.shape

        def _missing(what):
            def init():
                raise ValueError(
                    f"page-native attention found no {what} — pass the "
                    "paged KV arena as the 'cache' collection (int8 "
                    "arenas add their scales as 'kvscale'); see "
                    "decode_step_paged in models/generate.py")
            return init

        ck = self.variable("cache", "cached_key", _missing("cached_key"))
        cv = self.variable("cache", "cached_value",
                           _missing("cached_value"))
        quantized = ck.value.dtype == jnp.int8
        if quantized:
            sk = self.variable("kvscale", "cached_key",
                               _missing("cached_key scales"))
            sv = self.variable("kvscale", "cached_value",
                               _missing("cached_value scales"))
        P, ps = ck.value.shape[0], ck.value.shape[1]
        pp = page_table.shape[1]
        pos = kv_positions.astype(jnp.int32)                    # (B, T)

        def read_pages(store, scales, pidx):
            block = jnp.take(store, pidx, axis=0)       # (B, ps, H, D)
            if scales is None:
                return block
            return kv_dequantize(block, jnp.take(scales, pidx, axis=0),
                                 k.dtype)

        # ---- write first: the block attends its own just-written K/V
        # (key <= pos admits each query's own position), exactly like
        # the per-row mode of _decode_cache
        rows = jnp.arange(B)
        with jax.named_scope("kv_write"):
            for t in range(T):
                col = pos[:, t] // ps
                off = pos[:, t] % ps
                pidx = jnp.take_along_axis(page_table, col[:, None],
                                           axis=1)[:, 0]            # (B,)
                widx = jnp.where(pidx >= 0, pidx, P)   # −1 = dropped write
                if not quantized:
                    ck.value = ck.value.at[widx, off].set(k[:, t],
                                                          mode="drop")
                    cv.value = cv.value.at[widx, off].set(v[:, t],
                                                          mode="drop")
                    continue
                # int8: read-modify-requantize the one page this token
                # lands in. NOTE this rounds MORE often than the
                # dense-gather path (scatter_pages dequantizes once per
                # dispatch, accumulates every sub-step's writes in full
                # precision, requantizes once at the end; here each token
                # round-trips its page immediately, so multi-step dispatches
                # re-round a page's other entries whenever its absmax
                # carrier moves) — int8 page-native vs dense-gather token
                # identity is therefore EMPIRICAL (bounded extra rounding
                # vs argmax margins, pinned on the test configs incl.
                # steps_per_dispatch>1), not structural like the
                # full-precision case
                g = jnp.clip(pidx, 0, P - 1)
                for store, scales, new in ((ck, sk, k), (cv, sv, v)):
                    page = kv_dequantize(
                        jnp.take(store.value, g, axis=0),
                        jnp.take(scales.value, g, axis=0), new.dtype)
                    page = page.at[rows, off].set(new[:, t])
                    ns = kv_scales(page, (1, 3))
                    store.value = store.value.at[widx].set(
                        kv_quantize(page, ns), mode="drop")
                    scales.value = scales.value.at[widx].set(ns,
                                                             mode="drop")

        if cfg.attention_kernel == "pallas":
            # fused read side: page-table-indexed block loads, int8
            # dequant, masked blockwise scores, exact tiled softmax and
            # f32 V accumulation in one pallas_call — the XLA read
            # below up to f32 summation order (pinned by
            # tests/test_pallas_attention.py)
            from ray_lightning_tpu.models.pallas_attention import (
                paged_attention)
            return paged_attention(
                q, ck.value, cv.value,
                sk.value if quantized else None,
                sv.value if quantized else None, pos, page_table)

        # ---- scores blockwise over page columns, ONE exact softmax
        scale = cfg.head_dim ** -0.5

        def score_block(_, j):
            pidx = jnp.clip(page_table[:, j], 0, P - 1)
            kj = read_pages(ck.value, sk.value if quantized else None,
                            pidx)
            sj = jnp.einsum("bqhd,bkhd->bhqk", q, kj,
                            preferred_element_type=jnp.float32)
            return None, sj

        with jax.named_scope("attention/scores"):
            _, scores = jax.lax.scan(score_block, None, jnp.arange(pp))
            # (pp, B, H, T, ps) -> (B, H, T, pp*ps): page-major key order
            # IS absolute position order (column j covers
            # j*ps .. j*ps+ps-1)
            logits = jnp.moveaxis(scores, 0, 3).reshape(
                B, cfg.n_heads, T, pp * ps) * scale
            key_pos = jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, 1, pp * ps), 3)
            big_neg = jnp.finfo(jnp.float32).min
            logits = logits + jnp.where(key_pos <= pos[:, None, :, None],
                                        0.0, big_neg)
        with jax.named_scope("attention/softmax"):
            weights = jax.nn.softmax(logits, axis=-1)
            all_masked = jnp.all(logits <= big_neg * 0.5, axis=-1,
                                 keepdims=True)
            weights = jnp.where(all_masked, 0.0, weights).astype(q.dtype)

        # ---- output accumulated blockwise over V page columns (f32)
        def out_block(acc, j):
            pidx = jnp.clip(page_table[:, j], 0, P - 1)
            vj = read_pages(cv.value, sv.value if quantized else None,
                            pidx)
            wj = jax.lax.dynamic_slice_in_dim(weights, j * ps, ps,
                                              axis=3)
            return acc + jnp.einsum(
                "bhqk,bkhd->bqhd", wj, vj,
                preferred_element_type=jnp.float32), None

        with jax.named_scope("attention/context"):
            out, _ = jax.lax.scan(out_block,
                                  jnp.zeros((B, T, H, D), jnp.float32),
                                  jnp.arange(pp))
        return out.astype(q.dtype)


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic=True, adapter_ids=None):
        cfg = self.cfg
        h = _projection(cfg, features=cfg.d_ff, in_features=cfg.d_model,
                        name="up", dense=True)(x, adapter_ids)
        h = nn.gelu(h)
        # named seat for remat policies that save the GELU output
        from jax.ad_checkpoint import checkpoint_name
        h = checkpoint_name(h, "mlp_act")
        h = _projection(cfg, features=cfg.d_model, in_features=cfg.d_ff,
                        name="down", dense=True)(h, adapter_ids)
        if cfg.dropout > 0.0 and not deterministic:
            h = nn.Dropout(cfg.dropout)(h, deterministic=False)
        return h


class TransformerBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, kv_positions=None,
                 page_table=None, adapter_ids=None):
        cfg = self.cfg
        # the residual stream keeps its batch dim on the mesh's data axes
        # (identity without a strategy's mesh): a weight's fsdp cut is
        # then storage only, gathered here where the layer uses it
        x = constrain_batch(x)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        x = constrain_batch(x + MultiHeadAttention(cfg, name="attn")(
            h, mask=mask, deterministic=deterministic,
            kv_positions=kv_positions, page_table=page_table,
            adapter_ids=adapter_ids))
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        x = x + MlpBlock(cfg, name="mlp")(h, deterministic=deterministic,
                                          adapter_ids=adapter_ids)
        return constrain_batch(x)


class _ScanBlock(nn.Module):
    """Block wrapper with carry-style signature for nn.scan.

    ``deterministic`` is a static attribute (not part of the carry): scan
    carries are traced arrays, and dropout gating must stay a Python bool.
    """
    cfg: TransformerConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        x, mask, kv_positions, page_table, adapter_ids = carry
        x = TransformerBlock(self.cfg, name="block")(
            x, mask=mask, deterministic=self.deterministic,
            kv_positions=kv_positions, page_table=page_table,
            adapter_ids=adapter_ids)
        return (x, mask, kv_positions, page_table, adapter_ids), None


def latch_eos(next_tokens: jax.Array, done: jax.Array, eos_id):
    """Per-row eos latching shared by ``generate()``'s decode scan and the
    serving engine's step program.

    Rows already ``done`` keep emitting their eos id (static shapes: the
    program runs full length, finished rows must repeat a harmless token);
    rows that just sampled eos latch ``done``. ``eos_id`` is a scalar or a
    per-row ``(B,)`` int array — negative entries disable eos handling for
    that row (the serving engine's "no eos" sentinel, since a traced
    per-row id cannot be ``None``).

    Returns ``(tokens, done)`` — tokens with done rows pinned to eos, and
    the updated latch.
    """
    eos = jnp.asarray(eos_id, jnp.int32)
    has_eos = eos >= 0
    out = jnp.where(done & has_eos, eos, next_tokens)
    done = done | (has_eos & (out == eos))
    return out, done


def check_seq_len(cfg: TransformerConfig, length: int,
                  what: str = "sequence") -> None:
    """Trace-time guard shared by every model family with learned
    positions: on TPU, out-of-range ``nn.Embed`` lookups clamp silently,
    so a too-long sequence would train on garbage positional embeddings
    instead of raising."""
    if length > cfg.max_seq_len:
        raise ValueError(
            f"{what} length {length} exceeds max_seq_len="
            f"{cfg.max_seq_len}; positional embeddings would silently "
            "clamp")


def maybe_remat(block_cls, cfg: TransformerConfig, *,
                deterministic_argnum: int):
    """Wrap a block class in ``nn.remat`` when ``cfg.remat`` is set —
    the one source of truth for remat options across block families.

    ``deterministic_argnum`` indexes the block's ``deterministic`` arg
    counting ``self`` as 0 (flax subtracts 1 internally); it must stay a
    python bool under remat because dropout gating branches on it.
    """
    if not cfg.remat:
        return block_cls
    return nn.remat(block_cls, prevent_cse=False,
                    static_argnums=(deterministic_argnum,),
                    policy=_remat_policy(cfg))


def _remat_policy(cfg: TransformerConfig):
    if cfg.remat_policy is None:
        return None
    cp = jax.checkpoint_policies
    policies = {
        "dots": cp.checkpoint_dots,
        "dots_with_no_batch_dims": cp.checkpoint_dots_with_no_batch_dims,
        # additionally save each block's attention output (named
        # checkpoint in MultiHeadAttention): backward skips the full
        # attention recompute for seq*d_model bf16 bytes per layer —
        # the right trade once HBM headroom exists (see
        # docs/performance.md for the measured effect)
        "dots_with_no_batch_dims_save_attn": cp.save_from_both_policies(
            cp.checkpoint_dots_with_no_batch_dims,
            cp.save_only_these_names("attn_out")),
        # ...and the (B,T,d_ff) GELU output too — 4x the bytes of
        # attn_out; only for real HBM headroom
        "dots_with_no_batch_dims_save_attn_mlp": cp.save_from_both_policies(
            cp.checkpoint_dots_with_no_batch_dims,
            cp.save_only_these_names("attn_out", "mlp_act")),
    }
    if cfg.remat_policy not in policies:
        raise ValueError(f"remat_policy must be one of "
                         f"{sorted(policies)} or None, got "
                         f"{cfg.remat_policy!r}")
    return policies[cfg.remat_policy]


class TransformerStack(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, kv_positions=None,
                 page_table=None, adapter_ids=None):
        cfg = self.cfg
        if cfg.scan_layers:
            block_cls = _ScanBlock
            if cfg.remat:
                block_cls = nn.remat(
                    _ScanBlock, prevent_cse=False,
                    static_argnums=(), policy=_remat_policy(cfg))
            stack = nn.scan(
                block_cls,
                # kvscale: int8 page arenas carry per-layer absmax
                # scales alongside the per-layer cache codes (absent —
                # and free — everywhere else)
                variable_axes={"params": 0, "cache": 0, "kvscale": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                unroll=min(cfg.scan_unroll, cfg.n_layers),
                metadata_params={nn.PARTITION_NAME: "layers"})
            (x, _, _, _, _), _ = stack(cfg, deterministic, name="layers")(
                (x, mask, kv_positions, page_table, adapter_ids), None)
            return x
        block_cls = maybe_remat(TransformerBlock, cfg,
                                deterministic_argnum=3)
        for i in range(cfg.n_layers):
            x = block_cls(cfg, name=f"block_{i}")(x, mask, deterministic,
                                                  kv_positions, page_table,
                                                  adapter_ids)
        return x


def unstack_scan_params(params):
    """Convert scanned-layer params to the unrolled layout, in any model.

    Training wants ``scan_layers=True`` (one compiled block program);
    serving wants ``scan_layers=False`` (unrolled layers decode ~2×
    faster per token step under the TPU compiler — measured in
    ``docs/performance.md``, decode section). The two layouts store the
    same numbers in different trees: scanned stacks every block's leaves
    on a leading layer axis under ``…/layers/block``, unrolled names
    them ``…/block_i``. This rewrites every scanned stack found anywhere
    in the tree (LM, encoder, ViT, seq2seq encoder+decoder alike)::

        dec_cfg = dataclasses.replace(cfg, decode=True,
                                      scan_layers=False, scan_unroll=1)
        out = generate(TransformerLM(dec_cfg),
                       unstack_scan_params(params), toks, ...)
    """
    if not isinstance(params, dict):
        return params
    out = {}
    for key, val in params.items():
        if (key == "layers" and isinstance(val, dict)
                and set(val) == {"block"}):
            leaves = jax.tree_util.tree_leaves(val["block"])
            n_layers = leaves[0].shape[0]
            for i in range(n_layers):
                out[f"block_{i}"] = jax.tree_util.tree_map(
                    lambda x: x[i], val["block"])
        else:
            out[key] = unstack_scan_params(val)
    return out


def stack_scan_params(params):
    """Inverse of :func:`unstack_scan_params`: gather ``block_i``
    siblings back into the scanned ``layers/block`` stacked layout
    (e.g. to resume scanned training from unrolled-serving weights)."""
    if not isinstance(params, dict):
        return params
    blocks = sorted((k for k in params
                     if k.startswith("block_") and k[6:].isdigit()),
                    key=lambda k: int(k[6:]))
    out = {}
    if blocks and [int(k[6:]) for k in blocks] == list(range(len(blocks))):
        if "layers" in params:
            # a literal 'layers' sibling would collide with the stacked
            # output key and one of the two subtrees would be silently
            # dropped — refuse loudly instead
            raise ValueError(
                "stack_scan_params: this level has both block_i siblings "
                f"({blocks[0]}..{blocks[-1]}) and a literal 'layers' key; "
                "stacking would overwrite one of them — rename the "
                "'layers' subtree before restacking")
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0),
            *[params[k] for k in blocks])
        out["layers"] = {"block": stacked}
    else:
        blocks = []
    for key, val in params.items():
        if key not in blocks:
            out[key] = stack_scan_params(val)
    return out


def kv_cache_leaf(cfg, names):
    """The declaration of :class:`MultiHeadAttention`'s decode cache
    (``generate.CacheLeaf``): ``cached_key`` / ``cached_value`` hold one
    ``(max_seq_len, H, D)`` row a slot — on axis 0, or axis 1 under the
    layer scan's leading axis; ``cache_index`` is shared bookkeeping."""
    from ray_lightning_tpu.models.generate import CacheLeaf
    if names[-1] in ("cached_key", "cached_value"):
        axis = 1 if cfg.scan_layers else 0
        return CacheLeaf(axis, "global", seq_axis=axis + 1)
    return CacheLeaf(None)


class TransformerLM(nn.Module):
    """GPT-style causal language model (token + learned position embeds).

    ``positions`` (B, T) overrides the default 0..T-1 position ids —
    required in decode mode, where each single-token call sits at the
    current cache index (see :mod:`ray_lightning_tpu.models.generate`).

    ``kv_positions`` (B, T) switches the decode KV cache to per-row
    writes at explicit absolute positions (ragged batches where rows sit
    at different lengths; T>1 is a per-row contiguous block write — the
    speculative-decode verify path); leave None for the shared-index
    path (uniform decode steps and block prefill).

    ``page_table`` (B, pages_per_slot) additionally switches the cached
    attention to its **page-native** mode: K/V are read and written
    directly through the serving engine's page arena (passed as the
    ``cache`` collection; int8 arenas add a ``kvscale`` collection)
    instead of a dense per-row cache — see
    :meth:`MultiHeadAttention._page_native_attention` and
    :func:`ray_lightning_tpu.models.generate.decode_step_paged`.
    Requires ``kv_positions``.

    ``return_hidden=True`` returns the final hidden states (after
    ``ln_f``) instead of logits, for the chunked LM-head loss path
    (:func:`ray_lightning_tpu.ops.lm_head_loss.chunked_lm_head_xent`)
    that never materializes the full ``(B*T, V)`` logits tensor.
    """
    cfg: TransformerConfig

    def cache_leaf(self, names):
        return kv_cache_leaf(self.cfg, names)

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, positions=None,
                 return_hidden: bool = False, kv_positions=None,
                 page_table=None, adapter_ids=None):
        cfg = self.cfg
        B, T = tokens.shape
        if positions is None:  # decode mode passes cache-index positions
            check_seq_len(cfg, T)
        wte = QuantEmbed(cfg.vocab_size, cfg.d_model,
                         matmul_kernel=cfg.matmul_kernel,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="wte")
        x = wte(tokens)
        pos = positions if positions is not None else \
            jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        x = x + QuantEmbed(cfg.max_seq_len, cfg.d_model,
                           matmul_kernel=cfg.matmul_kernel,
                           dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="wpe")(pos)
        # batch-split into the stack and into the head (constrain_batch):
        # the logits and the cross entropy stay split, and a wte stored
        # along d (the head's contraction dim) is gathered, not computed on
        x = TransformerStack(cfg, name="stack")(
            constrain_batch(x), deterministic=deterministic,
            kv_positions=kv_positions, page_table=page_table,
            adapter_ids=adapter_ids)
        x = constrain_batch(nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x))
        if return_hidden:
            return x
        if cfg.tie_embeddings:
            logits = wte.attend(x)
        else:
            logits = QuantDense(cfg.vocab_size, use_bias=False,
                                matmul_kernel=cfg.matmul_kernel,
                                dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                name="lm_head")(x)
        return logits.astype(jnp.float32)


class TransformerEncoder(nn.Module):
    """BERT-style bidirectional encoder with optional segment embeddings."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, attention_mask=None, segment_ids=None,
                 deterministic: bool = True):
        cfg = self.cfg
        B, T = tokens.shape
        check_seq_len(cfg, T)
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="tok_embed")(tokens)
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        x = x + nn.Embed(cfg.max_seq_len, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="pos_embed")(pos)
        if cfg.num_segments > 0 and segment_ids is not None:
            x = x + nn.Embed(cfg.num_segments, cfg.d_model, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype,
                             name="seg_embed")(segment_ids)
        x = nn.LayerNorm(dtype=cfg.dtype, name="embed_ln")(x)
        mask = None
        if attention_mask is not None:
            big_neg = jnp.finfo(jnp.float32).min
            mask = jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                             big_neg)
        return TransformerStack(cfg, name="stack")(
            x, mask=mask, deterministic=deterministic)
