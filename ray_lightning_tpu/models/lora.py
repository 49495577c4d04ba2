"""Batched multi-LoRA layers: a resident adapter bank per projection.

S-LoRA / Punica-style serving (PAPERS.md): thousands of per-user
fine-tunes share ONE base model, one KV arena, and one set of compiled
programs. Each targeted projection keeps a resident bank of
``num_adapters`` low-rank pairs —

    ``lora_A`` (num_adapters, in_features, rank)
    ``lora_B`` (num_adapters, rank, out_features)

— and every batch row gathers its OWN pair by a per-row ``adapter_ids``
(B,) int32 and adds ``scale * (x @ A @ B)`` to the base projection.
Fixed shapes mean adapter churn (hot load/unload into bank slots,
:mod:`ray_lightning_tpu.serve.adapters`) never recompiles, and rows
bound to different adapters batch in one dispatch.

Design rules, in the house style of the PR 14 quant layers:

- **Delegation via** ``nn.share_scope``: :class:`LoraDenseGeneral` /
  :class:`LoraDense` build the stock quant layer in ``setup()`` and
  share its scope, so the base ``kernel``/``bias`` keep their flat
  param paths — ``tensor_parallel_rule``, ``un/stack_scan_params``,
  and every checkpoint keep matching, and a model with ``cfg.lora is
  None`` never instantiates these classes at all (byte-for-byte
  unchanged).
- **The delta rides OUTSIDE the base matmul**: the base projection is
  computed by the unmodified quant layer (including the fused
  ``matmul_kernel="pallas"`` dequant-matmul on QTensor kernels); the
  low-rank delta is a separate f32 contraction added afterwards. Weight
  quantization and LoRA therefore compose without touching either
  kernel.
- **Row −1 is the null adapter**: its delta is masked to exactly 0.0,
  so a null row's output is the base projection bit-for-bit — the
  serving engine's unadapted rows stay token-identical to an engine
  with no bank at all.
- ``adapter_ids=None`` (the training path: the trainer never threads
  ids) selects bank slot 0 for every row — a ``num_adapters=1`` model
  trains its single adapter exactly like classic LoRA.

The bank helpers at the bottom are the registry's storage layer:
zero-bank grafting onto an existing (possibly weight-quantized) tree,
per-slot install/extract/zero, and exact byte accounting.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ray_lightning_tpu.models.transformer import (QuantDense,
                                                  QuantDenseGeneral)

#: projection names a LoRA config may target — the four per-block
#: matmuls of the transformer family (attention qkv/out, MLP up/down)
LORA_TARGETS = ("qkv", "out", "up", "down")


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    """Static LoRA arming for a :class:`TransformerConfig` (hashable —
    it rides the frozen config through jit's static model argument).

    ``num_adapters`` is the RESIDENT bank size (serve-side: the
    ``max_resident_adapters`` ceiling; train-side: 1). ``alpha``
    defaults to ``rank`` — i.e. scale 1.0, the convention the identity
    tests pin — and the classic ``alpha/rank`` scaling is available for
    checkpoints trained elsewhere.
    """
    rank: int
    num_adapters: int = 1
    targets: Tuple[str, ...] = LORA_TARGETS
    alpha: Optional[float] = None

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"lora rank must be >= 1, got {self.rank}")
        if self.num_adapters < 1:
            raise ValueError(
                f"num_adapters must be >= 1, got {self.num_adapters}")
        if not self.targets:
            raise ValueError("lora targets must be a non-empty tuple")
        bad = [t for t in self.targets if t not in LORA_TARGETS]
        if bad:
            raise ValueError(
                f"unknown lora targets {bad}; known: {LORA_TARGETS}")
        if self.alpha is not None and self.alpha <= 0:
            raise ValueError(f"alpha must be > 0, got {self.alpha}")

    @property
    def scale(self) -> float:
        return (self.alpha if self.alpha is not None else
                float(self.rank)) / float(self.rank)


def _flat_features(features) -> int:
    feats = features if isinstance(features, tuple) else (features,)
    return int(math.prod(feats))


class _LoraBankMixin:
    """The shared bank declaration + delta contraction. Subclasses set
    ``self._base`` (a scope-shared quant layer) in ``setup()`` before
    calling ``_setup_bank``."""

    def _setup_bank(self):
        out_flat = _flat_features(self.features)
        n, r = self.lora.num_adapters, self.lora.rank
        # zero-init both halves: a fresh bank slot is an exact no-op
        # (classic LoRA zero-inits only B; zeroing A too makes
        # "unloaded slot == null adapter" a structural fact the
        # registry's zero_adapter() relies on)
        self.lora_A = self.param("lora_A", nn.initializers.zeros,
                                 (n, self.in_features, r),
                                 self.param_dtype)
        self.lora_B = self.param("lora_B", nn.initializers.zeros,
                                 (n, r, out_flat), self.param_dtype)

    def _lora_delta(self, x, base, adapter_ids):
        if adapter_ids is None:
            # training path: every row trains bank slot 0
            adapter_ids = jnp.zeros((x.shape[0],), jnp.int32)
        adapter_ids = jnp.asarray(adapter_ids, jnp.int32)
        n = self.lora.num_adapters
        g = jnp.clip(adapter_ids, 0, n - 1)
        a_g = jnp.take(self.lora_A, g, axis=0)      # (B, in, r)
        b_g = jnp.take(self.lora_B, g, axis=0)      # (B, r, out_flat)
        # f32 accumulation regardless of compute dtype: rank is tiny,
        # the delta's cost is noise next to the base matmul
        h = jnp.einsum("b...d,bdr->b...r", x.astype(jnp.float32),
                       a_g.astype(jnp.float32))
        delta = jnp.einsum("b...r,brn->b...n", h,
                           b_g.astype(jnp.float32))
        delta = delta.reshape(base.shape) * self.lora.scale
        # row −1 = null adapter: exactly-zero delta, base bit-for-bit
        mask = (adapter_ids >= 0).reshape(
            (-1,) + (1,) * (base.ndim - 1))
        return base + jnp.where(mask, delta, 0.0).astype(base.dtype)


class LoraDenseGeneral(nn.Module, _LoraBankMixin):
    """:class:`QuantDenseGeneral` plus a resident adapter bank.

    ``in_features`` is explicit (the bank is declared in ``setup()``,
    before any input is seen); call sites know it statically.
    """
    features: Any
    in_features: int
    lora: LoraConfig
    matmul_kernel: str = "xla"
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        self._base = QuantDenseGeneral(
            features=self.features, matmul_kernel=self.matmul_kernel,
            use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype)
        nn.share_scope(self, self._base)
        self._setup_bank()

    def __call__(self, x, adapter_ids=None):
        return self._lora_delta(x, self._base(x), adapter_ids)


class LoraDense(nn.Module, _LoraBankMixin):
    """:class:`QuantDense` plus a resident adapter bank."""
    features: int
    in_features: int
    lora: LoraConfig
    matmul_kernel: str = "xla"
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        self._base = QuantDense(
            self.features, matmul_kernel=self.matmul_kernel,
            use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype)
        nn.share_scope(self, self._base)
        self._setup_bank()

    def __call__(self, x, adapter_ids=None):
        return self._lora_delta(x, self._base(x), adapter_ids)


# ------------------------------------------------------- bank helpers
#
# The serving engine arms LoRA by GRAFTING zero banks onto an already
# trained (and possibly already weight-quantized) param tree — the
# trained base params never pass through a lora-model init, so base
# weights are bitwise the unadapted engine's. A "bank dict" is any
# param subtree whose key is a target name and which holds a "kernel"
# leaf (plain array or QTensor); an "adapter tree" is the nested dict
# of single-slot {"lora_A" (in, r), "lora_B" (r, out)} pairs that
# extract_adapter() slices out and the checkpoint layer publishes.
#
# All helpers operate on the UNROLLED layout (the serving layout —
# engines always run scan_layers=False). A scanned tree stacks every
# block's leaves under …/layers/block and is refused loudly: convert
# with transformer.unstack_scan_params first.

def _kernel_dims(kernel) -> Tuple[int, int]:
    """(in_features, out_flat) of a projection kernel — works on plain
    arrays and QTensor leaves alike (both carry the original .shape)."""
    shape = tuple(kernel.shape)
    return int(shape[0]), int(math.prod(shape[1:]))


def _walk_targets(params, targets, path=()):
    """Yield ``(path, target_dict)`` for every targeted projection
    subtree (a dict keyed by a target name that holds a kernel)."""
    if not isinstance(params, dict):
        return
    for key, val in params.items():
        if key == "layers" and isinstance(val, dict) and "block" in val:
            raise ValueError(
                "lora bank helpers need the unrolled param layout; this "
                "tree has a scanned …/layers/block stack — convert with "
                "transformer.unstack_scan_params first")
        if key in targets and isinstance(val, dict) and "kernel" in val:
            yield path + (key,), val
        elif isinstance(val, dict):
            yield from _walk_targets(val, targets, path + (key,))


def _map_targets(params, targets, fn):
    """Rebuild ``params`` with ``fn(path, target_dict)`` replacing every
    targeted projection dict (same refusal rules as _walk_targets)."""
    def rec(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, val in tree.items():
            if (key == "layers" and isinstance(val, dict)
                    and "block" in val):
                raise ValueError(
                    "lora bank helpers need the unrolled param layout; "
                    "this tree has a scanned …/layers/block stack — "
                    "convert with transformer.unstack_scan_params first")
            if (key in targets and isinstance(val, dict)
                    and "kernel" in val):
                out[key] = fn(path + (key,), val)
            else:
                out[key] = rec(val, path + (key,))
        return out
    return rec(params, ())


def install_lora_bank(params, lora: LoraConfig, dtype=jnp.float32):
    """Return a copy of ``params`` with ZERO adapter banks grafted onto
    every targeted projection (shapes derived from each kernel leaf —
    QTensor kernels included, so grafting composes with weight
    quantization in either order). Raises if nothing matched, which
    would silently arm no projection at all."""
    found = []

    def graft(path, proj):
        d_in, d_out = _kernel_dims(proj["kernel"])
        new = dict(proj)
        new["lora_A"] = jnp.zeros((lora.num_adapters, d_in, lora.rank),
                                  dtype)
        new["lora_B"] = jnp.zeros((lora.num_adapters, lora.rank, d_out),
                                  dtype)
        found.append(path)
        return new

    out = _map_targets(params, lora.targets, graft)
    if not found:
        raise ValueError(
            f"install_lora_bank found no projection named any of "
            f"{lora.targets} holding a kernel — wrong tree or targets?")
    return out


def extract_adapter(params, index: int = 0):
    """Slice bank slot ``index`` out of every lora bank in ``params``
    into an adapter tree (the publishable single-adapter artifact:
    nested dicts holding only ``lora_A`` (in, r) / ``lora_B`` (r, out)
    leaves). This is the train→serve handoff: train a
    ``num_adapters=1`` model, extract slot 0, publish through the
    checkpoint layer, hot-load by name."""
    found = {}
    for path, proj in _walk_targets(params, LORA_TARGETS):
        if "lora_A" not in proj:
            continue
        n = proj["lora_A"].shape[0]
        if not 0 <= index < n:
            raise ValueError(
                f"adapter index {index} out of range for bank of {n} "
                f"at {'/'.join(path)}")
        found[path] = {"lora_A": proj["lora_A"][index],
                       "lora_B": proj["lora_B"][index]}
    if not found:
        raise ValueError("extract_adapter found no lora banks — was the "
                         "model built with cfg.lora set?")
    out = {}
    for path, pair in found.items():
        node = out
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = pair
    return out


def _adapter_entries(adapter, path=()):
    if not isinstance(adapter, dict):
        return
    if "lora_A" in adapter and "lora_B" in adapter:
        yield path, adapter
        return
    for key, val in adapter.items():
        yield from _adapter_entries(val, path + (key,))


def install_adapter(params, adapter, index: int):
    """Return ``params`` with ``adapter`` (an adapter tree from
    :func:`extract_adapter`, possibly checkpoint-round-tripped)
    installed into bank slot ``index`` of every bank. Structure and
    shapes are validated exhaustively — a rank or dimension mismatch
    names the offending path instead of silently serving garbage."""
    entries = {path: pair for path, pair in _adapter_entries(adapter)}
    if not entries:
        raise ValueError("adapter tree holds no lora_A/lora_B pairs")
    consumed = set()

    def put(path, proj):
        if "lora_A" not in proj:
            return proj
        n, d_in, r = proj["lora_A"].shape
        if not 0 <= index < n:
            raise ValueError(
                f"adapter index {index} out of range for bank of {n} "
                f"at {'/'.join(path)}")
        pair = entries.get(path)
        if pair is None:
            raise ValueError(
                f"adapter tree is missing an entry for bank at "
                f"{'/'.join(path)}")
        a = jnp.asarray(pair["lora_A"])
        b = jnp.asarray(pair["lora_B"])
        want_a, want_b = (d_in, r), (r, proj["lora_B"].shape[2])
        if tuple(a.shape) != want_a or tuple(b.shape) != want_b:
            raise ValueError(
                f"adapter shape mismatch at {'/'.join(path)}: got "
                f"A{tuple(a.shape)}/B{tuple(b.shape)}, bank wants "
                f"A{want_a}/B{want_b} (rank/dims must match the "
                f"engine's lora_rank and base model)")
        consumed.add(path)
        new = dict(proj)
        new["lora_A"] = proj["lora_A"].at[index].set(
            a.astype(proj["lora_A"].dtype))
        new["lora_B"] = proj["lora_B"].at[index].set(
            b.astype(proj["lora_B"].dtype))
        return new

    out = _map_targets(params, LORA_TARGETS, put)
    extra = set(entries) - consumed
    if not consumed:
        raise ValueError("install_adapter found no lora banks — arm the "
                         "engine with max_resident_adapters first")
    if extra:
        raise ValueError(
            "adapter tree has entries with no matching bank: "
            + ", ".join("/".join(p) for p in sorted(extra)))
    return out


def zero_adapter(params, index: int):
    """Return ``params`` with bank slot ``index`` zeroed everywhere —
    an unloaded slot is indistinguishable from the null adapter."""
    def zero(path, proj):
        if "lora_A" not in proj:
            return proj
        new = dict(proj)
        new["lora_A"] = proj["lora_A"].at[index].set(0.0)
        new["lora_B"] = proj["lora_B"].at[index].set(0.0)
        return new
    return _map_targets(params, LORA_TARGETS, zero)


def adapter_bytes(params) -> int:
    """Exact bytes ONE resident adapter occupies across every bank in
    ``params`` (total bank bytes / num_adapters — the registry's
    accounting unit; ``tests/test_lora.py`` holds the bank to
    capacity times this)."""
    total = 0
    slots = None
    for _path, proj in _walk_targets(params, LORA_TARGETS):
        if "lora_A" not in proj:
            continue
        n = proj["lora_A"].shape[0]
        slots = n if slots is None else slots
        total += proj["lora_A"].nbytes + proj["lora_B"].nbytes
    if slots is None:
        raise ValueError("adapter_bytes found no lora banks")
    return total // slots
