"""Weight-only int8/int4 quantization: cut the decode param stream.

Decode is parameter-bandwidth-bound: every target pass streams the full
parameter set once (``serve.decode_step_roofline`` in ``PERF.md`` counts
exactly this), so at-rest weight bytes ARE per-token bytes. This module
shrinks them with **storage-only** quantization — the same contract as
the int8 KV arena (``serve/pages.py``): weights live in HBM as integer
codes + f32 scales and are dequantized back to their original dtype on
the way into every compiled program, so compute stays at ``cfg.dtype``
and the model math is unchanged up to one bounded rounding of each
weight.

Two formats, both absmax-scaled (symmetric, no zero points — the extra
code of asymmetric schemes buys little on weight distributions centered
at 0, and symmetric keeps dequant one fused multiply):

- ``"int8"`` — per-output-channel: one f32 scale per slice along the
  leaf's LAST axis (the output-features axis of every kernel in this
  model family: ``(in, out)`` Dense kernels, the ``(d_model, 3, H, Dh)``
  qkv kernel's head_dim, embedding columns). Error per weight is
  bounded by half a quantization step of its channel's absmax:
  ``|deq - w| <= amax / 254``.
- ``"int4"`` — group-wise: the last axis is cut into ``group_size``
  element groups, each with its own f32 scale (codes in [-7, 7], so
  ``|deq - w| <= group_amax / 14``); two codes pack into one int8
  (low nibble first), halving storage again. Per-channel scaling is
  too coarse at 4 bits — group-wise is the standard remedy (GPTQ/AWQ
  lineage).

Quantized leaves are :class:`QTensor` pytree nodes — codes and scales
are the children, so a quantized tree flows through ``jax.jit``
boundaries, donation and ``tree_map`` exactly like a plain one, and the
(bits, group_size, shape, dtype) metadata rides in the static aux data
(hashable: re-quantized trees hit the same compiled programs).
:func:`materialize_for_program` is the one program-entry guard every
serve/generate program calls (see ``models/generate.py``) — a no-op on
plain trees, a once-per-dispatch :func:`dequantize_params` under
``matmul_kernel="xla"``, a pass-through of the codes under
``matmul_kernel="pallas"`` (the fused dequant-matmul kernel,
``models/pallas_matmul.py``, then consumes them in place): callers
never need to know whether the params they hold are quantized.

Eligibility: floating-point leaves with ``ndim >= 2`` (matmul kernels
and embedding tables — together >99% of a transformer's bytes). Biases
and LayerNorm vectors stay at their original dtype: they are O(d) of
the stream and their precision is disproportionately load-bearing.

:func:`param_bytes` is the exact at-rest byte accounting for either
representation, computed from shapes/dtypes only (works on
``jax.eval_shape`` outputs — pure accounting callers never allocate),
and is what equal-byte comparisons and a param-bandwidth
roofline are required to cite instead of dtype arithmetic.

KV-cache quantization (:func:`kv_scales` / :func:`kv_quantize` /
:func:`kv_dequantize`) lives here too: it is the same absmax machinery
applied to cache leaves, and the serve layer (``serve/pages.py``)
re-exports it — models must not depend on serve.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["QTensor", "quantize_params", "dequantize_params",
           "is_quantized", "param_bytes", "check_weight_dtype",
           "pack_int4", "unpack_int4", "kv_scales", "kv_quantize",
           "kv_dequantize", "matmul_view", "materialize_for_program"]

#: default int4 group length along the last axis — 64 divides every
#: features dim in this model family (head_dim, d_model, d_ff, the
#: 64-padded vocab) and keeps the scale tax at one f32 per 32 packed
#: bytes (~6%)
DEFAULT_GROUP_SIZE = 64


def check_weight_dtype(weight_dtype) -> bool:
    """Normalize/validate a ``weight_dtype`` option; returns True for
    the quantized paths (mirrors ``check_kv_dtype``)."""
    if weight_dtype is None:
        return False
    if weight_dtype in ("int8", "int4"):
        return True
    raise ValueError(
        f"weight_dtype must be None, 'int8' or 'int4', got "
        f"{weight_dtype!r}")


# ------------------------------------------------------------ kv helpers
# absmax quantization shared by the KV arena (serve/pages.py re-exports
# these — the serve layer depends on models, never the reverse)

def kv_scales(values: jax.Array, reduce_axes: Tuple[int, ...]) -> jax.Array:
    """Absmax scales over ``reduce_axes`` (keepdims), guarded so an
    all-zero group dequantizes to exact zeros instead of NaN."""
    amax = jnp.max(jnp.abs(values.astype(jnp.float32)), axis=reduce_axes,
                   keepdims=True)
    return jnp.where(amax > 0, amax / 127.0, 1.0)


def kv_quantize(values: jax.Array, scales: jax.Array) -> jax.Array:
    return jnp.clip(jnp.round(values.astype(jnp.float32) / scales),
                    -127, 127).astype(jnp.int8)


def kv_dequantize(q: jax.Array, scales: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scales).astype(dtype)


# ---------------------------------------------------------- int4 packing
def pack_int4(codes: jax.Array) -> jax.Array:
    """Pack int4 codes (int8 values in [-8, 7], even-length last axis)
    two nibbles per int8 — low nibble first: ``packed[..., i]`` holds
    ``codes[..., 2i]`` (low) and ``codes[..., 2i+1]`` (high)."""
    lo = codes[..., 0::2]
    hi = codes[..., 1::2]
    return ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: sign-extend both nibbles and
    re-interleave to the doubled last axis."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)  # arithmetic
    hi = jnp.right_shift(packed, 4)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], 2 * packed.shape[-1])


# ---------------------------------------------------------------- QTensor
@jax.tree_util.register_pytree_node_class
class QTensor:
    """One quantized weight leaf: integer codes + f32 scales.

    ``bits == 8``: ``q`` has the original shape (int8 codes), ``scale``
    is per-output-channel (all-but-last axes reduced, keepdims).
    ``bits == 4``: ``q`` is nibble-packed — original shape with the last
    axis halved — and ``scale`` is ``(..., last/group_size, 1)`` over
    the grouped view. ``shape``/``dtype`` record the original leaf so
    :meth:`dequantize` is exact-shape and byte accounting stays honest.
    """

    __slots__ = ("q", "scale", "bits", "group_size", "shape", "dtype")

    def __init__(self, q, scale, bits: int, group_size: Optional[int],
                 shape: Tuple[int, ...], dtype):
        self.q = q
        self.scale = scale
        self.bits = bits
        self.group_size = group_size
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)

    def tree_flatten(self):
        return ((self.q, self.scale),
                (self.bits, self.group_size, self.shape, self.dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        bits, group_size, shape, dtype = aux
        return cls(q, scale, bits, group_size, shape, dtype)

    @property
    def nbytes(self) -> int:
        """Exact at-rest bytes (codes + scales) from shapes alone —
        valid on concrete arrays and ``ShapeDtypeStruct``\\ s alike."""
        return (int(np.prod(self.q.shape)) *
                np.dtype(self.q.dtype).itemsize
                + int(np.prod(self.scale.shape)) *
                np.dtype(self.scale.dtype).itemsize)

    def dequantize(self) -> jax.Array:
        """Codes x scales -> the original-dtype weight (one bounded
        rounding away from the value that was quantized)."""
        if self.bits == 8:
            w = self.q.astype(jnp.float32) * self.scale
            return w.astype(self.dtype)
        codes = unpack_int4(self.q).astype(jnp.float32)
        grouped = codes.reshape(*self.shape[:-1], -1, self.group_size)
        w = grouped * self.scale
        return w.reshape(self.shape).astype(self.dtype)

    def __repr__(self):
        return (f"QTensor(int{self.bits}, shape={self.shape}, "
                f"group_size={self.group_size})")


def _is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


def _quantize_leaf_int8(w) -> QTensor:
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=tuple(range(wf.ndim - 1)),
                   keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale, 8, None, w.shape, w.dtype)


def _quantize_leaf_int4(w, group_size: int) -> QTensor:
    last = w.shape[-1]
    if last % group_size:
        raise ValueError(
            f"group_size ({group_size}) must divide every quantized "
            f"leaf's last axis — got a {tuple(w.shape)} leaf "
            f"({last} % {group_size} != 0); pick a group_size that "
            "divides the model's feature dims")
    wf = jnp.asarray(w).astype(jnp.float32)
    grouped = wf.reshape(*w.shape[:-1], last // group_size, group_size)
    amax = jnp.max(jnp.abs(grouped), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 7.0, 1.0)
    codes = jnp.clip(jnp.round(grouped / scale), -7, 7).astype(jnp.int8)
    packed = pack_int4(codes.reshape(w.shape))
    return QTensor(packed, scale, 4, group_size, w.shape, w.dtype)


def quantize_params(params, weight_dtype: str = "int8",
                    group_size: Optional[int] = None):
    """Quantize every eligible leaf of ``params`` (floating, ndim >= 2)
    to :class:`QTensor` storage. ``group_size`` applies to the int4
    grouped scales (default :data:`DEFAULT_GROUP_SIZE`); int8 is
    per-output-channel and refuses an explicit group_size (nothing
    would consume it — a silently-ignored knob is a bug magnet).

    Deterministic and pure: re-quantizing the same params produces
    bit-identical codes/scales, which is what makes crash-rebuilt
    engines (``ServeSupervisor`` re-quantizes from the raw params it
    holds) token-identical to the uninterrupted run.
    """
    if not check_weight_dtype(weight_dtype):
        raise ValueError(
            "quantize_params needs weight_dtype='int8' or 'int4' "
            "(None means no quantization — don't call it)")
    if weight_dtype == "int8":
        if group_size is not None:
            raise ValueError(
                "group_size is an int4 option (int8 scales are "
                "per-output-channel); drop it or use weight_dtype='int4'")
    else:
        group_size = (DEFAULT_GROUP_SIZE if group_size is None
                      else group_size)
        if group_size < 2 or group_size % 2:
            raise ValueError(
                f"int4 group_size must be an even integer >= 2 (two "
                f"codes pack per byte inside each group), got "
                f"{group_size}")
    if is_quantized(params):
        raise ValueError(
            "params are already quantized — quantizing codes would "
            "silently destroy the weights; pass the original params")

    def q_leaf(path, leaf):
        name = str(getattr(path[-1], "key", getattr(path[-1], "name",
                                                    path[-1]))) \
            if path else ""
        # biases stay full precision even when ndim >= 2 (the
        # DenseGeneral qkv bias is (3, H, Dh)): O(d) of the stream,
        # disproportionately precision-load-bearing. LoRA adapter
        # banks (models/lora.py) stay full precision too: rank-r
        # deltas are O(r*d) of the stream and hot load/unload writes
        # per-slot slices in place — quantized codes would round every
        # co-resident adapter on each install
        if (name == "bias" or name.startswith("lora_")
                or not hasattr(leaf, "ndim") or leaf.ndim < 2
                or not jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        if weight_dtype == "int8":
            return _quantize_leaf_int8(leaf)
        return _quantize_leaf_int4(leaf, group_size)

    return jax.tree_util.tree_map_with_path(q_leaf, params)


def is_quantized(params) -> bool:
    """True when any leaf of ``params`` is a :class:`QTensor`."""
    return any(_is_qtensor(leaf) for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=_is_qtensor))


def dequantize_params(params):
    """Materialize original-dtype weights from a quantized tree; the
    identity on plain trees. Every serve/generate program calls this at
    its entry (a trace-time no-op when nothing is quantized), so the
    dequant happens ONCE per dispatch, outside the step scans — XLA
    sees int8/int4 codes stream from HBM and the dequantized tree as
    dispatch-scoped scratch."""
    if not is_quantized(params):
        return params
    return jax.tree_util.tree_map(
        lambda leaf: leaf.dequantize() if _is_qtensor(leaf) else leaf,
        params, is_leaf=_is_qtensor)


def matmul_view(qt: "QTensor", transpose: bool = False):
    """Kernel-input views of one quantized leaf for the fused
    dequant-matmul kernel (``models/pallas_matmul.py``): the stored
    codes and scales reshaped to the 2-D tile-friendly layout the
    kernel's BlockSpecs slice, WITHOUT materializing any dequantized
    weight (reshapes of the at-rest arrays, plus — int8 dense
    orientation only — an ``N``-float tile of the per-channel scale
    vector, negligible next to the codes).

    Two orientations, matching the two ways this model family consumes
    a weight leaf:

    - ``transpose=False`` (Dense/DenseGeneral kernels, stored
      ``(K, *features)``): contraction runs over axis 0, the output
      axes flatten to ``N``. Codes view ``(K, N)`` int8 (int4:
      ``(K, N/2)`` packed — nibble pairs flatten contiguously because
      ``group_size`` divides the stored last axis). Scales: int8
      per-output-channel expands to a ``(1, N)`` per-column vector
      (the stored scale repeats per leading output index — exact, no
      arithmetic); int4 group scales view as ``(K, N/group_size)``
      where flattened column ``n`` belongs to group ``n //
      group_size``.
    - ``transpose=True`` (the tied LM head: ``wte.attend`` contracts
      ``x @ E.T`` over the EMBEDDING's last axis): codes view
      ``(N, K)`` (int4: ``(N, K/2)``), int8 scales ``(1, K)`` (they
      ride the contraction axis — the kernel dequantizes element-wise
      before the dot, never folds scales into activations, so its
      operands are the dequantize-then-matmul path's exactly), int4
      scales ``(N, K/group_size)``.

    Returns ``(codes2d, scales2d, K, N)``.
    """
    shape = qt.shape
    if transpose:
        K = shape[-1]
        N = int(np.prod(shape[:-1], dtype=np.int64))
        if qt.bits == 8:
            return qt.q.reshape(N, K), qt.scale.reshape(1, K), K, N
        return (qt.q.reshape(N, K // 2),
                qt.scale.reshape(N, K // qt.group_size), K, N)
    K = shape[0]
    N = int(np.prod(shape[1:], dtype=np.int64))
    if qt.bits == 8:
        last = shape[-1]
        scales = jnp.tile(qt.scale.reshape(1, last), (1, N // last))
        return qt.q.reshape(K, N), scales, K, N
    return (qt.q.reshape(K, N // 2),
            qt.scale.reshape(K, N // qt.group_size), K, N)


def materialize_for_program(params, cfg=None):
    """The ONE shared program-entry guard every serve/generate program
    calls on its params (the single seam the entry points cannot drift
    from): a trace-time no-op on plain trees; on weight-quantized trees
    it is **kernel-aware**:

    - ``cfg.matmul_kernel == "xla"`` (or no cfg): materialize the
      original-dtype weights once per dispatch, outside the step scans
      (:func:`dequantize_params` — the PR 11 behavior: codes stream
      from HBM, the dequantized tree is dispatch-scoped scratch).
    - ``cfg.matmul_kernel == "pallas"``: the codes/scales flow through
      the jit boundary AS the param leaves (``QTensor`` is a
      registered pytree) and every consuming layer dispatches the
      fused dequant-matmul kernel — no dense dequantized weight arena
      exists anywhere, so the per-dispatch param byte stream is the
      codes+scales floor :func:`param_bytes` accounts.

    ``cfg`` is the consuming model's ``TransformerConfig`` (callers
    pass ``model.cfg``); model families without a ``matmul_kernel``
    field always materialize.
    """
    if not is_quantized(params):
        return params
    if cfg is not None and getattr(cfg, "matmul_kernel", "xla") == "pallas":
        if getattr(cfg, "scan_layers", False):
            raise ValueError(
                "matmul_kernel='pallas' cannot run quantized weights "
                "through scanned layers: nn.scan slices every param "
                "leaf along the layer axis, and a QTensor's broadcast-"
                "shaped scales have no such axis. Serving wants "
                "scan_layers=False anyway (docs/performance.md decode "
                "section) — unstack_scan_params the weights first")
        return params
    return dequantize_params(params)


def param_bytes(params) -> int:
    """Exact at-rest parameter bytes for a plain OR quantized tree,
    from shapes/dtypes only (no device reads — pass ``jax.eval_shape``
    structs for configs that were never materialized). This is the
    number a param-bandwidth roofline and equal-byte
    comparisons must cite: dtype arithmetic (``2 * n_params``) goes
    stale the moment storage and compute dtypes diverge."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=_is_qtensor):
        if _is_qtensor(leaf):
            total += leaf.nbytes
        else:
            total += (int(np.prod(np.asarray(leaf.shape, np.int64)))
                      * np.dtype(leaf.dtype).itemsize
                      if hasattr(leaf, "shape") else 0)
    return int(total)
