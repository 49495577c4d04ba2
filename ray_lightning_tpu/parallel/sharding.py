"""Sharding rules: pytree → NamedSharding trees for each parallelism flavor.

This is the TPU-native seat of the reference's gradient-sync machinery: where
DDP wraps the module and all-reduces grads (NCCL inside
``DistributedDataParallel``, bound at ``ray_lightning/ray_ddp.py:202-206``)
and FairScale shards optimizer state (via PTL's ``DDPSpawnShardedStrategy``,
``ray_lightning/ray_ddp_sharded.py:12-13``), we instead *annotate* where each
array lives on the mesh and let XLA insert psum / reduce-scatter /
all-gather. The strategy classes pick which rule applies to params vs
optimizer state vs batch.
"""
from __future__ import annotations

import functools
import math
import threading
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (every device holds the whole array)."""
    return NamedSharding(mesh, P())


def data_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes that carry batch-dim sharding (single source of
    truth for batch_sharding / pipelined_stack / sp_sharded_attention)."""
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)


# The mesh of the step being traced: a strategy runs its step's Python body
# under ``under_mesh`` so model code can pin activations to the mesh's data
# axes without threading the mesh through configs (the sp / pp meshes of
# ring_attention / pipeline are handed over the same way, but this one
# lives only for the length of a trace).
_AMBIENT = threading.local()


def under_mesh(mesh: Mesh, fn: Callable) -> Callable:
    """``fn`` with ``mesh`` ambient — the one :func:`constrain_batch` sees —
    while its body runs, which under ``jax.jit`` is while it is traced."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        prev = getattr(_AMBIENT, "mesh", None)
        _AMBIENT.mesh = mesh
        try:
            return fn(*args, **kwargs)
        finally:
            _AMBIENT.mesh = prev
    return traced


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the step being traced (:func:`under_mesh`), else None."""
    return getattr(_AMBIENT, "mesh", None)


def data_axis_size(mesh: Mesh) -> int:
    """How many ways the mesh's data axes split a batch."""
    return math.prod(mesh.shape[a] for a in data_axis_names(mesh))


def constrain_batch(x: jax.Array) -> jax.Array:
    """Keep dim 0 of an activation split over the ambient mesh's data axes.

    Every other dim is ``PartitionSpec.UNCONSTRAINED`` (not ``None``), so
    ``tp`` / ``sp`` / ``ep`` cuts of those dims still propagate. This is
    what makes a parameter's ``fsdp`` cut *storage only*: with the batch
    dim owning the data axes through every block, the partitioner cannot
    compute on a weight's stored shards (a contraction over a cut dim, its
    partial results all-reduced at the global batch's size) and has to
    all-gather the weight where the layer uses it and reduce the weight's
    gradient back onto the stored cut.

    The identity — the same object back, nothing lowered — when there is no
    ambient mesh (the serve engine's programs, a bare ``model.apply``), when
    the data axes' sizes multiply to 1, when dim 0 does not divide by them,
    and inside a manual (``shard_map``) region, where the arrays are
    already per-device blocks.
    """
    mesh = ambient_mesh()
    if mesh is None or not getattr(x, "ndim", 0):
        return x
    axes = data_axis_names(mesh)
    size = data_axis_size(mesh)
    if size == 1 or x.shape[0] % size \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return x
    spec = P(axes, *[P.UNCONSTRAINED] * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def compose_rules(*rules):
    """Combine ``(path, leaf) -> PartitionSpec`` rules: the first rule
    returning a non-trivial spec wins, so e.g. MoE expert banks take the
    ``ep`` layout while the attention blocks around them take the
    Megatron ``tp`` layout::

        MeshStrategy(axes={"dp": 2, "ep": 2, "tp": 2},
                     param_rule=compose_rules(expert_parallel_rule,
                                              tensor_parallel_rule))
    """
    def rule(path, leaf):
        for r in rules:
            spec = r(path, leaf)
            if any(s is not None for s in spec):
                return spec
        return P()
    return rule


def leading_dim_rule(keyword: str, axis: str):
    """Build a ``(path, leaf) -> PartitionSpec`` rule sharding the leading
    dim of every param whose path contains ``keyword`` along ``axis`` —
    the shared shape of expert-parallel ('experts' → 'ep') and
    pipeline-parallel ('blocks' → 'pp') layouts."""
    def rule(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "name", p)))
                 for p in path]
        if any(keyword in n for n in names):
            spec = [None] * getattr(leaf, "ndim", 0)
            if spec:
                spec[0] = axis
            return P(*spec)
        return P()
    return rule


def batch_sharding(mesh: Mesh,
                   data_axes: Optional[Sequence[str]] = None) -> NamedSharding:
    """Shard the leading (batch) dim across the data axes of the mesh.

    The analog of the reference's ``DistributedSampler`` kwargs
    (``ray_ddp.py:325-334``): instead of N dataloaders each reading 1/N of
    the data, one global batch is laid out with its batch dim split across
    ``dp``×``fsdp`` (and any other data-like axes present).
    """
    if data_axes is None:
        data_axes = data_axis_names(mesh)
    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    if not axes:
        return replicated(mesh)
    return NamedSharding(mesh, P(axes))


def largest_divisible_dim(shape: Tuple[int, ...], size: int) -> Optional[int]:
    """Pick the best dim to shard ``size``-ways: largest dim divisible by it.

    Used for ZeRO-1 / FSDP parameter+optimizer-state sharding where no
    per-layer logical rule exists (flat sharding, matching FairScale's
    greedy parameter bucketing semantics but resolved per-array). A
    storage choice only: it may land on a contraction dim (``qkv/kernel``'s
    ``d``, a bias's head dim) because nothing computes on the shards.
    """
    best, best_size = None, 0
    for i, d in enumerate(shape):
        if d % size == 0 and d >= size and d > best_size:
            best, best_size = i, d
    return best


def shard_leaf_spec(leaf: Any, axis_name: str, size: int) -> P:
    """PartitionSpec sharding one array along its best dim, else replicated."""
    shape = getattr(leaf, "shape", ())
    if size <= 1 or not shape:
        return P()
    dim = largest_divisible_dim(tuple(shape), size)
    if dim is None:
        return P()
    spec = [None] * len(shape)
    spec[dim] = axis_name
    return P(*spec)


def shard_pytree_along_axis(tree: Any, mesh: Mesh, axis_name: str) -> Any:
    """NamedSharding tree sharding every leaf along ``axis_name`` where possible.

    This is the FSDP/ZeRO rule: each array is split along its largest
    divisible dim over the axis; arrays too small to split stay replicated
    (their memory is negligible by construction). The dim chosen says where
    a leaf is *stored* (checkpoints and ``params_sharding`` depend on it),
    not how it is computed on: :func:`constrain_batch` keeps activations
    split over the data axes, so a step gathers the leaf whole where it is
    used, whichever dim was cut.
    """
    size = mesh.shape[axis_name]

    def _leaf(leaf):
        return NamedSharding(mesh, shard_leaf_spec(leaf, axis_name, size))

    return jax.tree_util.tree_map(_leaf, tree)


def replicated_pytree(tree: Any, mesh: Mesh) -> Any:
    shard = replicated(mesh)
    return jax.tree_util.tree_map(lambda _: shard, tree)


def apply_rule(tree: Any, mesh: Mesh,
               rule: Callable[[Tuple[Any, ...], Any], P],
               fallback_replicate: bool = False) -> Any:
    """Map a ``(path, leaf) -> PartitionSpec`` rule over a pytree.

    Used by tensor-parallel strategies where sharding depends on the
    parameter's role (e.g. attention qkv vs mlp down-projection).

    ``fallback_replicate=True`` replicates any leaf whose shape cannot
    satisfy the rule's spec instead of letting pjit reject it. This is
    for DERIVED trees (optimizer state): name-matching rules see e.g.
    adafactor's factored ``v_row['...']['experts_down']`` — a ``(1,)``
    placeholder that matches the expert param rule by path but not by
    shape. Parameters themselves keep the loud failure (a rule that
    cannot shard a param is a bug, not a fallback case).
    """
    def _spec_fits(spec: P, leaf) -> bool:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            return True
        if len(spec) > len(shape):
            return False
        for dim, names in zip(shape, spec):
            if names is None:
                continue
            size = 1
            for n in (names if isinstance(names, tuple) else (names,)):
                size *= mesh.shape[n]
            if dim % size:
                return False
        return True

    def _leaf(path, leaf):
        spec = rule(path, leaf)
        if fallback_replicate and not _spec_fits(spec, leaf):
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(_leaf, tree)


def put_host_local_batch(local_batch: Any, sharding: Any) -> Any:
    """Assemble a global array from per-process host-LOCAL batch shards.

    The memory-lean alternative to :func:`put_global_batch` for multi-host
    jobs: each process loads only its own slice of the global batch (use
    ``strategy.distributed_sampler_kwargs`` to shard the loader — rank r
    of n replicas loads samples ``r, r+n, …`` or the r-th contiguous
    block, matching the batch sharding's dp layout), and
    ``jax.make_array_from_process_local_data`` stitches the global array
    without any host ever materializing the full batch. Single-process:
    plain ``device_put``. ``sharding`` may be one sharding or a pytree.
    """
    if jax.process_count() == 1:
        return jax.device_put(local_batch, sharding)
    is_tree = not isinstance(sharding, jax.sharding.Sharding)

    def _leaf(x, s):
        return jax.make_array_from_process_local_data(s, np.asarray(x))

    if is_tree:
        return jax.tree_util.tree_map(_leaf, local_batch, sharding)
    return jax.tree_util.tree_map(lambda x: _leaf(x, sharding),
                                  local_batch)


def put_global_batch(batch: Any, sharding: Any) -> Any:
    """Place a host-global batch onto a (possibly multi-process) mesh.

    Single-process: plain ``device_put``. Multi-controller SPMD: every
    process holds the same host-global batch (loaders are seeded
    identically), and ``jax.make_array_from_callback`` transfers **only the
    shards this process's devices own** — the per-host batch feeding the
    reference gets from ``DistributedSampler`` (``ray_ddp.py:325-334``),
    without N loaders needing rank-aware slicing. ``sharding`` may be a
    single sharding (applied to every leaf) or a matching pytree.
    """
    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    is_tree = not isinstance(sharding, jax.sharding.Sharding)

    def _leaf(x, s):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, s,
                                            lambda idx: x[idx])

    if is_tree:
        return jax.tree_util.tree_map(_leaf, batch, sharding)
    return jax.tree_util.tree_map(lambda x: _leaf(x, sharding), batch)
