"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pp`` axis.

Net-new beyond the reference (SURVEY.md §2.3: PP absent upstream). The
``pp`` mesh axis splits the *layer* dimension: stage ``s`` owns layers
``[s·L/S, (s+1)·L/S)``. :func:`pipeline_apply` runs the classic
collective-permute schedule inside ``shard_map``:

- the loop runs ``M + S - 1`` ticks for ``M`` microbatches over ``S``
  stages; at each tick every stage applies its layer block to the
  activation it holds, then the activations rotate one hop along the ring
  (``lax.ppermute``) — stage 0 injects microbatch ``t``, the last stage
  retires microbatch ``t - (S-1)``;
- the schedule is a ``lax.scan``, so **jax autodiff derives the pipelined
  backward automatically** (the transpose of ppermute is the reverse hop;
  the backward bubble mirrors the forward one);
- warm-up/drain ticks compute on garbage activations (static shapes — the
  TPU way); their outputs are masked out of the result and, because the
  output selects only retired ticks, autodiff sends exactly zero cotangent
  back through them.

The bubble fraction is ``(S-1)/(M+S-1)`` — pick ``M ≫ S``. Communication
is one activation-sized neighbor hop per tick, riding ICI.

This is the building block: it is pure jax (params in, activations out), so
it slots under any step built with ``shard_map`` — see
``tests/test_pipeline.py`` for a full pipelined training step (loss +
grads + psum across dp×pp) driven this way.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P


PP_AXIS_NAME = "pp"

# Mesh registered by the trainer (worker-side, at step-build time) — the
# same pattern as ring attention's sp mesh: model code nests a shard_map
# without threading the mesh through configs, so configs stay pure data
# and client-mode drivers never build a mesh.
_PP_MESH: Optional[Mesh] = None


def set_pp_mesh(mesh: Optional[Mesh]) -> None:
    global _PP_MESH
    _PP_MESH = mesh


def get_pp_mesh() -> Optional[Mesh]:
    if _PP_MESH is not None and PP_AXIS_NAME in _PP_MESH.axis_names \
            and _PP_MESH.shape[PP_AXIS_NAME] > 1:
        return _PP_MESH
    return None


def _pipeline_parallel_rule():
    from ray_lightning_tpu.parallel.sharding import leading_dim_rule
    return leading_dim_rule("blocks", PP_AXIS_NAME)


def pipeline_parallel_rule(path, leaf):
    """``MeshStrategy(param_rule=...)`` rule: stacked layer params (leading
    layers dim, path containing ``blocks``) shard over ``pp``; embeddings /
    head / norms replicate. Pairs with :func:`pipelined_stack`."""
    return _pipeline_parallel_rule()(path, leaf)


def pipelined_stack(layer_fn: Callable[[Any, jax.Array], jax.Array],
                    stacked_params: Any,
                    x: jax.Array,
                    *,
                    n_microbatches: Optional[int] = None) -> jax.Array:
    """Apply a stacked layer sequence, pipelined over a registered pp mesh.

    ``stacked_params`` leaves have a leading layers dim; ``layer_fn(p, x)``
    applies ONE layer. Without a registered pp mesh (or a too-small batch)
    this is a plain serial ``lax.scan`` — models can call it
    unconditionally, exactly like ring attention's sp entry point. With a
    mesh, layers shard over ``pp`` (use :func:`pipeline_parallel_rule` so
    the params already live there), the batch dim splits over the mesh's
    data axes, and each data group runs the GPipe schedule.
    """
    def serial(params, x):
        def body(x, p):
            return layer_fn(p, x), None
        out, _ = jax.lax.scan(body, x, params)
        return out

    mesh = get_pp_mesh()
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if mesh is None:
        return serial(stacked_params, x)
    S = mesh.shape[PP_AXIS_NAME]
    if n_layers % S != 0:
        return serial(stacked_params, x)
    from ray_lightning_tpu.parallel.sharding import data_axis_names
    data_axes = data_axis_names(mesh)
    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]
    B = x.shape[0]
    if n_microbatches is not None:
        M = n_microbatches
        if B % (data_size * M) != 0:
            # an explicit request that cannot be honored is a
            # misconfiguration — surface it, never silently reschedule
            raise ValueError(
                f"batch size {B} is not divisible by data_size "
                f"{data_size} x n_microbatches {M}; adjust the batch or "
                "the microbatch count")
    else:
        M = 2 * S
        if B % (data_size * M) != 0:
            M = max(1, B // data_size)
            if B % (data_size * M) != 0:
                return serial(stacked_params, x)

    def local(params, xb):
        mb = split_microbatches(xb, M)
        out = pipeline_apply(lambda p, z: serial(p, z), params, mb)
        return out.reshape(xb.shape)

    spec_x = P(data_axes if data_axes else None)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(PP_AXIS_NAME), spec_x), out_specs=spec_x,
        check_vma=False)
    return fn(stacked_params, x)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   microbatches: jax.Array,
                   *,
                   axis_name: str = PP_AXIS_NAME) -> jax.Array:
    """Run ``microbatches`` through an ``S``-stage pipeline.

    Must be called inside ``shard_map`` with ``axis_name`` bound.

    Args:
        stage_fn: ``(stage_params, x) -> y`` applying THIS stage's layer
            block; ``y`` must have ``x``'s shape (residual-style stacks).
        stage_params: this stage's parameters (already pp-sharded by the
            caller's in_specs).
        microbatches: ``(M, mb, ...)`` — the full microbatched input,
            replicated across stages (only stage 0 reads it).

    Returns:
        ``(M, mb, ...)`` outputs, replicated across the pp group (a single
        psum selects the last stage's retired activations).
    """
    stage = jax.lax.axis_index(axis_name)
    n_stages = axis_size(axis_name)
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    total_ticks = M + n_stages - 1

    # The scan carry circulates stage outputs, so the buffers (and the
    # injected input) must share one dtype. Promote the input to the
    # params' result type up front (bf16 batches through f32 params run at
    # f32 — the bf16-mixed convention), then confirm via eval_shape.
    leaves = jax.tree_util.tree_leaves(stage_params)
    compute_dtype = jnp.result_type(
        microbatches.dtype, *[l.dtype for l in leaves]) if leaves \
        else microbatches.dtype
    microbatches = microbatches.astype(compute_dtype)
    out_aval = jax.eval_shape(
        stage_fn, stage_params,
        jax.ShapeDtypeStruct(mb_shape, compute_dtype))
    if out_aval.shape != mb_shape:
        raise ValueError(
            f"stage_fn must preserve the activation shape (pipeline "
            f"stages chain): got {out_aval.shape} from {mb_shape}")
    out_dtype = out_aval.dtype
    microbatches = microbatches.astype(out_dtype)

    # ring: stage s sends to s+1; the wrap-around link carries no live data
    perm = [(s, (s + 1) % n_stages) for s in range(n_stages)]

    def tick(carry, t):
        recv, outputs = carry
        # stage 0 injects microbatch t (clamped during drain ticks; the
        # extra compute is masked out of `outputs` below)
        inject = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, M - 1), keepdims=False)
        x = jnp.where(stage == 0, inject, recv)
        y = stage_fn(stage_params, x).astype(out_dtype)
        # last stage retires microbatch t-(S-1) at ticks t >= S-1
        out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
        live = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(live, y,
                      jax.lax.dynamic_index_in_dim(outputs, out_idx,
                                                   keepdims=False)),
            out_idx, axis=0)
        recv = jax.lax.ppermute(y, axis_name, perm)
        return (recv, outputs), None

    init = (jnp.zeros(mb_shape, out_dtype),
            jnp.zeros((M,) + mb_shape, out_dtype))
    (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(total_ticks))
    # only the last stage holds real outputs; one psum replicates them
    outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
    return jax.lax.psum(outputs, axis_name)


def split_microbatches(batch: jax.Array, n_microbatches: int) -> jax.Array:
    """``(B, ...) -> (M, B/M, ...)`` leading-dim microbatch split."""
    B = batch.shape[0]
    if B % n_microbatches != 0:
        raise ValueError(
            f"batch size {B} not divisible by {n_microbatches} microbatches")
    return batch.reshape((n_microbatches, B // n_microbatches)
                         + batch.shape[1:])
