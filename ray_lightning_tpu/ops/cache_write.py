"""Writes into a decode cache that touch the bytes they write.

A serve program holds its cache as donated buffers of the whole slot
pool: ``(B, L, H, D)`` K/V rows and rings, ``(B, N, D)`` recurrent
state, ``(n_layers, B, ...)`` when the layers are scanned. Both of the
pool's writers go through here — the decode step's block at each row's
own position (:func:`write_rows`) and the prefill's whole rows at their
slots (:func:`inject_rows`) — and neither hands XLA an op whose result
is a leaf rebuilt (PERF.md section 6, PR 29, has the compiled programs
and their times):

- a prefill row is one contiguous stretch of its leaf, so the inject is
  a loop over rows around a ``dynamic_update_slice`` at a scalar slot,
  which XLA runs in place on the donated buffer. The whole-pool
  ``where(keep, pool, take(rows, slot_map))`` it replaces was compiled
  into fusions that read and wrote every leaf whole;
- one position of one row is not: the TPU lays a ``(B, L, H, D)`` leaf
  out with ``L`` minor-most (``H x D`` is too small to tile), so a
  position is a column through ``H x D / 16`` tiles. The ``vmap`` of
  ``dynamic_update_slice`` this replaces (one batched scatter to jax)
  was compiled into a loop of ``B`` trips of three small ops a leaf,
  ~5 us a trip. :func:`write_rows` is one Pallas call a layer instead:
  grid over rows, the row's starts scalar-prefetched, each trip reads
  the 128-position block that holds the row's position, replaces one
  lane and writes the block back to the buffer it came from
  (``input_output_aliases``). It sees the leaf as ``(B, H, D, L)``,
  which is the same bytes, so the transposes around it are bitcasts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128     # positions a grid step reads and writes back


def _write_position_kernel(n, start_ref, *refs):
    """``refs``: ``n`` new blocks ``(1, D, H)``, ``n`` cache blocks
    ``(1, H, D, tile)``, the ``n`` outputs they alias."""
    news, caches, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    row = pl.program_id(0)
    for new, cache, out in zip(news, caches, outs):
        _, H, D, tile = out.shape
        here = lax.broadcasted_iota(jnp.int32, (D, tile), 1) \
            == start_ref[row] % tile
        for h in range(H):      # head h of the new position is one lane
            col = jnp.broadcast_to(new[0, :, h:h + 1], (D, tile))
            out[0, h] = jnp.where(here, col, cache[0, h])


def _write_position(views, news, start):
    """One position a row into every ``(B, H, D, L)`` view: ``news``
    are ``(B, D, H)``, ``start (B,)`` lies inside ``L``."""
    n, B = len(views), start.shape[0]
    new_specs = [pl.BlockSpec((1,) + x.shape[1:], lambda b, s: (b, 0, 0))
                 for x in news]
    view_specs = []
    for v in views:
        L = v.shape[-1]
        tile = LANES if L % LANES == 0 else L
        view_specs.append(pl.BlockSpec(
            (1,) + v.shape[1:-1] + (tile,),
            lambda b, s, tile=tile: (b, 0, 0, s[b] // tile)))
    return pl.pallas_call(
        functools.partial(_write_position_kernel, n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=new_specs + view_specs, out_specs=view_specs),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in views],
        input_output_aliases={1 + n + i: i for i in range(n)},
        interpret=jax.default_backend() != "tpu",
        name="cache_write_rows")(start, *news, *views)


@jax.jit
def write_rows(caches, blocks, start):
    """Row ``b``'s block goes to ``cache[b, start[b]:start[b] + T]``.

    ``caches`` is a leaf ``(B, L, H, D)`` or a tree of them (a layer's K
    and V share one call), ``blocks`` the same tree of ``(B, T, H, D)``
    with ``T >= 1`` (a position a call), ``start`` ``(B,)``. A start
    past ``L - T`` clamps to it, as ``dynamic_update_slice`` does: a
    parked row re-writes its frozen last position. Jitted so that the
    layers of an unscanned model share one lowering of the kernel.
    """
    leaves, treedef = jax.tree_util.tree_flatten(caches)
    blocks = treedef.flatten_up_to(blocks)
    T, L = blocks[0].shape[1], leaves[0].shape[1]
    start = jnp.clip(start.astype(jnp.int32), 0, L - T)
    views = [jnp.moveaxis(leaf, 1, -1) for leaf in leaves]
    for t in range(T):
        news = [jnp.swapaxes(block[:, t], 1, 2).astype(leaf.dtype)
                for block, leaf in zip(blocks, leaves)]
        views = _write_position(views, news, start + t)
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.moveaxis(view, -1, 1) for view in views])


def inject_rows(pool, rows, layout, slots, valid):
    """Row ``r`` of every per-slot leaf of ``rows`` goes whole into slot
    ``slots[r]`` of ``pool``'s leaf, along the axis the leaf declares
    (``layout``: the tree of ``generate.CacheLeaf``); leaves no slot
    owns keep ``pool``'s. An invalid row writes back the slot row it
    reads, whatever its ``slots`` entry says, so one program covers
    every fill level.
    """
    slots = slots.astype(jnp.int32)

    def inject(leaf, new, decl):
        if not decl.per_slot:
            return leaf
        axis = decl.slot_axis

        def put(r, leaf):
            row = jnp.where(
                valid[r], lax.dynamic_slice_in_dim(new, r, 1, axis=axis),
                lax.dynamic_slice_in_dim(leaf, slots[r], 1, axis=axis))
            return lax.dynamic_update_slice_in_dim(leaf, row, slots[r],
                                                   axis=axis)

        return lax.fori_loop(0, slots.shape[0], put, leaf)

    return jax.tree_util.tree_map(inject, pool, rows, layout)


def take_rows(pool, layout, slots):
    """The slots' rows of every per-slot leaf of ``pool``, as a cache of
    ``len(slots)`` rows (a copy); leaves no slot owns are handed on."""
    slots = slots.astype(jnp.int32)

    def take(leaf, decl):
        if not decl.per_slot:
            return leaf
        # a slice a row, each one contiguous stretch of its leaf: a
        # gather (jnp.take) of whole K/V rows ran at a thirteenth of
        # the memory's speed on a v5e (PERF.md section 6, PR 32)
        return jnp.concatenate(
            [lax.dynamic_slice_in_dim(leaf, slots[r], 1, decl.slot_axis)
             for r in range(slots.shape[0])], axis=decl.slot_axis)

    return jax.tree_util.tree_map(take, pool, layout)


def inject_blocks(pool, rows, layout, slots, valid, start, width):
    """:func:`inject_rows` for a *piece* of a prompt: a leaf that holds
    positions (``kind == "global"``) takes only row ``r``'s positions
    ``start[r] .. start[r] + width - 1`` — the bytes the piece wrote —
    every other per-slot leaf (a recurrent state) its row whole. In
    place on the donated pool, a loop trip a row, an invalid row writing
    back what it reads."""
    slots = slots.astype(jnp.int32)
    start = start.astype(jnp.int32)

    def inject(leaf, new, decl):
        if not decl.per_slot:
            return leaf
        piece = decl.kind == "global"
        sizes = list(leaf.shape)
        sizes[decl.slot_axis] = 1
        if piece:
            sizes[decl.seq_axis] = width

        def put(r, leaf):
            at = [0] * leaf.ndim
            src = list(at)
            at[decl.slot_axis], src[decl.slot_axis] = slots[r], r
            if piece:
                at[decl.seq_axis] = src[decl.seq_axis] = start[r]
            block = jnp.where(valid[r], lax.dynamic_slice(new, src, sizes),
                              lax.dynamic_slice(leaf, at, sizes))
            return lax.dynamic_update_slice(leaf, block, at)

        return lax.fori_loop(0, slots.shape[0], put, leaf)

    return jax.tree_util.tree_map(inject, pool, rows, layout)
