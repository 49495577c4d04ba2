"""Ring attention: sequence-parallel attention over the ``sp`` mesh axis.

Net-new beyond the reference (SURVEY.md §5: long-context "entirely absent"),
first-class here per the TPU design brief. Each of the N ``sp`` ranks holds a
sequence shard of Q/K/V; K/V shards rotate around the ring via
``lax.ppermute`` (ICI neighbor hops) for N steps while each rank accumulates
online-softmax partial results for its local queries — attention over the
full sequence with O(T/N) activation memory per chip and communication
overlapped across steps.

:func:`ring_attention` must run inside ``shard_map`` with the ``sp`` axis
bound; called with no axis bound it falls back to plain attention, so models
can enable ``attention_impl='ring'`` unconditionally.
:func:`sp_sharded_attention` is the training-path entry
(``TransformerConfig.attention_impl='ring'`` resolves to it): when the
trainer has registered a mesh with an ``sp`` axis (``set_sp_mesh``, done by
``Trainer._setup_state``), it nests a ``shard_map`` over just the attention
call inside the jitted train step — the rest of the model stays GSPMD
(positions, embeddings, loss all see global shapes) while K/V genuinely
rotate around the ring via ``ppermute``. ``SequenceParallelStrategy``
provides the matching ``dp×sp`` batch layout.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu.ops.attention import dot_product_attention
from ray_lightning_tpu.ops.flash_attention import (_BIG_NEG, _block_update,
                                                   _finalize)

SP_AXIS_NAME = "sp"

# Mesh registered by the trainer (worker-side, at step-build time) so model
# code can nest a shard_map without threading the mesh through configs —
# configs stay pure data and client-mode drivers never build a mesh.
_SP_MESH: Optional[Mesh] = None


def set_sp_mesh(mesh: Optional[Mesh]) -> None:
    global _SP_MESH
    _SP_MESH = mesh


def get_sp_mesh() -> Optional[Mesh]:
    if _SP_MESH is not None and SP_AXIS_NAME in _SP_MESH.axis_names \
            and _SP_MESH.shape[SP_AXIS_NAME] > 1:
        return _SP_MESH
    return None


def sp_sharded_attention(q: jax.Array,
                         k: jax.Array,
                         v: jax.Array,
                         *,
                         causal: bool = False,
                         mask: Optional[jax.Array] = None,
                         dropout_rate: float = 0.0,
                         dropout_rng: Optional[jax.Array] = None) -> jax.Array:
    """Ring attention over the registered sp mesh; plain attention without
    one. Global shapes (B, T, H, D) — the shard_map is internal."""
    mesh = get_sp_mesh()
    if mesh is None:
        return ring_attention(q, k, v, causal=causal, mask=mask,
                              dropout_rate=dropout_rate,
                              dropout_rng=dropout_rng)
    if mask is not None or (dropout_rate > 0.0 and dropout_rng is not None):
        # Falling back to full attention here would silently re-materialize
        # O(T) per-chip attention memory — an OOM, not a slowdown, at the
        # lengths sequence parallelism targets. Fail loudly instead.
        raise NotImplementedError(
            "attention_impl='ring' under a sequence-parallel mesh supports "
            "neither attention dropout nor custom masks (K/V shards "
            "rotate; no global score matrix exists to mask). Set "
            "dropout=0.0 / drop the mask, or use attention_impl='dot'.")
    if q.shape[1] % mesh.shape[SP_AXIS_NAME] != 0:
        return ring_attention(q, k, v, causal=causal)
    from ray_lightning_tpu.parallel.sharding import (data_axis_names,
                                                     data_axis_size)
    data_axes = data_axis_names(mesh)
    if q.shape[0] % data_axis_size(mesh) != 0:
        return ring_attention(q, k, v, causal=causal)
    # keep heads tp-sharded through the ring when a tp axis exists (ring
    # attention is independent per head) — otherwise the shard_map boundary
    # all-gathers the heads dim and every tp peer redundantly runs the ring
    head_axis = None
    if "tp" in mesh.axis_names and mesh.shape["tp"] > 1 \
            and q.shape[2] % mesh.shape["tp"] == 0:
        head_axis = "tp"
    spec = P(data_axes if data_axes else None, SP_AXIS_NAME, head_axis)
    fn = shard_map(
        lambda a, b, c: ring_attention(a, b, c, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ring_attention(q: jax.Array,
                   k: jax.Array,
                   v: jax.Array,
                   *,
                   causal: bool = False,
                   mask: Optional[jax.Array] = None,
                   dropout_rate: float = 0.0,
                   dropout_rng: Optional[jax.Array] = None,
                   axis_name: str = SP_AXIS_NAME,
                   softmax_dtype=jnp.float32) -> jax.Array:
    """Sequence-parallel attention. Local shapes (B, T_local, H, D).

    Sequence positions are assumed contiguous per rank (rank r owns
    ``[r*T_local, (r+1)*T_local)``), which is how the batch sharding lays
    out a ``P(..., 'sp', ...)`` sequence dim.
    """
    del softmax_dtype
    try:
        my_rank = jax.lax.axis_index(axis_name)
        n = axis_size(axis_name)
    except NameError:
        return dot_product_attention(
            q, k, v, causal=causal, mask=mask, dropout_rate=dropout_rate,
            dropout_rng=dropout_rng)
    if mask is not None or (dropout_rate > 0.0 and dropout_rng is not None):
        raise NotImplementedError(
            "ring_attention supports causal/full attention without "
            "attention-dropout or custom masks; use attention_impl='dot' "
            "for those.")

    B, T_local, H, D = q.shape
    scale = D ** -0.5
    total = n * T_local
    qpos = my_rank * T_local + jnp.arange(T_local)

    perm = [(r, (r + 1) % n) for r in range(n)]

    def step(carry, t):
        m, l, acc, kv = carry
        kj, vj = kv
        # at step t we hold the shard originally owned by rank (my - t) % n
        src = jax.lax.rem(my_rank - t + n, n)
        kpos = src * T_local + jnp.arange(T_local)
        m, l, acc = _block_update((m, l, acc), q, kj, vj, qpos, kpos,
                                  causal, total, scale)
        # rotate kv to the next rank; overlap with the next step's compute
        kv = jax.lax.ppermute((kj, vj), axis_name, perm)
        return (m, l, acc, kv), None

    init = (jnp.full((B, H, T_local), _BIG_NEG, jnp.float32),
            jnp.zeros((B, H, T_local), jnp.float32),
            jnp.zeros((B, T_local, H, D), jnp.float32),
            (k, v))
    (m, l, acc, _), _ = jax.lax.scan(step, init, jnp.arange(n))
    return _finalize(l, acc, q.dtype)
