"""Fully-sharded data parallelism (params + grads + optimizer state).

Net-new beyond the reference's ZeRO-1 (`SURVEY.md` §2.3 marks FSDP as the
TPU equivalent of FairScale's sharded training, `SURVEY.md` §2.2 row
FairScale): every parameter and optimizer-state array is *stored* sharded
along its largest divisible dim over the ``fsdp`` axis, and the batch is
split over the same axis.

The stored dim is not a computation dim. The step is traced with the
strategy's mesh ambient (``shardlib.under_mesh`` in
``Strategy.make_train_step`` / ``make_eval_step``), and the model's
``shardlib.constrain_batch`` seats (``models/transformer.py``: the residual
stream at a block's entry, after its attention residual and at its exit;
the embedding sum; the hidden state before the head) keep every
activation's batch dim on the mesh's data axes. That leaves XLA's SPMD
partitioner one schedule, the FSDP one, derived from annotations instead of
hand-written hooks: all-gather a layer's weights where the layer uses them
(inside the layer scan's body, in the forward and again in the remat'd
backward), reduce the weight gradients back onto the stored cut. Without
the seats the partitioner is free to compute on the stored shards instead —
for a head count that does not divide the mesh it contracted attention over
a quarter of each head and all-reduced ``[B, H, T, T]`` scores in every
layer. ``obs/census.py::collective_census`` reads the schedule off a
compiled step; ``tests/test_fsdp_schedule.py`` holds it.
"""
from __future__ import annotations

from typing import Any

from ray_lightning_tpu.parallel import sharding as shardlib
from ray_lightning_tpu.parallel.mesh import FSDP_AXIS, MeshSpec
from ray_lightning_tpu.strategies.base import Strategy


class FSDPStrategy(Strategy):
    strategy_name = "fsdp_tpu"

    def mesh_spec(self) -> MeshSpec:
        return MeshSpec({FSDP_AXIS: self.num_workers})

    def params_sharding(self, abstract_params: Any) -> Any:
        return shardlib.shard_pytree_along_axis(
            abstract_params, self.mesh, FSDP_AXIS)

    def opt_state_sharding(self, abstract_opt_state: Any) -> Any:
        return shardlib.shard_pytree_along_axis(
            abstract_opt_state, self.mesh, FSDP_AXIS)
