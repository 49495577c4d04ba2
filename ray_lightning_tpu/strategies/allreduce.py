"""Explicit-allreduce data parallelism (HorovodRayStrategy parity).

The reference's ``HorovodRayStrategy`` (``ray_lightning/ray_horovod.py:32-
183``) is DP where gradient sync is *explicit* — Horovod's
``DistributedOptimizer`` all-reduces on ``step()`` rather than DDP hooking
backward. The TPU-native equivalent keeps that per-rank programming model:
the step runs under ``jax.shard_map`` so each mesh slot computes grads on
its local batch shard, then explicitly ``lax.pmean``-s them over ``dp``
before the optimizer update — the direct analog of ``hvd.allreduce``
lowered to an XLA collective on ICI.

Numerically identical to :class:`RayStrategy`; exists for (a) API parity,
(b) per-rank control (rank-dependent RNG, custom fused collectives), and
(c) as the substrate strategies with hand-written pallas collectives hook
into.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_lightning_tpu.parallel.mesh import DP_AXIS, MeshSpec
from ray_lightning_tpu.strategies.base import Strategy


class HorovodRayStrategy(Strategy):
    """DP with explicit per-rank gradient allreduce via shard_map."""
    strategy_name = "horovod_ray"

    def mesh_spec(self) -> MeshSpec:
        return MeshSpec({DP_AXIS: self.num_workers})

    def make_train_step(self, loss_fn: Callable, tx: optax.GradientTransformation,
                        state_shardings: Any, batch_sharding: NamedSharding,
                        donate: bool = True,
                        log_grad_norm: bool = False,
                        guard_nonfinite: bool = False) -> Callable:
        from ray_lightning_tpu.reliability.guard import tree_all_finite
        mesh = self.mesh

        def per_rank_step(state, batch):
            # Per-rank RNG: fold in the dp rank so e.g. dropout masks differ
            # across ranks — matching the per-process seeds of the
            # reference's Horovod workers.
            rank = jax.lax.axis_index(DP_AXIS)
            rng = jax.random.fold_in(
                jax.random.fold_in(state.rng, state.step), rank)
            grad_fn = jax.value_and_grad(
                jax.named_scope("loss")(loss_fn), has_aux=True)
            (loss, (logs, new_ms)), grads = grad_fn(
                state.params, state.model_state, batch, rng)
            # The explicit allreduce — hvd.allreduce ≙ lax.pmean over ICI.
            with jax.named_scope("grad_exchange"):
                grads = jax.lax.pmean(grads, DP_AXIS)
                loss = jax.lax.pmean(loss, DP_AXIS)
            if log_grad_norm:  # post-allreduce: the effective update norm
                with jax.named_scope("grad_norm"):
                    logs = {**logs,
                            "grad_norm": optax.global_norm(grads)}
            logs = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, DP_AXIS)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                logs)
            new_ms = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, DP_AXIS)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                new_ms)
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(grads, state.opt_state,
                                             state.params)
                new_params = optax.apply_updates(state.params, updates)
            if guard_nonfinite:
                # checked on the post-allreduce grads, so every rank
                # reaches the same keep/skip verdict with no extra
                # collective (the pmean already synchronized them)
                ok = jnp.isfinite(loss) & tree_all_finite(grads)
                keep = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
                    lambda n, o: jnp.where(ok, n, o), new, old)
                new_params = keep(new_params, state.params)
                new_opt = keep(new_opt, state.opt_state)
                new_ms = keep(new_ms, state.model_state)
                logs = {**logs, "nonfinite": (~ok).astype(jnp.float32)}
            new_state = state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt,
                model_state=new_ms)
            return new_state, {"loss": loss, **logs}

        batch_spec = batch_sharding.spec
        mapped = shard_map(
            per_rank_step,
            mesh=mesh,
            in_specs=(P(), batch_spec),
            out_specs=(P(), P()),
            check_vma=False)
        # CPU gating as in Strategy.make_train_step: donation + zero-copy
        # host buffers alias on the CPU backend (use-after-free garbage)
        donate = donate and jax.default_backend() != "cpu"
        return jax.jit(mapped, donate_argnums=(0,) if donate else ())

    def join(self) -> None:
        """Barrier parity with ``hvd.join()`` (``ray_horovod.py:143-151``).

        Under SPMD every rank runs the same program, so stragglers cannot
        diverge in step count; blocking on outstanding work is the honest
        equivalent.
        """
        jax.effects_barrier()


AllReduceStrategy = HorovodRayStrategy
