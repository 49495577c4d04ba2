"""Strategy base class: resource spec + mesh/sharding policy + rank model.

Parity seat of ``ray_lightning/ray_ddp.py:30-136`` (worker-resource config,
launcher installation, rank bookkeeping) re-founded on the mesh model: a
strategy owns

1. a **resource spec** (``num_workers`` etc. — constructor parity with
   ``ray_ddp.py:76-126``, including the ``resources_per_worker`` CPU/TPU
   override semantics),
2. a **mesh policy** (`mesh_spec()`): which named axes exist and their sizes,
3. **sharding rules**: where params / optimizer state / batch live on the
   mesh — this is the part that replaces DDP-wrap vs FairScale-wrap vs
   Horovod-optimizer as the differences between strategies, and
4. the **rank model** (world_size / global_rank / local_rank / node_rank
   properties, ``ray_ddp.py:215-267`` parity) for code that thinks in ranks.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.launchers.local import LocalLauncher
from ray_lightning_tpu.parallel import sharding as shardlib
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh


class Strategy:
    strategy_name = "base_tpu"

    def __init__(self,
                 num_workers: int = 1,
                 num_cpus_per_worker: int = 1,
                 use_gpu: bool = False,
                 use_tpu: Optional[bool] = None,
                 init_hook: Optional[Callable] = None,
                 resources_per_worker: Optional[Dict] = None,
                 worker_runtime_env: Optional[Dict] = None,
                 use_ray: Optional[bool] = None,
                 allow_colocated_workers: bool = False,
                 gang: Optional[Any] = None,
                 standby: Optional[Any] = None,
                 **kwargs: Any):
        """Resource-spec semantics mirror ``ray_ddp.py:85-112``:
        ``resources_per_worker`` entries override the dedicated args —
        ``CPU`` beats ``num_cpus_per_worker``; ``TPU`` (or legacy ``GPU``)
        beats ``use_tpu``/``use_gpu``. ``num_workers`` is the number of
        accelerator shards (chips), not OS processes — one XLA process
        drives every chip it can address.
        """
        resources_per_worker = dict(resources_per_worker or {})
        self.worker_runtime_env = dict(worker_runtime_env or {})
        self.num_workers = int(num_workers)
        self.num_cpus_per_worker = resources_per_worker.pop(
            "CPU", num_cpus_per_worker)

        accel = resources_per_worker.pop("TPU",
                                         resources_per_worker.pop("GPU", None))
        # An explicit TPU/GPU entry pins the Ray resource request; the bare
        # use_tpu flag leaves it to the launcher, which requests the host's
        # full chip count so Ray spreads one single-owner actor per host.
        self._explicit_chip_request = accel is not None
        if accel is not None:
            self.num_chips_per_worker = accel
        elif use_tpu is not None:
            self.num_chips_per_worker = int(use_tpu)
        else:
            self.num_chips_per_worker = int(use_gpu)
        self.use_tpu = self.num_chips_per_worker > 0
        # `use_gpu` retained as an alias so reference-style constructor
        # calls (`ray_ddp.py:79`) keep working unmodified.
        self.use_gpu = self.use_tpu

        if self.use_tpu and 0 < self.num_chips_per_worker < 1 \
                and num_workers > 1:
            warnings.warn(
                "Less than 1 TPU chip per worker: chips cannot be shared "
                "across SPMD ranks; collectives over ICI require whole "
                "chips. Use 1 chip per worker or a CPU mesh for testing.")

        self.additional_resources_per_worker = resources_per_worker
        self.init_hook = init_hook
        self.use_ray = use_ray
        self.allow_colocated_workers = allow_colocated_workers
        # GangConfig (reliability.gang): arms worker heartbeats + the
        # driver-side hang/death watchdog on Ray-backed launchers this
        # strategy configures. None = the fail-fast-only fault model.
        self.gang = gang
        # StandbyPool (reliability.elastic): warm pre-spawned workers
        # the configured launcher promotes into rank slots on restart.
        self.standby = standby
        self.extra_kwargs = kwargs

        self._mesh: Optional[Mesh] = None
        self._local_rank = 0
        self._global_rank = 0
        self._node_rank = 0
        self._is_remote = False
        self.global_to_local: Optional[list] = None

    # ------------------------------------------------------------------ #
    # launcher
    # ------------------------------------------------------------------ #
    def configure_launcher(self):
        """Install the launcher. Parity: ``ray_ddp.py:128-136``.

        Local (single-process SPMD) by default — one XLA process already
        drives every chip on this host, so no actors are needed. When a Ray
        cluster is attached (``ray.is_initialized()``), the Ray-backed
        multi-host launcher takes over and schedules one executor actor per
        TPU host, exactly where the reference always installs its
        ``RayLauncher``. ``use_ray`` overrides the auto-detection both
        ways: ``False`` keeps training local even inside a notebook that
        happened to ``ray.init()`` for unrelated reasons (round-1 review:
        silent escalation surprised exactly that case); ``True`` demands a
        Ray cluster and fails loudly when none is attached.
        """
        from ray_lightning_tpu.launchers import ray_launcher as _rl
        if self.use_ray is False:
            return LocalLauncher(self)
        ray = _rl._import_ray()
        if ray is not None and ray.is_initialized():
            return _rl.RayLauncher(self, ray_module=ray, gang=self.gang,
                                   standby=self.standby)
        if self.use_ray is True:
            raise RuntimeError(
                "use_ray=True but no Ray runtime is attached: install ray "
                "and call ray.init() (or connect via ray.init('ray://...')) "
                "before fit, or drop use_ray to train locally.")
        return LocalLauncher(self)

    def worker_setup(self, process_idx: int,
                     num_processes: Optional[int] = None,
                     coordinator_address: Optional[str] = None) -> None:
        """Initialize this worker's distributed runtime, then ranks.

        Parity seat of ``_worker_setup`` → ``init_process_group(env://)``
        (``ray_ddp.py:171-213``): NCCL TCP-store rendezvous becomes
        ``jax.distributed.initialize`` against the coordinator brokered by
        the launcher; afterwards every process sees the global device mesh
        and XLA collectives ride ICI/DCN. Single-process (local launcher or
        fake actors) skips initialization — the local mesh is already whole.

        When called without explicit arguments (an out-of-band worker, e.g.
        a user-spawned process joining the job), the coordinator address and
        world size fall back to the ``TL_COORDINATOR_ADDRESS`` /
        ``TL_NUM_PROCESSES`` env vars the launcher broadcasts to every
        actor — the same env-var rendezvous contract as the reference's
        ``MASTER_ADDR``/``MASTER_PORT`` (``ray_launcher.py:160-176``).
        """
        import os as _os
        # Env fallback only when the caller left BOTH at their defaults —
        # an explicit num_processes=1 means "definitely single-process" and
        # must never be overridden by stale TL_* vars.
        if coordinator_address is None and num_processes is None:
            coordinator_address = _os.environ.get("TL_COORDINATOR_ADDRESS")
            try:
                num_processes = int(
                    _os.environ.get("TL_NUM_PROCESSES", "1"))
            except ValueError:
                num_processes = 1
        if num_processes is None:
            num_processes = 1
        if coordinator_address is not None and num_processes > 1:
            if not jax.distributed.is_initialized():
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_idx)
            if jax.process_index() != process_idx:
                raise AssertionError(
                    f"Launcher assigned global rank {process_idx} but the "
                    f"coordinator handed out process_index "
                    f"{jax.process_index()}: rank map and device mesh "
                    "disagree; per-host batch shards would be misrouted.")
        self.set_world_ranks(process_idx)

    # ------------------------------------------------------------------ #
    # mesh + sharding policy (the strategy-defining part)
    # ------------------------------------------------------------------ #
    def mesh_spec(self) -> MeshSpec:
        raise NotImplementedError

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = build_mesh(self.mesh_spec(), self._mesh_devices())
            if jax.process_count() > 1:
                # Rank-map ↔ mesh alignment: per-host batch feeding relies
                # on global rank r owning the r-th contiguous device block.
                from ray_lightning_tpu.parallel.topology import (
                    assert_mesh_process_alignment)
                assert_mesh_process_alignment(
                    self._mesh, global_rank=self._global_rank,
                    process_index=jax.process_index())
        return self._mesh

    def _mesh_devices(self):
        return jax.devices()

    def params_sharding(self, abstract_params: Any) -> Any:
        """Default: replicate parameters (pure DP)."""
        return shardlib.replicated_pytree(abstract_params, self.mesh)

    def opt_state_sharding(self, abstract_opt_state: Any) -> Any:
        """Default: replicate optimizer state (pure DP)."""
        return shardlib.replicated_pytree(abstract_opt_state, self.mesh)

    def model_state_sharding(self, abstract_model_state: Any) -> Any:
        return shardlib.replicated_pytree(abstract_model_state, self.mesh)

    def batch_sharding(self) -> NamedSharding:
        return shardlib.batch_sharding(self.mesh)

    def scalar_sharding(self) -> NamedSharding:
        return shardlib.replicated(self.mesh)

    def make_train_step(self, loss_fn: Callable, tx: Any,
                        state_shardings: Any, batch_sharding: NamedSharding,
                        donate: bool = True,
                        log_grad_norm: bool = False,
                        guard_nonfinite: bool = False) -> Callable:
        """Build the compiled training step: ``state', logs = step(state, batch)``.

        The jit path: gradient synchronization is *derived* by XLA from the
        sharding annotations (replicated params + dp-sharded batch ⇒ psum of
        grads over ICI, fused into backprop) — this replaces the reference's
        DDP wrapper as the seat of gradient sync (``ray_ddp.py:202-206``).
        Strategies needing explicit per-rank collectives (Horovod parity)
        override this with a ``shard_map`` version.

        ``log_grad_norm`` adds the pre-clip global gradient norm to the
        step logs — computed inside the same XLA program (fused with the
        update), so it costs no extra host sync.

        ``guard_nonfinite`` (the trainer's ``nonfinite_action`` seat)
        checks the loss AND every gradient element for NaN/Inf inside
        the compiled program; a poisoned step keeps the old
        params/opt/model state (a device-side select — donation-safe,
        both versions exist inside the program) and reports
        ``logs["nonfinite"]=1.0`` for the host to act on. The step/rng
        counters still advance: the batch was *attempted*, and the next
        batch draws fresh randomness.
        """
        import optax

        from ray_lightning_tpu.reliability.guard import tree_all_finite

        # device scopes (docs/observability.md): the forward reads
        # ".../jvp(loss)/...", the gradient ".../transpose(jvp(loss))/..."
        # in a profile; the exchange this path leaves to XLA carries the
        # name of the op whose result is exchanged
        scoped_loss = jax.named_scope("loss")(loss_fn)

        def step(state, batch):
            rng = jax.random.fold_in(state.rng, state.step)
            grad_fn = jax.value_and_grad(scoped_loss, has_aux=True)
            (loss, (logs, new_ms)), grads = grad_fn(
                state.params, state.model_state, batch, rng)
            if log_grad_norm:
                with jax.named_scope("grad_norm"):
                    logs = {**logs,
                            "grad_norm": optax.global_norm(grads)}
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(grads, state.opt_state,
                                             state.params)
                new_params = optax.apply_updates(state.params, updates)
            if guard_nonfinite:
                with jax.named_scope("nonfinite_guard"):
                    ok = jnp.isfinite(loss) & tree_all_finite(grads)
                    keep = lambda new, old: jax.tree_util.tree_map(  # noqa: E731,E501
                        lambda n, o: jnp.where(ok, n, o), new, old)
                    new_params = keep(new_params, state.params)
                    new_opt = keep(new_opt, state.opt_state)
                    new_ms = keep(new_ms, state.model_state)
                    logs = {**logs,
                            "nonfinite": (~ok).astype(jnp.float32)}
            new_state = state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt,
                model_state=new_ms)
            return new_state, {"loss": loss, **logs}

        # Donation is gated off on the CPU backend, same as the serve
        # engine's _pick(): CPU jax honors donation by aliasing buffers
        # in place, and CPU device_put/device_get are ZERO-COPY — so a
        # donated step can overwrite memory that host numpy still views
        # (checkpoint-restored states, test snapshots), which surfaces
        # as use-after-free garbage/NaN. Real accelerators copy across
        # the host/HBM boundary, so donation there is both safe and the
        # memory win it exists for.
        donate = donate and jax.default_backend() != "cpu"
        # traced with the mesh ambient: the model's constrain_batch seats
        # keep activations on the data axes, parameter cuts are storage
        return jax.jit(
            shardlib.under_mesh(self.mesh, step),
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, self.scalar_sharding()),
            donate_argnums=(0,) if donate else ())

    def make_eval_step(self, eval_fn: Callable, state_shardings: Any,
                       batch_sharding: NamedSharding) -> Callable:
        """Compiled eval step: ``logs = eval_step(state, batch, rng)``."""

        def step(state, batch, rng):
            return eval_fn(state.params, state.model_state, batch, rng)

        return jax.jit(
            shardlib.under_mesh(self.mesh, step),
            in_shardings=(state_shardings, batch_sharding,
                          self.scalar_sharding()),
            out_shardings=self.scalar_sharding())

    # ------------------------------------------------------------------ #
    # rank model (parity: ray_ddp.py:138-267)
    # ------------------------------------------------------------------ #
    def set_remote(self, remote: bool) -> None:
        self._is_remote = remote

    def set_global_to_local(self, global_to_local: list) -> None:
        """Driver-computed global→(local, node) map. Parity ``:146-153``."""
        self.global_to_local = global_to_local

    def set_world_ranks(self, process_idx: int = 0) -> None:
        """Parity ``ray_ddp.py:155-169``. Under single-process SPMD the
        process index is the JAX process index (one per TPU host)."""
        self._global_rank = process_idx
        if self.global_to_local is not None and \
                process_idx < len(self.global_to_local):
            self._local_rank, self._node_rank = \
                self.global_to_local[process_idx]
        else:
            self._local_rank, self._node_rank = 0, process_idx

    def set_world_size(self, num_workers: int) -> None:
        """Adopt a world size chosen at RESTART time — the elastic
        recovery seat (``GangSupervisor(elastic=True)``).

        The reference fixes the world at construction; elastic resume
        needs the surviving-capacity count decided *after* a failure.
        Resizing drops the mesh and the driver-computed rank map (both
        describe a world that no longer exists — they rebuild lazily on
        the next launch/fit at the new size); the next restore then
        re-shards the newest checkpoint onto the resized mesh via the
        full-host-array restore path. Only strategies whose mesh is
        derived from ``num_workers`` (the 1-D dp/fsdp families) support
        this — :class:`MeshStrategy` overrides it to refuse.
        """
        n = int(num_workers)
        if n < 1:
            raise ValueError(f"world size must be >= 1, got {n}")
        if n == self.num_workers:
            return
        self.num_workers = n
        self._mesh = None
        self.global_to_local = None
        self.set_world_ranks(min(self._global_rank, n - 1))

    @property
    def world_size(self) -> int:
        """Number of data-parallel ranks. Parity ``ray_ddp.py:215-222``."""
        return self.num_workers

    @property
    def global_rank(self) -> int:
        return self._global_rank

    @property
    def local_rank(self) -> int:
        return self._local_rank

    @property
    def node_rank(self) -> int:
        return self._node_rank

    @property
    def is_remote(self) -> bool:
        return self._is_remote

    @property
    def root_device(self) -> jax.Device:
        """First addressable device of this process's mesh slice.

        Parity with ``ray_ddp.py:269-323`` (CUDA device resolution from
        ``ray.get_gpu_ids``): on TPU, device assignment is the runtime's
        job — the first addressable mesh device is canonical.
        """
        for d in self.mesh.devices.flat:
            if d.process_index == jax.process_index():
                return d
        return jax.local_devices()[0]

    @property
    def accelerator_name(self) -> str:
        """Parity: ``accelerator="_gpu" if use_gpu else "cpu"``
        (``ray_ddp.py:122-123``) — the delayed variant so TPU-less drivers
        can construct the trainer (client mode / CPU head node)."""
        return "_tpu" if self.use_tpu else "cpu"

    @property
    def accelerator(self):
        from ray_lightning_tpu.accelerators import resolve_accelerator
        return resolve_accelerator(self.accelerator_name)

    @property
    def distributed_sampler_kwargs(self) -> Dict[str, int]:
        """Parity ``ray_ddp.py:325-334``: how a rank-sharded dataloader
        should slice. Under SPMD, used only by per-process host data
        feeding (each process loads its shard of the global batch)."""
        return dict(num_replicas=self.num_workers, rank=self.global_rank)

    def teardown(self) -> None:
        self._mesh = None
        # drop the trainer-registered ring/pipeline meshes so later
        # model.apply calls outside a trainer run locally, not in a
        # shard_map over a dead run's devices
        from ray_lightning_tpu.parallel import pipeline as _pipe
        from ray_lightning_tpu.parallel import ring_attention as _ring
        _ring.set_sp_mesh(None)
        _pipe.set_pp_mesh(None)

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(num_workers={self.num_workers}, "
                f"use_tpu={self.use_tpu})")
