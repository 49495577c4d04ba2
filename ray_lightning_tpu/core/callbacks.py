"""Trainer callbacks.

Callback hooks mirror the subset of PTL's callback API the reference's tests
actually exercise (the "callback-as-probe" pattern, SURVEY.md §4): epoch
start/end, batch end, validation end, sanity-check gates, plus checkpoint
save/load state. ``EpochStatsCallback`` is the TPU analog of the reference's
``CUDACallback`` (``examples/ray_ddp_sharded_example.py:16-45``) measuring
epoch wall-time and device memory.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np


class Callback:
    def setup(self, trainer, pl_module, stage: str) -> None: ...
    def teardown(self, trainer, pl_module, stage: str) -> None: ...
    def on_fit_start(self, trainer, pl_module) -> None: ...
    def on_fit_end(self, trainer, pl_module) -> None: ...
    def on_sanity_check_start(self, trainer, pl_module) -> None: ...
    def on_sanity_check_end(self, trainer, pl_module) -> None: ...
    def on_train_start(self, trainer, pl_module) -> None: ...
    def on_train_end(self, trainer, pl_module) -> None: ...
    def on_train_epoch_start(self, trainer, pl_module) -> None: ...
    def on_train_epoch_end(self, trainer, pl_module) -> None: ...
    def on_train_batch_start(self, trainer, pl_module, batch,
                             batch_idx: int) -> None: ...
    def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                           batch_idx: int) -> None: ...
    def on_validation_start(self, trainer, pl_module) -> None: ...
    def on_validation_end(self, trainer, pl_module) -> None: ...
    def on_validation_epoch_start(self, trainer, pl_module) -> None: ...
    def on_validation_epoch_end(self, trainer, pl_module) -> None: ...
    def on_validation_batch_start(self, trainer, pl_module, batch,
                                  batch_idx: int,
                                  dataloader_idx: int = 0) -> None: ...
    def on_validation_batch_end(self, trainer, pl_module, outputs, batch,
                                batch_idx: int,
                                dataloader_idx: int = 0) -> None: ...
    def on_test_start(self, trainer, pl_module) -> None: ...
    def on_test_end(self, trainer, pl_module) -> None: ...
    def on_test_epoch_start(self, trainer, pl_module) -> None: ...
    def on_test_epoch_end(self, trainer, pl_module) -> None: ...
    def on_test_batch_start(self, trainer, pl_module, batch,
                            batch_idx: int,
                            dataloader_idx: int = 0) -> None: ...
    def on_test_batch_end(self, trainer, pl_module, outputs, batch,
                          batch_idx: int,
                          dataloader_idx: int = 0) -> None: ...
    def on_predict_start(self, trainer, pl_module) -> None: ...
    def on_predict_end(self, trainer, pl_module) -> None: ...
    def on_predict_epoch_start(self, trainer, pl_module) -> None: ...
    def on_predict_epoch_end(self, trainer, pl_module) -> None: ...
    def on_predict_batch_start(self, trainer, pl_module, batch,
                               batch_idx: int,
                               dataloader_idx: int = 0) -> None: ...
    def on_predict_batch_end(self, trainer, pl_module, outputs, batch,
                             batch_idx: int,
                             dataloader_idx: int = 0) -> None: ...
    def on_before_optimizer_step(self, trainer, pl_module,
                                 optimizer) -> None:
        """Fired once per training batch, before the compiled step.

        TPU-native semantic shift vs PTL: grads, update, and apply are
        fused into ONE XLA program (the whole point — psum fuses into
        backprop), so there is no host point "after backward, before
        step". This hook is the per-batch seat for LR scheduling /
        optimizer introspection; per-gradient inspection belongs inside
        ``training_step`` (jnp ops) instead.
        """
        ...
    def on_save_checkpoint(self, trainer, pl_module,
                           checkpoint: Dict[str, Any]) -> None: ...
    def on_load_checkpoint(self, trainer, pl_module,
                           checkpoint: Dict[str, Any]) -> None: ...
    def state_dict(self) -> Dict[str, Any]:
        return {}
    def load_state_dict(self, state: Dict[str, Any]) -> None: ...
    def sharded_state(self) -> Optional[Any]:
        """Optional pytree of ``jax.Array`` leaves to persist with the
        checkpoint. Unlike ``state_dict`` (host scalars, msgpack-encoded),
        this travels the same path as the train state: consolidated for
        the stream format, written shard-by-shard for orbax — so device
        trees (e.g. an EMA of sharded params) checkpoint without a host
        gather."""
        return None
    def load_sharded_state(self, tree: Any) -> None: ...


class ModelCheckpoint(Callback):
    """Epoch-end checkpointing with best-model tracking.

    Parity target: PTL's ``ModelCheckpoint`` as used by the reference —
    runs inside the rank-0 worker, and only ``best_model_path`` crosses back
    to the driver (``ray_lightning/launchers/ray_launcher.py:320-322``).
    """

    def __init__(self,
                 dirpath: Optional[str] = None,
                 filename: str = "epoch={epoch}-step={step}",
                 monitor: Optional[str] = None,
                 mode: str = "min",
                 save_top_k: int = 1,
                 save_last: bool = False,
                 save_format: str = "stream",
                 async_save: bool = False,
                 every_n_train_steps: int = 0,
                 keep_last_n: Optional[int] = None):
        if mode not in ("min", "max"):
            raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
        if save_format not in ("stream", "orbax"):
            raise ValueError(
                f"save_format must be 'stream' or 'orbax', got "
                f"{save_format!r}")
        if async_save and save_format != "orbax":
            raise ValueError("async_save requires save_format='orbax'")
        if every_n_train_steps < 0:
            raise ValueError(
                f"every_n_train_steps must be >= 0, got "
                f"{every_n_train_steps}")
        if keep_last_n is not None and keep_last_n < 1:
            raise ValueError(
                f"keep_last_n must be >= 1 (the newest committed "
                f"checkpoint is never pruned), got {keep_last_n}")
        self.dirpath = dirpath
        self.filename = filename
        self.monitor = monitor
        self.mode = mode
        self.save_top_k = save_top_k
        self.save_last = save_last
        self.save_format = save_format
        self.async_save = async_save
        # periodic cadence for crash-safe resume: every N train batches,
        # save an unmonitored mid-epoch checkpoint (the ckpt records its
        # batch-in-epoch position, so resume="auto" fast-forwards the
        # loader instead of replaying or skipping the half-epoch). 0 =
        # epoch-end saves only.
        self.every_n_train_steps = every_n_train_steps
        # retention for long chaos runs: after each save, prune committed
        # checkpoints beyond the newest keep_last_n (tmp-safe and
        # marker-aware — see core.checkpoint.prune_checkpoints; the
        # best/top-k ledger and 'last' are always protected). None = keep
        # everything the top-k ledger doesn't already prune.
        self.keep_last_n = keep_last_n
        self.best_model_path: str = ""
        self.best_model_score: Optional[float] = None
        self.last_model_path: str = ""
        self._saved: list = []  # (score, path), worst-first
        self._last_saved_path: str = ""
        # rolling crash-safety checkpoint (monitored configs only; see
        # _save — unmonitored configs keep periodic saves in the ledger)
        self._last_periodic_path: str = ""

    def setup(self, trainer, pl_module, stage: str) -> None:
        if self.dirpath is None:
            self.dirpath = os.path.join(trainer.default_root_dir,
                                        "checkpoints")

    def _is_better(self, score: float) -> bool:
        if self.best_model_score is None:
            return True
        return (score < self.best_model_score if self.mode == "min" else
                score > self.best_model_score)

    def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                           batch_idx: int) -> None:
        # periodic mid-epoch cadence: unmonitored (metrics may not exist
        # yet), purely for crash-safe resume
        if self.every_n_train_steps < 1 or \
                trainer.global_step % self.every_n_train_steps:
            return
        self._save(trainer, monitor_val=None, periodic=True)

    def on_train_epoch_end(self, trainer, pl_module) -> None:
        self._save(trainer, monitor_val=self._monitor_value(trainer))

    _SKIP = object()  # monitored metric absent: skip this save entirely

    def _monitor_value(self, trainer):
        if self.monitor is None:
            return None
        raw = trainer.callback_metrics.get(self.monitor)
        if raw is None:
            # PTL semantics: monitored metric absent this epoch (e.g.
            # validation didn't run) ⇒ skip, never rank an unscored
            # checkpoint against real scores.
            if trainer.global_rank == 0:
                import warnings
                warnings.warn(
                    f"ModelCheckpoint: monitored metric "
                    f"{self.monitor!r} not found in callback_metrics; "
                    "skipping checkpoint this epoch.")
            return self._SKIP
        return float(np.asarray(raw))

    def _save(self, trainer, monitor_val, periodic: bool = False) -> None:
        if self.save_top_k == 0 or monitor_val is self._SKIP:
            return
        # The orbax save is a *collective*: every jax.distributed process
        # must join (each writes its own non-addressable shards and all
        # meet at orbax's multihost sync barrier). Only the stream format —
        # a rank-0 host consolidation — may be rank-gated. Decisions below
        # (skip / filename) are computed identically on every rank from
        # replicated metrics, so all ranks stay convergent.
        collective = self.save_format == "orbax" and jax.process_count() > 1
        if trainer.global_rank != 0 and not collective:
            return
        name = self.filename.format(
            epoch=trainer.current_epoch, step=trainer.global_step)
        if monitor_val is not None:
            name = f"{name}-{self.monitor}={monitor_val:.4f}"
        if trainer.global_rank == 0:
            os.makedirs(self.dirpath, exist_ok=True)
        suffix = ".ckpt" if self.save_format == "stream" else ".orbax"
        path = os.path.join(self.dirpath, name + suffix)
        trainer.save_checkpoint(path, save_format=self.save_format,
                                async_save=self.async_save)
        self._last_saved_path = path
        # 'last' tracks epoch-end saves only: rewriting it every periodic
        # tick would double the cadence's checkpoint I/O for a copy the
        # step-ordered resume scan never prefers over the periodic file
        if self.save_last and not periodic:
            last_path = os.path.join(self.dirpath, "last" + suffix)
            trainer.save_checkpoint(last_path,
                                    save_format=self.save_format,
                                    async_save=self.async_save)
        if trainer.global_rank != 0:
            return
        # bookkeeping + pruning stay rank-0-only
        if periodic and self.monitor is not None:
            # a monitored checkpoint ledger scores in metric units; an
            # unmonitored crash-safety save must NOT compete there (a
            # recency score of -global_step would beat every real
            # mode='min' metric and hijack best_model_path / top-k).
            # Periodic saves instead roll: keep only the newest one.
            prev = self._last_periodic_path
            if prev and prev != path and os.path.exists(prev) and \
                    prev != self.best_model_path and \
                    all(prev != p for _s, p in self._saved):
                if os.path.isdir(prev):
                    import shutil
                    shutil.rmtree(prev, ignore_errors=True)
                else:
                    os.remove(prev)
            self._last_periodic_path = path
            self._retention_prune()
            return
        score = monitor_val if monitor_val is not None else \
            -float(trainer.global_step)  # no monitor: newest is best
        if self._is_better(score):
            self.best_model_score = score
            self.best_model_path = path
        # a periodic save and an epoch-end save can land on the same
        # step= path: keep one ledger entry per file on disk
        self._saved = [(s, p) for s, p in self._saved if p != path]
        self._saved.append((score, path))
        self._prune()
        if self.save_last:
            self.last_model_path = os.path.join(self.dirpath,
                                                "last" + suffix)
        self._retention_prune()

    def _retention_prune(self) -> None:
        """``keep_last_n`` retention: bound what long chaos runs leave in
        the checkpoint dir. Everything the callback still tracks (top-k
        ledger, best, 'last', the rolling periodic save) is protected —
        recency pruning must never delete a path the metric ledger
        would hand out."""
        if not self.keep_last_n or not self.dirpath:
            return
        if self.async_save and jax.process_count() > 1:
            # multi-host: other hosts' commit progress is unobservable
            # from here, so rank 0 must drain before deleting anything
            # (never rmtree across an unobserved async commit barrier).
            # Single-host needs no barrier: AsyncCheckpointer serializes
            # saves, so the only possibly-in-flight dir is
            # _last_saved_path — protected below — and a full wait per
            # save would serialize the loop async_save exists to overlap.
            from ray_lightning_tpu.core.checkpoint import \
                wait_for_async_saves
            wait_for_async_saves()
        from ray_lightning_tpu.core.checkpoint import prune_checkpoints
        protect = {p for _s, p in self._saved}
        protect.update({self.best_model_path, self.last_model_path,
                        self._last_periodic_path, self._last_saved_path})
        prune_checkpoints(self.dirpath, self.keep_last_n, protect=protect)

    def _prune(self) -> None:
        if self.save_top_k < 0:
            return
        reverse = self.mode == "max"
        self._saved.sort(key=lambda t: t[0], reverse=reverse)
        while len(self._saved) > self.save_top_k:
            _score, path = self._saved.pop()
            if path != self.best_model_path and os.path.exists(path):
                if os.path.isdir(path):  # orbax checkpoints are directories
                    # directories from *previous* epochs are already
                    # committed (AsyncCheckpointer serializes saves), but
                    # the save issued THIS call can itself be the worst
                    # and get pruned immediately — wait for that one case
                    # instead of serializing every epoch. Multi-process
                    # orbax saves are collective: this process's local
                    # serialization order says nothing about the other
                    # hosts' commit progress, so there rank 0 must drain
                    # its async queue before deleting any directory
                    # (ADVICE round 2: never rmtree across an unobserved
                    # commit barrier).
                    import shutil
                    if self.async_save and (
                            path == self._last_saved_path
                            or jax.process_count() > 1):
                        from ray_lightning_tpu.core.checkpoint import \
                            wait_for_async_saves
                        wait_for_async_saves()
                    shutil.rmtree(path, ignore_errors=True)
                else:
                    os.remove(path)

    def state_dict(self) -> Dict[str, Any]:
        return {
            "best_model_path": self.best_model_path,
            "best_model_score": self.best_model_score,
            "last_model_path": self.last_model_path,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.best_model_path = state.get("best_model_path", "")
        self.best_model_score = state.get("best_model_score")
        self.last_model_path = state.get("last_model_path", "")


class EarlyStopping(Callback):
    """Stop training when a monitored metric stops improving.

    Parity target: PTL's ``EarlyStopping`` as exercised through the
    reference's launcher (``tests/test_ddp.py:289-308`` — patience-driven
    stop on ``val_loss`` inside a Ray worker). Runs identically on every
    rank: the monitored metric comes from replicated ``callback_metrics``,
    so all SPMD processes reach the same stop decision with no collective.
    """

    def __init__(self,
                 monitor: str = "val_loss",
                 min_delta: float = 0.0,
                 patience: int = 3,
                 mode: str = "min",
                 check_on_train_epoch_end: bool = False,
                 verbose: bool = False,
                 strict: bool = True):
        if mode not in ("min", "max"):
            raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
        self.monitor = monitor
        self.min_delta = abs(min_delta)
        self.patience = patience
        self.mode = mode
        self.check_on_train_epoch_end = check_on_train_epoch_end
        self.verbose = verbose
        self.strict = strict
        self.wait_count = 0
        self.stopped_epoch = 0
        self.best_score: Optional[float] = None

    def _improved(self, score: float) -> bool:
        if self.best_score is None:
            return True
        if self.mode == "min":
            return score < self.best_score - self.min_delta
        return score > self.best_score + self.min_delta

    def _run_check(self, trainer) -> None:
        if trainer.sanity_checking:
            return
        raw = trainer.callback_metrics.get(self.monitor)
        if raw is None:
            if self.strict:
                raise RuntimeError(
                    f"EarlyStopping: monitored metric {self.monitor!r} not "
                    f"found in callback_metrics "
                    f"({sorted(trainer.callback_metrics)}); pass strict="
                    "False to skip epochs where it is absent.")
            return
        score = float(np.asarray(raw))
        if self._improved(score):
            self.best_score = score
            self.wait_count = 0
            return
        self.wait_count += 1
        if self.wait_count >= self.patience:
            trainer.should_stop = True
            self.stopped_epoch = trainer.current_epoch
            if self.verbose and trainer.global_rank == 0:
                print(f"EarlyStopping: {self.monitor} did not improve for "  # tl-lint: allow-print — verbose=True console UI
                      f"{self.wait_count} checks (best "
                      f"{self.best_score:.6f}); stopping at epoch "
                      f"{self.stopped_epoch}.")

    def on_validation_end(self, trainer, pl_module) -> None:
        if not self.check_on_train_epoch_end:
            self._run_check(trainer)

    def on_train_epoch_end(self, trainer, pl_module) -> None:
        if self.check_on_train_epoch_end:
            self._run_check(trainer)

    def state_dict(self) -> Dict[str, Any]:
        return {
            "wait_count": self.wait_count,
            "stopped_epoch": self.stopped_epoch,
            "best_score": self.best_score,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.wait_count = state.get("wait_count", 0)
        self.stopped_epoch = state.get("stopped_epoch", 0)
        self.best_score = state.get("best_score")


class EpochStatsCallback(Callback):
    """Epoch wall-time + device HBM stats, averaged across the mesh.

    TPU analog of the reference's ``CUDACallback``
    (``examples/ray_ddp_sharded_example.py:16-45``), which records epoch
    time and peak CUDA memory and all-reduces the averages. Under SPMD a
    single process sees every local device, so the "all-reduce" is a host
    mean over per-device memory stats.
    """

    def __init__(self, print_stats: bool = True):
        self.print_stats = print_stats
        self.epoch_times: list = []
        self.peak_memory_mib: list = []
        self._t0 = 0.0

    def on_train_epoch_start(self, trainer, pl_module) -> None:
        self._t0 = time.perf_counter()

    def on_train_epoch_end(self, trainer, pl_module) -> None:
        trainer.block_until_ready()
        dt = time.perf_counter() - self._t0
        self.epoch_times.append(dt)
        # the CPU backend reports no stats (None); a TPU reports
        # peak_bytes_in_use, and a failure to read it there is a failure
        stats = [d.memory_stats() for d in trainer.devices]
        peaks = [s["peak_bytes_in_use"] / 2**20 for s in stats
                 if s is not None]
        peak = float(np.mean(peaks)) if peaks else 0.0
        self.peak_memory_mib.append(peak)
        if self.print_stats and trainer.global_rank == 0:
            shown = (f"{peak:.0f} MiB" if peaks else
                     "n/a (backend reports no memory stats)")
            print(f"Epoch {trainer.current_epoch}: {dt:.2f}s, "  # tl-lint: allow-print — print_stats=True console UI
                  f"avg peak HBM {shown}")


class EMAWeightAveraging(Callback):
    """Maintain an exponential moving average of the parameters on-device.

    TPU-native take on PTL's ``StochasticWeightAveraging``: the average is
    updated by a jitted elementwise merge that inherits the params'
    shardings (EMA shards live beside the param shards — no host copy, no
    gather), so it composes with DP/ZeRO/FSDP meshes unchanged.

    ``swap_validation=True`` runs every validation/test epoch with the
    averaged weights (swapped in before the eval loop, restored after) —
    monitored metrics and early stopping then see the EMA model. The raw
    weights are restored before ``ModelCheckpoint`` saves; checkpoints
    always carry BOTH trees (raw params in the train state, the EMA
    average in this callback's sharded state), so either model can be
    exported after resume.
    """

    def __init__(self, decay: float = 0.999, update_every: int = 1,
                 swap_validation: bool = False):
        if not 0.0 < decay < 1.0:
            raise ValueError(f"decay must be in (0, 1), got {decay}")
        self.decay = decay
        self.update_every = max(1, int(update_every))
        self.swap_validation = swap_validation
        self.ema_params = None
        self._stashed = None
        self._update = None

    def on_train_start(self, trainer, pl_module) -> None:
        if self.ema_params is None:
            # start from a true COPY of the current params (restored EMA
            # arrives via load_state_dict before this hook): the train
            # step donates its input state, so aliasing the live buffers
            # would leave the EMA pointing at deleted memory
            import jax.numpy as jnp
            self.ema_params = jax.tree_util.tree_map(
                jnp.copy, trainer.train_state.params)
        else:  # resumed: host numpy → device, following the live sharding
            self.ema_params = jax.tree_util.tree_map(
                lambda host, live: jax.device_put(host, live.sharding),
                self.ema_params, trainer.train_state.params)
        decay = self.decay

        @jax.jit
        def update(ema, params):
            return jax.tree_util.tree_map(
                lambda e, p: decay * e + (1.0 - decay) * p, ema, params)

        self._update = update

    def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                           batch_idx: int) -> None:
        if trainer.global_step % self.update_every == 0:
            self.ema_params = self._update(self.ema_params,
                                           trainer.train_state.params)

    # -- swap the averaged weights in for evaluation ------------------- #
    def _swap_in(self, trainer) -> None:
        if self.swap_validation and self.ema_params is not None \
                and self._stashed is None:
            self._stashed = trainer.train_state.params
            trainer.train_state = trainer.train_state.replace(
                params=self.ema_params)

    def _swap_out(self, trainer) -> None:
        if self._stashed is not None:
            trainer.train_state = trainer.train_state.replace(
                params=self._stashed)
            self._stashed = None

    def on_validation_start(self, trainer, pl_module) -> None:
        self._swap_in(trainer)

    def on_validation_end(self, trainer, pl_module) -> None:
        self._swap_out(trainer)

    def on_test_start(self, trainer, pl_module) -> None:
        self._swap_in(trainer)

    def on_test_end(self, trainer, pl_module) -> None:
        self._swap_out(trainer)

    def sharded_state(self) -> Optional[Any]:
        # the EMA tree rides the train-state path (shard-by-shard under
        # orbax) — NEVER through the msgpack meta, which would host-gather
        # shards that multi-host processes can't even address
        return self.ema_params

    def load_sharded_state(self, tree: Any) -> None:
        # host numpy (stream/orbax restore) — re-placed onto the live
        # sharding by on_train_start
        self.ema_params = tree


class LambdaCallback(Callback):
    """Attach ad-hoc hook functions — the tests' callback-as-probe helper."""

    def __init__(self, **hooks):
        for name, fn in hooks.items():
            if not hasattr(Callback, name):
                raise ValueError(f"Unknown callback hook {name!r}")
            setattr(self, name, fn)


class LearningRateMonitor(Callback):
    """Record the scheduled learning rate into ``callback_metrics``.

    PTL's ``LearningRateMonitor`` analog for the optax world: requires the
    module's ``configure_optimizers`` to return ``(tx, schedule_fn)`` (the
    schedule is baked into ``tx``; the handle is for observability).
    ``logging_interval``: "epoch" (default) records at each train-epoch
    end; "step" records every batch.
    """

    def __init__(self, logging_interval: str = "epoch",
                 key: str = "lr"):
        if logging_interval not in ("epoch", "step"):
            raise ValueError("logging_interval must be 'epoch' or 'step'")
        self.logging_interval = logging_interval
        self.key = key

    def _record(self, trainer) -> None:
        lr = trainer.current_lr
        if lr is not None:
            trainer.callback_metrics[self.key] = lr

    def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                           batch_idx: int) -> None:
        if self.logging_interval == "step":
            self._record(trainer)

    def on_train_epoch_end(self, trainer, pl_module) -> None:
        if self.logging_interval == "epoch":
            self._record(trainer)
