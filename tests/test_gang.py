"""Gang supervision: heartbeats, hang/death detection, coordinated restart.

The load-bearing assertions (ISSUE 5 pinned tests):

- a distributed fit on the **process backend** with an injected
  ``worker.exit`` (hard ``os._exit``, no Python exception) and —
  separately — a ``worker.stall`` (wedged training loop) is detected,
  the full gang is torn down, and :class:`GangSupervisor` restarts it
  on a fresh launch (fresh rendezvous port) reaching **bitwise-identical
  final params** to an uninterrupted run;
- a stalled worker never wedges the driver past ``heartbeat_timeout``
  (bounded-time detection, with the per-rank postmortem naming the
  silent rank);
- the gang lifecycle is observable: the injected-fault run emits
  ``worker.dead``/``worker.heartbeat_missed`` → ``gang.teardown`` →
  ``gang.restart`` in that order on the :class:`Telemetry` handle, and
  a disarmed launcher allocates no channel/monitor and emits nothing.
"""
import os
import time

import jax
import numpy as np
import pytest

import ray_lightning_tpu as rlt
from ray_lightning_tpu import ModelCheckpoint, RayStrategy, Trainer
from ray_lightning_tpu.launchers import utils as launcher_utils
from ray_lightning_tpu.launchers.process_backend import ProcessRay
from ray_lightning_tpu.launchers.ray_launcher import RayLauncher
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.obs import Telemetry
from ray_lightning_tpu.reliability import (FaultPlan, GangConfig,
                                           GangFailure, GangSupervisor,
                                           InjectedFault, RetryPolicy)
from ray_lightning_tpu.reliability.gang import GangMonitor
from ray_lightning_tpu.testing.fake_ray import (FakeRay, RecordingExecutor,
                                                ThreadedFakeRay)

GANG_SITES = ("worker.dead", "worker.error", "worker.heartbeat_missed",
              "gang.teardown", "gang.restart")

# Children must form their own 1-device CPU worlds (same contract as
# tests/test_process_backend.py).
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1 "
                 "--xla_backend_optimization_level=1",
}


@pytest.fixture(autouse=True)
def _reset_executor_seam():
    yield
    launcher_utils.set_executable_cls(None)
    RecordingExecutor.instances.clear()


def _snap(tree):
    return jax.tree_util.tree_map(np.array, jax.device_get(tree))


def _params_equal(a, b):
    la = jax.tree_util.tree_leaves(_snap(a))
    lb = jax.tree_util.tree_leaves(_snap(b))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _gang_sites(tel):
    return [e.site for e in tel.events() if e.site in GANG_SITES]


# --------------------------------------------------------------------- #
# monitor arithmetic (fake clock: fully deterministic)
# --------------------------------------------------------------------- #
def test_gang_monitor_timeout_arithmetic():
    """Silence verdicts are pure clock arithmetic: startup grace until a
    rank's first step beat, heartbeat_timeout after."""
    t = [0.0]
    cfg = GangConfig(heartbeat_timeout=1.0, startup_grace=5.0,
                     clock=lambda: t[0])
    mon = GangMonitor(2, cfg, node_ips=["10.0.0.1", "10.0.0.2"])
    mon.start()
    # rank 0 completes a step; rank 1 only sends liveness markers
    mon.observe(0, 1, 0.0)
    mon.observe(1, -1, 0.0)
    t[0] = 2.0  # rank 0 past timeout? beat at 0.0 + stepped -> silent
    assert mon.silent_ranks() == [0]
    mon.observe(0, 2, 0.0)
    assert mon.silent_ranks() == []
    t[0] = 4.5  # rank 1 beat-less for 4.5s but still pre-step: grace
    mon.observe(0, 3, 0.0)
    assert mon.silent_ranks() == []
    t[0] = 5.2  # rank 1's grace (5.0) exceeded; rank 0 beat 0.7s ago
    assert mon.silent_ranks() == [1]
    pms = mon.postmortems(silent=[1])
    assert pms[1].silent and not pms[0].silent
    assert pms[1].last_step == -1 and pms[0].last_step == 3
    assert pms[1].node_ip == "10.0.0.2"
    assert pms[0].beats == 3 and pms[1].beats == 1
    # stray beats from a previous generation's channel are ignored
    mon.observe(7, 99, 0.0)
    assert 7 not in mon.postmortems()


def test_gang_failure_message_carries_postmortems():
    cfg = GangConfig(heartbeat_timeout=1.0, clock=lambda: 0.0)
    mon = GangMonitor(2, cfg, node_ips=["a", "b"])
    err = mon.heartbeat_failure([1])
    assert err.reason == "worker.heartbeat_missed"
    assert "rank 1" in str(err) and "SILENT" in str(err)
    assert err.postmortems[1].silent and not err.postmortems[0].silent


# --------------------------------------------------------------------- #
# watchdog over a live (threaded) gang: silent rank named, full gang dies
# --------------------------------------------------------------------- #
def _beat_loop(chan, rank, n, dt):
    for step in range(1, n + 1):
        chan.put((rank, step, 0.0))
        time.sleep(dt)
    return rank


def _silent_worker(hold_s):
    time.sleep(hold_s)
    return "late"


def test_silent_rank_detected_and_full_gang_killed():
    """One rank beats, the other goes quiet: the watchdog raises within
    the timeout naming ONLY the silent rank, and teardown kills the whole
    gang (the beating peer would wedge in a collective forever)."""
    fake = ThreadedFakeRay()
    launcher_utils.set_executable_cls(RecordingExecutor)
    strategy = RayStrategy(num_workers=2)
    gang = GangConfig(heartbeat_timeout=0.4, startup_grace=0.4)
    launcher = RayLauncher(strategy, ray_module=fake, gang=gang)
    launcher.setup_workers(tune_enabled=False)
    chan = launcher._gang_channel
    futures = [
        launcher._workers[0].execute.remote(_beat_loop, chan, 0, 60, 0.05),
        launcher._workers[1].execute.remote(_silent_worker, 8.0),
    ]
    t0 = time.monotonic()
    with pytest.raises(GangFailure) as ei:
        launcher._process_results(futures, None)
    assert time.monotonic() - t0 < 6.0  # bounded: no 8s wedge
    failure = ei.value
    assert failure.reason == "worker.heartbeat_missed"
    assert [r for r, pm in failure.postmortems.items() if pm.silent] == [1]
    assert failure.postmortems[0].beats > 0
    assert failure.postmortems[1].node_ip == "127.0.0.1"
    assert launcher._gang_failed  # escalation recorded for teardown
    launcher.teardown_workers()
    assert len(fake.killed_actors) == 2  # the FULL gang, not just rank 1


def _return_fast():
    return "fast"


def test_completed_rank_is_not_declared_silent():
    """Completion skew is not a hang: a rank whose future resolved stops
    beating BY DESIGN and must leave the silence verdict while slower
    peers keep working past the timeout."""
    fake = ThreadedFakeRay()
    launcher_utils.set_executable_cls(RecordingExecutor)
    strategy = RayStrategy(num_workers=2)
    gang = GangConfig(heartbeat_timeout=0.3, startup_grace=0.3)
    launcher = RayLauncher(strategy, ray_module=fake, gang=gang)
    launcher.setup_workers(tune_enabled=False)
    chan = launcher._gang_channel
    futures = [
        launcher._workers[0].execute.remote(_return_fast),
        # rank 1 keeps beating well past rank 0's completion + timeout
        launcher._workers[1].execute.remote(_beat_loop, chan, 1, 30, 0.05),
    ]
    results = launcher._process_results(futures, None)  # must NOT raise
    assert results[0] == "fast"
    launcher.teardown_workers()


def test_monitor_mark_done_excludes_rank():
    t = [0.0]
    cfg = GangConfig(heartbeat_timeout=1.0, startup_grace=1.0,
                     clock=lambda: t[0])
    mon = GangMonitor(2, cfg)
    mon.start()
    mon.observe(0, 5, 0.0)
    mon.observe(1, 5, 0.0)
    mon.mark_done(0)
    t[0] = 10.0
    assert mon.silent_ranks() == [1]  # rank 0 finished, only 1 is hung


class _RecordingBeatShim:
    """Launcher stand-in recording heartbeat ticks."""

    def __init__(self):
        self.beats = []

    def drain_queue(self):
        pass

    def heartbeat(self, step):
        self.beats.append(step)


def test_eval_loop_ticks_heartbeats(tmp_path):
    """Evaluation emits heartbeats too: eval batches advance no
    global_step, but a rank chewing through them is not hung — without
    these beats any validate/test/predict longer than startup_grace
    would be declared a hang and the gang killed mid-eval."""
    trainer = Trainer(strategy=RayStrategy(num_workers=1), max_epochs=1,
                      seed=0, limit_train_batches=2, limit_val_batches=3,
                      default_root_dir=str(tmp_path))
    model = BoringModel()
    trainer.fit(model)  # local fit materializes state + compiled val step
    shim = _RecordingBeatShim()
    trainer._launcher = shim
    trainer._run_validation(trainer._dataloader("val_dataloader"), model)
    assert len(shim.beats) == 3  # one per eval batch
    # steps clamp >= 1: the monitor must switch off startup_grace once
    # evaluation demonstrably progresses
    assert all(b >= 1 for b in shim.beats)


# --------------------------------------------------------------------- #
# coordinated restart, in-process backends (cheap, deterministic)
# --------------------------------------------------------------------- #
def _fake_make_trainer(fake, root, ck, tel=None,
                       heartbeat_timeout: float = 30.0):
    def make_trainer():
        strategy = RayStrategy(num_workers=1)
        trainer = Trainer(strategy=strategy, max_epochs=3, seed=0,
                          limit_train_batches=4, limit_val_batches=0,
                          callbacks=[ModelCheckpoint(dirpath=ck)],
                          default_root_dir=root, telemetry=tel)
        trainer._launcher = RayLauncher(
            strategy, ray_module=fake,
            gang=GangConfig(heartbeat_timeout=heartbeat_timeout))
        return trainer
    return make_trainer


def test_gang_restart_threaded_fake_bitwise_and_event_order(tmp_path):
    """A worker crash mid-epoch-2 under gang supervision: detection →
    full-gang teardown → supervised restart resuming from the newest
    checkpoint; final params bitwise-identical to the uninterrupted run
    and the pinned event order on the telemetry handle."""
    # uninterrupted reference through the same backend
    ref_fake = ThreadedFakeRay()
    ref = _fake_make_trainer(ref_fake, str(tmp_path / "ref"),
                             str(tmp_path / "ref_ck"))()
    ref.fit(BoringModel())
    ref_params = ref.train_state_dict["params"]

    fake = ThreadedFakeRay()
    tel = Telemetry()
    make_trainer = _fake_make_trainer(fake, str(tmp_path / "run"),
                                      str(tmp_path / "ck"), tel=tel)
    sup = GangSupervisor(make_trainer,
                         RetryPolicy(max_attempts=3, base_delay=0.0),
                         sleep=lambda s: None, telemetry=tel)
    with FaultPlan.at("train.step", [9]).armed():
        trainer = sup.fit(BoringModel)
    assert sup.attempts == 2 and sup.restarts == 1
    assert trainer.state == "finished"
    assert len(sup.failures) == 1
    assert sup.failures[0].reason == "worker.error"
    assert sup.failures[0].postmortems[0].last_step == 9
    _params_equal(trainer.train_state_dict["params"], ref_params)
    assert _gang_sites(tel) == ["worker.error", "gang.teardown",
                                "gang.restart"]


def test_gang_rendezvous_fault_retried_on_fresh_setup(tmp_path):
    """An injected rendezvous.init failure (driver-side brokering) fails
    the attempt without leaking actors; the supervised retry re-runs
    setup_workers (fresh port probe) and completes. Driver-side site
    ticks persist across attempts, so tick 0 fires exactly once."""
    fake = FakeRay()
    make_trainer = _fake_make_trainer(fake, str(tmp_path / "run"),
                                      str(tmp_path / "ck"))
    sup = GangSupervisor(make_trainer,
                         RetryPolicy(max_attempts=3, base_delay=0.0),
                         sleep=lambda s: None)
    plan = FaultPlan.at("rendezvous.init", [0])
    with plan.armed():
        trainer = sup.fit(BoringModel)
    assert plan.fired == 1
    assert sup.attempts == 2 and sup.restarts == 1
    assert trainer.state == "finished"
    # the failed attempt's actors were torn down, not leaked
    assert len(fake.killed_actors) == len(fake.created_actors)
    # an InjectedFault is not a GangFailure: no postmortem to record
    assert sup.failures == []


def test_gang_disarmed_is_zero_surface(tmp_path):
    """gang=None: no channel, no monitor, no gang events — the fail-fast
    fault model and its cost profile are untouched."""
    fake = FakeRay()
    tel = Telemetry()
    strategy = RayStrategy(num_workers=1)
    trainer = Trainer(strategy=strategy, max_epochs=1, seed=0,
                      limit_train_batches=2, limit_val_batches=0,
                      default_root_dir=str(tmp_path), telemetry=tel)
    launcher = RayLauncher(strategy, ray_module=fake)
    trainer._launcher = launcher
    trainer.fit(BoringModel())
    assert launcher._gang_channel is None
    assert launcher._gang_monitor is None
    assert _gang_sites(tel) == []
    assert "gang_restarts_total" not in tel.metrics.snapshot()


def test_worker_exit_mode_degrades_to_raise_in_process(tmp_path):
    """mode="exit" outside a spawned worker process must never kill the
    test runner: it degrades to InjectedFault (and the fail-fast path
    surfaces it when gang supervision is disarmed)."""
    assert not os.environ.get("TL_WORKER_PROCESS")
    fake = FakeRay()
    strategy = RayStrategy(num_workers=1)
    trainer = Trainer(strategy=strategy, max_epochs=1, seed=0,
                      limit_train_batches=2, limit_val_batches=0,
                      default_root_dir=str(tmp_path))
    trainer._launcher = RayLauncher(strategy, ray_module=fake)
    with pytest.raises(InjectedFault):
        with FaultPlan.at("worker.exit", [0], mode="exit").armed():
            trainer.fit(BoringModel())


def test_worker_fault_rank_addressing():
    """A rank-addressed FaultSpec only fires on its rank; rank-less specs
    fire for anyone; same (site, tick) may target different ranks."""
    plan = FaultPlan([
        rlt.reliability.FaultSpec("worker.stall", 0, "raise", rank=1),
        rlt.reliability.FaultSpec("worker.stall", 0, "raise", rank=2),
    ])
    with plan.armed():
        assert plan.fire("worker.stall", rank=0) is None  # tick 0, rank 0
    plan2 = FaultPlan.at("worker.stall", [0], mode="raise", rank=1)
    with plan2.armed():
        with pytest.raises(InjectedFault):
            plan2.fire("worker.stall", rank=1)
    # duplicate (site, tick, rank) rejected; distinct ranks allowed above
    with pytest.raises(ValueError, match="duplicate"):
        FaultPlan([
            rlt.reliability.FaultSpec("worker.stall", 0, "raise", rank=1),
            rlt.reliability.FaultSpec("worker.stall", 0, "raise", rank=1),
        ])


# --------------------------------------------------------------------- #
# the real thing: OS-process workers killed/stalled mid-fit (PINNED)
# --------------------------------------------------------------------- #
def _proc_make_trainer(ray_mod, root, ck, tel, gang):
    def make_trainer():
        strategy = RayStrategy(num_workers=1)
        trainer = Trainer(strategy=strategy, max_epochs=3, seed=0,
                          limit_train_batches=4, limit_val_batches=0,
                          callbacks=[ModelCheckpoint(dirpath=ck)],
                          default_root_dir=root, telemetry=tel)
        trainer._launcher = RayLauncher(strategy, ray_module=ray_mod,
                                        gang=gang)
        return trainer
    return make_trainer


@pytest.fixture(scope="module")
def process_ref_params(tmp_path_factory):
    """The uninterrupted process-backend fit: the bitwise reference both
    chaos tests compare against (one spawned world, shared)."""
    root = tmp_path_factory.mktemp("gang_ref")
    ray_mod = ProcessRay(worker_env=dict(WORKER_ENV))
    ray_mod.init()
    try:
        make_trainer = _proc_make_trainer(
            ray_mod, str(root), str(root / "ck"), None,
            GangConfig(heartbeat_timeout=120.0))
        trainer = make_trainer()
        trainer.fit(BoringModel())
    finally:
        ray_mod.shutdown()
    return _snap(trainer.train_state_dict["params"])


@pytest.mark.multiproc
def test_gang_worker_exit_restart_bitwise(tmp_path, process_ref_params):
    """PINNED: a worker hard-killed mid-epoch-2 (os._exit — no exception,
    the OOM/preemption death) is detected via actor death, the gang is
    torn down, and the supervised restart resumes from the epoch-1
    checkpoint to bitwise-identical final params. Event order pinned:
    worker.dead -> gang.teardown -> gang.restart."""
    ray_mod = ProcessRay(worker_env=dict(WORKER_ENV))
    ray_mod.init()
    tel = Telemetry()
    make_trainer = _proc_make_trainer(
        ray_mod, str(tmp_path), str(tmp_path / "ck"), tel,
        GangConfig(heartbeat_timeout=120.0))
    sup = GangSupervisor(make_trainer,
                         RetryPolicy(max_attempts=3, base_delay=0.0),
                         sleep=lambda s: None, telemetry=tel)
    try:
        with FaultPlan.at("worker.exit", [9], mode="exit").armed():
            trainer = sup.fit(BoringModel)
    finally:
        ray_mod.shutdown()
    assert sup.attempts == 2 and sup.restarts == 1
    assert trainer.state == "finished"
    assert len(sup.failures) == 1
    failure = sup.failures[0]
    assert failure.reason == "worker.dead"
    assert failure.postmortems[0].dead
    assert failure.postmortems[0].last_step == 9  # beat through step 9
    _params_equal(trainer.train_state_dict["params"], process_ref_params)
    assert _gang_sites(tel) == ["worker.dead", "gang.teardown",
                                "gang.restart"]
    assert tel.metrics.snapshot()["gang_restarts_total"] == 1


@pytest.mark.multiproc
def test_gang_worker_stall_detected_within_timeout_and_restarted(
        tmp_path, process_ref_params):
    """PINNED: a worker wedged mid-epoch-2 (120s stall >> 5s timeout)
    never wedges the driver past the timeout — the watchdog's postmortem
    names the silent rank, teardown kills the stalled process, and the
    restart reaches bitwise-identical final params."""
    ray_mod = ProcessRay(worker_env=dict(WORKER_ENV))
    ray_mod.init()
    tel = Telemetry()
    gang = GangConfig(heartbeat_timeout=5.0, startup_grace=120.0)
    make_trainer = _proc_make_trainer(
        ray_mod, str(tmp_path), str(tmp_path / "ck"), tel, gang)
    sup = GangSupervisor(make_trainer,
                         RetryPolicy(max_attempts=3, base_delay=0.0),
                         sleep=lambda s: None, telemetry=tel)
    t0 = time.monotonic()
    try:
        with FaultPlan.at("worker.stall", [9], mode="stall",
                          stall_s=120.0).armed():
            trainer = sup.fit(BoringModel)
    finally:
        ray_mod.shutdown()
    # the stall alone is 120s: finishing this fast proves the driver
    # never waited it out (detection + kill + restart, all bounded)
    assert time.monotonic() - t0 < 90.0
    assert sup.attempts == 2 and sup.restarts == 1
    assert trainer.state == "finished"
    failure = sup.failures[0]
    assert failure.reason == "worker.heartbeat_missed"
    assert failure.postmortems[0].silent
    assert failure.postmortems[0].last_step == 9
    assert failure.postmortems[0].last_beat_age_s >= 5.0  # past timeout
    _params_equal(trainer.train_state_dict["params"], process_ref_params)
    assert _gang_sites(tel) == ["worker.heartbeat_missed", "gang.teardown",
                                "gang.restart"]


@pytest.mark.multiproc
def test_gang_standby_promotion_process_backend(tmp_path,
                                                process_ref_params):
    """PINNED (ISSUE 6): a worker hard-killed mid-epoch-2 restarts by
    PROMOTING a pre-warmed standby — no actor spawn on the recovery
    critical path — with PR 5's postmortem and event-order contract
    intact (worker.dead -> gang.teardown -> gang.restart, the promotion
    following the restart), bitwise-identical final params, and ZERO
    live actor processes after fit teardown + pool shutdown (the
    no-leak contract every channel/store/pool teardown path owes)."""
    from ray_lightning_tpu.launchers.ray_launcher import ExecutorBase
    from ray_lightning_tpu.reliability import StandbyPool
    ray_mod = ProcessRay(worker_env=dict(WORKER_ENV))
    ray_mod.init()
    tel = Telemetry()
    # num_standby=2 + a synchronous prefill makes the restart's warm
    # promotion deterministic: attempt 1 takes one (the take-first spawn
    # cache), the restart takes the other — no background-refill race
    pool = StandbyPool(ray_mod, num_standby=2, telemetry=tel)
    pool.fill(lambda: ray_mod.remote(ExecutorBase).options().remote())
    gang = GangConfig(heartbeat_timeout=120.0)

    def make_trainer():
        strategy = RayStrategy(num_workers=1)
        trainer = Trainer(strategy=strategy, max_epochs=3, seed=0,
                          limit_train_batches=4, limit_val_batches=0,
                          callbacks=[ModelCheckpoint(
                              dirpath=str(tmp_path / "ck"))],
                          default_root_dir=str(tmp_path), telemetry=tel)
        trainer._launcher = RayLauncher(strategy, ray_module=ray_mod,
                                        gang=gang, standby=pool)
        return trainer

    sup = GangSupervisor(make_trainer,
                         RetryPolicy(max_attempts=3, base_delay=0.0),
                         sleep=lambda s: None, telemetry=tel, standby=pool)
    try:
        with FaultPlan.at("worker.exit", [9], mode="exit").armed():
            trainer = sup.fit(BoringModel)
        pool.shutdown()
        # the no-leak pin: gang teardown killed every worker (promoted
        # ones included) and pool shutdown killed every idle standby
        assert ray_mod.live_actor_count() == 0
    finally:
        ray_mod.shutdown()
    assert sup.attempts == 2 and sup.restarts == 1
    assert trainer.state == "finished"
    assert pool.promotions == 2  # attempt 1 AND the restart, both warm
    failure = sup.failures[0]
    assert failure.reason == "worker.dead"
    assert failure.postmortems[0].dead
    assert failure.postmortems[0].last_step == 9
    _params_equal(trainer.train_state_dict["params"], process_ref_params)
    sites = [e.site for e in tel.events()
             if e.site in GANG_SITES + ("standby.promoted",)]
    assert sites == ["standby.promoted", "worker.dead", "gang.teardown",
                     "gang.restart", "standby.promoted"]
