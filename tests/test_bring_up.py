"""The bring-up contract (PR 22): the program says the truth about the
device it runs on.

- ``use_tpu=True`` is a gate, not a hint: ``Trainer.fit`` raises at train
  start when the process that executes sees no TPU — while constructing
  the strategy and the trainer on a device-less driver still works
  (``tests/test_client.py`` pins the no-device-touch half).
- one compile-cache helper: ``JAX_COMPILATION_CACHE_DIR`` when set, else
  one fixed in-checkout path, exported for spawned workers.
- the process-fleet worker default names no platform and no XLA flags.
- Pallas kernels choose interpret mode from the backend and never on a
  TPU one.
- ``ServeEngine.lowered_step_text`` shows which kernels a step program
  really holds (``chip_smoke.py`` reads ``tpu_custom_call`` from it).
"""
import logging
import os

import jax
import pytest

import ray_lightning_tpu as rlt
from ray_lightning_tpu.models import BoringModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# the use_tpu gate
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("make_strategy", [
    lambda: rlt.RayStrategy(num_workers=1, use_tpu=True),
    lambda: rlt.FSDPStrategy(num_workers=2, use_tpu=True),
    lambda: rlt.MeshStrategy(axes={"dp": 2, "fsdp": 2}, use_tpu=True),
], ids=["ddp", "fsdp", "mesh"])
def test_use_tpu_fit_raises_at_train_start_on_cpu_only_process(
        make_strategy, tmp_path):
    # construction is the driver's half and must stay device-free
    trainer = rlt.Trainer(strategy=make_strategy(), max_epochs=1,
                          default_root_dir=str(tmp_path))
    assert trainer.strategy.use_tpu
    with pytest.raises(RuntimeError, match="no TPU device is visible"):
        trainer.fit(BoringModel(batch_size=8))
    # it stopped before any state was built, not after a CPU fit
    assert trainer.global_step == 0
    assert getattr(trainer, "train_state", None) is None


def test_cpu_strategy_is_not_gated(tmp_path):
    trainer = rlt.Trainer(strategy=rlt.RayStrategy(num_workers=1),
                          max_epochs=1, limit_train_batches=2,
                          limit_val_batches=0,
                          default_root_dir=str(tmp_path))
    trainer.fit(BoringModel(batch_size=8))
    assert trainer.global_step == 2


# --------------------------------------------------------------------- #
# the compile-cache helper
# --------------------------------------------------------------------- #
@pytest.fixture
def restore_cache_config(monkeypatch):
    """The helper writes process-global jax config; put the suite's own
    cache settings back afterwards."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield monkeypatch
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_honours_the_environment(restore_cache_config,
                                               tmp_path):
    from ray_lightning_tpu.util import COMPILE_CACHE_ENV, enable_compile_cache
    placed = str(tmp_path / "placed_cache")
    restore_cache_config.setenv(COMPILE_CACHE_ENV, placed)
    assert enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert os.environ[COMPILE_CACHE_ENV] == placed


def test_compile_cache_default_is_one_fixed_in_checkout_path(
        restore_cache_config):
    from ray_lightning_tpu.util import COMPILE_CACHE_ENV, enable_compile_cache
    restore_cache_config.delenv(COMPILE_CACHE_ENV)
    first = enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    # exported, so spawned workers share it — and stable call after call
    assert os.environ[COMPILE_CACHE_ENV] == first
    restore_cache_config.delenv(COMPILE_CACHE_ENV)
    assert enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


def test_no_other_cache_directory_is_set_in_code():
    """One helper owns the decision: nothing else in the program names a
    compilation-cache directory (tests/conftest.py keeps its setdefault)."""
    sources = [os.path.join(REPO, f) for f in
               ("chip_smoke.py", "__graft_entry__.py")]
    for root in ("ray_lightning_tpu", "examples", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            sources += [os.path.join(dirpath, f) for f in files
                        if f.endswith(".py")]
    needles = ("jax_compilation_cache_dir", "JAX_COMPILATION_CACHE_DIR",
               "set_cache_dir", "initialize_cache")
    hits = [p for p in sources
            if not p.endswith(os.path.join("ray_lightning_tpu", "util.py"))
            and any(n in open(p).read() for n in needles)]
    assert hits == []


# --------------------------------------------------------------------- #
# process-fleet worker default
# --------------------------------------------------------------------- #
def test_default_worker_env_names_no_platform_or_optimisation_level():
    from ray_lightning_tpu.launchers.serve_worker import (SEAT_ENV_VAR,
                                                          default_worker_env)
    env = default_worker_env(3)
    assert env == {SEAT_ENV_VAR: "3"}
    # a TPU host maps seats onto chips through per_seat_env
    env = default_worker_env(1, lambda s: {"TPU_VISIBLE_CHIPS": str(s)})
    assert env == {SEAT_ENV_VAR: "1", "TPU_VISIBLE_CHIPS": "1"}
    assert not any("JAX_PLATFORMS" in k or "XLA_FLAGS" in k for k in env)


# --------------------------------------------------------------------- #
# kernels: no quiet fallback on a TPU backend
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend,interpret", [("tpu", False),
                                               ("cpu", True)])
def test_pallas_interpret_mode_follows_the_backend(monkeypatch, backend,
                                                   interpret):
    from ray_lightning_tpu.models import pallas_attention
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_attention.interpret_default() is interpret


# --------------------------------------------------------------------- #
# mesh layout is logged, and the step program can be read
# --------------------------------------------------------------------- #
def test_build_mesh_logs_the_layout_it_used(caplog):
    from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
    with caplog.at_level(logging.INFO,
                         logger="ray_lightning_tpu.parallel.mesh"):
        mesh = build_mesh(MeshSpec({"dp": 2, "fsdp": 2}), jax.devices()[:4])
    assert dict(mesh.shape) == {"dp": 2, "fsdp": 2}
    assert "plain reshape" in caplog.text and "cpu" in caplog.text


@pytest.mark.serve
@pytest.mark.parametrize("engine_kw", [
    {}, dict(page_size=8, page_native=True, kv_dtype="int8",
             attention_kernel="pallas")], ids=["dense", "paged-pallas"])
def test_lowered_step_text_is_the_dispatched_program(serve_nano_family,
                                                     engine_kw):
    from ray_lightning_tpu.serve import ServeClient
    dec, params = serve_nano_family[:2]
    client = ServeClient(dec, params, num_slots=3, prefill_len=8,
                         **engine_kw)
    client.submit([5, 17, 3, 9], max_new_tokens=4)
    out = client.run_until_idle()
    assert len(out[0].tokens) == 4
    text = client.engine.lowered_step_text()
    client.shutdown()
    assert "stablehlo" in text or "func.func" in text
    # interpret mode off-TPU: the kernel is expanded, never a Mosaic call
    assert "tpu_custom_call" not in text
