"""Accelerator abstraction + the delayed-TPU shim.

Parity with ``ray_lightning/accelerators/delayed_gpu_accelerator.py:22-50``
and the registry wiring in ``accelerators/__init__.py:13-21``: the
reference's ``_GPUAccelerator`` exists so a **driver with no GPU** (laptop /
CPU head node / Ray-client session) can construct a GPU trainer — device
availability is asserted *inside the worker*, not at construction. The TPU
analog: :class:`DelayedTPUAccelerator.is_available` is hardcoded ``True``
and device setup defers to the worker, where
:meth:`~ray_lightning_tpu.strategies.base.Strategy.worker_setup` initializes
the runtime; it raises only when training actually starts on a host with no
TPU (parity: ``util.py:35-38``).

Strategies select by name the same way the reference does
(``accelerator="_gpu" if use_gpu else "cpu"``, ``ray_ddp.py:122-123``):
here ``"_tpu"`` when ``use_tpu`` else ``"cpu"``.
"""
from __future__ import annotations

from typing import Dict, List, Type


class Accelerator:
    name = "base"

    @staticmethod
    def is_available() -> bool:
        raise NotImplementedError

    @staticmethod
    def parse_devices(devices):
        return devices

    @staticmethod
    def get_devices() -> List:
        import jax
        return jax.local_devices()

    def setup_environment(self, root_device=None) -> None:
        """Driver-side setup. Default: assert availability."""
        if not self.is_available():
            raise RuntimeError(
                f"{type(self).__name__}: no {self.name} device available")

    def on_train_start(self) -> None:
        """Worker-side gate, called once training begins."""


class CPUAccelerator(Accelerator):
    name = "cpu"

    @staticmethod
    def is_available() -> bool:
        return True


class TPUAccelerator(Accelerator):
    """Strict TPU accelerator: requires chips visible *now*."""
    name = "tpu"

    @staticmethod
    def is_available() -> bool:
        import jax
        try:
            return any(d.platform == "tpu" for d in jax.devices())
        except RuntimeError:
            return False


class DelayedTPUAccelerator(TPUAccelerator):
    """TPU accelerator whose availability check is deferred to the worker.

    ``is_available() -> True`` unconditionally (parity:
    ``delayed_gpu_accelerator.py:47-50``) so a TPU-less driver — laptop,
    CPU-only head node, Ray-client session — can build the trainer; worker-
    side :meth:`on_train_start` raises if the actor landed somewhere with no
    TPU after all (parity: ``util.py:35-38``).
    """
    name = "_tpu"

    @staticmethod
    def is_available() -> bool:
        return True

    def setup_environment(self, root_device=None) -> None:
        # Deliberately no device touch on the driver
        # (parity: delayed_gpu_accelerator.py:30-36).
        return None

    def on_train_start(self) -> None:
        if not TPUAccelerator.is_available():
            import jax
            raise RuntimeError(
                "use_tpu=True but no TPU device is visible in the process "
                "that executes training (jax backend: "
                f"{jax.default_backend()!r}); refusing to train on another "
                "backend under a TPU strategy. Drop use_tpu to train on "
                "this backend.")


ACCELERATOR_REGISTRY: Dict[str, Type[Accelerator]] = {}


def register_accelerator(cls: Type[Accelerator]) -> None:
    """Parity: PTL AcceleratorRegistry registration at import time
    (``accelerators/__init__.py:13-21``)."""
    ACCELERATOR_REGISTRY[cls.name] = cls


register_accelerator(CPUAccelerator)
register_accelerator(TPUAccelerator)
register_accelerator(DelayedTPUAccelerator)


def resolve_accelerator(name: str) -> Accelerator:
    if name not in ACCELERATOR_REGISTRY:
        raise KeyError(
            f"Unknown accelerator {name!r}; registered: "
            f"{sorted(ACCELERATOR_REGISTRY)}")
    return ACCELERATOR_REGISTRY[name]()
