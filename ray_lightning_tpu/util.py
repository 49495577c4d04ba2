"""Shared utilities: optional-dependency sentinels, state byte-streams, result plumbing.

TPU-native re-design of the reference's worker utilities
(``ray_lightning/util.py:42-102``): the ``Unavailable`` sentinel pattern is kept,
``to_state_stream``/``load_state_stream`` become msgpack byte-streams of numpy
pytrees (instead of ``torch.save`` of CUDA state dicts), and ``process_results``
polls executor futures while draining the driver-side callable queue.
"""
from __future__ import annotations

import io
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from flax import serialization


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory — the one place this repo decides where compiled programs
    are kept (entry points call it: ``chip_smoke.py``,
    ``benchmark/run.py``, the CLI, the examples; the library never does
    on import).

    If ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and
    no other is set in code. If not, the cache lives at one fixed path
    inside the checkout (``<repo>/.jax_cache``, git-ignored) — never a
    temp name, pid or timestamp: the path is part of the cache key, so a
    directory that moves never hits. Either way the choice is exported to
    the environment so spawned workers share it.
    """
    path = os.environ.get(COMPILE_CACHE_ENV)
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        os.environ[COMPILE_CACHE_ENV] = path
    # jax reads the variable only at import; tell the live config too
    jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # jax's default keeps only compiles over 1 s; a cold chip call
        # pays for dozens of smaller programs too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path


class Unavailable:
    """Sentinel for unavailable optional dependencies.

    Mirrors ``ray_lightning/util.py:42-46``: any attribute access or
    instantiation raises, so import-time references stay cheap while use
    fails loudly.
    """

    def __init__(self, *args, **kwargs):
        raise RuntimeError(
            "This class is not usable because an optional dependency "
            "(e.g. `ray`) is not installed.")

    def __getattr__(self, name):
        raise RuntimeError(
            "This object is a placeholder for an unavailable optional "
            "dependency.")


def _to_numpy_pytree(tree: Any) -> Any:
    """Convert every array leaf to host numpy (device → host, zero surprises)."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x) if hasattr(x, "__array__") else x, tree)


def to_state_stream(state: Any) -> bytes:
    """Serialize a pytree of arrays to an in-memory byte stream.

    TPU analog of ``ray_lightning/util.py:73-77``: the reference streams a
    ``torch`` state dict through ``io.BytesIO`` so weights survive a
    multi-node return (no shared filesystem needed). Here the state is a JAX
    pytree; device arrays are pulled to host and msgpack-encoded.
    """
    return serialization.msgpack_serialize(_to_numpy_pytree(state))


def load_state_stream(stream: bytes, target: Optional[Any] = None) -> Any:
    """Inverse of :func:`to_state_stream`.

    TPU analog of ``ray_lightning/util.py:80-92``. ``map_location`` has no
    TPU equivalent: arrays are restored as host numpy and re-placed onto
    devices by whichever sharding the caller applies next (device placement
    is a sharding decision under XLA, not a serialization one).

    Args:
        stream: bytes produced by :func:`to_state_stream`.
        target: optional pytree template; when given, the restored state
            keeps the template's treedef (msgpack alone cannot restore
            custom pytree node types).
    """
    restored = serialization.msgpack_restore(stream)
    if target is not None:
        return serialization.from_state_dict(target, restored)
    return restored


def tensor_metrics_to_numpy(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """Convert metric values (device scalars) to plain numpy for IPC.

    Parity with ``ray_lightning/launchers/ray_launcher.py:339-347``, where
    callback/logged metrics are converted tensor→numpy before crossing the
    worker→driver boundary.
    """
    out = {}
    for k, v in metrics.items():
        if hasattr(v, "__array__"):
            arr = np.asarray(v)
            out[k] = arr.item() if arr.ndim == 0 else arr
        else:
            out[k] = v
    return out


def numpy_metrics_to_device(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """Driver-side inverse of :func:`tensor_metrics_to_numpy`.

    Parity with ``ray_lightning/launchers/ray_launcher.py:375-380`` (numpy →
    tensor restore). Scalars stay Python floats — in JAX there is no benefit
    to re-wrapping them in device arrays on the driver.
    """
    return dict(metrics)


def process_results(futures: List[Any],
                    queue: Optional[Any] = None,
                    poll_interval_s: float = 0.05,
                    sleep: Callable[[float], None] = time.sleep
                    ) -> List[Any]:
    """Drive the driver-side event loop until every worker future resolves.

    Parity with ``ray_lightning/util.py:57-70``: busy-poll the outstanding
    futures while draining the session queue, executing any queued callables
    *in the driver process* (the mechanism Tune-style reporting rides on,
    ``ray_lightning/util.py:49-54``).

    ``futures`` are executor-agnostic: anything with ``.done()``/``.result()``
    (concurrent.futures) or resolved via the installed executor backend.
    ``sleep`` is injectable (the package sleep-lint contract) so tests can
    drive the poll loop without wall time.
    """
    pending = list(futures)
    while pending:
        _drain_queue(queue)
        not_done = []
        for f in pending:
            if _future_done(f):
                continue
            not_done.append(f)
        if not not_done:
            break
        pending = not_done
        sleep(poll_interval_s)
    _drain_queue(queue)
    return [_future_result(f) for f in futures]


def _future_done(f: Any) -> bool:
    if hasattr(f, "done"):
        return f.done()
    return True  # plain values are already "done"


def _future_result(f: Any) -> Any:
    if hasattr(f, "result"):
        return f.result()
    return f


def _drain_queue(queue: Optional[Any]) -> None:
    """Execute every callable currently sitting in the session queue.

    Parity with ``_handle_queue`` (``ray_lightning/util.py:49-54``): items
    are ``(actor_rank, item)``; callables run driver-side, everything else is
    ignored.
    """
    if queue is None:
        return
    while not queue.empty():
        (_rank, item) = queue.get()
        if isinstance(item, Callable):
            item()
