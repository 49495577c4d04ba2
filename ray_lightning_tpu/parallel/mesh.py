"""Device-mesh construction: the TPU-native replacement for process groups.

Where the reference bootstraps a flat ``torch.distributed`` world over NCCL
(``ray_lightning/ray_ddp.py:171-213``), the TPU design expresses *all*
parallelism as named axes of a ``jax.sharding.Mesh``; XLA inserts the
collectives (psum / all-gather / reduce-scatter) from sharding annotations,
riding ICI within a slice and DCN across slices.

Axis vocabulary (a superset of the reference's single DP axis — the
reference implements only DP / allreduce-DP / ZeRO-1, see SURVEY.md §2.3):

- ``dp``   data parallel (batch split; params replicated)
- ``fsdp`` fully-sharded data parallel (batch + params + opt-state split)
- ``tp``   tensor parallel (weight matrices split; activations gathered)
- ``sp``   sequence/context parallel (sequence dim split; ring attention)
- ``pp``   pipeline parallel (layer groups split)
- ``ep``   expert parallel (MoE experts split)

Mesh-axis *order* matters on hardware: the innermost (last) axes map to
physically closest devices. We order meshes ``(pp, dp, fsdp, ep, sp, tp)``
so that tensor-parallel collectives — the most latency-sensitive — ride the
tightest ICI loops, matching the standard scaling-book recipe.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

_log = logging.getLogger(__name__)

DP_AXIS = "dp"
FSDP_AXIS = "fsdp"
TP_AXIS = "tp"
SP_AXIS = "sp"
PP_AXIS = "pp"
EP_AXIS = "ep"

# Outer → inner physical ordering (inner = last = fastest ICI neighborhood).
_CANONICAL_ORDER: Tuple[str, ...] = (PP_AXIS, DP_AXIS, FSDP_AXIS, EP_AXIS,
                                     SP_AXIS, TP_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A named multi-axis parallelism layout.

    ``axes`` maps axis name → size. A size of ``-1`` on at most one axis
    means "absorb all remaining devices" (like a reshape wildcard).

    ``dcn_axes`` (multi-slice pods): axis name → how many ways that axis
    crosses slice boundaries over DCN. Each entry must divide the axis's
    total size; the remaining factor stays inside a slice on ICI, with the
    DCN partition OUTER (slow links carry the outermost, least-frequent
    collectives — the scaling-book recipe; typically only ``dp`` or ``pp``
    belong here).
    """
    axes: Dict[str, int]
    dcn_axes: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        unknown = [a for a in self.axes if a not in _CANONICAL_ORDER]
        if unknown:
            raise ValueError(
                f"Unknown mesh axes {unknown}; valid: {_CANONICAL_ORDER}")
        wildcards = [a for a, s in self.axes.items() if s == -1]
        if len(wildcards) > 1:
            raise ValueError("At most one mesh axis may be -1 (wildcard)")
        for a, d in self.dcn_axes.items():
            if a not in self.axes:
                raise ValueError(
                    f"dcn_axes[{a!r}] has no matching entry in axes "
                    f"({sorted(self.axes)})")
            if d < 1:
                raise ValueError(f"dcn_axes[{a!r}] must be >= 1, got {d}")
            size = self.axes[a]
            if size != -1 and size % d != 0:
                raise ValueError(
                    f"dcn_axes[{a!r}]={d} does not divide axes[{a!r}]="
                    f"{size}")
        if self.dcn_axes and wildcards:
            raise ValueError(
                "dcn_axes cannot be combined with a -1 wildcard axis — "
                "resolve the axis sizes explicitly for multi-slice layouts")
        if self.dcn_axes:
            # Slice blocks must be contiguous in the mesh's flat device
            # order (multi-host feeding assumes process-contiguous order,
            # strategies/base.py assert_mesh_process_alignment): every
            # axis OUTSIDE the last DCN-bearing axis must itself be fully
            # DCN, otherwise iterating it re-visits slices (interleaving).
            names = self.axis_names
            last_dcn = max(i for i, a in enumerate(names)
                           if a in self.dcn_axes)
            for a in names[:last_dcn]:
                if self.axes[a] != self.dcn_axes.get(a, 1):
                    raise ValueError(
                        f"dcn_axes must occupy the outermost mesh axes: "
                        f"axis {a!r} (size {self.axes[a]}) lies outside "
                        f"DCN-bearing axis {names[last_dcn]!r} but is not "
                        f"fully DCN — either give {a!r} a dcn factor "
                        f"equal to its size or move the DCN split to the "
                        f"outermost axes (canonical order {names})")

    @property
    def num_slices(self) -> int:
        return math.prod(self.dcn_axes.values()) if self.dcn_axes else 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a for a in _CANONICAL_ORDER if a in self.axes)

    def resolved_sizes(self, num_devices: int) -> Tuple[int, ...]:
        sizes = [self.axes[a] for a in self.axis_names]
        if -1 in sizes:
            known = math.prod(s for s in sizes if s != -1)
            if num_devices % known != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes "
                    f"product {known} for spec {self.axes}")
            sizes[sizes.index(-1)] = num_devices // known
        return tuple(sizes)

    def num_required_devices(self, num_devices: int) -> int:
        return math.prod(self.resolved_sizes(num_devices))

    @staticmethod
    def data_parallel(num_workers: int = -1) -> "MeshSpec":
        return MeshSpec({DP_AXIS: num_workers})

    @staticmethod
    def fsdp(num_workers: int = -1) -> "MeshSpec":
        return MeshSpec({FSDP_AXIS: num_workers})


def build_mesh(spec: MeshSpec,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a ``jax.sharding.Mesh`` for ``spec``.

    Replaces the reference's IP-derived flat rank world
    (``ray_lightning/launchers/ray_launcher.py:131-158``): device *topology*
    (which chips share ICI links) is what determines collective cost on TPU,
    so we delegate physical layout to ``mesh_utils.create_device_mesh`` which
    understands v4/v5 3D tori, and fall back to a plain reshape off-TPU.

    A spec smaller than the device count uses a prefix subset of devices —
    the analog of the reference launching fewer workers than the cluster has
    slots.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    sizes = spec.resolved_sizes(len(devices))
    needed = math.prod(sizes)
    if needed > len(devices):
        raise ValueError(
            f"Mesh spec {dict(zip(spec.axis_names, sizes))} needs {needed} "
            f"devices but only {len(devices)} are available")
    use = devices[:needed]
    if spec.dcn_axes:
        return Mesh(_hybrid_device_array(spec, sizes, use),
                    spec.axis_names)
    layout = "device-list order (plain reshape)"
    dev_array = None
    if needed == len(devices) and use[0].platform == "tpu":
        try:
            dev_array = mesh_utils.create_device_mesh(
                sizes, devices=np.asarray(use))
            layout = "topology-aware (mesh_utils.create_device_mesh)"
        except (ValueError, AssertionError) as exc:
            layout += f" — create_device_mesh refused: {exc}"
    if dev_array is None:
        dev_array = np.asarray(use).reshape(sizes)
    _log.info("mesh %s over %d %s device(s): %s",
              dict(zip(spec.axis_names, sizes)), needed, use[0].platform,
              layout)
    return Mesh(dev_array, spec.axis_names)


def _hybrid_device_array(spec: MeshSpec, sizes: Sequence[int],
                         use: Sequence[jax.Device]) -> np.ndarray:
    """Device array for a multi-slice layout: DCN factors outer, ICI
    factors inner, so within-slice neighbors differ only along ICI.

    On real multislice TPU (devices carry ``slice_index``) this delegates
    to ``mesh_utils.create_hybrid_device_mesh``. Off-TPU the slice
    structure is EMULATED by chunking the device list into ``num_slices``
    equal contiguous groups — the layout invariants (tested on the CPU
    mesh) are identical, which is what makes multi-slice shardings
    compile-checkable without a real pod.
    """
    names = spec.axis_names
    dcn_sizes = [spec.dcn_axes.get(a, 1) for a in names]
    ici_sizes = [s // d for s, d in zip(sizes, dcn_sizes)]
    num_slices = math.prod(dcn_sizes)
    if all(getattr(d, "slice_index", None) is not None for d in use):
        # real multislice hardware: never fall back to emulation — a
        # pseudo-slice chunking that straddles true slice boundaries would
        # silently put ICI-only axes (tp/sp) on DCN
        try:
            return mesh_utils.create_hybrid_device_mesh(
                ici_sizes, dcn_sizes, devices=np.asarray(use))
        except (ValueError, AssertionError) as exc:
            raise ValueError(
                f"create_hybrid_device_mesh failed for ici={ici_sizes} "
                f"dcn={dcn_sizes} over {len(use)} devices "
                f"({num_slices} slices expected): {exc}") from exc
    # emulated slices: contiguous chunks of the device list. Build the
    # array so that indexing along axis k decomposes as
    # (dcn_k outer, ici_k inner): first lay devices out as
    # [slice grid (dcn_sizes)] x [per-slice grid (ici_sizes)], then
    # interleave each axis's (dcn, ici) pair into one dimension.
    arr = np.asarray(use).reshape(tuple(dcn_sizes) + tuple(ici_sizes))
    n = len(names)
    # permute (d0..dn-1, i0..in-1) -> (d0, i0, d1, i1, ...)
    perm = [x for k in range(n) for x in (k, n + k)]
    arr = arr.transpose(perm)
    return arr.reshape(tuple(sizes))


def multi_host_device_order(mesh: Mesh) -> List[int]:
    """Process indices in mesh order — used by the launcher's rank mapping."""
    from ray_lightning_tpu.parallel.topology import multi_host_device_order
    return multi_host_device_order(mesh)
