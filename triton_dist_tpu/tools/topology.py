"""Device-topology introspection (the TPU analogue of NVML queries).

Reference: ``python/triton_dist/utils/nv_utils.py`` / ``amd_utils.py`` —
NVML link-matrix / NUMA topology / clock queries feeding the perf models
and the launcher. TPUs expose their topology through the JAX device
objects themselves: torus ``coords``, ``slice_index`` (DCN boundaries),
``device_kind`` (chip generation), ``process_index`` (host mapping) — no
driver library needed. This module turns those into the structures the
rest of the stack consumes: a chip spec for the perf models, an ICI
neighbour/hop map for schedule choices, and slice groups marking where
DCN (not ICI) carries traffic.

Works on any backend: CPU/interpret devices (no coords) degrade to a
single-group, zero-topology answer instead of failing — the same
single-host fallback the reference's ``nvml_init``-less path takes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from triton_dist_tpu.tools.perf_model import ChipSpec, V5E, V5P


@dataclasses.dataclass(frozen=True)
class DeviceInfo:
    id: int
    kind: str
    process_index: int
    coords: Optional[Tuple[int, ...]]   # torus position (TPU only)
    core_on_chip: int
    slice_index: int                    # DCN island (0 on single-slice)


def describe_devices(devices: Optional[Sequence] = None) -> List[DeviceInfo]:
    """One record per device, NVML-topo style (reference
    ``nv_utils.get_gpu_topo``)."""
    if devices is None:
        devices = jax.devices()
    out = []
    for d in devices:
        out.append(DeviceInfo(
            id=d.id,
            kind=getattr(d, "device_kind", d.platform),
            process_index=d.process_index,
            coords=tuple(getattr(d, "coords", ()) or ()) or None,
            core_on_chip=getattr(d, "core_on_chip", 0),
            slice_index=getattr(d, "slice_index", 0) or 0,
        ))
    return out


_KIND_SPECS = (
    # (substring of device_kind lowercased, ChipSpec)
    ("v5 lite", V5E),
    ("v5e", V5E),
    ("v5p", V5P),
    ("v5", V5P),
    ("v6", ChipSpec(bf16_tflops=918.0, hbm_gbps=1638.0,
                    ici_gbps_per_link=100.0, ici_links=4)),  # v6e
    ("v4", ChipSpec(bf16_tflops=275.0, hbm_gbps=1228.0,
                    ici_gbps_per_link=100.0, ici_links=6)),
)


def detect_chip(devices: Optional[Sequence] = None) -> ChipSpec:
    """ChipSpec for the attached hardware (reference: clock/SM queries
    feeding ``gemm_perf_model``), keyed by ``device_kind`` (a v5e
    reports ``"TPU v5 lite"``). A TPU whose kind matches no row raises:
    another chip's peaks under this one's name would be a wrong number,
    not an estimate. Non-TPU backends (the CPU test mesh) get the V5P
    row so the perf models stay usable as relative estimators."""
    if devices is None:
        devices = jax.devices()
    kind = getattr(devices[0], "device_kind", devices[0].platform)
    for sub, spec in _KIND_SPECS:
        if sub in kind.lower():
            return spec
    if devices[0].platform == "tpu":
        raise ValueError(
            f"unknown TPU device_kind {kind!r}: add its peaks to "
            "tools/topology._KIND_SPECS")
    return V5P


def torus_dims(infos: Sequence[DeviceInfo]) -> Tuple[int, ...]:
    """Extent of each torus axis covered by ``infos`` (coords max+1)."""
    coords = [i.coords for i in infos if i.coords is not None]
    if not coords:
        return ()
    nd = len(coords[0])
    return tuple(max(c[a] for c in coords) + 1 for a in range(nd))


def ici_hop_distance(a: DeviceInfo, b: DeviceInfo,
                     dims: Tuple[int, ...]) -> Optional[int]:
    """Manhattan distance on the wrapped torus; None across slices
    (traffic rides DCN there, not ICI)."""
    if a.slice_index != b.slice_index:
        return None
    if a.coords is None or b.coords is None:
        return 0 if a.id == b.id else 1   # topology-less backend
    hops = 0
    for x, y, n in zip(a.coords, b.coords, dims):
        d = abs(x - y)
        hops += min(d, n - d) if n > 1 else d
    return hops


def link_matrix(devices: Optional[Sequence] = None) -> List[List[Optional[int]]]:
    """Pairwise ICI hop counts (None = different slice / DCN) — the
    analogue of ``nvidia-smi topo -m`` the reference shells out for."""
    infos = describe_devices(devices)
    dims = torus_dims(infos)
    return [[ici_hop_distance(a, b, dims) for b in infos] for a in infos]


def _groups(infos: Sequence[DeviceInfo]) -> Dict[int, List[int]]:
    groups: Dict[int, List[int]] = {}
    for i in infos:
        groups.setdefault(i.slice_index, []).append(i.id)
    return groups


def slice_groups(devices: Optional[Sequence] = None) -> Dict[int, List[int]]:
    """Device ids per DCN slice (reference: NUMA/node grouping). Mesh
    axes laid over different groups cross DCN; keep them outermost
    (``parallel/mesh.AXIS_ORDER``)."""
    return _groups(describe_devices(devices))


def neighbors(devices: Optional[Sequence] = None) -> Dict[int, List[int]]:
    """1-hop ICI adjacency per device id (ring/torus schedule input)."""
    infos = describe_devices(devices)
    dims = torus_dims(infos)
    out: Dict[int, List[int]] = {}
    for a in infos:
        out[a.id] = [b.id for b in infos
                     if b.id != a.id
                     and ici_hop_distance(a, b, dims) == 1]
    return out


def summary(devices: Optional[Sequence] = None) -> dict:
    """One JSON-able blob: chip spec, torus shape, slices, hosts —
    what ``nv_utils`` prints at launcher startup. Devices are walked
    exactly once."""
    infos = describe_devices(devices)
    chip = V5P
    for sub, spec in _KIND_SPECS:
        if infos and sub in infos[0].kind.lower():
            chip = spec
            break
    return {
        "num_devices": len(infos),
        "device_kind": infos[0].kind if infos else "none",
        "torus_dims": list(torus_dims(infos)),
        "slices": {str(k): v for k, v in _groups(infos).items()},
        "hosts": sorted({i.process_index for i in infos}),
        "chip": dataclasses.asdict(chip),
    }
