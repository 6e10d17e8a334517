"""Device-mesh conventions and rank addressing.

The reference is SPMD-one-process-per-GPU with rank arithmetic done by hand
in every kernel (``rank``/``num_ranks``/``local_world_size``; see
``python/triton_dist/language/distributed_ops.py:84-96``). The TPU-native
design centralises this: a :class:`jax.sharding.Mesh` with canonical axis
names, and :class:`MeshContext` resolving per-axis ranks to the *logical
device ids* that Pallas remote DMA (``pltpu.make_async_remote_copy``) and
``pltpu.semaphore_signal`` take.

Canonical axis order (outer → inner): ``dp, pp, ep, sp, tp``. Innermost
axes map to the fastest ICI loops; ``tp`` traffic rides nearest-neighbour
links. Inter-slice (DCN) axes should be outermost — the analogue of the
reference's ``CommScope.INTRA_NODE``/``INTER_NODE`` split
(``DistributedAttrDefs.td:45``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

AXIS_ORDER = ("dp", "pp", "ep", "sp", "tp")


def make_mesh(*, dp: int = 1, pp: int = 1, ep: int = 1, sp: int = 1,
              tp: int = 1, devices: Optional[Sequence[jax.Device]] = None,
              allow_split_physical_axes: bool = True) -> Mesh:
    """Build a mesh over the given (or all) devices with canonical axes.

    Axes of size 1 are still present so the same kernels address any
    configuration uniformly.
    """
    sizes = {"dp": dp, "pp": pp, "ep": ep, "sp": sp, "tp": tp}
    total = math.prod(sizes.values())
    explicit_devices = devices is not None
    if devices is None:
        devices = jax.devices()
    if total != len(devices):
        raise ValueError(
            f"mesh {sizes} needs {total} devices, have {len(devices)}")
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if not explicit_devices and devices[0].platform == "tpu":
        # Topology-aware placement: inner axes land on ICI-adjacent chips.
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            shape, allow_split_physical_axes=allow_split_physical_axes)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def logical_device_id(mesh_axes: Sequence[str], axis: str, target_rank,
                      axis_sizes: Sequence[int]):
    """Linearized (row-major over ``mesh_axes``) logical device id of the
    device that has rank ``target_rank`` along ``axis`` and this device's
    coordinates along every other axis.

    Must be called inside a ``shard_map``-traced region (uses
    ``jax.lax.axis_index``). This is how a one-sided put targets "my TP
    peer r" on a multi-axis mesh — the analogue of NVSHMEM PE numbering
    (reference: ``language/extra/libshmem_device.py:50`` ``my_pe`` and
    the team-translate helpers).
    """
    device_id = 0
    for name, size in zip(mesh_axes, axis_sizes):
        idx = target_rank if name == axis else jax.lax.axis_index(name)
        device_id = device_id * size + idx
    return device_id


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Static description of the mesh as seen by a kernel.

    Carried by every op context (the analogue of the reference's
    ``rank/world_size/local_world_size`` triplet in e.g.
    ``AllGatherGEMMTensorParallelContext``,
    ``kernels/nvidia/allgather_gemm.py:449``).
    """

    axes: tuple  # tuple[str, ...] — mesh axis names, outer→inner
    sizes: tuple  # tuple[int, ...] — corresponding sizes

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "MeshContext":
        return cls(axes=tuple(mesh.axis_names),
                   sizes=tuple(mesh.shape[a] for a in mesh.axis_names))

    def size(self, axis: str) -> int:
        return self.sizes[self.axes.index(axis)]

    def rank(self, axis: str):
        """Traced: this device's rank along ``axis``."""
        return jax.lax.axis_index(axis)

    def device_id(self, axis: str, target_rank):
        """Traced: logical device id of ``target_rank`` along ``axis``."""
        return logical_device_id(self.axes, axis, target_rank, self.sizes)

    def spec(self, *names) -> P:
        """PartitionSpec helper: ``ctx.spec("tp", None)`` etc."""
        return P(*names)


def flat_axis_rank(axis):
    """(total size, my flat rank) over one axis name or an
    outer-major tuple of axis names — THE convention shared by
    ``P((outer, inner))`` shardings, ``EP2DContext`` expert ownership,
    and multi-slice cache layouts. Must be called inside shard_map.
    """
    import jax.numpy as jnp

    if isinstance(axis, (tuple, list)):
        n, me = 1, jnp.int32(0)
        for nm in tuple(axis):
            sz = jax.lax.axis_size(nm)
            n *= sz
            me = me * sz + jax.lax.axis_index(nm)
        return n, me
    return jax.lax.axis_size(axis), jax.lax.axis_index(axis)
