"""Intra-kernel profiler: device-side slot recorder.

Reference: ``python/triton_dist/tools/profiler/language.py:38`` device
``Profiler`` struct recording ``(tag, timestamp)`` slots (``record``
:145, ``%globaltimer``-based) into a preallocated buffer
(``context.py:50-76``) with Perfetto export (``viewer.py:115``).

TPU differences: Mosaic exposes no in-kernel clock, so slots record
``(tag, value)`` pairs (progress counters, semaphore reads, tile ids)
in *program order*; true wall-time per region comes from the XLA/xprof
trace (``profiler_utils.group_profile``). The combination covers the
reference's use cases: megakernel SM-activity metrics and per-tile
progress inspection.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class Profiler:
    """Handle over a profiler slot buffer.

    The host allocates an int32 output/scratch of shape (capacity, 2)
    plus a (1,) SMEM cursor; kernels call :func:`record` with it.
    """
    capacity: int = 256

    def scratch_shapes(self):
        return [pltpu.VMEM((self.capacity, 2), jnp.int32),
                pltpu.SMEM((1,), jnp.int32)]

    def out_shape(self):
        import jax
        return jax.ShapeDtypeStruct((self.capacity, 2), jnp.int32)


def record(buf_ref, cursor_ref, tag: int, value):
    """Append (tag, value) to the profiler buffer (drops on overflow).

    Reference ``Profiler.record`` (``tools/profiler/language.py:145``);
    tags are small ints mapped to names at export time.
    """
    import jax
    from jax.experimental import pallas as pl

    idx = cursor_ref[0]

    @pl.when(idx < buf_ref.shape[0])
    def _():
        row = jnp.stack([jnp.asarray(tag, jnp.int32),
                         jnp.asarray(value, jnp.int32)]).reshape(1, 2)
        buf_ref[pl.ds(idx, 1), :] = row

    cursor_ref[0] = idx + 1
