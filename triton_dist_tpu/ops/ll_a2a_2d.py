"""Hierarchical 2-hop low-latency all-to-all over an (outer, inner) =
(DCN, ICI) 2-axis mesh.

The flat :func:`~triton_dist_tpu.ops.low_latency.ll_a2a` addresses
every peer chip directly, so on a multi-node mesh each dispatch pays
``(n_out - 1) * n_in`` separate puts across the slow DCN fabric. This
driver factors the exchange into two single-axis hops (reference
``all_to_all_vdev_2d_offset_inter_node.py`` — intra-node shuffle first,
then ONE aggregated inter-node slab per peer node):

- **hop 1 (ICI)**: each chip regroups its per-global-rank chunks by
  *inner* index and exchanges them within the node — after this hop,
  inner-rank ``i`` of every node holds all of its node's traffic bound
  for inner-rank ``i`` of every *other* node, as one contiguous
  ``n_out * C`` slab per destination node.
- **hop 2 (DCN)**: one slab put per peer node over the outer axis —
  DCN payload puts per dispatch drop from ``(n_out-1) * n_in`` to
  ``n_out - 1``, i.e. by the ICI group factor.

With outer-major global ranks ``g = o * n_in + i`` (the
:func:`~triton_dist_tpu.parallel.mesh.flat_axis_rank` order used by
``EP2DContext`` expert ownership), the composition is bit-equivalent to
a flat a2a up to the second wire quantization: both hops ride the
shared per-row absmax wire recipe of ``ll_a2a``
(:func:`~triton_dist_tpu.ops.low_latency.quantize_rows`), so tokens
are quantized once per fabric.

Each hop is a single-axis remote DMA, so the whole path runs under the
jax-0.4.x interpreter; ``impl="xla"`` swaps the Pallas kernel for a
``lax.all_to_all`` of the identical wire payload — numerically equal,
and the only legal choice inside a *global* mesh shard_map of a
multi-process run (interpret-mode Pallas gates on a barrier sized to
the full axis env; see ``tests/multihost_worker.py``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from triton_dist_tpu.parallel.mesh import MeshContext
from triton_dist_tpu.ops.low_latency import (
    ll_a2a, quantize_rows, wire_roundtrip,
)

# --- trace-time put ledger ---------------------------------------------------
# ll_a2a_2d is invoked host-side at trace time, so a with-scope around
# one dispatch trace observes exactly that dispatch's hop schedule.
# Tests use this to ASSERT the DCN coalescing claim (puts per dispatch
# == peer-NODE count, not peer-chip count) instead of trusting it.
_PUT_LEDGER: Optional[list] = None


@contextlib.contextmanager
def record_dispatch_puts():
    """Collect one entry per hop of every ll_a2a_2d traced inside the
    scope: ``{"hop", "axis", "peers", "payload_puts", "wire_puts"}``
    (wire_puts counts the paired payload+scale puts the ll wire
    protocol issues per peer)."""
    global _PUT_LEDGER
    prev, _PUT_LEDGER = _PUT_LEDGER, []
    try:
        yield _PUT_LEDGER
    finally:
        _PUT_LEDGER = prev


def _note(hop: str, axis: str, n_peers: int) -> None:
    if _PUT_LEDGER is not None:
        _PUT_LEDGER.append({
            "hop": hop, "axis": axis, "peers": n_peers,
            "payload_puts": n_peers, "wire_puts": 2 * n_peers,
        })


def hop_put_counts(ctx: MeshContext, *, outer_axis: str = "dcn",
                   inner_axis: str = "ici") -> dict:
    """Analytic per-dispatch put counts for a hierarchy: what the 2-hop
    schedule issues per fabric vs what a flat ll over the same mesh
    would push across DCN (``(n_out-1) * n_in`` chip-to-chip puts)."""
    n_out, n_in = ctx.size(outer_axis), ctx.size(inner_axis)
    return {"ici": n_in - 1, "dcn": n_out - 1,
            "flat_dcn": (n_out - 1) * n_in}


# --- hops --------------------------------------------------------------------

def _resolve_impl(ctx: MeshContext, impl: str) -> str:
    """``impl="kernel"`` degrades to the numerically-identical
    ``"xla"`` wire path when the Pallas route cannot run: under
    interpret mode a mesh where two axes are real (the genuine 2D case
    on the CPU battery) has no kernel hop that is known to run. On
    hardware — or on a degenerate 1×n / n×1 hierarchy under interpret
    — the kernel path stands."""
    if impl != "kernel":
        return impl
    from triton_dist_tpu.utils.distributed import use_interpret

    nontrivial = sum(1 for s in ctx.sizes if s > 1)
    if use_interpret() and nontrivial > 1:
        return "xla"
    return impl


def _hop(x, *, ctx: MeshContext, axis: str, step: int, wire_dtype,
         impl: str, force_kernel: bool):
    """One single-axis ll exchange of x (n, C, d) → received (n, C, d).

    ``impl="kernel"`` is the Pallas RDMA path; ``impl="xla"`` carries
    the SAME wire payload (quantize_rows int8/fp8 + f32 scales) through
    ``lax.all_to_all`` — numerically identical by construction, and
    safe inside a global-mesh shard_map of a multi-process interpret
    run where a Pallas call would deadlock."""
    if impl == "kernel":
        return ll_a2a(x, ctx=ctx, axis=axis, step=step,
                      wire_dtype=wire_dtype, force_kernel=force_kernel)
    if impl != "xla":
        raise ValueError(f"unknown ll2d hop impl {impl!r}")
    if ctx.size(axis) == 1:
        return wire_roundtrip(x, wire_dtype)
    q, scale = quantize_rows(x, wire_dtype)
    qr = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                            tiled=True)
    sr = jax.lax.all_to_all(scale, axis, split_axis=0, concat_axis=0,
                            tiled=True)
    return (qr.astype(jnp.float32) * sr).astype(x.dtype)


def ll_a2a_2d(x, *, ctx: MeshContext, outer_axis: str = "dcn",
              inner_axis: str = "ici", step=0, wire_dtype=jnp.int8,
              impl: str = "kernel", force_kernel: bool = False):
    """Two-hop low-latency a2a: x (n, C, d) with outer-major rank order
    (x[o * n_in + i] goes to global rank (o, i)); returns (n, C, d)
    received, exactly the flat ``ll_a2a`` contract.

    ``step`` passes through UNCHANGED to both hops — they ride
    different axes (distinct kernels and buffers), and the dispatch /
    return-hop callers alternate it (2·layer / 2·layer+1) so
    consecutive same-axis calls land on opposite slot parities.

    Fault scopes: each hop runs under its own
    :func:`~triton_dist_tpu.resilience.faults.on_op_call` op name
    (``"ll2d_ici"`` / ``"ll2d_dcn"``) so chaos plans can drop or wedge
    one fabric without touching the other.
    """
    from triton_dist_tpu.resilience import faults

    n_out, n_in = ctx.size(outer_axis), ctx.size(inner_axis)
    n = n_out * n_in
    if x.shape[0] != n:
        raise ValueError(
            f"leading dim {x.shape[0]} != {outer_axis}x{inner_axis}"
            f"={n_out}x{n_in}={n}")
    _, c, d = x.shape
    impl = _resolve_impl(ctx, impl)

    # Hop 1 (ICI): regroup chunks inner-major — chunk for global rank
    # (o, i) rides to local inner peer i, packed at outer position o of
    # its n_out*C slab.
    with faults.on_op_call("ll2d_ici"):
        inner_send = (x.reshape(n_out, n_in, c, d)
                      .transpose(1, 0, 2, 3)
                      .reshape(n_in, n_out * c, d))
        _note("ici", inner_axis, n_in - 1)
        inner_recv = _hop(inner_send, ctx=ctx, axis=inner_axis,
                          step=step, wire_dtype=wire_dtype, impl=impl,
                          force_kernel=force_kernel)

    # Hop 2 (DCN): inner_recv[j] is peer j's slab of chunks bound for
    # my inner rank, one per destination node — regroup outer-major so
    # each peer NODE gets ONE n_in*C slab put.
    with faults.on_op_call("ll2d_dcn"):
        outer_send = (inner_recv.reshape(n_in, n_out, c, d)
                      .transpose(1, 0, 2, 3)
                      .reshape(n_out, n_in * c, d))
        _note("dcn", outer_axis, n_out - 1)
        outer_recv = _hop(outer_send, ctx=ctx, axis=outer_axis,
                          step=step, wire_dtype=wire_dtype, impl=impl,
                          force_kernel=force_kernel)

    # outer_recv[o] = node o's slab for me, inner-major inside — which
    # is exactly global-rank-major after the flatten.
    return outer_recv.reshape(n, c, d)
