"""Device-side one-sided communication primitives for Pallas TPU kernels.

Semantics map (reference → here):

- ``dl.rank()/num_ranks()`` (``language/distributed_ops.py:84,90``)
  → :func:`rank` / :func:`num_ranks` over a named mesh axis.
- ``libshmem_device.putmem_block(dst, src, nbytes, pe)``
  (``language/extra/libshmem_device.py:~120``) → :func:`putmem_block` —
  an async remote DMA; completion is a *semaphore*, not a flag word.
- ``libshmem_device.putmem_signal_block(..., sig_ptr, sig_val, SIGNAL_SET, pe)``
  → :func:`putmem_signal_block` — remote DMA plus a remote semaphore
  signal the consumer waits on.
- ``dl.notify(ptr, rank, signal=v, comm_scope=...)``
  (``distributed_ops.py:103``) → :func:`notify` — remote semaphore signal.
- ``dl.wait(barrierPtrs, N, scope, semantic)`` (``distributed_ops.py:57``)
  → :func:`wait` — semaphore wait. TPU semaphores are counting, so the
  reference's ``signal_wait_until(CMP_EQ, value)`` value-compare protocol
  becomes a count protocol: producers ``inc`` by 1, consumers wait for a
  target count (SURVEY.md §7 "hard parts" — phase/parity re-design).
- ``dl.consume_token`` → :func:`consume_token` (no-op: Mosaic orders
  memory through semaphore waits; kept for API parity).
- ``libshmem_device.barrier_all()`` → :func:`barrier_all`.

All functions must be called inside a Pallas kernel traced under
``shard_map`` (they use ``jax.lax.axis_index``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.parallel.mesh import logical_device_id

SIGNAL_SET = "set"   # reference: SignalOp::SET (DistributedAttrDefs.td:36)
SIGNAL_ADD = "add"   # reference: SignalOp::ADD


# The full public surface (tests/test_shmem.py asserts this covers the
# reference's ~80-name libshmem_device API one-to-one).
__all__ = [
    "SIGNAL_SET", "SIGNAL_ADD",
    "rank", "num_ranks", "my_pe", "n_pes",
    "remote_put",
    "putmem", "putmem_block", "putmem_warp", "putmem_wave", "putmem_wg",
    "putmem_nbi", "putmem_nbi_block", "putmem_nbi_warp",
    "putmem_nbi_wave", "putmem_nbi_wg",
    "putmem_rma", "putmem_rma_block", "putmem_rma_warp",
    "putmem_rma_nbi", "putmem_rma_nbi_block", "putmem_rma_nbi_warp",
    "putmem_signal", "putmem_signal_block", "putmem_signal_warp",
    "putmem_signal_wave", "putmem_signal_wg",
    "putmem_signal_nbi", "putmem_signal_nbi_block",
    "putmem_signal_nbi_warp", "putmem_signal_nbi_wave",
    "putmem_signal_nbi_wg",
    "putmem_signal_rma", "putmem_signal_rma_block",
    "putmem_signal_rma_warp", "putmem_signal_rma_nbi",
    "putmem_signal_rma_nbi_block", "putmem_signal_rma_nbi_warp",
    "ulong_put_signal", "int_p",
    "getmem", "getmem_block", "getmem_warp", "getmem_wave", "getmem_wg",
    "getmem_nbi", "getmem_nbi_block", "getmem_nbi_warp",
    "getmem_nbi_wave", "getmem_nbi_wg",
    "broadcast", "broadcast_block", "broadcast_warp",
    "broadcastmem", "broadcastmem_block", "broadcastmem_warp",
    "fcollect", "fcollect_block", "fcollect_warp",
    "fcollectmem", "fcollectmem_block", "fcollectmem_warp",
    "amo_add", "fence", "quiet", "quiet_pe",
    "notify", "signal_op", "wait", "signal_wait_until",
    "uint64_wait_until_equals", "wait_arrivals", "consume_token",
    "barrier", "barrier_block", "barrier_warp",
    "barrier_all", "barrier_all_block", "barrier_all_vec",
    "barrier_all_warp", "barrier_all_wave", "barrier_all_wg",
    "barrier_tile",
    "sync_all", "sync_all_block", "sync_all_warp",
    "team_sync_block", "team_sync_warp",
    "team_my_pe", "team_n_pes", "team_translate_pe",
    "local_copy", "local_copy_async",
    "remote_ptr", "remote_mc_ptr", "set_rocshmem_ctx",
]


# ---------------------------------------------------------------------------
# Rank queries
# ---------------------------------------------------------------------------

def rank(axis: str):
    """This device's rank along ``axis`` (reference: dl.rank())."""
    return jax.lax.axis_index(axis)


def num_ranks(axis: str) -> int:
    """Static size of ``axis`` (reference: dl.num_ranks())."""
    return jax.lax.axis_size(axis)


# SHMEM-flavoured aliases (reference: libshmem_device.my_pe/n_pes)
my_pe = rank
n_pes = num_ranks


def _resolve_device_id(ctx, axis: str, peer):
    """Logical device id of ``peer`` along ``axis`` given a MeshContext."""
    if ctx is None:
        # Single-axis mesh: the peer rank is the logical id.
        return peer
    return logical_device_id(ctx.axes, axis, peer, ctx.sizes)


# ---------------------------------------------------------------------------
# One-sided puts / gets
# ---------------------------------------------------------------------------

def remote_put(src_ref, dst_ref, send_sem, recv_sem, peer, *, axis: str,
               ctx=None, start: bool = True):
    """One-sided put: copy ``src_ref`` into ``dst_ref`` on device ``peer``
    (rank along ``axis``). Returns the DMA handle; caller may ``.wait()``
    the send side, the remote side waits its ``recv_sem``.

    Reference: ``libshmem_device.putmem_nbi_block`` lowered to NVSHMEM
    (``NVIDIA/DistributedOpToLLVM.cpp:94-154``); here it is a single
    Mosaic ``make_async_remote_copy`` riding ICI (or DCN across slices).

    Fault-injection hook (``resilience.faults``): inside an active
    plan's op scope a put may be delayed (a dependent-FLOP spin folded
    into the device id on the target rank), dropped, or duplicated —
    the adversarial schedules the signal protocols must tolerate or
    detect. Free when no plan is active.
    """
    from triton_dist_tpu.resilience import faults

    fault = faults.put_fault() if start else None
    device_id = _resolve_device_id(ctx, axis, peer)
    if fault is not None and fault.kind == "delay_dma" and fault.iters:
        # The spin's result feeds the DMA descriptor, so it cannot be
        # dead-code-eliminated; it costs iters dependent FLOPs on
        # fault.rank and nothing elsewhere.
        device_id = device_id + faults.rank_spin_zero(
            axis, fault.rank, fault.iters)
    copy = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=device_id,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    if start:
        if fault is not None and fault.kind == "drop_put":
            @pl.when(jax.lax.axis_index(axis) != fault.rank)
            def _():
                copy.start()
        elif fault is not None and fault.kind == "dup_put":
            copy.start()

            @pl.when(jax.lax.axis_index(axis) == fault.rank)
            def _():
                copy.start()   # second descriptor bind = duplicate DMA
        else:
            copy.start()
    return copy


def putmem_block(dst_ref, src_ref, peer, send_sem, recv_sem, *, axis: str,
                 ctx=None):
    """SHMEM-argument-order alias of :func:`remote_put` (dst first)."""
    return remote_put(src_ref, dst_ref, send_sem, recv_sem, peer, axis=axis,
                      ctx=ctx)


def putmem_signal_block(dst_ref, src_ref, sig_sem, peer, send_sem, recv_sem,
                        *, axis: str, ctx=None, sig_inc: int = 1):
    """Put + remote user-semaphore signal.

    ORDERING CAVEAT (differs from NVSHMEM putmem_signal): the remote
    ``sig_sem`` signal is issued after the local send drains
    (``wait_send``) and may overtake the bulk data in flight. Only the
    DMA's own ``recv_sem`` certifies data arrival on the destination —
    consumers must wait ``recv_sem`` before reading ``dst_ref`` and use
    ``sig_sem`` purely for application-level sequencing (tile counters
    etc.). The fused ops in this package follow that discipline.

    The returned handle's send side is ALREADY drained — do not pass it
    to :func:`fence`/:func:`quiet` again. TPU semaphore waits consume
    counts (unlike NVSHMEM quiet, which is idempotent), so a second
    drain blocks forever.

    Reference: ``libshmem_device.putmem_signal_block`` / ``_nbi``.
    """
    copy = remote_put(src_ref, dst_ref, send_sem, recv_sem, peer, axis=axis,
                      ctx=ctx)
    copy.wait_send()
    notify(sig_sem, peer, axis=axis, ctx=ctx, inc=sig_inc)
    return copy


def getmem_block(dst_ref, src_ref, peer, requester, send_sem, recv_sem, *,
                 axis: str, ctx=None):
    """One-sided get in SPMD lockstep form: fetch ``peer``'s ``src_ref``
    into my ``dst_ref`` (reference ``libshmem_device.getmem_block``).

    TPU remote DMA is push-only, so the get is realised by the data
    owner. In an SPMD kernel every rank executes the same get, making
    the access pattern a rank permutation: I pull from ``peer``, and by
    symmetry ``requester`` — the rank with ``peer(requester) == me`` —
    pulls from me (for a shift ``peer = (me+off) % n`` that is
    ``requester = (me-off) % n``). This call issues the put that
    realises the *requester's* get (my ``src_ref`` → the requester's
    ``dst_ref``, symmetric address); my own ``dst_ref`` is filled by my
    peer's matching put. Consume the result with
    ``wait_arrivals(recv_sem, dst_ref, 1)`` — the reference's blocking
    get maps to put + arrival wait. The full-mesh *pull* allgather
    schedule (``low_latency_allgather.py``) is this pattern n-1 times.
    """
    return remote_put(src_ref, dst_ref, send_sem, recv_sem, requester,
                      axis=axis, ctx=ctx)


# ---------------------------------------------------------------------------
# Granularity / nbi tiers of the put-get surface
#
# The reference's libshmem_device multiplies every transfer op by a
# thread-granularity suffix (_block/_warp/_wave/_wg — which SIMT lanes
# participate, ``libshmem_device.py:~120-320``) and an _nbi (non-
# blocking) tier. A TPU core drives ONE DMA engine — there are no
# sub-core lanes to scope a transfer to — so every granularity maps to
# the same whole-core async DMA, and *all* puts here are already nbi
# (completion is the semaphore, not the call). The aliases keep the
# reference surface addressable one-to-one.
# ---------------------------------------------------------------------------

putmem = putmem_block
putmem_nbi = putmem_block
putmem_nbi_block = putmem_block
putmem_nbi_warp = putmem_block
putmem_nbi_wave = putmem_block
putmem_nbi_wg = putmem_block
putmem_warp = putmem_block
putmem_wave = putmem_block
putmem_wg = putmem_block
getmem = getmem_block
getmem_nbi = getmem_block
getmem_nbi_block = getmem_block
getmem_nbi_warp = getmem_block
getmem_nbi_wave = getmem_block
getmem_nbi_wg = getmem_block
getmem_warp = getmem_block
getmem_wave = getmem_block
getmem_wg = getmem_block

# The reference's _rma tier pins transfers to the proxy/RMA engine
# (IBGDA vs P2P copy, ``libshmem_device.py`` putmem_rma*). TPU exposes
# exactly one remote-DMA path — the ICI/DCN DMA engine — so the RMA
# tier IS the normal put.
putmem_rma = putmem_block
putmem_rma_block = putmem_block
putmem_rma_warp = putmem_block
putmem_rma_nbi = putmem_block
putmem_rma_nbi_block = putmem_block
putmem_rma_nbi_warp = putmem_block


def putmem_signal_nbi_block(dst_ref, src_ref, sig_sem, peer, send_sem,
                            recv_sem, *, axis: str, ctx=None,
                            sig_inc: int = 1):
    """Non-blocking put+signal: the signal is issued WITHOUT draining
    the send side first, so it may overtake the bulk data in flight
    (stronger caveat than :func:`putmem_signal_block`, same as the
    reference's ``putmem_signal_nbi`` ordering). Consumers must wait
    the DMA's own ``recv_sem`` before reading; ``sig_sem`` is
    application-level sequencing only."""
    copy = remote_put(src_ref, dst_ref, send_sem, recv_sem, peer,
                      axis=axis, ctx=ctx)
    notify(sig_sem, peer, axis=axis, ctx=ctx, inc=sig_inc)
    return copy


# put+signal granularity/rma tiers (same collapse as the puts above).
putmem_signal = putmem_signal_block
putmem_signal_warp = putmem_signal_block
putmem_signal_wave = putmem_signal_block
putmem_signal_wg = putmem_signal_block
putmem_signal_rma = putmem_signal_block
putmem_signal_rma_block = putmem_signal_block
putmem_signal_rma_warp = putmem_signal_block
putmem_signal_nbi = putmem_signal_nbi_block
putmem_signal_nbi_warp = putmem_signal_nbi_block
putmem_signal_nbi_wave = putmem_signal_nbi_block
putmem_signal_nbi_wg = putmem_signal_nbi_block
putmem_signal_rma_nbi = putmem_signal_nbi_block
putmem_signal_rma_nbi_block = putmem_signal_nbi_block
putmem_signal_rma_nbi_warp = putmem_signal_nbi_block
def ulong_put_signal(dst_ref, value, staging_ref, sig_sem, peer,
                     send_sem, recv_sem, *, axis: str, ctx=None,
                     sig_inc: int = 1):
    """Word-sized put of an immediate + remote signal (reference
    ``libshmem_device.ulong_put_signal(ptr, value, sig, ...)``).

    Like :func:`int_p`, TPU DMA sources from memory: the immediate is
    staged through the caller's 1-element ``staging_ref`` and shipped
    as a normal put+signal (same ordering caveats as
    :func:`putmem_signal_block`)."""
    staging_ref[...] = jnp.full_like(staging_ref[...], value)
    return putmem_signal_block(dst_ref, staging_ref, sig_sem, peer,
                               send_sem, recv_sem, axis=axis, ctx=ctx,
                               sig_inc=sig_inc)


def int_p(dst_ref, value, staging_ref, peer, send_sem, recv_sem, *,
          axis: str, ctx=None):
    """Single-word put of an immediate (reference
    ``libshmem_device.int_p(ptr, value, pe)``).

    TPU DMA sources from memory, not immediates, so the caller provides
    a 1-element ``staging_ref`` (SMEM/VMEM scratch); the value is
    stored there and shipped with the normal remote DMA. Arrival is the
    destination's ``recv_sem`` — there is no raced flag-word store.
    """
    staging_ref[...] = jnp.full_like(staging_ref[...], value)
    return remote_put(staging_ref, dst_ref, send_sem, recv_sem, peer,
                      axis=axis, ctx=ctx)


# ---------------------------------------------------------------------------
# In-kernel team collectives (broadcast / fcollect)
# ---------------------------------------------------------------------------

def broadcastmem(dst_ref, src_ref, root: int, send_sem, recv_sem, *,
                 axis: str, ctx=None, barrier: bool = True):
    """In-kernel broadcast: the root pushes ``src_ref`` into every
    peer's ``dst_ref``; non-roots block until arrival. Completes fully
    before returning on every rank (reference
    ``libshmem_device.broadcast[mem]``; ``root`` is a static int,
    matching the reference's PE_root argument).

    By default an internal :func:`barrier_all` precedes the puts: the
    scratch recv semaphore is only safe once every target has entered
    the kernel (the skewed-entry hazard — see :func:`barrier_tile`'s
    caveat). Pass ``barrier=False`` ONLY if the caller already ran a
    full barrier over ``axis`` in this kernel."""
    me = rank(axis)
    n = num_ranks(axis)
    if barrier:
        barrier_all(axis, ctx=ctx)

    @pl.when(me == root)
    def _():
        pltpu.sync_copy(src_ref, dst_ref)
        for off in range(1, n):
            peer = jax.lax.rem(root + off, n)
            remote_put(src_ref, dst_ref, send_sem, recv_sem, peer,
                       axis=axis, ctx=ctx)
        for _ in range(n - 1):
            pltpu.make_async_copy(src_ref, src_ref, send_sem).wait()

    @pl.when(me != root)
    def _():
        wait_arrivals(recv_sem, dst_ref, 1)


def fcollect(dst_ref, src_ref, send_sem, recv_sem, *, axis: str,
             ctx=None, barrier: bool = True):
    """In-kernel all-gather ("flat collect"): every rank pushes its
    ``src_ref`` into slot ``me`` of every peer's ``dst_ref``
    ((n, *src.shape)); returns with all n slots valid on every rank
    (reference ``libshmem_device.fcollect[mem]`` — the full-mesh push
    form, the same schedule as ``ops/allgather.py`` mode
    "full_mesh" but usable mid-kernel on arbitrary refs).

    Like that schedule, a full :func:`barrier_all` precedes the puts by
    default — full-mesh traffic on scratch semaphores is unsafe under
    skewed kernel entry (only the collective-id-keyed barrier semaphore
    tolerates skew). ``barrier=False`` only after the caller's own full
    barrier over ``axis``."""
    me = rank(axis)
    n = num_ranks(axis)
    if barrier:
        barrier_all(axis, ctx=ctx)
    pltpu.sync_copy(src_ref, dst_ref.at[me])
    for off in range(1, n):
        peer = jax.lax.rem(me + off, n)
        remote_put(src_ref, dst_ref.at[me], send_sem, recv_sem, peer,
                   axis=axis, ctx=ctx)
    for _ in range(n - 1):
        pltpu.make_async_copy(src_ref, src_ref, send_sem).wait()
    wait_arrivals(recv_sem, dst_ref.at[0], n - 1)


# Typed-value and granularity tiers of broadcast/fcollect: Pallas refs
# are typed (there is no separate bytes-vs-elements form), and one DMA
# engine per core collapses the thread tiers — so the reference's
# broadcast/broadcastmem x {,_block,_warp} six-way split is one
# function each.
broadcast = broadcastmem
broadcast_block = broadcastmem
broadcast_warp = broadcastmem
broadcastmem_block = broadcastmem
broadcastmem_warp = broadcastmem
fcollect_block = fcollect
fcollect_warp = fcollect
fcollectmem = fcollect
fcollectmem_block = fcollect
fcollectmem_warp = fcollect


# ---------------------------------------------------------------------------
# AMO (atomic memory operations)
#
# The reference exposes remote word atomics (atomic_fetch_add / set /
# compare_swap, ``libshmem_device.py`` AMO constants). TPU has no
# remote atomics on arbitrary HBM words; the hardware's atomic
# primitive is the COUNTING SEMAPHORE, so add-style AMO protocols map
# to remote semaphore increments (amo_add below == signal_op ADD) and
# fetch/compare styles must be re-designed around counts
# (docs/primitives.md). This is the documented semantic delta, not an
# emulation.
# ---------------------------------------------------------------------------

def amo_add(sem, value: int, peer, *, axis: str, ctx=None):
    """Remote add on a semaphore "word" (the TPU AMO analogue)."""
    notify(sem, peer, axis=axis, ctx=ctx, inc=value)


# ---------------------------------------------------------------------------
# Memory ordering (fence / quiet)
# ---------------------------------------------------------------------------

def fence(*copies):
    """Local ordering of my outstanding puts (reference
    ``libshmem_device.fence`` :176). Drains the given handles' send
    semaphores: my source buffers are reusable and the payloads are
    committed to the interconnect in order.

    WEAKER THAN NVSHMEM fence: send-drain does NOT order *remote
    delivery* — a subsequent :func:`notify` can still overtake the bulk
    data in flight (same caveat as :func:`putmem_signal_block`). Remote
    arrival is only certified on the receiver by its ``recv_sem`` wait;
    there is no sender-side primitive for it on TPU.
    """
    for c in copies:
        c.wait_send()


def quiet(*copies):
    """Local completion of my outstanding puts (reference
    ``libshmem_device.quiet`` :166): after return, every given handle's
    send side has drained — source buffers are safe to overwrite.

    WEAKER THAN NVSHMEM quiet, which certifies remote completion: on
    TPU only the *receiver* can certify arrival (its ``recv_sem``).
    Do not follow quiet with a raced flag signal — consumers must wait
    the DMA's own recv semaphore before reading the destination.

    NOT idempotent (also unlike NVSHMEM): each handle's send side can
    be drained exactly once — by quiet/fence, ``copy.wait()``, or a
    put+signal helper's internal drain — a second wait consumes counts
    that never come.
    """
    for c in copies:
        c.wait_send()


def quiet_pe(peer, *copies):
    """Per-PE quiet (reference ``libshmem_device.quiet_pe``): TPU DMA
    handles are already per-transfer, so draining the handles aimed at
    ``peer`` IS the per-PE form — the caller passes exactly those."""
    del peer
    quiet(*copies)


# ---------------------------------------------------------------------------
# Signal / wait
# ---------------------------------------------------------------------------

def notify(sem, peer=None, *, axis: Optional[str] = None, ctx=None,
           inc: int = 1):
    """Signal a semaphore, optionally on a remote device.

    Reference: ``dl.notify`` (``distributed_ops.py:103``) — release-store /
    ``signal_op`` by CommScope (``NVIDIA/DistributedOpToLLVM.cpp:243-353``).
    Local signal: ``notify(sem)``. Remote: ``notify(sem, peer, axis="tp")``.

    Fault-injection hook: an active drop_signal/dup_signal fault zeroes
    or doubles the increment on the target rank (uniformly traced — the
    site executes on every rank, only the increment diverges).
    """
    if axis is not None:
        from triton_dist_tpu.resilience import faults

        fault = faults.signal_fault()
        if fault is not None:
            me = jax.lax.axis_index(axis)
            scale = 0 if fault.kind == "drop_signal" else 2
            inc = jnp.where(me == fault.rank, scale * inc,
                            inc).astype(jnp.int32)
    if peer is None:
        pltpu.semaphore_signal(sem, inc=inc)
    else:
        pltpu.semaphore_signal(
            sem, inc=inc,
            device_id=_resolve_device_id(ctx, axis, peer),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )


def signal_op(sig_sem, signal, sig_op: str, peer, *, axis: str, ctx=None):
    """Reference ``libshmem_device.signal_op(ptr, val, SIGNAL_*, pe)``.

    TPU semaphores are counting: ADD maps to an increment; SET-to-value
    protocols must be re-expressed as counts (the collective kernels use
    monotonically increasing per-tile counts instead of set-flags).
    """
    if sig_op != SIGNAL_ADD:
        raise NotImplementedError(
            "SIGNAL_SET has no TPU analogue; use counting (SIGNAL_ADD) "
            "protocols — see ops/collectives for the patterns.")
    notify(sig_sem, peer, axis=axis, ctx=ctx, inc=signal)


def wait(sem, value: int = 1):
    """Block until ``sem``'s count reaches ``value``; decrements by
    ``value`` (TPU semaphore-wait semantics).

    Reference: ``dl.wait(barrierPtrs, numBarriers, scope, semantic)``
    (``distributed_ops.py:57``) — the PTX acquire spin loop
    (``DistributedOpToLLVM.cpp:156-229``) becomes a hardware semaphore
    wait: no SM/core spinning, the scalar unit sleeps until count.
    """
    pltpu.semaphore_wait(sem, value)


def signal_wait_until(sem, cmp: str, value: int):
    """Reference ``libshmem_device.signal_wait_until(ptr, CMP_EQ, val)``.

    Only >=-then-consume (counting) semantics exist on TPU; CMP_EQ with
    monotone counters is equivalent to waiting for the count."""
    if cmp not in ("eq", "ge"):
        raise NotImplementedError(f"cmp {cmp!r} not expressible on TPU")
    pltpu.semaphore_wait(sem, value)


def uint64_wait_until_equals(sem, value: int):
    """Reference ``libshmem_device.uint64_wait_until_equals(ptr, val)``
    — the word is a counting semaphore here (see
    :func:`signal_wait_until` for the count-protocol mapping)."""
    signal_wait_until(sem, "eq", value)


def wait_arrivals(sem, ref, count: int = 1):
    """Wait for ``count`` DMA deliveries of ``ref``'s size on a *DMA*
    semaphore. TPU DMA semaphores count transfer units, so an aggregate
    arrival wait is expressed as ``count`` descriptor waits of the common
    chunk shape (``count`` must be static).

    This is the consumer half of the reference's per-tile
    ``signal_wait_until`` on flag words (``distributed_ops.py:57``).
    """
    for _ in range(count):
        pltpu.make_async_copy(ref, ref, sem).wait()


def consume_token(value, token=None):
    """API-parity no-op (reference ``dl.consume_token``,
    ``distributed_ops.py:74``): Mosaic already orders reads after the
    semaphore waits that guard them."""
    return value


# ---------------------------------------------------------------------------
# Barriers
# ---------------------------------------------------------------------------

def barrier_all(axis: str, *, ctx=None):
    """Barrier over all devices along ``axis``.

    Full-mesh signal + wait on the global barrier semaphore — the
    analogue of ``libshmem_device.barrier_all`` / the reference's
    ``barrier_all_intra_node_*`` kernels (``kernels/nvidia/common_ops.py``).
    Requires ``collective_id`` in the kernel's CompilerParams.
    """
    n = num_ranks(axis)
    inc = _skewed_barrier_inc(axis)
    sem = pltpu.get_barrier_semaphore()
    for peer in range(n):
        pltpu.semaphore_signal(
            sem, inc=inc if peer == 0 else 1,
            device_id=_resolve_device_id(ctx, axis, peer),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(sem, n)


def _skewed_barrier_inc(axis: str):
    """Barrier-signal increment carrying an injected arrival skew: a
    skew_barrier fault spins the target rank before its first signal
    (the spin result rides the increment so it cannot be DCE'd; the
    increment stays exactly 1)."""
    from triton_dist_tpu.resilience import faults

    fault = faults.barrier_fault()
    if fault is None or not fault.iters:
        return 1
    return 1 + faults.rank_spin_zero(axis, fault.rank, fault.iters)


def barrier_tile(axis: str, *, ctx=None, sem=None):
    """Neighbour-pair barrier (cheaper than :func:`barrier_all`): signal
    both ring neighbours, wait for both.

    Uses the *global* barrier semaphore (keyed by the kernel's
    ``collective_id``) by default: unlike scratch semaphores it is safe
    against skewed kernel entry — a fast peer's signal cannot alias into
    whatever kernel this device is still running.
    """
    if sem is None:
        sem = pltpu.get_barrier_semaphore()
    n = num_ranks(axis)
    me = rank(axis)
    left = jax.lax.rem(me + n - 1, n)
    right = jax.lax.rem(me + 1, n)
    notify(sem, left, axis=axis, ctx=ctx, inc=_skewed_barrier_inc(axis))
    notify(sem, right, axis=axis, ctx=ctx)
    wait(sem, 2)


def barrier(team):
    """Barrier over a :class:`~triton_dist_tpu.lang.teams.Team`
    (reference ``libshmem_device.barrier(team)`` :126): every team PE
    signals every other and waits for the full team count on the
    collective-id-keyed barrier semaphore.

    NVSHMEM's ``barrier`` implies quiet (outstanding puts complete);
    here put completion is certified per-DMA by the receiver's
    ``recv_sem`` — this barrier orders *kernel progress* only, which
    makes it the same operation as :func:`sync_all` scoped to a team
    (the delta :func:`quiet` documents).
    """
    sem = pltpu.get_barrier_semaphore()
    n = team.n_pes()
    for pe in range(n):
        pltpu.semaphore_signal(
            sem, inc=1,
            device_id=team.device_id(pe),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(sem, n)


# Granularity tiers (one core drives the kernel — see the put tiers).
barrier_block = barrier
barrier_warp = barrier
barrier_all_block = barrier_all
barrier_all_vec = barrier_all
barrier_all_warp = barrier_all
barrier_all_wave = barrier_all
barrier_all_wg = barrier_all

# NVSHMEM splits barrier_all (quiet + sync) from sync_all (sync only).
# On TPU put completion is the receiver's recv_sem, never a sender-side
# global drain, so the split collapses: barrier_all IS sync-only, and
# sync_all is the same function (documented in barrier()/quiet()).
sync_all = barrier_all
sync_all_block = barrier_all
sync_all_warp = barrier_all

# Team sync tiers: barrier(team) is already sync-only (see above).
team_sync_block = barrier
team_sync_warp = barrier


# ---------------------------------------------------------------------------
# Team queries — function forms of lang.teams.Team's methods, matching
# the reference's flat-function surface (``team_my_pe`` :69,
# ``team_n_pes`` :74, ``team_translate_pe`` :475).
# ---------------------------------------------------------------------------

def team_my_pe(team):
    return team.my_pe()


def team_n_pes(team) -> int:
    return team.n_pes()


def team_translate_pe(src_team, pe, dest_team):
    return src_team.translate_pe(pe, dest_team)


# ---------------------------------------------------------------------------
# Local copies (HBM<->VMEM staging helpers)
# ---------------------------------------------------------------------------

def local_copy(src_ref, dst_ref):
    """Synchronous local DMA (for ANY/HBM-space refs)."""
    pltpu.sync_copy(src_ref, dst_ref)


def local_copy_async(src_ref, dst_ref, sem, *, start: bool = True):
    copy = pltpu.make_async_copy(src_ref, dst_ref, sem)
    if start:
        copy.start()
    return copy


# ---------------------------------------------------------------------------
# Documented platform impossibilities.
#
# These reference symbols expose raw device pointers or vendor-runtime
# state; Pallas has no device-pointer type — remote addressing is the
# DMA descriptor's ``device_id`` — so they cannot exist on TPU. They
# raise (rather than being absent) so reference-surface callers get the
# redesign pointer instead of an AttributeError.
# ---------------------------------------------------------------------------

def remote_ptr(local_ref, peer):
    """Reference ``libshmem_device.remote_ptr(ptr, pe)``: translate a
    symmetric address to a peer's raw pointer for direct ld/st. No TPU
    analogue — remote memory is reached only through DMA descriptors
    (:func:`remote_put`) and semaphore signals (:func:`notify`)."""
    raise NotImplementedError(
        "TPU has no raw remote pointers; address peers via remote_put/"
        "notify device_id (docs/primitives.md)")


def remote_mc_ptr(team, local_ref):
    """Reference ``libshmem_device.remote_mc_ptr`` (NVLS multicast
    pointer): no ICI analogue — multimem stores do not exist; one-shot
    multicast is expressed as the full-mesh push schedule
    (:func:`fcollect`, ``ops/allreduce.py`` one-shot)."""
    raise NotImplementedError(
        "no ICI multicast pointer; use the full-mesh push schedules "
        "(fcollect / ops.allreduce one-shot)")


def set_rocshmem_ctx(ctx):
    """Reference ``libshmem_device.set_rocshmem_ctx`` (ROCSHMEM device
    context registration): vendor-runtime state with no TPU counterpart
    — Mosaic kernels carry their communication identity in
    ``collective_id`` CompilerParams (``lang/pallas_helpers.py``)."""
    raise NotImplementedError(
        "no device SHMEM context on TPU; collective identity is the "
        "kernel's collective_id (lang/pallas_helpers.core_call)")
