"""Low-latency collective family: fast AllGather + slot-parity A2A.

Reference: ``python/triton_dist/kernels/nvidia/low_latency_allgather.py``
(``create_fast_allgather_context`` :798-847 with pull / push_2d /
push_3d schedules) and ``low_latency_all_to_all_v2.py`` (:156 dispatch,
:360 combine — double-buffered signal slots + optional fp8 on-wire
quant).

TPU redesign:

- **fast_allgather**: latency-optimal schedules for small (decode-time)
  messages. ``push_1d`` = direct put to all n-1 peers (one hop, n-1
  fan-out). ``push_2d``/``push_3d`` factor the rank grid into 2/3
  virtual dimensions: phase p pushes the (growing) block along one
  dimension only, so per-rank fan-out drops to Σ(dims-1) at the cost of
  extra hops — the right trade when the message is latency-bound. The
  reference's ``pull`` mode has no TPU analogue (Mosaic remote DMA is
  push-only); requesting it raises.
- **ll_a2a**: the decode-path all-to-all. Payload rows are quantized
  *inside the kernel* on the way into the send buffer (per-row absmax
  scale, int8/fp8 wire dtype) and dequantized on arrival — the
  reference's in-kernel online quant. Signal slots are parity-indexed
  by a host-side step counter so back-to-back decode steps never alias
  a stale arrival from step k with step k+1's wait (the v2
  double-buffer, ``low_latency_all_to_all_v2.py:156,360``).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import triton_dist_tpu.lang as dl
from triton_dist_tpu.lang import core_call
from triton_dist_tpu.parallel.mesh import MeshContext
from triton_dist_tpu.utils.distributed import use_interpret


def _factor(n: int, ndims: int) -> Tuple[int, ...]:
    """Near-balanced factorization of n into ndims factors."""
    dims = []
    rem = n
    for d in range(ndims, 1, -1):
        f = max(1, round(rem ** (1.0 / d)))
        while rem % f:
            f -= 1
        dims.append(f)
        rem //= f
    dims.append(rem)
    return tuple(dims)


def _push_nd_kernel(x_ref, out_ref, send_sem, recv_sem, *, axis: str,
                    ctx: MeshContext, dims: Sequence[int]):
    """Phase p: every rank pushes its current block (all chunks gathered
    so far) to the dims[p]-1 peers that differ only in virtual
    coordinate p. After phase p the block spans Π dims[:p+1] chunks."""
    n = 1
    for d in dims:
        n *= d
    me = dl.rank(axis)
    csize = x_ref.shape[0]

    # Virtual coordinates of me: row-major over dims.
    strides = []
    s = 1
    for d in reversed(dims):
        strides.append(s)
        s *= d
    strides = list(reversed(strides))  # stride of each dim

    dl.local_copy(x_ref, out_ref.at[pl.ds(me * csize, csize)])
    dl.barrier_all(axis, ctx=ctx)

    block = 1      # chunks gathered so far (consecutive in my dim walk)
    sem_i = 0
    for p in reversed(range(len(dims))):   # innermost (fastest) first
        d = dims[p]
        stride = strides[p]
        if d == 1:
            continue
        my_c = jax.lax.rem(jax.lax.div(me, stride), d)
        # My block start: my own chunk region for the dims processed so
        # far. Blocks are unions of chunks {me with coords p' (done)
        # freed}; since "done" dims are the faster-varying ones, the
        # block is NOT contiguous in rank order unless stride juggling —
        # send chunk-by-chunk instead (simple, still few peers).
        for off in range(1, d):
            peer_c = jax.lax.rem(my_c + off, d)
            peer = me + (peer_c - my_c) * stride
            for b in range(block):
                # b-th chunk of my current block: ranks differing from
                # me only in already-done (faster) dims.
                src_rank = _block_rank(me, b, dims, strides, p)
                chunk = out_ref.at[pl.ds(src_rank * csize, csize)]
                dl.remote_put(chunk, chunk, send_sem.at[sem_i],
                              recv_sem.at[p], peer, axis=axis, ctx=ctx)
            sem_i += 1
        # Wait the (d-1)*block inbound chunks of this phase.
        dl.wait_arrivals(recv_sem.at[p], x_ref, (d - 1) * block)
        block *= d

    # Drain sends: one slot per (phase, offset), `block` puts each.
    block = 1
    si = 0
    for p in reversed(range(len(dims))):
        d = dims[p]
        if d == 1:
            continue
        for off in range(1, d):
            dl.wait_arrivals(send_sem.at[si], x_ref, block)
            si += 1
        block *= d


def _block_rank(me, b, dims: Sequence[int], strides: Sequence[int],
                upto: int):
    """Rank holding the b-th chunk of my current block: my coordinates
    with the already-processed (faster, index > upto) dims replaced by
    b's digits."""
    r = me
    bb = b
    for p in reversed(range(len(dims))):
        if p <= upto:
            break
        d, stride = dims[p], strides[p]
        my_c = jax.lax.rem(jax.lax.div(r, stride), d)
        digit = bb % d
        bb //= d
        r = r + (digit - my_c) * stride
    return r


def fast_allgather(x, *, ctx: MeshContext, axis: str = "tp",
                   mode: str = "push_1d", force_kernel: bool = False):
    """Latency-optimized AllGather for small messages (decode path).

    mode: "push_1d" (direct, 1 hop), "push_2d" / "push_3d" (factored
    grid, fewer sends per rank, more hops). Reference
    ``create_fast_allgather_context`` modes; "pull" is not expressible
    with push-only TPU remote DMA.
    """
    n = ctx.size(axis)
    if n == 1 and not force_kernel:
        return x
    if mode == "pull":
        raise NotImplementedError(
            "TPU remote DMA is push-only; use push_1d/2d/3d "
            "(reference pull mode reads peer buffers, "
            "low_latency_allgather.py:798)")
    if mode == "push_1d":
        from triton_dist_tpu.ops.allgather import all_gather
        return all_gather(x, ctx=ctx, axis=axis, mode="full_mesh",
                          force_kernel=force_kernel)
    ndims = {"push_2d": 2, "push_3d": 3}.get(mode)
    if ndims is None:
        raise ValueError(f"unknown fast_allgather mode {mode!r}")
    dims = _factor(n, ndims)
    max_fanout = sum(d - 1 for d in dims if d > 1)
    kernel = functools.partial(_push_nd_kernel, axis=axis, ctx=ctx,
                               dims=dims)
    return core_call(
        kernel,
        comm=True,
        out_shape=jax.ShapeDtypeStruct(
            (n * x.shape[0],) + tuple(x.shape[1:]), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((max(max_fanout, 1),)),  # sends
            pltpu.SemaphoreType.DMA((len(dims),)),           # per phase
        ],
    )(x)


# ---------------------------------------------------------------------------
# Low-latency A2A with slot parity + in-kernel quantization
# ---------------------------------------------------------------------------

# Scale-column width on the wire: HBM slices on hardware must align to
# the 128-lane tiling, interpret mode keeps width 1 (its buffers starve
# past ~64 KB and it has no tiling constraint). Tests override this to
# exercise the HARDWARE layout under interpret (the divergence point
# must not be CPU-untestable).
_SCALE_WIDTH_OVERRIDE = None


def _scale_width() -> int:
    if _SCALE_WIDTH_OVERRIDE is not None:
        return _SCALE_WIDTH_OVERRIDE
    return 1 if use_interpret() else 128


def wire_max(dtype) -> float:
    """Largest representable magnitude of the wire dtype."""
    d = jnp.dtype(dtype)
    if d == jnp.int8:
        return 127.0
    return float(jnp.finfo(d).max)


def quantize_rows(v, wire_dtype):
    """Per-row absmax quantization: v (…, d) float → (payload, scale).
    THE wire recipe — in-kernel, n==1, and XLA debug paths all share it
    so they cannot diverge numerically."""
    v = v.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(v), axis=-1, keepdims=True) / wire_max(wire_dtype),
        1e-12)
    q = v / scale
    if jnp.dtype(wire_dtype) == jnp.int8:
        q = jnp.round(q)
    return q.astype(wire_dtype), scale


def wire_roundtrip(x, wire_dtype):
    """Quantize + immediately dequantize (the n == 1 short-circuit)."""
    q, scale = quantize_rows(x, wire_dtype)
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


def _wire_exchange(x_src, out_dst, qout, sout, qin, sin, qx, sx, qv,
                   send_sem, recv_sem, *, axis: str, ctx: MeshContext,
                   n: int, wire_dtype):
    """THE wire protocol, shared by the single-step and multi-step
    kernels: stage+quantize each destination chunk (each peer's put
    fires the moment its chunk is staged, so quantization of later
    chunks overlaps wire time of earlier ones), paired payload/scale
    puts, 2(n-1) arrival waits, dequantize into the output, drain
    sends.

    x_src(r)/out_dst(r): refs of the chunk for/from rank r;
    qout/sout: (n, ...) outgoing staging; qin/sin: (n, ...) inbound
    slots (the caller picks the parity slice); send_sem: (2(n-1),)
    slice; recv_sem: one slot."""
    me = dl.rank(axis)

    def stage(dst_rank):
        pltpu.sync_copy(x_src(dst_rank), qv)
        q, scale = quantize_rows(qv[...], wire_dtype)
        qx[...] = q
        # Scales ride lane-aligned (col 0 is the value): HBM slices on
        # hardware must align to the 128-lane tiling. Interpret mode
        # keeps width 1 — its buffers starve past ~64 KB and it has no
        # tiling constraint.
        sx[...] = jnp.broadcast_to(scale, sx.shape)
        pltpu.sync_copy(qx, qout.at[dst_rank])
        pltpu.sync_copy(sx, sout.at[dst_rank])

    copies = []
    for off in range(1, n):
        peer = jax.lax.rem(me + off, n)
        stage(peer)
        copies.append(dl.remote_put(
            qout.at[peer], qin.at[me], send_sem.at[2 * (off - 1)],
            recv_sem, peer, axis=axis, ctx=ctx))
        copies.append(dl.remote_put(
            sout.at[peer], sin.at[me], send_sem.at[2 * (off - 1) + 1],
            recv_sem, peer, axis=axis, ctx=ctx))

    # My own chunk, staged last (it has no wire to catch), crosses to
    # the inbound side locally.
    stage(me)
    pltpu.sync_copy(qout.at[me], qin.at[me])
    pltpu.sync_copy(sout.at[me], sin.at[me])

    # 2(n-1) slot arrivals (payload + scale per peer); DMA semaphores
    # count transfer units, so the waits are order-free.
    for _ in range(n - 1):
        dl.wait_arrivals(recv_sem, qin.at[0], 1)
        dl.wait_arrivals(recv_sem, sin.at[0], 1)

    # Dequantize the inbound side into the output.
    for r in range(n):
        pltpu.sync_copy(qin.at[r], qx)
        pltpu.sync_copy(sin.at[r], sx)
        qv[...] = (qx[...].astype(jnp.float32) * sx[:, :1]
                   ).astype(qv.dtype)
        pltpu.sync_copy(qv, out_dst(r))

    for copy in copies:
        copy.wait_send()


def _ll_a2a_kernel(x_ref, out_ref, qbuf, sbuf, qx, sx, qv, send_sem,
                   recv_sem, *, axis: str, ctx: MeshContext, n_ranks: int,
                   slot: int, wire_dtype):
    """One exchange. Buffers are indexed [side] (0 = outgoing, 1 =
    inbound — an arrival must never overwrite an outgoing chunk that
    hasn't left yet); only the SEMAPHORES carry the step-slot parity.
    In this allocation model (fresh XLA output buffers per call + full
    drain + entry barrier) parity is defense-in-depth; the multi-step
    :func:`_ll_a2a_steps_kernel` is where it is load-bearing."""
    dl.barrier_all(axis, ctx=ctx)
    _wire_exchange(lambda r: x_ref.at[r], lambda r: out_ref.at[r],
                   qbuf.at[0], sbuf.at[0], qbuf.at[1], sbuf.at[1],
                   qx, sx, qv, send_sem.at[slot], recv_sem.at[slot],
                   axis=axis, ctx=ctx, n=n_ranks, wire_dtype=wire_dtype)


def _ll_a2a_steps_kernel(x_ref, out_ref, qin, sin, qout, sout, qx, sx,
                         qv, send_sem, recv_sem, credit_sem, *,
                         axis: str, ctx: MeshContext, n_ranks: int,
                         n_steps: int, wire_dtype):
    """Multi-step A2A loop in ONE kernel invocation: slot parity is
    LOAD-BEARING and a credit protocol replaces per-step barriers.

    Why in-kernel: scratch/DMA semaphores are physical registers
    allocated per kernel — across *invocations* a fast peer's signal
    can land while this device still runs a different kernel whose
    allocation aliases the same register, so cross-call credit
    protocols are unsound on TPU and every invocation needs its entry
    rendezvous (docs/primitives.md rule 2). Inside one invocation the
    registers are live for the whole loop, so steps amortize ONE entry
    barrier over S steps:

    - step s uses inbound slot parity ``p = s % 2`` (buffers AND
      semaphores);
    - before writing peers' parity-p slots at step s >= 2, wait n-1
      CREDITS on ``credit_sem[p]`` — each granted by a peer at the end
      of its step s-2 after consuming that slot (the flow control the
      reference's double-buffered signal slots imply,
      ``low_latency_all_to_all_v2.py:156,360``);
    - after consuming step s, grant credits for parity p — except in
      the last two steps, so every semaphore drains by kernel exit.
    """
    s = pl.program_id(0)
    n = n_ranks
    me = dl.rank(axis)
    p = jax.lax.rem(s, 2)

    @pl.when(s == 0)
    def _():
        dl.barrier_all(axis, ctx=ctx)

    # Flow control: peers' parity-p inbound slots are free once each
    # peer granted its step-(s-2) credit.
    @pl.when(s >= 2)
    def _():
        dl.wait(credit_sem.at[p], n - 1)

    _wire_exchange(lambda r: x_ref.at[s, r], lambda r: out_ref.at[s, r],
                   qout, sout, qin.at[p], sin.at[p], qx, sx, qv,
                   send_sem.at[p], recv_sem.at[p],
                   axis=axis, ctx=ctx, n=n, wire_dtype=wire_dtype)

    # Grant parity-p credits for step s+2 (skip the final two steps so
    # the credit semaphores drain before kernel exit).
    @pl.when(s < n_steps - 2)
    def _():
        for off in range(1, n):
            peer = jax.lax.rem(me + off, n)
            dl.notify(credit_sem.at[p], peer, axis=axis, ctx=ctx)


def ll_a2a_steps(xs, *, ctx: MeshContext, axis: str = "ep",
                 wire_dtype=jnp.int8, force_kernel: bool = False):
    """S back-to-back low-latency A2A steps in ONE kernel invocation —
    the persistent-workspace decode loop: one entry barrier total,
    slot-parity wire buffers reused across steps, credit-based flow
    control instead of per-step rendezvous (see the kernel docstring).

    xs: (S, n, C, d); returns (S, n, C, d), step s matching
    ``ll_a2a(xs[s], step=s)`` bit-for-bit. S >= 2 (a single step has
    nothing to amortize — call :func:`ll_a2a`).
    """
    n = ctx.size(axis)
    n_steps, nx, c, d = xs.shape
    if n_steps < 2:
        raise ValueError("ll_a2a_steps needs S >= 2; use ll_a2a")
    if nx != n:
        raise ValueError(f"dim 1 {nx} != axis size {n}")
    if n == 1 and not force_kernel:
        return jax.vmap(lambda x: wire_roundtrip(x, wire_dtype))(xs)
    # force_kernel with n == 1 runs the full multi-step kernel (stage,
    # parity slots, credits degenerate to no peers) — the single-chip
    # lowering check the battery uses.
    scale_w = _scale_width()
    kernel = functools.partial(
        _ll_a2a_steps_kernel, axis=axis, ctx=ctx, n_ranks=n,
        n_steps=n_steps, wire_dtype=wire_dtype)
    out, *_ = core_call(
        kernel,
        comm=True,
        grid=(n_steps,),
        out_shape=(
            jax.ShapeDtypeStruct((n_steps, n, c, d), xs.dtype),
            jax.ShapeDtypeStruct((2, n, c, d), wire_dtype),    # qin
            jax.ShapeDtypeStruct((2, n, c, scale_w), jnp.float32),
            jax.ShapeDtypeStruct((n, c, d), wire_dtype),       # qout
            jax.ShapeDtypeStruct((n, c, scale_w), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tuple(pl.BlockSpec(memory_space=pltpu.HBM)
                        for _ in range(5)),
        scratch_shapes=[
            pltpu.VMEM((c, d), wire_dtype),         # qx
            pltpu.VMEM((c, scale_w), jnp.float32),  # sx
            pltpu.VMEM((c, d), xs.dtype),           # qv
            pltpu.SemaphoreType.DMA((2, max(2 * (n - 1), 1))),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),      # credits
        ],
    )(xs)
    return out


def ll_a2a(x, *, ctx: MeshContext, axis: str = "ep", step=0,
           wire_dtype=jnp.int8, force_kernel: bool = False):
    """Slot-parity low-latency all-to-all with in-kernel quantization.

    x: (n, C, d) — x[r] goes to rank r; returns (n, C, d) received
    (dequantized). ``step`` is the host-side decode step counter; its
    parity picks the signal/buffer slot so two back-to-back calls never
    alias (reference v2 double-buffering). Wire format: ``wire_dtype``
    payload + per-row float32 scales.

    Per-destination chunks stage whole in VMEM (decode messages are
    small; C·d up to ~512K elements). Larger payloads belong on the
    bandwidth-bound :func:`~triton_dist_tpu.ops.all_to_all`.
    """
    n = ctx.size(axis)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {n}")
    _, c, d = x.shape
    slot = int(step) % 2
    if n == 1 and not force_kernel:
        # Wire round-trip for parity with the distributed numerics.
        return wire_roundtrip(x, wire_dtype)

    scale_w = _scale_width()
    kernel = functools.partial(
        _ll_a2a_kernel, axis=axis, ctx=ctx, n_ranks=n, slot=slot,
        wire_dtype=wire_dtype)
    out, _, _ = core_call(
        kernel,
        comm=True,
        out_shape=(
            jax.ShapeDtypeStruct((n, c, d), x.dtype),
            jax.ShapeDtypeStruct((2, n, c, d), wire_dtype),
            jax.ShapeDtypeStruct((2, n, c, scale_w), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(
            # Explicit HBM: with no pipelined output the compiler may
            # try to stack-allocate these full-size buffers in VMEM.
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ),
        scratch_shapes=[
            pltpu.VMEM((c, d), wire_dtype),        # qx wire tile
            pltpu.VMEM((c, scale_w), jnp.float32),  # sx scales tile
            pltpu.VMEM((c, d), x.dtype),           # qv dequant tile
            pltpu.SemaphoreType.DMA((2, max(2 * (n - 1), 1))),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )(x)
    return out
