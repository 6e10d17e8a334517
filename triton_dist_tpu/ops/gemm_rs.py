"""Fused GEMM + ReduceScatter (tensor-parallel row-linear forward).

Reference: ``python/triton_dist/kernels/nvidia/gemm_reduce_scatter.py``
(producer GEMM signalling per-tile, :233/:384) + ``reduce_scatter.py``
consumer; host API ``gemm_rs`` (:754).

TPU redesign — a ring-reduce fused into the GEMM grid: step ``s``
computes the partial product for the output chunk owned by device
``c = (me - s - 1) % n``, adds the partial received from the left
neighbour (which already accumulated s upstream devices), and forwards
the running sum right. After ``n`` steps the fully-reduced chunk ``me``
is written out. Compute of step ``s+1`` overlaps the transfer of step
``s``'s running sum.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import triton_dist_tpu.lang as dl
from triton_dist_tpu.lang import core_call, overlap
from triton_dist_tpu.parallel.mesh import MeshContext

# Overlap-schedule config space (lang/overlap.py): "rs" is the
# reduce-scatter-producer ring order — step s computes chunk
# (me - s - 1) % n so each chunk's running sum visits ranks in ring
# sequence, finishing at its owner, with compute hiding every hop.
# "identity" is the unswizzled baseline: the full partial GEMM first,
# then a separate ring reduce-scatter — compute and communication
# fully serialized.
SWIZZLE_MODES = ("rs", "identity")


@dataclasses.dataclass(frozen=True)
class GemmRSContext:
    """Analogue of the reference's ``create_gemm_rs_context``
    (``gemm_reduce_scatter.py:51``)."""
    mesh: MeshContext
    axis: str = "tp"
    block_m: int = 256
    block_n: int = 256
    block_k: int = 512
    out_dtype: Optional[jnp.dtype] = None
    swizzle_mode: str = "rs"
    # Staging depth for the INBOUND running sum (this op's analogue of
    # ag_gemm's panel prefetch): 1 = sync-copy the received tile at its
    # fold point; 2 (and the 0 = auto default) = start the HBM->VMEM
    # copy at the tile's first K-block so it rides under the whole MXU
    # contraction. Depth 3 clamps to 2 — one tile is consumed per fold,
    # so a single copy of lead time covers the load.
    prefetch_depth: int = 0


def create_gemm_rs_context(mesh: MeshContext, axis: str = "tp",
                           block_m: int = 256, block_n: int = 256,
                           block_k: int = 512, out_dtype=None,
                           swizzle_mode: str = "rs",
                           prefetch_depth: int = 0) -> GemmRSContext:
    if swizzle_mode not in SWIZZLE_MODES:
        raise ValueError(f"unknown gemm_rs swizzle_mode {swizzle_mode!r} "
                         f"(expected one of {SWIZZLE_MODES})")
    if not 0 <= prefetch_depth <= 3:
        raise ValueError(f"prefetch_depth must be 0 (auto) or 1..3, got "
                         f"{prefetch_depth}")
    return GemmRSContext(mesh=mesh, axis=axis, block_m=block_m,
                         block_n=block_n, block_k=block_k,
                         out_dtype=out_dtype, swizzle_mode=swizzle_mode,
                         prefetch_depth=prefetch_depth)


def gemm_rs_ref(a, b, *, axis: str = "tp", **_):
    """Oracle: einsum + psum_scatter."""
    partial = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.psum_scatter(partial, axis, scatter_dimension=0,
                                tiled=True).astype(a.dtype)


def _rs_blocks(ctx: GemmRSContext, m_loc, n_dim, k_loc):
    """Shared tile-size clamp + divisibility check for both gemm_rs
    kernel paths. tm snaps down to a divisor of the ragged local M
    (``ag_gemm``'s policy — the two ops share one token count in a TP
    layer, so a prompt length ``ag_gemm`` accepts must not be refused
    here)."""
    tm = min(ctx.block_m, m_loc)
    tn = min(ctx.block_n, n_dim)
    tk = min(ctx.block_k, k_loc)
    while tm > 1 and m_loc % tm:
        tm //= 2
    if m_loc % tm or n_dim % tn or k_loc % tk:
        raise ValueError(
            f"block sizes (block_m={tm}, block_n={tn}, block_k={tk}) must "
            f"divide (M_loc={m_loc}, N={n_dim}, K_loc={k_loc})")
    return tm, tn, tk, m_loc // tm, n_dim // tn, k_loc // tk


def _gemm_rs_kernel(a_ref, b_ref, w_ref, o_ref, recv_hbm, send_hbm,
                    acc_v, tmp_v, out_v, send_sem, recv_sem, tmp_sem, *,
                    axis: str, ctx: MeshContext, m_loc: int, tm: int,
                    tn: int, n_ranks: int, n_buf: int, sim: bool = False):
    """``sim=True`` (single-chip overlap proxy): the ring runs against
    myself — sends, waits, adds, and per-step traffic are all real, but
    the received partial is folded with the runtime weight ``w_ref``
    (0 in sim, 1 in real — a value the compiler cannot fold away), so
    the per-chunk outputs stay the verifiable local GEMM result.

    ``n_buf`` (resolved from ``ctx.prefetch_depth``): 2 = the received
    running-sum tile starts its HBM->VMEM copy at the tile's FIRST
    K-block and is only waited at the fold (the load hides under the
    contraction); 1 = sync copy at the fold point (the unprefetched
    baseline the knob is benchmarked against)."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    n_i = pl.num_programs(1)
    n_j = pl.num_programs(2)
    n_k = pl.num_programs(3)
    me = dl.rank(axis)
    n = n_ranks
    right = me if sim else jax.lax.rem(me + 1, n)

    first = jnp.logical_and(
        s == 0, jnp.logical_and(i == 0, jnp.logical_and(j == 0, kk == 0)))

    @pl.when(first)
    def _():
        dl.barrier_tile(axis, ctx=ctx)

    chunk_start = jnp.logical_and(
        i == 0, jnp.logical_and(j == 0, kk == 0))

    @pl.when(jnp.logical_and(s > 0, chunk_start))
    def _():
        # Running sum for this step's chunk arrives from the left.
        dl.wait_arrivals(recv_sem.at[s - 1], recv_hbm.at[s - 1], 1)

    if n_buf > 1:
        @pl.when(jnp.logical_and(s > 0, kk == 0))
        def _():
            # Prefetch this tile's inbound partial under the K loop
            # (arrival was certified at chunk start, which runs earlier
            # in this same body for i == j == 0).
            pltpu.make_async_copy(
                recv_hbm.at[s - 1, pl.ds(i * tm, tm), pl.ds(j * tn, tn)],
                tmp_v, tmp_sem).start()

    # Partial product for this (tile, K-block), accumulated over kk.
    @pl.when(kk == 0)
    def _():
        acc_v[...] = jnp.zeros_like(acc_v)

    acc_v[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _():
        @pl.when(s > 0)
        def _():
            # Add the accumulated partial from upstream devices (weight
            # 1.0; the sim self-ring weights it 0.0 — same VPU work).
            if n_buf > 1:
                pltpu.make_async_copy(tmp_v, tmp_v, tmp_sem).wait()
            else:
                pltpu.sync_copy(
                    recv_hbm.at[s - 1, pl.ds(i * tm, tm),
                                pl.ds(j * tn, tn)],
                    tmp_v)
            acc_v[...] = acc_v[...] + tmp_v[...] * w_ref[0, 0]

        @pl.when(s < n - 1)
        def _():
            pltpu.sync_copy(acc_v, send_hbm.at[s, pl.ds(i * tm, tm),
                                               pl.ds(j * tn, tn)])

            # Chunk complete → forward the running sum right.
            @pl.when(jnp.logical_and(i == n_i - 1, j == n_j - 1))
            def _():
                dl.remote_put(send_hbm.at[s], recv_hbm.at[s],
                              send_sem.at[s], recv_sem.at[s], right,
                              axis=axis, ctx=ctx)

        if sim:
            # Every chunk's (local-partial) result is emitted so the
            # whole output is checkable against the plain GEMM.
            c = overlap.chunk_at(s, me, n, "rs")
            out_v[...] = acc_v[...].astype(out_v.dtype)
            pltpu.sync_copy(out_v, o_ref.at[pl.ds(c * m_loc + i * tm, tm),
                                            pl.ds(j * tn, tn)])
        else:
            @pl.when(s == n - 1)
            def _():
                # Fully reduced tile of my own chunk (manual store: the
                # output is only defined at the last ring step, so it
                # cannot be a pipelined BlockSpec). Note at s == n-1 the
                # recv add above (s > 0) has already folded in the
                # upstream partials; with n == 1 (forced rankless) acc
                # is the whole result.
                out_v[...] = acc_v[...].astype(out_v.dtype)
                pltpu.sync_copy(out_v, o_ref.at[pl.ds(i * tm, tm),
                                                pl.ds(j * tn, tn)])

    last = jnp.logical_and(
        s == n - 1,
        jnp.logical_and(i == n_i - 1,
                        jnp.logical_and(j == n_j - 1, kk == n_k - 1)))

    @pl.when(last)
    def _():
        for t in range(n - 1):
            dl.wait_arrivals(send_sem.at[t], recv_hbm.at[0], 1)


def _gemm_rs_identity_kernel(a_ref, b_ref, w_ref, o_ref, part_hbm,
                             recv_hbm, send_hbm, acc_v, tmp_v, sum_v,
                             out_v, send_sem, recv_sem, *, axis: str,
                             ctx: MeshContext, m_loc: int, tm: int,
                             tn: int, n_ranks: int, sim: bool = False):
    """Unswizzled baseline ("identity" schedule): the FULL partial GEMM
    first — chunks walked in plain 0..n-1 order into a partials
    workspace — then a serialized ring reduce-scatter at the last grid
    body. Compute and communication never overlap: this is the schedule
    the "rs" swizzle is parity-tested and benchmarked against.

    Interpret-mesh safety: every ring put sits in the final body's
    static hop loop — identical sites in identical order on all ranks
    (the module-level convergence rule in ``lang/overlap.py``).
    ``sim=True`` matches the ring kernel's proxy contract: self-targeted
    hops, received partials runtime-weighted by ``w_ref`` (0), per-chunk
    local results emitted across the full (m_full, N) output."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    n_i = pl.num_programs(1)
    n_j = pl.num_programs(2)
    n_k = pl.num_programs(3)
    me = dl.rank(axis)
    n = n_ranks
    right = me if sim else jax.lax.rem(me + 1, n)

    first = jnp.logical_and(
        s == 0, jnp.logical_and(i == 0, jnp.logical_and(j == 0, kk == 0)))

    @pl.when(first)
    def _():
        dl.barrier_tile(axis, ctx=ctx)

    @pl.when(kk == 0)
    def _():
        acc_v[...] = jnp.zeros_like(acc_v)

    acc_v[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _():
        # Chunk s's partial tile is complete — bank it for the reduce
        # phase (chunk id IS the grid step under "identity").
        pltpu.sync_copy(acc_v, part_hbm.at[s, pl.ds(i * tm, tm),
                                           pl.ds(j * tn, tn)])
        if sim:
            out_v[...] = acc_v[...].astype(out_v.dtype)
            pltpu.sync_copy(out_v, o_ref.at[pl.ds(s * m_loc + i * tm, tm),
                                            pl.ds(j * tn, tn)])

    last = jnp.logical_and(
        s == n - 1,
        jnp.logical_and(i == n_i - 1,
                        jnp.logical_and(j == n_j - 1, kk == n_k - 1)))

    @pl.when(last)
    def _():
        # Serialized ring reduce-scatter over the banked partials: hop t
        # folds and forwards the running sum for chunk (me - t - 1) % n
        # — the same visit order as the fused "rs" schedule, but with
        # every hop's latency fully exposed (nothing left to compute).
        for t in range(n):
            c_t = overlap.chunk_at(t, me, n, "rs")
            if t > 0:
                dl.wait_arrivals(recv_sem.at[t - 1], recv_hbm.at[t - 1],
                                 1)
            for ti in range(n_i):
                for tj in range(n_j):
                    rows, cols = pl.ds(ti * tm, tm), pl.ds(tj * tn, tn)
                    pltpu.sync_copy(
                        part_hbm.at[c_t, rows, cols], sum_v)
                    if t > 0:
                        pltpu.sync_copy(recv_hbm.at[t - 1, rows, cols],
                                        tmp_v)
                        sum_v[...] = sum_v[...] + tmp_v[...] * w_ref[0, 0]
                    if t < n - 1:
                        pltpu.sync_copy(sum_v,
                                        send_hbm.at[t, rows, cols])
                    elif not sim:
                        out_v[...] = sum_v[...].astype(out_v.dtype)
                        pltpu.sync_copy(out_v, o_ref.at[rows, cols])
            if t < n - 1:
                dl.remote_put(send_hbm.at[t], recv_hbm.at[t],
                              send_sem.at[t], recv_sem.at[t], right,
                              axis=axis, ctx=ctx)
        for t in range(n - 1):
            dl.wait_arrivals(send_sem.at[t], recv_hbm.at[0], 1)


def _gemm_rs_2d_kernel(a_ref, b_ref, o_ref, recv_hbm, send_hbm, opart,
                       osend_hbm, acc_v, tmp_v, out_v, isend, irecv,
                       osend, orecv, *, inner_axis: str, outer_axis: str,
                       ctx: MeshContext, tm: int, tn: int,
                       n_in: int, n_o: int):
    """Hierarchical (outer x inner) fused GEMM+ReduceScatter.

    Super-step t ring-reduces — through the producer GEMM, exactly like
    the 1D kernel — the chunks destined for outer group
    ``o_dst = (o + n_o - 1 - t) % n_o``; the finished group-sum crosses
    the slow outer link ONCE to its destination rank, where it is folded
    during the final super-step (my own group, scheduled last so every
    inbound outer transfer hides under n_in chunks of compute).
    Reference: inter-node ``gemm_reduce_scatter.py`` (SURVEY §2.5).
    """
    q = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    n_i = pl.num_programs(1)
    n_j = pl.num_programs(2)
    n_k = pl.num_programs(3)
    o = dl.rank(outer_axis)
    ii = dl.rank(inner_axis)
    t = jax.lax.div(q, n_in)          # super-step (destination group)
    s = jax.lax.rem(q, n_in)          # inner ring step
    o_dst = jax.lax.rem(o + n_o - 1 - t, n_o)
    # (the A rows multiplied this step — inner chunk (ii - s - 1) % n_in
    # of group o_dst — are selected host-side by the a_index BlockSpec)
    i_right = jax.lax.rem(ii + 1, n_in)
    u = t * (n_in - 1) + s - 1        # inner transfer slot (s >= 1)
    last_super = t == n_o - 1         # o_dst == o

    first = jnp.logical_and(q == 0, jnp.logical_and(
        i == 0, jnp.logical_and(j == 0, kk == 0)))

    @pl.when(first)
    def _():
        dl.barrier_tile(inner_axis, ctx=ctx)
        # Outer puts target rank (o + n_o - 1 - t) — up to n_o-1 hops
        # away — so a neighbour-pair barrier is NOT enough: every outer
        # peer must be in-kernel before the first group-sum ships.
        if n_o > 2:
            dl.barrier_all(outer_axis, ctx=ctx)
        else:
            dl.barrier_tile(outer_axis, ctx=ctx)

    chunk_start = jnp.logical_and(
        i == 0, jnp.logical_and(j == 0, kk == 0))

    if n_in > 1:
        @pl.when(jnp.logical_and(s > 0, chunk_start))
        def _():
            # Running sum for this step's chunk arrives from inner-left.
            dl.wait_arrivals(irecv.at[u], recv_hbm.at[u], 1)

    @pl.when(jnp.logical_and(last_super,
                             jnp.logical_and(s == n_in - 1, chunk_start)))
    def _():
        # My own chunk's group-sums from every other outer group landed
        # over the outer link during earlier super-steps.
        for h in range(n_o - 1):
            dl.wait_arrivals(orecv.at[h], opart.at[h], 1)

    @pl.when(kk == 0)
    def _():
        acc_v[...] = jnp.zeros_like(acc_v)

    acc_v[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _():
        if n_in > 1:
            @pl.when(s > 0)
            def _():
                pltpu.sync_copy(
                    recv_hbm.at[u, pl.ds(i * tm, tm), pl.ds(j * tn, tn)],
                    tmp_v)
                acc_v[...] = acc_v[...] + tmp_v[...]

        @pl.when(s < n_in - 1)
        def _():
            pltpu.sync_copy(acc_v, send_hbm.at[t * (n_in - 1) + s,
                                               pl.ds(i * tm, tm),
                                               pl.ds(j * tn, tn)])

            @pl.when(jnp.logical_and(i == n_i - 1, j == n_j - 1))
            def _():
                dl.remote_put(send_hbm.at[t * (n_in - 1) + s],
                              recv_hbm.at[t * (n_in - 1) + s],
                              isend.at[t * (n_in - 1) + s],
                              irecv.at[t * (n_in - 1) + s], i_right,
                              axis=inner_axis, ctx=ctx)

        @pl.when(jnp.logical_and(jnp.logical_not(last_super),
                                 s == n_in - 1))
        def _():
            # Group-sum complete -> stage and ship it over the outer
            # link to rank (o_dst, ii). The sender's super-step t is a
            # unique slot at the receiver: t == (o - o_dst - 1) % n_o.
            pltpu.sync_copy(acc_v, osend_hbm.at[t, pl.ds(i * tm, tm),
                                                pl.ds(j * tn, tn)])

            @pl.when(jnp.logical_and(i == n_i - 1, j == n_j - 1))
            def _():
                dl.remote_put(osend_hbm.at[t], opart.at[t], osend.at[t],
                              orecv.at[t], o_dst, axis=outer_axis,
                              ctx=ctx)

        @pl.when(jnp.logical_and(last_super, s == n_in - 1))
        def _():
            # Fold the n_o-1 inbound group-sums and emit my tile.
            for h in range(n_o - 1):
                pltpu.sync_copy(
                    opart.at[h, pl.ds(i * tm, tm), pl.ds(j * tn, tn)],
                    tmp_v)
                acc_v[...] = acc_v[...] + tmp_v[...]
            out_v[...] = acc_v[...].astype(out_v.dtype)
            pltpu.sync_copy(out_v, o_ref.at[pl.ds(i * tm, tm),
                                            pl.ds(j * tn, tn)])

    last = jnp.logical_and(q == n_o * n_in - 1, jnp.logical_and(
        i == n_i - 1, jnp.logical_and(j == n_j - 1, kk == n_k - 1)))

    @pl.when(last)
    def _():
        if n_in > 1:
            for w in range(n_o * (n_in - 1)):
                dl.wait_arrivals(isend.at[w], recv_hbm.at[0], 1)
        for h in range(n_o - 1):
            dl.wait_arrivals(osend.at[h], opart.at[0], 1)


def _gemm_rs_2d(a, b, ctx: GemmRSContext):
    """Host wrapper: ``ctx.axis`` is an ``(outer, inner)`` tuple."""
    outer_axis, inner_axis = ctx.axis
    mesh = ctx.mesh
    n_o = mesh.size(outer_axis)
    n_in = mesh.size(inner_axis)
    n = n_o * n_in
    m_full, k_loc = a.shape
    _, n_dim = b.shape
    out_dtype = ctx.out_dtype or a.dtype
    if n_o == 1:
        return gemm_rs(a, b, dataclasses.replace(ctx, axis=inner_axis))
    if ctx.swizzle_mode != "rs":
        raise ValueError(
            "the hierarchical (outer, inner) gemm_rs only has the 'rs' "
            f"schedule (got swizzle_mode={ctx.swizzle_mode!r})")
    if m_full % n:
        raise ValueError(f"M={m_full} not divisible by mesh size {n}")
    m_loc = m_full // n
    tm, tn, tk, n_i, n_j, n_k = _rs_blocks(ctx, m_loc, n_dim, k_loc)

    def a_index(q, i, j, kk):
        o = jax.lax.axis_index(outer_axis)
        ii = jax.lax.axis_index(inner_axis)
        t = jax.lax.div(q, n_in)
        s = jax.lax.rem(q, n_in)
        o_dst = jax.lax.rem(o + n_o - 1 - t, n_o)
        c = jax.lax.rem(ii - s - 1 + n_in, n_in)
        return ((o_dst * n_in + c) * n_i + i, kk)

    kernel = functools.partial(
        _gemm_rs_2d_kernel, inner_axis=inner_axis, outer_axis=outer_axis,
        ctx=mesh, tm=tm, tn=tn, n_in=n_in, n_o=n_o)

    n_islots = max(n_o * (n_in - 1), 1)
    out, *_ = core_call(
        kernel,
        comm=True,
        grid=(n_o * n_in, n_i, n_j, n_k),
        out_shape=(
            jax.ShapeDtypeStruct((m_loc, n_dim), out_dtype),
            jax.ShapeDtypeStruct((n_islots, m_loc, n_dim), jnp.float32),
            jax.ShapeDtypeStruct((n_islots, m_loc, n_dim), jnp.float32),
            jax.ShapeDtypeStruct((n_o - 1, m_loc, n_dim), jnp.float32),
            jax.ShapeDtypeStruct((n_o - 1, m_loc, n_dim), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((tm, tk), a_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, tn), lambda q, i, j, kk: (kk, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                        for _ in range(5)),
        scratch_shapes=[
            pltpu.VMEM((tm, tn), jnp.float32),               # acc_v
            pltpu.VMEM((tm, tn), jnp.float32),               # tmp_v
            pltpu.VMEM((tm, tn), out_dtype),                 # out_v
            pltpu.SemaphoreType.DMA((n_islots,)),            # isend
            pltpu.SemaphoreType.DMA((n_islots,)),            # irecv
            pltpu.SemaphoreType.DMA((max(n_o - 1, 1),)),     # osend
            pltpu.SemaphoreType.DMA((max(n_o - 1, 1),)),     # orecv
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * m_full * k_loc * n_dim,
            bytes_accessed=(m_full * k_loc + k_loc * n_dim * n * n_i
                            + m_loc * n_dim) * a.dtype.itemsize,
            transcendentals=0,
        ),
    )(a, b)
    return out


def gemm_rs(a, b, ctx: GemmRSContext, *, force_kernel: bool = False,
            sim_ranks: int = 0):
    """Overlapped per-shard (A @ B) reduce-scattered along ``ctx.axis``
    — see :func:`_gemm_rs_impl` for the full contract.

    Resilience hook wrapper: fault plans count/scope on op
    ``"gemm_rs"``, and the degradation policy
    (``resilience.policy.should_fallback``) re-dispatches through the
    XLA oracle."""
    from triton_dist_tpu.resilience import faults, policy

    with faults.on_op_call("gemm_rs"):
        if (policy.should_fallback("gemm_rs") and not force_kernel
                and not sim_ranks):
            out = gemm_rs_ref(a, b, axis=ctx.axis)
            return out.astype(ctx.out_dtype) if ctx.out_dtype else out
        return _gemm_rs_impl(a, b, ctx, force_kernel=force_kernel,
                             sim_ranks=sim_ranks)


def _gemm_rs_impl(a, b, ctx: GemmRSContext, *, force_kernel: bool = False,
                  sim_ranks: int = 0):
    """Overlapped per-shard (A @ B) reduce-scattered along ``ctx.axis``.

    ``a``: (M, K_loc) — activations, K sharded (row-parallel);
    ``b``: (K_loc, N) — row-parallel weight shard.
    Returns C shard of shape (M / n, N).

    ``sim_ranks > 1`` (requires a size-1 mesh axis): single-chip overlap
    proxy — the ring runs with self-targeted puts at the full schedule
    and traffic; the output is the FULL (M, N) local GEMM (received
    partials are runtime-weighted to zero so every chunk stays
    verifiable). What bench.py measures on one chip.

    ``ctx.axis`` may be an ``(outer, inner)`` tuple for the
    hierarchical dcn x ici form (reference inter-node GEMM+RS): inner
    rings reduce per-group sums which cross the outer link once each
    (see :func:`_gemm_rs_2d_kernel`).
    """
    if isinstance(ctx.axis, (tuple, list)):
        if sim_ranks or force_kernel:
            raise ValueError("sim_ranks/force_kernel apply to the "
                             "single-axis form only")
        return _gemm_rs_2d(a, b, dataclasses.replace(
            ctx, axis=tuple(ctx.axis)))
    mesh = ctx.mesh
    n = mesh.size(ctx.axis)
    m_full, k_loc = a.shape
    _, n_dim = b.shape
    out_dtype = ctx.out_dtype or a.dtype
    sim = False
    if sim_ranks and sim_ranks > 1:
        if n != 1:
            raise ValueError("sim_ranks requires a size-1 mesh axis "
                             f"(got {n} ranks)")
        n, sim = sim_ranks, True
    if n == 1 and not force_kernel:
        # force_kernel=True keeps the pallas pipeline even rankless
        # (single-chip kernel-efficiency benchmarking, like ag_gemm).
        return jnp.dot(a, b, preferred_element_type=jnp.float32
                       ).astype(out_dtype)
    if m_full % n:
        raise ValueError(f"M={m_full} not divisible by axis size {n}")
    m_loc = m_full // n
    tm, tn, tk, n_i, n_j, n_k = _rs_blocks(ctx, m_loc, n_dim, k_loc)
    mode = ctx.swizzle_mode
    # Inbound-partial staging depth: one tile per fold, so anything
    # deeper than classic double buffering clamps to 2 (0 = auto = 2).
    n_buf = 1 if ctx.prefetch_depth == 1 else 2

    def a_index(s, i, j, kk):
        me = jax.lax.axis_index(ctx.axis)
        c = overlap.chunk_at(s, me, n, mode)
        return (c * n_i + i, kk)

    # Runtime fold weight for received partials (see kernel docstring).
    w_recv = jnp.full((1, 1), 0.0 if sim else 1.0, jnp.float32)
    out_rows = m_full if sim else m_loc

    in_specs = [
        pl.BlockSpec((tm, tk), a_index, memory_space=pltpu.VMEM),
        pl.BlockSpec((tk, tn), lambda s, i, j, kk: (kk, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), lambda s, i, j, kk: (0, 0),
                     memory_space=pltpu.VMEM),
    ]
    cost = pl.CostEstimate(
        flops=2 * m_full * k_loc * n_dim,
        bytes_accessed=(m_full * k_loc + k_loc * n_dim * n * n_i
                        + m_loc * n_dim) * a.dtype.itemsize,
        transcendentals=0,
    )
    ring_ws = jax.ShapeDtypeStruct((max(n - 1, 1), m_loc, n_dim),
                                   jnp.float32)

    if mode == "identity":
        kernel = functools.partial(
            _gemm_rs_identity_kernel, axis=ctx.axis, ctx=mesh,
            m_loc=m_loc, tm=tm, tn=tn, n_ranks=n, sim=sim)
        out, *_ = core_call(
            kernel,
            comm=True,
            grid=(n, n_i, n_j, n_k),
            out_shape=(
                jax.ShapeDtypeStruct((out_rows, n_dim), out_dtype),
                jax.ShapeDtypeStruct((n, m_loc, n_dim), jnp.float32),
                ring_ws, ring_ws,
            ),
            in_specs=in_specs,
            out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                            for _ in range(4)),
            scratch_shapes=[
                pltpu.VMEM((tm, tn), jnp.float32),           # acc_v
                pltpu.VMEM((tm, tn), jnp.float32),           # tmp_v
                pltpu.VMEM((tm, tn), jnp.float32),           # sum_v
                pltpu.VMEM((tm, tn), out_dtype),             # out_v
                pltpu.SemaphoreType.DMA((max(n - 1, 1),)),   # send_sem
                pltpu.SemaphoreType.DMA((max(n - 1, 1),)),   # recv_sem
            ],
            cost_estimate=cost,
        )(a, b, w_recv)
        return out

    kernel = functools.partial(
        _gemm_rs_kernel, axis=ctx.axis, ctx=mesh, m_loc=m_loc, tm=tm,
        tn=tn, n_ranks=n, n_buf=n_buf, sim=sim)

    # Ring workspaces are extra outputs (Mosaic forbids HBM scratch on
    # real TPUs); callers discard them.
    out, _recv_ws, _send_ws = core_call(
        kernel,
        comm=True,
        grid=(n, n_i, n_j, n_k),
        out_shape=(
            jax.ShapeDtypeStruct((out_rows, n_dim), out_dtype),
            ring_ws, ring_ws,
        ),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((tm, tn), jnp.float32),               # acc_v
            pltpu.VMEM((tm, tn), jnp.float32),               # tmp_v
            pltpu.VMEM((tm, tn), out_dtype),                 # out_v
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),       # send_sem
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),       # recv_sem
            pltpu.SemaphoreType.DMA(()),                     # tmp_sem
        ],
        cost_estimate=cost,
    )(a, b, w_recv)
    return out


def gemm_rs_tuned(a, b, mesh: MeshContext, *, axis: str = "tp",
                  configs=None, **kw):
    """Autotuned gemm_rs with perf-model pruning (reference:
    ``gemm_perf_model.py`` + ``comm_perf_model.py`` prune every sweep
    before timing): configs whose modeled VMEM cannot lower, or whose
    modeled roofline time is >2x the best candidate's, are vetoed
    without a compile."""
    from triton_dist_tpu import tune
    from triton_dist_tpu.autotuner import autotune
    from triton_dist_tpu.tools.perf_model import (
        gemm_rs_vmem_bytes, gemm_time_model_s,
    )

    if configs is None:
        configs = [
            {"block_m": 1024, "block_n": 128, "block_k": 4096},
            {"block_m": 512, "block_n": 128, "block_k": 4096},
            {"block_m": 512, "block_n": 128, "block_k": 2048},
            {"block_m": 256, "block_n": 256, "block_k": 1024},
            # Overlap-engine sweep (lang/overlap.py knobs): the
            # unprefetched fold (does hiding the partial load under the
            # contraction pay at this shape?) and the serialized
            # comm-after-compute baseline (wins only when the problem
            # is too small to hide any hop).
            {"block_m": 512, "block_n": 128, "block_k": 4096,
             "prefetch_depth": 1},
            {"block_m": 512, "block_n": 128, "block_k": 2048,
             "swizzle_mode": "identity"},
        ]

    def _prune(cfg, a_, b_):
        m, k_loc = a_.shape
        n_dim = b_.shape[1]
        n = mesh.size(axis)

        def fits(c):
            return gemm_rs_vmem_bytes(
                c.get("block_m", 256), c.get("block_n", 256),
                c.get("block_k", 512), m // n, k_loc, n_dim,
                a_.dtype.itemsize) <= 14 * 1024 * 1024

        def t_model(c):
            return gemm_time_model_s(
                m, k_loc, n_dim, c.get("block_m", 256),
                c.get("block_n", 256), c.get("block_k", 512),
                dtype_bytes=a_.dtype.itemsize)

        if not fits(cfg):
            return False
        # Time baseline over the VMEM-FEASIBLE subset only: an
        # infeasible config must not set a phantom best time that
        # vetoes every runnable candidate.
        feasible = [c for c in configs if fits(c)]
        best = min(t_model(c) for c in feasible)
        return t_model(cfg) <= 2.0 * best

    @autotune("gemm_rs", configs,
              key_fn=lambda a_, b_, **kk: {
                  "m": a_.shape[0], "k": a_.shape[1], "n": b_.shape[1],
                  "dtype": str(a_.dtype), "world": mesh.size(axis),
                  "mesh": tune.mesh_key(mesh)},
              prune_fn=_prune)
    def _run(a_, b_, block_m=256, block_n=256, block_k=512,
             swizzle_mode="rs", prefetch_depth=0):
        ctx = create_gemm_rs_context(mesh, axis, block_m, block_n,
                                     block_k, swizzle_mode=swizzle_mode,
                                     prefetch_depth=prefetch_depth)
        return gemm_rs(a_, b_, ctx, **kw)

    return _run(a, b)
