"""Paged split-KV flash decode as a Pallas kernel (distributed).

Reference: ``python/triton_dist/kernels/nvidia/flash_decode.py`` —
split-KV GQA decode kernel :130, block_table/workspace host APIs
``gqa_fwd_batch_decode*`` :763-1095 (paged KV, per-rank partials,
cross-rank combine :393-482). Round 1 only had the dense-cache XLA
composition (``ops/flash_decode.py``); this adds the kernel-level form:

- **Paged KV**: the cache is a page pool ``(num_pages, KV, page, hd)``
  (or every layer's pool whole, ``(L, num_pages, KV, page, hd)``, with a
  ``layer`` index) plus a per-sequence ``block_table (B, P_max)`` of
  page ids (SMEM) — pages stream through VMEM one at a time via
  dynamic-index DMA, so arbitrary context lengths serve from a fixed
  pool (no dense (B, T) cache materialization, and no layer cut out of
  the pool: XLA would copy it).
- **Online softmax in-kernel**: per (batch, page) grid step the running
  (m, l, acc) update happens in VMEM scratch — the flash recurrence.
- **RDMA combine**: each rank packs (acc, m, l) partials and one-sided
  puts them to every peer (one-shot exchange over ICI); every rank then
  reduces the log-sum-exp combine locally — the reference's
  intra/inter-rank combine kernels without a host-launched second pass,
  and no ``psum`` round-trip through XLA.

The per-page update is factored as :func:`page_attend` so the
megakernel's attention task can reuse the same body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import triton_dist_tpu.lang as dl
from triton_dist_tpu.lang import core_call
from triton_dist_tpu.parallel.mesh import MeshContext


def page_attend(q2, kpage, vpage, m, l, acc, mask, rep: int,
                kscale=None, vscale=None):
    """One online-softmax step over a KV page.

    q2: (H, hd) fp32 queries (head-major); kpage/vpage: (KV, page, hd)
    head-major pages; m/l: (H, 1) running max / normalizer; acc:
    (H, hd); mask: (1, page) validity; rep = H // KV (GQA ratio).
    ``kscale``/``vscale``: (KV,) fp32 per-head dequant scales of a
    QUANTIZED (int8/fp8) page — the dequant fuses into the page's
    f32 upcast, so quantized pools stream through the same flash
    recurrence with no dense dequantized materialization.
    Everything stays 2-D/batched-3-D — Mosaic has no legal layout cast
    for the grouped (KV, rep, ...) forms. Pure function on values —
    shared with the megakernel attention task."""
    scale = q2.shape[-1] ** -0.5
    kf = kpage.astype(jnp.float32)
    vf = vpage.astype(jnp.float32)
    if kscale is not None:
        kf = kf * kscale.reshape(-1, 1, 1)
        vf = vf * vscale.reshape(-1, 1, 1)
    krep = jnp.repeat(kf, rep, axis=0)                         # (H,p,hd)
    vrep = jnp.repeat(vf, rep, axis=0)
    # Batched MAT-mat (unit M dim): a batched vec-mat has no lhs
    # non-contracting dim and Mosaic's dot attr cannot express it.
    s = jnp.einsum("hqd,hpd->hqp", q2[:, None, :], krep)[:, 0, :] * scale
    s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum(
        "hqp,hpd->hqd", p[:, None, :], vrep)[:, 0, :]
    return m_new, l_new, acc_new


def _is_quantized_pool(arr) -> bool:
    return jnp.dtype(arr.dtype) in (jnp.dtype(jnp.int8),
                                    jnp.dtype(jnp.float8_e4m3fn))


def _require_pool_scales(pool, k_scale, *, reject_spurious=False):
    """The ONE spelling of every paged reader's quantization contract
    (decode kernel/ref and the Q-block kernel/ref all share it): an
    int8/fp8 pool without scales fails loudly rather than attending
    raw quantized bytes; ``reject_spurious`` additionally rejects
    scales paired with an unquantized pool (the reverse mismatch)."""
    if _is_quantized_pool(pool) and k_scale is None:
        raise ValueError(
            f"k_pages is a QUANTIZED pool ({pool.dtype}) but no "
            "k_scale/v_scale was passed — a scaleless reader would "
            "attend raw quantized bytes (kv_dtype mismatch between "
            "the pool's writer and this reader?)")
    if (reject_spurious and k_scale is not None
            and not _is_quantized_pool(pool)):
        raise ValueError(
            f"k_scale passed for an unquantized ({pool.dtype}) "
            "pool — scales only pair with int8/fp8 storage")


def _lse_reduce(parts, hd: int):
    """Log-sum-exp combine of flash partials: parts (r, B, H, 2+hd)
    [acc | m | l] → one combined partial (B, H, 2+hd). Associative —
    the hierarchical (inner-then-outer) exchange reduces in two stages
    (reference intra/inter-rank combine pair, flash_decode.py:393/482).
    """
    m_r = parts[:, :, :, hd:hd + 1]
    l_r = parts[:, :, :, hd + 1:hd + 2]
    acc_r = parts[:, :, :, :hd]
    m_g = jnp.max(m_r, axis=0, keepdims=True)
    m_g_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
    corr = jnp.where(jnp.isfinite(m_r), jnp.exp(m_r - m_g_safe), 0.0)
    l_tot = jnp.sum(l_r * corr, axis=0)
    acc_tot = jnp.sum(acc_r * corr, axis=0)
    return jnp.concatenate([acc_tot, m_g[0], l_tot], axis=-1)


def _pool_layer(pool, layer):
    """A paged reader's view of its pool: ``(num_pages, KV, page, hd)``
    as it is, or ``(L, num_pages, KV, page, hd)`` with a ``layer`` (the
    serving steps unroll their layers). Returns what the kernel takes
    as its leading operand: ``None`` for the 4-D pool, else the layer as
    a ``(1,)`` int32 array. The kernel reads it from SMEM and fetches a
    page as ``pool.at[layer, pid]``, so the 5-D pool is never sliced
    outside the kernel, and the layer is DATA: every layer of a step
    program calls the same jitted function with the same shapes, which
    JAX traces once and lowers to one Mosaic kernel (a layer baked into
    the kernel made each call a kernel of its own to trace and lower:
    16 a program with a chunk and a decode batch, most of its set-up
    time, PERF.md section 6, PR 30)."""
    if pool.ndim == 5:
        if layer is None:
            raise ValueError(
                "a 5-D (L, num_pages, KV, page, hd) pool needs a layer")
        return jnp.asarray(layer, jnp.int32).reshape(1)
    if layer is not None:
        raise ValueError(
            f"layer={layer!r} given with a {pool.ndim}-D pool: only the "
            "whole 5-D pool is indexed by layer")
    return None


def window_walk_pages(rows: int, window: int, page: int) -> int:
    """Pages that ``rows`` consecutive query positions walk under
    ``window``, at most: the keys in reach of any of them are ``rows +
    window - 1`` consecutive positions, which begin anywhere in a page.
    One decode row: ``rows`` 1."""
    return (rows + window - 2) // page + 2


def _decode_kernel(*refs, axes, ctx: MeshContext, page: int, p_max: int,
                   kvh: int, rep: int, hd: int, shard_len: int,
                   paged: bool, sim: bool, quantized: bool = False,
                   layered: bool = False, window: int = 0,
                   ring: int = 0):
    """``axes``: list of (axis_name, n_ax) exchange stages, innermost
    first (1 entry = flat; 2 = hierarchical outer x inner, where the
    flat shard order is outer-major). ``paged=False`` reads a dense
    head-major (B, KV, T_loc, hd) cache with pages carved from T_loc.
    ``sim=True``: self-targeted puts at full schedule/traffic (every
    gather slot receives my own partial; the LSE-combine of n identical
    partials is exact) — the single-chip bench proxy.
    ``quantized=True``: the pools are int8/fp8 and two extra
    (B, P_max, KV) fp32 scale tables ride in VMEM — the dequant fuses
    into each page's compute step (:func:`page_attend`).
    ``layered=True``: the first operand is :func:`_pool_layer`'s layer
    index, read from SMEM and put before the page id.
    ``window`` > 0 (paged, local, unquantized): a slot's query, at
    position ``len - 1``, reads keys ``j >= len - window``. The table
    is then a RING of ``ring`` entries (page ``n`` of the slot in entry
    ``n % ring``) and ``p_max`` the pages a slot WALKS (the grid's last
    dimension), from the page that holds its oldest key in reach."""
    ks_ref = vs_ref = None
    prefix = ()
    if layered:
        prefix, refs = (refs[0][0],), refs[1:]
    if paged and quantized:
        (table_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
         o_ref, part_gather) = refs[:9]
        scratch = refs[9:]
    elif paged:
        (table_ref, len_ref, q_ref, kp_ref, vp_ref, o_ref,
         part_gather) = refs[:7]
        scratch = refs[7:]
    else:
        table_ref = None
        len_ref, q_ref, kp_ref, vp_ref, o_ref, part_gather = refs[:6]
        scratch = refs[6:]
    (kpage, vpage, m_l, acc_s, part_stage, gather_v, psem, send_sem,
     recv_sem) = scratch

    b = pl.program_id(0)
    p = pl.program_id(1)
    n_b = pl.num_programs(0)
    n = 1
    for _, n_ax in axes:
        n *= n_ax
    h = kvh * rep
    # Flat rank over the exchange axes (outer-major for 2 stages;
    # ``axes`` lists innermost first).
    if sim or n == 1:
        me = 0
    elif len(axes) == 2:
        me = dl.rank(axes[1][0]) * axes[0][1] + dl.rank(axes[0][0])
    else:
        me = dl.rank(axes[0][0])
    off = me * shard_len          # my shard's global position offset

    # Page p of batch b lives at pool slot table[b, p]. Pages past this
    # batch's (local) length are skipped entirely.
    local_end = jnp.clip(len_ref[b] - off, 0, shard_len)
    if window:
        def first_page(b_):
            return jnp.maximum(len_ref[b_] - window, 0) // page

        page_no = first_page(b) + p
    else:
        page_no = p
    active = page_no * page < local_end
    lin = b * p_max + p
    par = jax.lax.rem(lin, 2)

    def load(b2, p2, buf):
        if window:
            p2 = jax.lax.rem(first_page(b2) + p2, ring)
        if paged:
            pid = table_ref[b2, p2]
            ksrc = kp_ref.at[(*prefix, pid)]
            vsrc = vp_ref.at[(*prefix, pid)]
        else:
            # Dense head-major cache: page p2 is a T_loc slice — the
            # (KV, page, hd) block feeds page_attend with no transpose.
            ksrc = kp_ref.at[b2, :, pl.ds(p2 * page, page)]
            vsrc = vp_ref.at[b2, :, pl.ds(p2 * page, page)]
        pltpu.make_async_copy(ksrc, kpage.at[buf], psem.at[buf]).start()
        pltpu.make_async_copy(vsrc, vpage.at[buf], psem.at[buf]).start()

    @pl.when(jnp.logical_and(active, lin == 0))
    def _():
        load(b, p, 0)        # cold start; later pages are prefetched

    @pl.when(active)
    def _():
        # K and V of this page (issued here at lin==0, else one step
        # ahead). Per-parity semaphores keep this wait from consuming
        # the prefetch we are about to fire for the NEXT page.
        pltpu.make_async_copy(kpage.at[par], kpage.at[par],
                              psem.at[par]).wait()
        pltpu.make_async_copy(vpage.at[par], vpage.at[par],
                              psem.at[par]).wait()

    # Prefetch the next block's page while this one computes.
    nxt = lin + 1
    b2 = jnp.minimum(nxt // p_max, n_b - 1)
    p2 = jax.lax.rem(nxt, p_max)
    end2 = jnp.clip(len_ref[b2] - off, 0, shard_len)
    page_no2 = first_page(b2) + p2 if window else p2
    active2 = jnp.logical_and(nxt < n_b * p_max, page_no2 * page < end2)

    @pl.when(active2)
    def _():
        load(b2, p2, jax.lax.rem(nxt, 2))

    @pl.when(p == 0)
    def _():
        m_l[:, 0:1] = jnp.full((h, 1), -jnp.inf, jnp.float32)
        m_l[:, 1:2] = jnp.zeros((h, 1), jnp.float32)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(active)
    def _():
        q2 = q_ref[0, b].astype(jnp.float32)
        pos = page_no * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        mask = pos < local_end
        if window:
            mask = jnp.logical_and(mask, pos >= local_end - window)
        ksc = vsc = None
        if quantized:
            # Per-page per-head dequant scales, gathered host-side
            # through the block table — the fused-dequant hook.
            ksc = ks_ref[b, p]
            vsc = vs_ref[b, p]
        m, l, acc = page_attend(q2, kpage[par], vpage[par],
                                m_l[:, 0:1], m_l[:, 1:2], acc_s[...],
                                mask, rep, kscale=ksc, vscale=vsc)
        m_l[:, 0:1] = m
        m_l[:, 1:2] = l
        acc_s[...] = acc

    # Pack this batch's partial after its last page: (h, hd+2) =
    # [acc | m | l].
    @pl.when(p == p_max - 1)
    def _():
        part_stage[b, :, :hd] = acc_s[...]
        part_stage[b, :, hd:hd + 2] = m_l[...]

        @pl.when(b == n_b - 1)
        def _():
            # Exchange + LSE-reduce, one stage per axis (innermost
            # first: intra-slice partials merge before a single small
            # DCN hop per outer peer — reference intra/inter-rank
            # combine kernels, flash_decode.py:393-482).
            sem_base = 0
            for ax, n_ax in axes:
                if n_ax == 1:
                    continue
                me_ax = 0 if sim else dl.rank(ax)
                dl.barrier_all(ax, ctx=ctx)
                for offp in range(1, n_ax):
                    if sim:
                        # Self-puts: every slot receives my partial.
                        dl.remote_put(part_stage, part_gather.at[offp],
                                      send_sem.at[sem_base + offp - 1],
                                      recv_sem, me_ax, axis=ax, ctx=ctx)
                    else:
                        peer = jax.lax.rem(me_ax + offp, n_ax)
                        dl.remote_put(part_stage, part_gather.at[me_ax],
                                      send_sem.at[sem_base + offp - 1],
                                      recv_sem, peer, axis=ax, ctx=ctx)
                dl.wait_arrivals(recv_sem, part_stage, n_ax - 1)
                for offp in range(n_ax - 1):
                    dl.wait_arrivals(send_sem.at[sem_base + offp],
                                     part_stage, 1)
                sem_base += n_ax - 1
                pltpu.make_async_copy(part_gather.at[pl.ds(0, n_ax)],
                                      gather_v.at[pl.ds(0, n_ax)],
                                      psem.at[0]).start()
                pltpu.make_async_copy(gather_v.at[pl.ds(0, n_ax)],
                                      gather_v.at[pl.ds(0, n_ax)],
                                      psem.at[0]).wait()
                gather_v[0 if sim else me_ax] = part_stage[...]
                # Stage's combined partial becomes the next stage's
                # (or the final divide's) input.
                part_stage[...] = _lse_reduce(
                    gather_v[pl.ds(0, n_ax)], hd)

            out = (part_stage[:, :, :hd]
                   / jnp.maximum(part_stage[:, :, hd + 1:hd + 2], 1e-30))
            o_ref[...] = out.astype(o_ref.dtype)


def _normalize_axes(axis, ctx, sim_ranks):
    """→ (axes innermost-first [(name, n)], total n, sim flag)."""
    if axis is None:
        # Local attention: no partial exchange at all — the layout
        # where positions are NOT sharded (e.g. the serving engine's
        # TP-head-sharded pools, every rank holding the full sequence
        # for its heads).
        return [("_local", 1)], 1, False
    if sim_ranks and sim_ranks > 1:
        return [(axis if isinstance(axis, str) else axis[-1],
                 sim_ranks)], sim_ranks, True
    if isinstance(axis, (tuple, list)):
        outer, inner = axis
        n_o = ctx.size(outer) if ctx is not None else (
            jax.lax.axis_size(outer))
        n_in = ctx.size(inner) if ctx is not None else (
            jax.lax.axis_size(inner))
        return [(inner, n_in), (outer, n_o)], n_o * n_in, False
    if ctx is not None:
        n = ctx.size(axis)
    else:
        # Inside shard_map the axis binds even without a MeshContext
        # (single-axis meshes need no logical-id translation); falling
        # back to n=1 under a bound multi-rank axis would silently
        # return shard-local attention.
        try:
            n = jax.lax.axis_size(axis)
        except (NameError, KeyError):
            n = 1
    return [(axis, n)], n, False


def _check_kv_len(kv_len, p_max: int, page: int, n: int, sim: bool):
    """Concrete lengths beyond the pool's capacity are an error:
    positions past it would be dropped in silence."""
    if isinstance(kv_len, jax.core.Tracer):
        return
    import numpy as _np

    cap = p_max * page * (1 if sim else n)
    lens_np = _np.asarray(kv_len)
    if int(_np.max(lens_np)) > cap:
        # Name the offending batch slot: a serving layer maps slots
        # to requests, so "slot s outgrew its row" is actionable
        # where a bare max() is not.
        bad = int(_np.argmax(lens_np))
        layout = (f"sim: local pool only, {p_max} pages x {page}"
                  if sim else f"{n} ranks x {p_max} pages x {page}")
        raise ValueError(
            f"kv_len {int(lens_np[bad])} of batch slot {bad} "
            f"exceeds one block-table row's capacity {cap} "
            f"({layout}); the request is longer than its table row")


def _decode_call(q, k_arr, v_arr, block_table, kv_len, *, ctx, axis,
                 page, p_max, paged, sim_ranks=0, k_scale=None,
                 v_scale=None, layer=None, window: int = 0):
    """Shared host plumbing for the paged and dense decode kernels."""
    b, h, hd = q.shape
    kvh = k_arr.shape[-3]
    rep = h // kvh
    quantized = k_scale is not None
    axes, n, sim = _normalize_axes(axis, ctx, sim_ranks)
    shard_len = p_max * page
    more = {}
    if window:
        if not paged or n > 1 or quantized:
            raise ValueError("window decode reads a local, unquantized "
                             "paged pool (axis=None)")
        # The table is a ring, which holds any position; the grid's
        # last dimension is the pages that ``window`` keys span.
        more = {"window": window, "ring": p_max}
        p_max = min(window_walk_pages(1, window, page), p_max)
        shard_len = 2 ** 30
    else:
        _check_kv_len(kv_len, p_max, page, n, sim)

    kernel = functools.partial(
        _decode_kernel, axes=axes, ctx=ctx, page=page, p_max=p_max,
        kvh=kvh, rep=rep, hd=hd, shard_len=shard_len, paged=paged,
        sim=sim, quantized=quantized, layered=layer is not None, **more)

    n_sem = max(sum(n_ax - 1 for _, n_ax in axes), 1)
    n_slots = max(max(n_ax for _, n_ax in axes), 1)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),     # kv_len
        pl.BlockSpec((1, b, h, hd), lambda bb, pp: (0, 0, 0, 0),
                     memory_space=pltpu.VMEM),     # q (whole)
        pl.BlockSpec(memory_space=pl.ANY),         # k pool / cache
        pl.BlockSpec(memory_space=pl.ANY),         # v pool / cache
    ]
    operands = [kv_len.astype(jnp.int32), q[None], k_arr, v_arr]
    if quantized:
        # Scales enter PRE-GATHERED through the block table as small
        # (B, P_max, KV) fp32 tables resident in VMEM — the kernel
        # reads its page's (KV,) scale at compute time and fuses the
        # dequant into the page's f32 upcast.
        sc_spec = pl.BlockSpec((b, p_max, kvh), lambda bb, pp: (0, 0, 0),
                               memory_space=pltpu.VMEM)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale[block_table].astype(jnp.float32),
                     v_scale[block_table].astype(jnp.float32)]
    if paged:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, block_table.astype(jnp.int32))
    if layer is not None:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, layer)

    out, _ = core_call(
        kernel,
        name="paged_flash_decode",
        comm=n > 1,
        grid=(b, p_max),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, hd), q.dtype),
            jax.ShapeDtypeStruct((n_slots, b, h, 2 + hd), jnp.float32),
        ),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((b, h, hd), lambda bb, pp: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.HBM),      # partial gather
        ),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, page, hd), k_arr.dtype),  # kpage x2
            pltpu.VMEM((2, kvh, page, hd), v_arr.dtype),  # vpage x2
            pltpu.VMEM((h, 2), jnp.float32),              # m | l
            pltpu.VMEM((h, hd), jnp.float32),             # acc
            pltpu.VMEM((b, h, 2 + hd), jnp.float32),      # part_stage
            pltpu.VMEM((n_slots, b, h, 2 + hd), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),                # page loads
            pltpu.SemaphoreType.DMA((n_sem,)),            # sends
            pltpu.SemaphoreType.DMA(()),                  # recv
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * hd * shard_len,
            bytes_accessed=2 * b * shard_len * kvh * hd
            * k_arr.dtype.itemsize,
            transcendentals=b * h * shard_len,
        ),
    )(*operands)
    return out


def paged_flash_decode(q, k_pages, v_pages, block_table, kv_len, *,
                       layer=None, ctx: MeshContext = None, axis="sp",
                       k_scale=None, v_scale=None, window: int = 0):
    """Paged-KV GQA decode step (in shard_map) on a 4-D pool, or 5-D + layer.

    Distributed: call inside shard_map.
    q: (B, H, hd) replicated along ``axis``;
    k_pages/v_pages: (num_pages, KV, page, hd) — this rank's page pool
    (head-major pages) — or every layer's pool whole, (L, num_pages,
    KV, page, hd), with ``layer`` an int or int32 scalar: the kernel fetches
    ``pool.at[layer, pid]`` and the caller cuts no layer out of the pool
    (a slice XLA would copy, see :class:`~triton_dist_tpu.serving.blocks.
    PagedKVCache`); int8/fp8 pools additionally REQUIRE
    ``k_scale``/``v_scale`` (num_pages, KV) fp32 per-page per-head
    dequant scales (fused into the page prefetch compute) — reading a
    quantized pool without them fails loudly rather than attending
    raw quantized bytes;
    block_table: (B, P_max) int32 page ids into the local pool (rank r's
    pages hold the global positions [r·P_max·page, (r+1)·P_max·page));
    kv_len: (B,) int32 *global* valid lengths (ragged per batch).
    Lengths beyond the total pool capacity (n·P_max·page) are an error
    — positions past capacity would be silently dropped otherwise, so
    concrete inputs are validated here.
    ``axis`` may be an ``(outer, inner)`` tuple for MULTI-SLICE decode:
    shards in outer-major flat order; the in-kernel partial exchange
    runs inner-axis first, so only one already-combined partial per
    outer peer crosses the slow link.
    ``window`` > 0 (static; ``axis=None``, an unquantized pool): the
    query reads its slot's last ``window`` keys alone, ``j >= kv_len -
    window``, and ``block_table`` (B, ring) is each slot's RING, page
    ``n`` of the slot in entry ``n % ring``; a slot's walk is the pages
    those keys span, whatever its length.
    Returns (B, H, hd).
    """
    _require_pool_scales(k_pages, k_scale, reject_spurious=True)
    if window:
        return _paged_decode_call(q, k_pages, v_pages, block_table, kv_len,
                                  _pool_layer(k_pages, layer), k_scale,
                                  v_scale, ctx=ctx, axis=axis,
                                  window=int(window))
    _check_kv_len(kv_len, block_table.shape[1], k_pages.shape[-2],
                  *_normalize_axes(axis, ctx, 0)[1:])
    return _paged_decode_call(q, k_pages, v_pages, block_table, kv_len,
                              _pool_layer(k_pages, layer), k_scale,
                              v_scale, ctx=ctx, axis=axis)


@functools.partial(jax.jit, static_argnames=("ctx", "axis", "window"))
def _paged_decode_call(q, k_pages, v_pages, block_table, kv_len, layer,
                       k_scale, v_scale, *, ctx, axis, window: int = 0):
    """:func:`paged_flash_decode` behind one jit: the layers of a step
    program share its trace and its lowering (:func:`_pool_layer`)."""
    more = {"window": window} if window else {}
    return _decode_call(q, k_pages, v_pages, block_table, kv_len,
                        ctx=ctx, axis=axis, page=k_pages.shape[-2],
                        p_max=block_table.shape[1], paged=True,
                        k_scale=k_scale, v_scale=v_scale, layer=layer,
                        **more)


def paged_flash_decode_ref(q, k_pages, v_pages, block_table, kv_len,
                           k_scale=None, v_scale=None):
    """XLA oracle for the local (single-rank) paged decode: gather the
    block table's pages into the dense position-major cache view and
    run plain masked attention. Token-exact with the dense-cache path
    by construction — the serving engine's ``attn_impl="ref"`` uses
    the same gather, so this doubles as its unit oracle. For a
    QUANTIZED pool the gather dequantizes with the per-page scales —
    the kernel's fused-dequant numerics oracle; a scaleless read of a
    quantized pool fails loudly (same contract as the kernel).

    q: (B, H, hd); k_pages/v_pages: (num_pages, KV, page, hd);
    block_table: (B, P_max) int32; kv_len: (B,) int32 (0 = empty slot —
    the output row is zeros-attention garbage the caller masks).
    Returns (B, H, hd).
    """
    from triton_dist_tpu.ops.chunked_prefill import gather_pages_dense
    from triton_dist_tpu.ops.flash_decode import flash_decode_ref

    _require_pool_scales(k_pages, k_scale)

    # Fully-masked rows (kv_len 0) would NaN the softmax; clamp to one
    # position — the row is garbage either way and callers mask it.
    safe_len = jnp.maximum(kv_len, 1)
    return flash_decode_ref(
        q, gather_pages_dense(k_pages, block_table, k_scale),
        gather_pages_dense(v_pages, block_table, v_scale), safe_len)


def sp_flash_decode_fused(q, k_cache, v_cache, kv_len, *,
                          ctx: MeshContext = None, axis="sp",
                          page: int = 128, sim_ranks: int = 0):
    """Fused distributed split-KV decode over a DENSE head-major cache
    — one kernel per decode step (online softmax + in-kernel RDMA
    partial exchange), replacing the pmax+2×psum XLA composition of
    :func:`~triton_dist_tpu.ops.flash_decode.sp_flash_decode`.

    q: (B, H, hd) replicated along ``axis``;
    k_cache/v_cache: (B, KV, T_loc, hd) — this rank's contiguous
    HEAD-MAJOR slice of the global cache (rank r holds global positions
    [r·T_loc, (r+1)·T_loc), outer-major flat order for tuple ``axis``);
    kv_len: (B,) int32 global valid lengths. ``page`` tiles T_loc
    through VMEM (T_loc % page == 0 required).

    ``sim_ranks > 1`` (single-chip bench proxy): full exchange schedule
    with self-targeted puts — every gather slot carries this rank's own
    partial, whose LSE-combine is exactly the local result.

    Reference: persistent split-KV kernels + combine,
    ``flash_decode.py:587-1095`` (the 1→32-GPU scaling headline).
    """
    t_loc = k_cache.shape[2]
    if t_loc % page:
        raise ValueError(f"T_loc={t_loc} not divisible by page={page}")
    return _decode_call(q, k_cache, v_cache, None, kv_len, ctx=ctx,
                        axis=axis, page=page, p_max=t_loc // page,
                        paged=False, sim_ranks=sim_ranks)