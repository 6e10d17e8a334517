"""Paged flash Q-BLOCK attention as a Pallas kernel (local form).

Reference: ``ops/paged_flash_decode.py`` is the one-query-per-slot
decode kernel (FlashAttention's IO-aware online softmax over
vLLM/PagedAttention-style block-table pages). The serving layer has two
more attention shapes on its hot path that until now attended through
the GATHER oracle — materializing every slot's entire dense KV row per
layer per call, O(p_max·page) HBM traffic regardless of how short the
slot actually is:

- the CHUNKED-PREFILL step (:func:`models.dense.prefill_chunk_paged`):
  a chunk of C consecutive queries of ONE slot, query i attending keys
  at global positions ``<= start + i``;
- the SPECULATIVE-VERIFICATION step (:func:`models.dense.
  verify_step_paged`): K candidate queries per slot across the whole
  decode batch, query j attending ``< lens[s] + j + 1``.

This module is the one kernel both ride: ``paged_flash_decode``
generalized from 1 query to a Q-BLOCK of Cq queries per slot. Pages
stream through VMEM double-buffered via the block table (pages past a
slot's maximum attended position are skipped entirely — the work
scales with the slot's RESIDENT page count, never with capacity), the
per-query causal mask comes from a ``(B, Cq)`` position vector (data —
the trace keys only on the block shape, so the serving jit caches
never grow), and int8/fp8 pools dequantize inside the page prefetch
compute exactly like the decode kernel's ``kscale``/``vscale`` path.

The gather path stays as :func:`paged_flash_qblock_ref` — the
interpret-friendly oracle the kernel is tested against (and the
serving engine's ``attn_impl="ref"``), built on the ONE shared gather
(:func:`ops.chunked_prefill.gather_pages_dense`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import core_call
from triton_dist_tpu.ops.paged_flash_decode import (  # noqa: F401
    _require_pool_scales, _pool_layer, window_walk_pages)


def qblock_page_attend(q2, kpage, vpage, m, l, acc, mask, rep: int,
                       kscale=None, vscale=None):
    """One online-softmax step of a Q-BLOCK over a KV page —
    :func:`~triton_dist_tpu.ops.paged_flash_decode.page_attend`
    generalized from a unit query dim to Cq queries.

    q2: (H, Cq, hd) fp32 head-major queries; kpage/vpage: (KV, page,
    hd) head-major pages; m/l: (H, Cq) running max / normalizer; acc:
    (H, Cq, hd); mask: (Cq, page) per-QUERY key validity (the causal
    mask restricted to this page); rep = H // KV (GQA ratio).
    ``kscale``/``vscale``: (KV,) fp32 per-head dequant scales of a
    quantized (int8/fp8) page — the dequant fuses into the page's f32
    upcast. Everything stays batched-3-D (the Mosaic-legal layout the
    decode kernel established). Pure function on values."""
    scale = q2.shape[-1] ** -0.5
    kf = kpage.astype(jnp.float32)
    vf = vpage.astype(jnp.float32)
    if kscale is not None:
        kf = kf * kscale.reshape(-1, 1, 1)
        vf = vf * vscale.reshape(-1, 1, 1)
    krep = jnp.repeat(kf, rep, axis=0)                       # (H,p,hd)
    vrep = jnp.repeat(vf, rep, axis=0)
    s = jnp.einsum("hqd,hpd->hqp", q2, krep) * scale         # (H,Cq,p)
    s = jnp.where(mask[None], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("hqp,hpd->hqd", p, vrep)
    return m_new, l_new, acc_new


def _qblock_kernel(*refs, page: int, p_max: int, kvh: int, rep: int,
                   hd: int, cq: int, quantized: bool,
                   layered: bool = False, window: int = 0,
                   ring: int = 0):
    """Grid (B, KV, P_max): slot-major, then one KV head (and its
    ``rep`` query heads) at a time, then that head's page walk with the
    decode kernel's double-buffered prefetch (per-parity semaphores);
    pages past a slot's maximum attended position (``end_ref``) are
    skipped. One KV head per step keeps the VMEM working set at
    (rep, Cq, ...) instead of (H, Cq, ...) — at H=32, Cq=512 the
    all-heads form needs 30 MB of scoped VMEM, which Mosaic refuses
    against its 16 MB limit. No partial exchange — this is the LOCAL
    (axis=None) form, the layout the serving engine's TP-head-sharded
    pools use (every rank holds the full sequence for its heads).
    ``layered=True``: the first operand is :func:`~triton_dist_tpu.ops.
    paged_flash_decode._pool_layer`'s layer index, read from SMEM and
    put before the page id.

    ``window`` > 0: a query at position ``i`` reads keys ``j`` with ``i
    - window < j <= i``. The table is then a RING of ``ring`` entries
    (page ``n`` of the slot in entry ``n % ring``), ``p_max`` is the
    pages a block WALKS (the grid's last dimension: what ``cq`` rows
    and the window span, whatever the context), and one more SMEM
    operand, ``lo_ref``, gives each block the first page that holds a
    key in its first row's reach: step ``p`` of the walk is page
    ``lo_ref[b] + p``."""
    ks_ref = vs_ref = lo_ref = None
    prefix = ()
    if layered:
        prefix, refs = (refs[0][0],), refs[1:]
    if window:
        lo_ref, refs = refs[0], refs[1:]
    if quantized:
        (table_ref, end_ref, pos_ref, q_ref, kp_ref, vp_ref, ks_ref,
         vs_ref, o_ref) = refs[:9]
        scratch = refs[9:]
    else:
        (table_ref, end_ref, pos_ref, q_ref, kp_ref, vp_ref,
         o_ref) = refs[:7]
        scratch = refs[7:]
    kpage, vpage, m_s, l_s, acc_s, psem = scratch

    b = pl.program_id(0)
    g = pl.program_id(1)
    p = pl.program_id(2)
    n_b = pl.num_programs(0)

    # Page p of slot b lives at pool slot table[b, p]; pages past the
    # slot's maximum attended position carry no unmasked key for ANY
    # query — skip them entirely (this is what makes the kernel scale
    # with resident pages, not capacity).
    if window:
        # No row bound: a ring holds any position. The walk's first
        # page always holds a key of the block's first row.
        end = jnp.maximum(end_ref[b], 1)
        page_no = lo_ref[b] + p
    else:
        end = jnp.clip(end_ref[b], 1, p_max * page)
        page_no = p
    active = page_no * page < end
    lin = (b * kvh + g) * p_max + p
    par = jax.lax.rem(lin, 2)

    def load(b2, g2, p2, buf):
        if window:
            p2 = jax.lax.rem(lo_ref[b2] + p2, ring)
        pid = table_ref[b2, p2]
        src = (*prefix, pid, pl.ds(g2, 1))
        pltpu.make_async_copy(kp_ref.at[src], kpage.at[buf],
                              psem.at[buf]).start()
        pltpu.make_async_copy(vp_ref.at[src], vpage.at[buf],
                              psem.at[buf]).start()

    @pl.when(jnp.logical_and(active, lin == 0))
    def _():
        load(b, g, p, 0)     # cold start; later pages are prefetched

    @pl.when(active)
    def _():
        # Per-parity semaphores: this wait cannot consume the prefetch
        # fired below for the NEXT page (the decode kernel's scheme).
        pltpu.make_async_copy(kpage.at[par], kpage.at[par],
                              psem.at[par]).wait()
        pltpu.make_async_copy(vpage.at[par], vpage.at[par],
                              psem.at[par]).wait()

    # Prefetch the next step's page while this one computes.
    nxt = lin + 1
    b2 = jnp.minimum(nxt // (kvh * p_max), n_b - 1)
    g2 = jax.lax.rem(nxt // p_max, kvh)
    p2 = jax.lax.rem(nxt, p_max)
    if window:
        end2 = jnp.maximum(end_ref[b2], 1)
        page_no2 = lo_ref[b2] + p2
    else:
        end2 = jnp.clip(end_ref[b2], 1, p_max * page)
        page_no2 = p2
    active2 = jnp.logical_and(nxt < n_b * kvh * p_max,
                              page_no2 * page < end2)

    @pl.when(active2)
    def _():
        load(b2, g2, p2, jax.lax.rem(nxt, 2))

    @pl.when(p == 0)
    def _():
        m_s[...] = jnp.full((rep, cq), -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros((rep, cq), jnp.float32)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(active)
    def _():
        q2 = q_ref[0].astype(jnp.float32)                # (rep, Cq, hd)
        key_pos = page_no * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        mask = key_pos <= pos_ref[0]         # (Cq, 1) -> (Cq, page)
        if window:
            mask = jnp.logical_and(mask, key_pos > pos_ref[0] - window)
        ksc = vsc = None
        if quantized:
            # This head's per-page dequant scale, picked out of the
            # (1, KV) row of the host-side block-table gather — the
            # fused-dequant hook.
            head = jax.lax.broadcasted_iota(jnp.int32, (1, kvh), 1) == g
            ksc = jnp.sum(jnp.where(head, ks_ref[b, pl.ds(p, 1)], 0.0),
                          axis=1)
            vsc = jnp.sum(jnp.where(head, vs_ref[b, pl.ds(p, 1)], 0.0),
                          axis=1)
        m, l, acc = qblock_page_attend(
            q2, kpage[par], vpage[par], m_s[...], l_s[...], acc_s[...],
            mask, rep, kscale=ksc, vscale=vsc)
        m_s[...] = m
        l_s[...] = l
        acc_s[...] = acc

    # The head's last page step: normalize and emit. Page 0 is always
    # active (end >= 1), so l has at least one key's mass per query.
    @pl.when(p == p_max - 1)
    def _():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)[..., None]
        o_ref[...] = out[None].astype(o_ref.dtype)


# Scoped-VMEM budget the Q-block working set is sized against (Mosaic's
# default limit on a v5e is 16 MiB; the rest is headroom for the
# compiler's own temporaries).
QBLOCK_VMEM_BUDGET = 10 * 1024 * 1024


def qblock_rows(cq: int, rep: int, hd: int, page: int, itemsize: int,
                budget: int = QBLOCK_VMEM_BUDGET) -> int:
    """Queries per kernel block: ``cq`` halved until one KV head's
    working set fits ``budget`` — per query row the f32 accumulator,
    the double-buffered q and out blocks, and the score/probability
    tiles. Pure host arithmetic."""
    per_row = rep * (hd * 4 + 4 * hd * itemsize + 2 * page * 4)
    bq = cq
    while bq * per_row > budget and bq % 2 == 0:
        bq //= 2
    return bq


def paged_flash_qblock(q, k_pages, v_pages, block_table, positions, *,
                       layer=None, k_scale=None, v_scale=None,
                       window: int = 0):
    """Paged-KV GQA attention of a Q-BLOCK per slot; 4-D pool, or 5-D + layer.

    The local form (no partial exchange).
    q: (B, Cq, H, hd) — Cq queries per slot (head-major, this rank's
    heads); k_pages/v_pages: (num_pages, KV, page, hd) — this rank's
    page pool — or every layer's pool whole, (L, num_pages, KV, page,
    hd), with ``layer`` an int or int32 scalar (the kernel fetches
    ``pool.at[layer, pid]``; no layer is cut out of the pool, which XLA
    would copy) — every attended key already resident (the chunk writer /
    candidate block append runs BEFORE the attend, exactly like the
    gather path); int8/fp8 pools additionally REQUIRE ``k_scale``/
    ``v_scale`` (num_pages, KV) fp32 per-page per-head dequant scales;
    block_table: (B, P_max) int32 page ids into the local pool;
    positions: (B, Cq) int32 — query (b, i) attends keys at global
    positions ``<= positions[b, i]`` (clamped to >= 0, so a parked
    slot's garbage row stays finite). Both serving masks are instances:
    the chunk case passes ``start + arange(C)`` and the verification
    case ``lens[s] + j`` (parked slots 0).

    ``window`` > 0 (static): a query reads only the last ``window``
    keys through its own, ``positions[b, i] - window < j``, and
    ``block_table`` (B, ring) is each slot's RING in the pool, page
    ``n`` of the slot in entry ``n % ring``. A row block then walks
    from the first page that holds a key in reach of its lowest
    position, :func:`window_walk_pages` pages at most whatever the
    context; the positions of a block lie within its row count of each
    other (a chunk's consecutive rows, its padding clamped to the last
    valid one). Not for quantized pools.

    Positions ride as DATA — the trace signature depends only on the
    block shape (B, Cq), never on lengths, so the serving dispatches
    built on this kernel keep their one-entry jit caches. Concrete
    positions beyond the table row's capacity are an error (the row
    cannot hold the key a query asks for).
    Returns (B, Cq, H, hd).
    """
    page = k_pages.shape[-2]
    p_max = block_table.shape[1]
    _require_pool_scales(k_pages, k_scale, reject_spurious=True)
    positions = jnp.maximum(jnp.asarray(positions, jnp.int32), 0)
    if window:
        if k_scale is not None:
            raise ValueError("window attention reads an unquantized pool")
        return _qblock_call(q, k_pages, v_pages, block_table, positions,
                            _pool_layer(k_pages, layer), None, None,
                            window=int(window))
    if not isinstance(positions, jax.core.Tracer):
        import numpy as _np

        cap = p_max * page
        pos_np = _np.asarray(positions)
        if int(_np.max(pos_np)) >= cap:
            bad = int(_np.argmax(_np.max(pos_np, axis=1)))
            raise ValueError(
                f"position {int(_np.max(pos_np))} of batch slot {bad} "
                f"exceeds one block-table row's capacity {cap} "
                f"({p_max} pages x {page}); the query asks for a key "
                "its table row cannot hold")
    return _qblock_call(q, k_pages, v_pages, block_table, positions,
                        _pool_layer(k_pages, layer), k_scale, v_scale)


@functools.partial(jax.jit, static_argnames=("window",))
def _qblock_call(q, k_pages, v_pages, block_table, positions, layer,
                 k_scale, v_scale, window: int = 0):
    """:func:`paged_flash_qblock` behind one jit: the layers of a step
    program share its trace and its lowering (``_pool_layer``)."""
    b, cq, h, hd = q.shape
    kvh, page = k_pages.shape[-3:-1]
    p_max = block_table.shape[1]
    rep = h // kvh
    quantized = k_scale is not None
    # A Q-block too large for VMEM splits into row blocks that ride the
    # grid as extra slots sharing their slot's table row. Each gets its
    # own page-skip bound, so an early block of a causal chunk also
    # stops at its own last position.
    bq = qblock_rows(cq, rep, hd, page, q.dtype.itemsize)
    nq = cq // bq
    if nq > 1:
        q = q.reshape(b * nq, bq, h, hd)
        positions = positions.reshape(b * nq, bq)
        block_table = jnp.repeat(block_table, nq, axis=0)
    nb = b * nq
    # Max attended position + 1 per slot — the kernel's page-skip bound.
    end = jnp.max(positions, axis=1) + 1
    q_hm = q.transpose(0, 2, 1, 3)              # (nb, H, bq, hd)

    ring = 0
    if window:
        # The table is a ring; the grid's last dimension is the walk.
        ring, p_max = p_max, window_walk_pages(bq, window, page)
        if p_max > ring:
            raise ValueError(
                f"a block of {bq} rows under window {window} walks "
                f"{p_max} pages of {page}, the ring has {ring}")
        lo = jnp.maximum(jnp.min(positions, axis=1) - (window - 1),
                         0) // page

    kernel = functools.partial(
        _qblock_kernel, page=page, p_max=p_max, kvh=kvh, rep=rep,
        hd=hd, cq=bq, quantized=quantized, layered=layer is not None,
        **({"window": window, "ring": ring} if window else {}))

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),          # block_table
        pl.BlockSpec(memory_space=pltpu.SMEM),          # end
        pl.BlockSpec((1, bq, 1), lambda bb, gg, pp: (bb, 0, 0),
                     memory_space=pltpu.VMEM),          # positions
        pl.BlockSpec((1, rep, bq, hd), lambda bb, gg, pp: (bb, gg, 0, 0),
                     memory_space=pltpu.VMEM),          # q (one KV group)
        pl.BlockSpec(memory_space=pl.ANY),              # k pool
        pl.BlockSpec(memory_space=pl.ANY),              # v pool
    ]
    operands = [block_table.astype(jnp.int32), end.astype(jnp.int32),
                positions[..., None], q_hm, k_pages, v_pages]
    if quantized:
        # Scales enter PRE-GATHERED through the block table as small
        # (B, P_max, KV) fp32 tables resident in VMEM (the decode
        # kernel's fused-dequant plumbing).
        sc_spec = pl.BlockSpec((nb, p_max, kvh),
                               lambda bb, gg, pp: (0, 0, 0),
                               memory_space=pltpu.VMEM)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale[block_table].astype(jnp.float32),
                     v_scale[block_table].astype(jnp.float32)]
    if window:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, lo.astype(jnp.int32))
    if layer is not None:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, layer)

    out = core_call(
        kernel,
        name="paged_flash_qblock",
        grid=(nb, kvh, p_max),
        out_shape=jax.ShapeDtypeStruct((nb, h, bq, hd), q.dtype),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rep, bq, hd),
                               lambda bb, gg, pp: (bb, gg, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, 1, page, hd), k_pages.dtype),    # kpage x2
            pltpu.VMEM((2, 1, page, hd), v_pages.dtype),    # vpage x2
            pltpu.VMEM((rep, bq), jnp.float32),             # m
            pltpu.VMEM((rep, bq), jnp.float32),             # l
            pltpu.VMEM((rep, bq, hd), jnp.float32),         # acc
            pltpu.SemaphoreType.DMA((2,)),                  # page loads
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * cq * h * hd * p_max * page,
            bytes_accessed=2 * nb * p_max * page * kvh * hd
            * k_pages.dtype.itemsize,
            transcendentals=b * cq * h * p_max * page,
        ),
    )(*operands)
    return out.transpose(0, 2, 1, 3).reshape(b, cq, h, hd)


def paged_flash_qblock_ref(q, k_pages, v_pages, block_table, positions,
                           k_scale=None, v_scale=None):
    """XLA gather oracle for :func:`paged_flash_qblock` — the
    pre-kernel serving path, kept verbatim: gather each slot's pages
    into the dense position-major view
    (:func:`~triton_dist_tpu.ops.chunked_prefill.gather_pages_dense`,
    the ONE shared gather) and run per-query masked fp32 attention
    (the :func:`~triton_dist_tpu.ops.chunked_prefill.chunk_attend`
    numerics). A scaleless read of a quantized pool fails loudly —
    the kernel's contract. Returns (B, Cq, H, hd)."""
    from triton_dist_tpu.ops.chunked_prefill import gather_pages_dense

    _require_pool_scales(k_pages, k_scale)
    b, cq, h, hd = q.shape
    kvh = k_pages.shape[1]
    rep = h // kvh
    positions = jnp.maximum(jnp.asarray(positions, jnp.int32), 0)
    kd = gather_pages_dense(k_pages, block_table, k_scale)
    vd = gather_pages_dense(v_pages, block_table, v_scale)
    t = kd.shape[1]
    k = jnp.repeat(kd, rep, axis=2)             # (B, T, H, hd)
    v = jnp.repeat(vd, rep, axis=2)
    scores = jnp.einsum("bchd,bthd->bhct", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    mask = (jnp.arange(t, dtype=jnp.int32)[None, None]
            <= positions[:, :, None])           # (B, Cq, T)
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhct,bthd->bchd", probs, v)
