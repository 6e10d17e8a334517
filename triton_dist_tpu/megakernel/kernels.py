"""Per-task device code for the megakernel interpreter.

Reference: ``mega_triton_kernel/kernels/`` (linear, flash_decode paged,
norm, activation, allreduce via symm buffers, barrier) — one Triton
function per task type, dispatched by generated if/elif
(``core/code_generator.py:193-243``).

TPU redesign: task bodies are closures over a static ``KernelConfig``;
dispatch is ``lax.switch`` on the prefetched task type. All tensors live
in one HBM arena of shape ``(rows, W)`` — activations as consecutive
``(B, W)`` tiles, weights pre-tiled into ``(W, W)`` blocks (tile-major),
so every dynamic access is a contiguous ``pl.ds`` row slice.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import triton_dist_tpu.lang as dl
from triton_dist_tpu.megakernel.task import ARGS_MAX, TaskType
from triton_dist_tpu.parallel.mesh import MeshContext


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    w: int                  # arena lane width (tile size)
    batch: int              # decode batch B
    h_loc: int              # local attention heads
    kv_loc: int             # local KV heads
    hd: int                 # head dim (<= w)
    rope_theta: float
    rms_eps: float
    n_ranks: int            # TP size
    axis: str               # mesh axis name ("tp")
    mesh: MeshContext
    ar_ws_off: int          # arena row offset of the allreduce workspace
    ar_max_tiles: int       # max (B, W) tiles a single allreduce moves
    seq: int = 1            # rows per batch entry (prefill: B*S rows)
    # Paged KV (reference mega_triton_kernel paged flash_decode task):
    # the cache is a page pool (layers, n_pages, page, kv_loc, hd) and a
    # per-batch block table maps page index -> pool slot.
    paged: bool = False
    page: int = 0           # page length (builder: t_tile | page, seq | page)
    p_max: int = 0          # pages per sequence (max_len // page)
    # MoE (qwen_moe): static routing hyperparams for the MOE_WEIGHTS
    # task (top-k is a static python loop in the body).
    moe_topk: int = 0
    moe_norm: bool = True
    # Hybrid (qwen_next) GDN geometry (0 = no GDN layers).
    gdn_h_loc: int = 0
    gdn_dk: int = 0
    gdn_dv: int = 0
    # Quantized KV pools (``kv_quant="int8"|"fp8"``, paged only): the
    # cache arrays store 1 B/elem with one fp32 scale per (layer, page,
    # kv_head) riding in the k_scale/v_scale operands — quantize fused
    # into write_kv, dequant into every cache read (the
    # ops/paged_flash_qblock scheme applied to the persistent lane).
    # None = the original fp32 pools, bit-identical code path.
    kv_quant: "str | None" = None
    qmax: float = 0.0
    # Q-block verification build (WRITE_KV_QBLOCK/ATTN_QBLOCK): batch
    # rows are (slot, j) pairs, ``seq`` rows per slot, each at its own
    # per-row position.
    qblock: bool = False
    # Prefill-chunk build (WRITE_KV_CHUNK/ATTN_CHUNK): one C-row prompt
    # chunk per launch, per-row positions SIGN-ENCODED in the cache_len
    # vector (see ``_chunk_apos``) so resident-prefix rows attend
    # without re-writing and bucket-padding rows are dead.
    chunk: bool = False


def _act(arena, off, tiles_b):
    """Contiguous activation slab: ``tiles_b`` rows of the arena."""
    return arena.at[pl.ds(off, tiles_b)]


def _kv_slice(cache, refs, cfg, layer, bb, start, span, kv_head):
    """Cache slice (span, hd) of batch ``bb`` at global KV position
    ``start``: dense direct index, or block-table indirection in paged
    mode (pool slot ``tbl[bb, start // page]``, offset ``start % page``).
    The builder guarantees spans never cross a page (t_tile | page,
    seq | page, and page-aligned bases), so one slice is always enough —
    the same alignment contract as ``ops/paged_flash_decode``."""
    if not cfg.paged:
        return cache.at[layer, bb, pl.ds(start, span), kv_head, :]
    tbl_s = refs["tbl_s"]
    pid = tbl_s[bb * cfg.p_max + start // cfg.page]
    return cache.at[layer, pid,
                    pl.ds(jax.lax.rem(start, cfg.page), span), kv_head, :]


# ---------------------------------------------------------------------------
# Quantized-pool helpers (cfg.kv_quant): symmetric max-abs per
# (layer, page, kv_head), the layer path's PagedKVCache scheme fused
# into the persistent kernel. Scales live in the k_scale/v_scale
# operands shaped (layers, num_pages, kv_loc, 1); a (1, 1) VMEM
# scratch (refs["vscl"]) stages each scalar DMA.
# ---------------------------------------------------------------------------

def _quant_cast(x, qdtype, qmax):
    """fp32 → pool storage dtype (int8 rounds-to-nearest, fp8 is a
    saturating cast) — must track serving.blocks._quantize."""
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        return jnp.clip(jnp.round(x), -qmax, qmax).astype(jnp.int8)
    return jnp.clip(x, -qmax, qmax).astype(qdtype)


def _read_scale(refs, which, layer, pid, kv_head):
    """One (layer, page, kv_head) scale scalar off the HBM table.
    ``kv_head`` must be a STATIC int (the quantized bodies run static
    head loops for exactly this reason)."""
    vscl = refs["vscl"]
    pltpu.sync_copy(refs[which].at[layer, pid, pl.ds(kv_head, 1)], vscl)
    return vscl[0, 0]


def _write_scale(refs, which, layer, pid, kv_head, s):
    vscl = refs["vscl"]
    vscl[...] = jnp.reshape(s, (1, 1))
    pltpu.sync_copy(vscl, refs[which].at[layer, pid, pl.ds(kv_head, 1)])


def _quant_store_token(cfg, refs, cache, scale_name, layer, pid, off,
                       kv_head, head_row):
    """Quantize ONE token's (1, hd) row into a quantized page at
    ``(pid, off, kv_head)``, maintaining the per-(layer, page, kv_head)
    running max-abs scale: the page's FIRST position (``off == 0``)
    RESETS the scale, so a freed-and-reused page never inherits a
    stale one; a later token whose amax exceeds the running amax grows
    the scale and RESCALES the already-stored page content to it first
    — the in-kernel form of the layer path's dequant→merge→requant
    (double-rounds old tokens exactly like the XLA merge does)."""
    qmax = cfg.qmax
    vqd, vqt = refs["vqd"], refs["vqt"]
    amax = jnp.max(jnp.abs(head_row))
    s_old = _read_scale(refs, scale_name, layer, pid, kv_head)
    fresh = off == 0
    s_tok = jnp.where(amax > 0, amax / qmax, 0.0)
    s_new = jnp.where(fresh,
                      jnp.where(amax > 0, amax / qmax, 1.0),
                      jnp.maximum(s_old, s_tok))

    @pl.when(jnp.logical_and(jnp.logical_not(fresh), s_new > s_old))
    def _():
        ratio = s_old / s_new
        t_tile = vqt.shape[0]
        for tt in range(cfg.page // t_tile):     # static: t_tile | page
            sl = cache.at[layer, pid, pl.ds(tt * t_tile, t_tile),
                          kv_head, :]
            pltpu.sync_copy(sl, vqt)
            vqt[...] = _quant_cast(
                vqt[...].astype(jnp.float32) * ratio, vqt.dtype, qmax)
            pltpu.sync_copy(vqt, sl)

    vqd[...] = _quant_cast(head_row / s_new, vqd.dtype, qmax)
    pltpu.sync_copy(vqd, cache.at[layer, pid, pl.ds(off, 1),
                                  kv_head, :])
    _write_scale(refs, scale_name, layer, pid, kv_head, s_new)


def _dequant_tile(cfg, refs, cache, scale_name, layer, pid, start,
                  kv_head):
    """One (t_tile, hd) cache tile dequantized to fp32 — the read half
    of the fused scheme (start is the in-page offset; the builder's
    t_tile | page contract keeps the tile inside one page)."""
    vqt = refs["vqt"]
    s = _read_scale(refs, scale_name, layer, pid, kv_head)
    pltpu.sync_copy(cache.at[layer, pid, pl.ds(start, vqt.shape[0]),
                             kv_head, :], vqt)
    return vqt[...].astype(jnp.float32) * s


# ---------------------------------------------------------------------------
# Task bodies. Common closure args: cfg + refs
# (args_s, len_s, arena, k_cache, v_cache, vmem scratches, sems).
# ---------------------------------------------------------------------------

def rmsnorm_body(cfg, args, refs):
    arena, va, vb, vc, acc = (refs["arena"], refs["va"], refs["vb"],
                              refs["vc"], refs["acc"])
    in_off, w_off, out_off, d_tiles = args[0], args[1], args[2], args[3]
    b = cfg.batch

    def ssq_step(j, ssq):
        pltpu.sync_copy(arena.at[pl.ds(in_off + j * b, b)], va)
        x = va[...].astype(jnp.float32)
        return ssq + jnp.sum(x * x, axis=1, keepdims=True)

    ssq = jax.lax.fori_loop(0, d_tiles, ssq_step,
                            jnp.zeros((b, 1), jnp.float32))
    inv = jax.lax.rsqrt(ssq / (d_tiles * cfg.w).astype(jnp.float32)
                        + cfg.rms_eps)

    def norm_step(j, _):
        pltpu.sync_copy(arena.at[pl.ds(in_off + j * b, b)], va)
        pltpu.sync_copy(arena.at[pl.ds(w_off + j, 1)],
                        vc.at[pl.ds(0, 1)])
        vb[...] = (va[...].astype(jnp.float32) * inv
                   * vc[0:1, :].astype(jnp.float32))
        pltpu.sync_copy(vb, arena.at[pl.ds(out_off + j * b, b)])
        return 0

    jax.lax.fori_loop(0, d_tiles, norm_step, 0)


def linear_body(cfg, args, refs):
    arena, va, vw, acc = (refs["arena"], refs["va"], refs["vw"],
                          refs["acc"])
    in_off, w_off, out_off = args[0], args[1], args[2]
    k_tiles, n_tiles, j = args[3], args[4], args[5]
    b, w = cfg.batch, cfg.w

    def kt_step(kt, a):
        pltpu.sync_copy(arena.at[pl.ds(in_off + kt * b, b)], va)
        pltpu.sync_copy(
            arena.at[pl.ds(w_off + (kt * n_tiles + j) * w, w)], vw)
        return a + jnp.dot(va[...], vw[...],
                           preferred_element_type=jnp.float32)

    out = jax.lax.fori_loop(0, k_tiles, kt_step,
                            jnp.zeros((b, w), jnp.float32))
    acc[...] = out
    pltpu.sync_copy(acc, arena.at[pl.ds(out_off + j * b, b)])


def add_body(cfg, args, refs):
    arena, va, vb, vc = refs["arena"], refs["va"], refs["vb"], refs["vc"]
    a_off, b_off, out_off, tiles = args[0], args[1], args[2], args[3]
    b = cfg.batch

    def step(j, _):
        pltpu.sync_copy(arena.at[pl.ds(a_off + j * b, b)], va)
        pltpu.sync_copy(arena.at[pl.ds(b_off + j * b, b)], vb)
        vc[...] = va[...] + vb[...]
        pltpu.sync_copy(vc, arena.at[pl.ds(out_off + j * b, b)])
        return 0

    jax.lax.fori_loop(0, tiles, step, 0)


def silu_mul_body(cfg, args, refs):
    arena, va, vb, vc = refs["arena"], refs["va"], refs["vb"], refs["vc"]
    g_off, u_off, out_off, tiles = args[0], args[1], args[2], args[3]
    b = cfg.batch

    def step(j, _):
        pltpu.sync_copy(arena.at[pl.ds(g_off + j * b, b)], va)
        pltpu.sync_copy(arena.at[pl.ds(u_off + j * b, b)], vb)
        g = va[...].astype(jnp.float32)
        vc[...] = jax.nn.silu(g) * vb[...].astype(jnp.float32)
        pltpu.sync_copy(vc, arena.at[pl.ds(out_off + j * b, b)])
        return 0

    jax.lax.fori_loop(0, tiles, step, 0)


def moe_weights_body(cfg, args, refs):
    """Router epilogue: softmax over the first ``n_experts`` columns of
    the router-logits tile, keep the top-``cfg.moe_topk`` per row
    (static iterative argmax extraction — no in-kernel sort), optional
    renormalization; writes the (B, W) combine-weight tile (reference:
    the megakernel's routing happens host-side; in-kernel routing keeps
    the whole MoE decode step one launch)."""
    arena, va, vb, vc = (refs["arena"], refs["va"], refs["vb"],
                         refs["vc"])
    rl_off, wout_off, e_n = args[0], args[1], args[2]
    cnt_off = args[3]
    b = cfg.batch

    pltpu.sync_copy(arena.at[pl.ds(rl_off, b)], va)
    lg = va[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    lg = jnp.where(col < e_n, lg, -jnp.inf)
    p = jax.nn.softmax(lg, axis=-1)
    p = jnp.where(col < e_n, p, 0.0)
    mask = jnp.zeros(p.shape, jnp.bool_)
    work = p
    for _ in range(cfg.moe_topk):
        amax = jnp.argmax(work, axis=-1)
        pick = col == amax[:, None]
        mask = jnp.logical_or(mask, pick)
        work = jnp.where(pick, -jnp.inf, work)
    wbe = jnp.where(mask, p, 0.0)
    if cfg.moe_norm:
        wbe = wbe / jnp.maximum(jnp.sum(wbe, axis=-1, keepdims=True),
                                1e-30)
    vc[...] = wbe
    pltpu.sync_copy(vc, arena.at[pl.ds(wout_off, b)])
    # Expert-load telemetry: accumulate this layer's top-k selection
    # mask into the shared counts region (column e = expert e; rows
    # summed host-side). Monotonic across steps — the arena packs
    # zeroed and the host diffs snapshots; float32 stays count-exact
    # to 2^24 selections.
    pltpu.sync_copy(arena.at[pl.ds(cnt_off, b)], vb)
    vb[...] = vb[...] + mask.astype(jnp.float32)
    pltpu.sync_copy(vb, arena.at[pl.ds(cnt_off, b)])


def weighted_add_body(cfg, args, refs):
    """acc[+]= part * wbe[:, e] — the per-expert combine of the MoE
    FFN block (``init`` selects write vs accumulate; the expert-e
    column is selected maskwise, no dynamic gather)."""
    arena, va, vb, vc = (refs["arena"], refs["va"], refs["vb"],
                         refs["vc"])
    acc_off, part_off, wbe_off = args[0], args[1], args[2]
    e_idx, tiles, init = args[3], args[4], args[5]
    b = cfg.batch

    pltpu.sync_copy(arena.at[pl.ds(wbe_off, b)], va)
    col = jax.lax.broadcasted_iota(jnp.int32, va.shape, 1)
    wcol = jnp.sum(jnp.where(col == e_idx,
                             va[...].astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)                   # (B, 1)

    def step(j, _):
        pltpu.sync_copy(arena.at[pl.ds(part_off + j * b, b)], vb)
        pltpu.sync_copy(arena.at[pl.ds(acc_off + j * b, b)], vc)
        term = vb[...].astype(jnp.float32) * wcol
        vc[...] = jnp.where(init == 1, term, vc[...] + term)
        pltpu.sync_copy(vc, arena.at[pl.ds(acc_off + j * b, b)])
        return 0

    jax.lax.fori_loop(0, tiles, step, 0)


def _rms_rows(x, w_row, eps):
    """Row-wise RMSNorm of (rows, hd) fp32 with (hd,) weight."""
    var = jnp.mean(x * x, axis=1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w_row[None]


def _write_kv_body_quant(cfg, args, refs, len_s):
    """Quantized form of :func:`write_kv_body` (paged pools only):
    same per-row append, with quantize-on-write through the running
    per-(layer, page, kv_head) scales. Loops are STATIC python (the
    scale DMA needs a static head index); op-for-op the math matches
    the fp32 body, so the stored values dequantize to the same tokens
    the unquantized lane would have written, modulo quantization."""
    arena, k_cache, v_cache = (refs["arena"], refs["k_cache"],
                               refs["v_cache"])
    va, vb = refs["va"], refs["vb"]
    tbl_s = refs["tbl_s"]
    k_off, v_off, layer, knorm_off = args[0], args[1], args[2], args[3]
    b, hd, kv_loc, w = cfg.batch, cfg.hd, cfg.kv_loc, cfg.w
    heads_per_tile = w // hd
    kv_tiles = -(-(kv_loc * hd) // w)
    pos_rows = jnp.concatenate(
        [jnp.full((1, 1), len_s[bb], jnp.int32) for bb in range(b)],
        axis=0)

    pltpu.sync_copy(arena.at[pl.ds(knorm_off, 1)], vb.at[pl.ds(0, 1)])
    wrow = vb[0, :hd].astype(jnp.float32)

    for j in range(kv_tiles):                      # static tile loop
        pltpu.sync_copy(arena.at[pl.ds(k_off + j * b, b)], va)
        kt = va[...].astype(jnp.float32)
        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh      # STATIC head index
            if kv_head >= kv_loc:
                continue                           # padding head
            head = kt[:, hh * hd:(hh + 1) * hd]
            head = _rms_rows(head, wrow, cfg.rms_eps)
            head = _rope_rows(head, pos_rows, hd, cfg.rope_theta)
            for bb in range(b):
                pos = len_s[bb]
                pid = tbl_s[bb * cfg.p_max + pos // cfg.page]
                off = jax.lax.rem(pos, cfg.page)
                _quant_store_token(cfg, refs, k_cache, "k_scale",
                                   layer, pid, off, kv_head,
                                   head[bb:bb + 1])
        pltpu.sync_copy(arena.at[pl.ds(v_off + j * b, b)], va)
        vt = va[...].astype(jnp.float32)
        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh
            if kv_head >= kv_loc:
                continue
            for bb in range(b):
                pos = len_s[bb]
                pid = tbl_s[bb * cfg.p_max + pos // cfg.page]
                off = jax.lax.rem(pos, cfg.page)
                _quant_store_token(cfg, refs, v_cache, "v_scale",
                                   layer, pid, off, kv_head,
                                   vt[bb:bb + 1, hh * hd:(hh + 1) * hd])


def write_kv_body(cfg, args, refs, len_s):
    """Append the new token's K/V (with k-norm + rope on K) to the cache
    at EACH BATCH ROW'S OWN position ``len_s[bb]`` — the live-slot form
    the serving layer drives (a uniform batch passes a broadcast
    vector and degenerates to the old single-position append). Builder
    guarantees hd | w. Quantized pools route to the fused
    quantize-on-write variant; the fp32 path below is untouched (and
    stays bit-identical to the pre-quantization kernel)."""
    if cfg.kv_quant:
        return _write_kv_body_quant(cfg, args, refs, len_s)
    arena, k_cache, v_cache = (refs["arena"], refs["k_cache"],
                               refs["v_cache"])
    va, vb, vhd = refs["va"], refs["vb"], refs["vhd"]
    k_off, v_off, layer, knorm_off = args[0], args[1], args[2], args[3]
    b, hd, kv_loc, w = cfg.batch, cfg.hd, cfg.kv_loc, cfg.w
    heads_per_tile = w // hd
    kv_tiles = pl.cdiv(kv_loc * hd, w)
    # Per-row positions as a (b, 1) value vector (SMEM reads are
    # scalar; b is tiny and the loop static).
    pos_rows = jnp.concatenate(
        [jnp.full((1, 1), len_s[bb], jnp.int32) for bb in range(b)],
        axis=0)
    # Uniform-batch predicate: the classic decode (scalar broadcast)
    # keeps its ONE batched store per (tile, K/V) fast path; only a
    # genuinely ragged serving batch pays the per-row copies.
    uniform = jnp.bool_(True)
    for bb in range(1, b):
        uniform = jnp.logical_and(uniform, len_s[bb] == len_s[0])

    pltpu.sync_copy(arena.at[pl.ds(knorm_off, 1)],
                    vb.at[pl.ds(0, 1)])  # (1, w) k_norm
    wrow = vb[0, :hd].astype(jnp.float32)

    # Head loops are STATIC Python (and so are the column slices):
    # Mosaic has no lowering for value-level dynamic_slice with traced
    # starts, and heads_per_tile is tiny.
    def per_tile(j, _):
        pltpu.sync_copy(arena.at[pl.ds(k_off + j * b, b)], va)
        kt = va[...].astype(jnp.float32)        # (b, w)

        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh

            @pl.when(kv_head < cfg.kv_loc)  # skip padding heads
            def _():
                head = kt[:, hh * hd:(hh + 1) * hd]
                head = _rms_rows(head, wrow, cfg.rms_eps)
                head = _rope_rows(head, pos_rows, hd, cfg.rope_theta)
                vhd[...] = head.astype(vhd.dtype)
                if not cfg.paged:
                    @pl.when(uniform)
                    def _():
                        # Dense + uniform: all batches of one position
                        # are contiguous — one copy.
                        pltpu.sync_copy(
                            vhd, k_cache.at[layer, pl.ds(0, b),
                                            len_s[0], kv_head, :])

                    @pl.when(jnp.logical_not(uniform))
                    def _():
                        for bb in range(b):  # per-row positions
                            pltpu.sync_copy(
                                vhd.at[pl.ds(bb, 1)],
                                _kv_slice(k_cache, refs, cfg, layer,
                                          bb, len_s[bb], 1, kv_head))
                else:
                    for bb in range(b):  # per-batch pages
                        pltpu.sync_copy(
                            vhd.at[pl.ds(bb, 1)],
                            _kv_slice(k_cache, refs, cfg, layer, bb,
                                      len_s[bb], 1, kv_head))

        pltpu.sync_copy(arena.at[pl.ds(v_off + j * b, b)], va)
        vt = va[...]

        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh

            @pl.when(kv_head < cfg.kv_loc)
            def _():
                vhd[...] = vt[:, hh * hd:(hh + 1) * hd].astype(vhd.dtype)
                if not cfg.paged:
                    @pl.when(uniform)
                    def _():
                        pltpu.sync_copy(
                            vhd, v_cache.at[layer, pl.ds(0, b),
                                            len_s[0], kv_head, :])

                    @pl.when(jnp.logical_not(uniform))
                    def _():
                        for bb in range(b):
                            pltpu.sync_copy(
                                vhd.at[pl.ds(bb, 1)],
                                _kv_slice(v_cache, refs, cfg, layer,
                                          bb, len_s[bb], 1, kv_head))
                else:
                    for bb in range(b):
                        pltpu.sync_copy(
                            vhd.at[pl.ds(bb, 1)],
                            _kv_slice(v_cache, refs, cfg, layer, bb,
                                      len_s[bb], 1, kv_head))
        return 0

    jax.lax.fori_loop(0, kv_tiles, per_tile, 0)


def _attn_decode_body_quant(cfg, args, refs, len_s):
    """Quantized form of :func:`attn_decode_body`: the same per-row
    online-softmax stream with the dequant fused into each (t_tile,
    hd) page read — pre-gathered scales are impossible here because
    write_kv of the SAME launch updates them, so each tile reads its
    page's scale live. Static head loops (scale DMA needs a static
    head index); per-(1, hd) query math is op-for-op the fp32 body's,
    so bf16-vs-quant divergence is the quantization error only."""
    arena, k_cache, v_cache, va = (refs["arena"], refs["k_cache"],
                                   refs["v_cache"], refs["va"])
    tbl_s = refs["tbl_s"]
    q_off, out_off, layer, qnorm_off = args[0], args[1], args[2], args[3]
    b, hd, w = cfg.batch, cfg.hd, cfg.w
    h_loc, kv_loc = cfg.h_loc, cfg.kv_loc
    t_tile = refs["vqt"].shape[0]
    pos_rows = jnp.concatenate(
        [jnp.full((1, 1), len_s[bb], jnp.int32) for bb in range(b)],
        axis=0)
    group = h_loc // kv_loc
    heads_per_tile = w // hd

    pltpu.sync_copy(arena.at[pl.ds(qnorm_off, 1)],
                    refs["vb"].at[pl.ds(0, 1)])
    qn_row = refs["vb"][0, :hd].astype(jnp.float32)

    q_tiles = -(-(h_loc * hd) // w)
    for j in range(q_tiles):                       # static tile loop
        pltpu.sync_copy(arena.at[pl.ds(q_off + j * b, b)], va)
        qtile = va[...].astype(jnp.float32)
        col_blocks = []
        for hh in range(heads_per_tile):
            h_idx = j * heads_per_tile + hh        # STATIC head index
            if h_idx >= h_loc:
                col_blocks.append(jnp.zeros((b, hd), jnp.float32))
                continue
            kv_head = h_idx // group
            q = qtile[:, hh * hd:(hh + 1) * hd]
            q = _rms_rows(q, qn_row, cfg.rms_eps)
            q = _rope_rows(q, pos_rows, hd, cfg.rope_theta)
            q = q / jnp.sqrt(jnp.float32(hd))
            row_blocks = []
            for bb in range(b):
                kv_len = len_s[bb] + 1
                n_tiles_t = pl.cdiv(kv_len, t_tile)

                def tstep(tt, carry, bb=bb, q=q, kv_head=kv_head,
                          kv_len=kv_len):
                    m, l, acc = carry
                    pid = tbl_s[bb * cfg.p_max
                                + (tt * t_tile) // cfg.page]
                    start = jax.lax.rem(tt * t_tile, cfg.page)
                    kt = _dequant_tile(cfg, refs, k_cache, "k_scale",
                                       layer, pid, start, kv_head)
                    s = jnp.dot(q[bb:bb + 1], kt.T,
                                preferred_element_type=jnp.float32)
                    tpos = tt * t_tile + jax.lax.broadcasted_iota(
                        jnp.int32, (1, t_tile), 1)
                    s = jnp.where(tpos < kv_len, s, -jnp.inf)
                    m_new = jnp.maximum(
                        m, jnp.max(s, axis=1, keepdims=True))
                    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                    p = jnp.where(jnp.isfinite(s),
                                  jnp.exp(s - m_safe), 0.0)
                    corr = jnp.where(jnp.isfinite(m),
                                     jnp.exp(m - m_safe), 0.0)
                    vt = _dequant_tile(cfg, refs, v_cache, "v_scale",
                                       layer, pid, start, kv_head)
                    acc = acc * corr + jnp.dot(
                        p, vt, preferred_element_type=jnp.float32)
                    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                    return (m_new, l, acc)

                m0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
                l0 = jnp.zeros((1, 1), jnp.float32)
                acc0 = jnp.zeros((1, hd), jnp.float32)
                m, l, acc = jax.lax.fori_loop(0, n_tiles_t, tstep,
                                              (m0, l0, acc0))
                row_blocks.append(acc / jnp.maximum(l, 1e-30))
            col_blocks.append(jnp.concatenate(row_blocks, axis=0))
        refs["acc"][...] = jnp.concatenate(col_blocks, axis=1)
        pltpu.sync_copy(refs["acc"],
                        arena.at[pl.ds(out_off + j * b, b)])


def attn_decode_body(cfg, args, refs, len_s):
    """Single-token GQA flash decode over the (already appended) cache.

    q: (B, h_loc*hd) activation; out same shape. Loops heads × batch;
    each (head, batch) pair streams the cache in (T_TILE, hd) tiles with
    online-softmax accumulation — at EACH ROW'S OWN length ``len_s[bb]``
    (the live-slot serving form; a uniform batch degenerates to the old
    single-length decode, including the per-row tile-loop trip counts).
    Quantized pools route to the fused-dequant variant; the fp32 path
    below is untouched.
    """
    if cfg.kv_quant:
        return _attn_decode_body_quant(cfg, args, refs, len_s)
    arena, k_cache, v_cache, va, vkt = (refs["arena"], refs["k_cache"],
                                        refs["v_cache"], refs["va"],
                                        refs["vkt"])
    q_off, out_off, layer, qnorm_off = args[0], args[1], args[2], args[3]
    b, hd, w = cfg.batch, cfg.hd, cfg.w
    h_loc, kv_loc = cfg.h_loc, cfg.kv_loc
    t_tile = vkt.shape[0]
    pos_rows = jnp.concatenate(
        [jnp.full((1, 1), len_s[bb], jnp.int32) for bb in range(b)],
        axis=0)
    group = h_loc // kv_loc
    heads_per_tile = w // hd

    pltpu.sync_copy(arena.at[pl.ds(qnorm_off, 1)],
                    refs["vb"].at[pl.ds(0, 1)])
    qn_row = refs["vb"][0, :hd].astype(jnp.float32)

    def per_qtile(j, _):
        pltpu.sync_copy(arena.at[pl.ds(q_off + j * b, b)], va)
        qtile = va[...].astype(jnp.float32)     # (b, w)
        col_blocks = []

        # Static head/batch loops with concat assembly: Mosaic lowers
        # neither dynamic_slice nor dynamic_update_slice on values.
        for hh in range(heads_per_tile):
            h_idx = j * heads_per_tile + hh
            # Padding heads beyond h_loc compute garbage that is
            # discarded below; clamp the cache index to stay in bounds.
            kv_head = jnp.minimum(h_idx // group, cfg.kv_loc - 1)
            q = qtile[:, hh * hd:(hh + 1) * hd]
            q = _rms_rows(q, qn_row, cfg.rms_eps)
            q = _rope_rows(q, pos_rows, hd, cfg.rope_theta)
            q = q / jnp.sqrt(jnp.float32(hd))
            row_blocks = []

            for bb in range(b):
                kv_len = len_s[bb] + 1
                n_tiles_t = pl.cdiv(kv_len, t_tile)

                # All-2-D online softmax: Mosaic has no 1-D vector ops.
                def tstep(tt, carry, bb=bb, q=q, kv_head=kv_head,
                          kv_len=kv_len):
                    m, l, acc = carry
                    pltpu.sync_copy(
                        _kv_slice(k_cache, refs, cfg, layer, bb,
                                  tt * t_tile, t_tile, kv_head), vkt)
                    kt = vkt[...].astype(jnp.float32)   # (t_tile, hd)
                    s = jnp.dot(q[bb:bb + 1], kt.T,
                                preferred_element_type=jnp.float32)
                    tpos = tt * t_tile + jax.lax.broadcasted_iota(
                        jnp.int32, (1, t_tile), 1)
                    s = jnp.where(tpos < kv_len, s, -jnp.inf)  # (1, T)
                    m_new = jnp.maximum(
                        m, jnp.max(s, axis=1, keepdims=True))
                    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                    p = jnp.where(jnp.isfinite(s),
                                  jnp.exp(s - m_safe), 0.0)
                    corr = jnp.where(jnp.isfinite(m),
                                     jnp.exp(m - m_safe), 0.0)
                    pltpu.sync_copy(
                        _kv_slice(v_cache, refs, cfg, layer, bb,
                                  tt * t_tile, t_tile, kv_head), vkt)
                    vt = vkt[...].astype(jnp.float32)
                    acc = acc * corr + jnp.dot(
                        p, vt, preferred_element_type=jnp.float32)
                    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                    return (m_new, l, acc)

                m0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
                l0 = jnp.zeros((1, 1), jnp.float32)
                acc0 = jnp.zeros((1, hd), jnp.float32)
                m, l, acc = jax.lax.fori_loop(0, n_tiles_t, tstep,
                                              (m0, l0, acc0))
                row_blocks.append(acc / jnp.maximum(l, 1e-30))  # (1,hd)

            blk = jnp.concatenate(row_blocks, axis=0)   # (b, hd)
            # h_idx is traced (j rides the tile fori); zero padded heads.
            col_blocks.append(jnp.where(h_idx < cfg.h_loc, blk, 0.0))

        refs["acc"][...] = jnp.concatenate(col_blocks, axis=1)
        pltpu.sync_copy(refs["acc"], arena.at[pl.ds(out_off + j * b, b)])
        return 0

    q_tiles = pl.cdiv(h_loc * hd, w)
    jax.lax.fori_loop(0, q_tiles, per_qtile, 0)


def gather_body(cfg, args, refs, tok_s):
    """Embedding lookup over the *vocab-sharded* table: each rank holds
    ``vocab_loc`` entries; non-owners write zeros and the following
    ALLREDUCE task sums the one real contribution. Token ids arrive via
    scalar prefetch; out-of-shard (including out-of-vocab) ids simply
    produce a zero contribution, so no arena row outside the table is
    ever addressed."""
    arena, vb = refs["arena"], refs["vb"]
    table_off, out_off, d_tiles, vocab_loc = (args[0], args[1], args[2],
                                              args[3])
    b = cfg.batch
    me = dl.rank(cfg.axis)

    for bb in range(b):  # static batch
        tok_local = tok_s[bb] - me * vocab_loc
        owner = jnp.logical_and(tok_local >= 0, tok_local < vocab_loc)
        tok_safe = jnp.clip(tok_local, 0, vocab_loc - 1)

        def per_tile(j, _):
            @pl.when(owner)
            def _():
                pltpu.sync_copy(
                    arena.at[pl.ds(table_off + tok_safe * d_tiles + j, 1)],
                    vb.at[pl.ds(0, 1)])

            @pl.when(jnp.logical_not(owner))
            def _():
                vb[pl.ds(0, 1), :] = jnp.zeros((1, cfg.w), vb.dtype)

            pltpu.sync_copy(
                vb.at[pl.ds(0, 1)],
                arena.at[pl.ds(out_off + j * b + bb, 1)])
            return 0

        jax.lax.fori_loop(0, d_tiles, per_tile, 0)


def allreduce_body(cfg, args, refs):
    """One-shot in-kernel allreduce of an arena slab across the TP axis
    (reference: megakernel allreduce + barrier tasks,
    ``mega_triton_kernel/kernels/allreduce.py``)."""
    arena, va, vb, send_sem, recv_sem = (
        refs["arena"], refs["va"], refs["vb"], refs["send_sem"],
        refs["recv_sem"])
    # args[1] (tiles) is a traced prefetch read, but every ALLREDUCE the
    # builder records moves exactly ``ar_max_tiles`` tiles — use the
    # static value so the slab slice has a static SIZE (Mosaic needs
    # one).
    buf_off, tiles = args[0], cfg.ar_max_tiles
    b, n = cfg.batch, cfg.n_ranks
    if n == 1:
        return
    me = dl.rank(cfg.axis)
    rows = tiles * b
    slab = arena.at[pl.ds(buf_off, rows)]
    my_slot = arena.at[pl.ds(cfg.ar_ws_off + me * cfg.ar_max_tiles * b,
                             rows)]

    dl.barrier_all(cfg.axis, ctx=cfg.mesh)
    copies = []
    for off in range(1, n):
        peer = jax.lax.rem(me + off, n)
        copies.append(dl.remote_put(slab, my_slot, send_sem.at[off - 1],
                                    recv_sem, peer, axis=cfg.axis,
                                    ctx=cfg.mesh))
    for c in copies:
        c.wait_send()
    dl.wait_arrivals(recv_sem, slab, n - 1)

    def step(j, _):
        pltpu.sync_copy(arena.at[pl.ds(buf_off + j * b, b)], va)
        acc = va[...].astype(jnp.float32)
        for r_off in range(1, n):
            peer = jax.lax.rem(me + r_off, n)
            pltpu.sync_copy(
                arena.at[pl.ds(cfg.ar_ws_off
                               + peer * cfg.ar_max_tiles * b + j * b, b)],
                vb)
            acc = acc + vb[...].astype(jnp.float32)
        va[...] = acc
        pltpu.sync_copy(va, arena.at[pl.ds(buf_off + j * b, b)])
        return 0

    jax.lax.fori_loop(0, tiles, step, 0)


def _rope_rows(x, pos_rows, hd, theta):
    """x: (rows, hd) fp32; per-row positions pos_rows (rows, 1)."""
    half = hd // 2
    # Integer iota + cast: tpu.iota only produces integer vectors.
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1
                                   ).astype(jnp.float32) * 2.0
    inv = 1.0 / (theta ** (idx / hd))                 # (1, half)
    ang = pos_rows.astype(jnp.float32) * inv          # (rows, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=1)


def write_kv_prefill_body(cfg, args, refs, len_s):
    """Batched prefill cache append: rows are (batch, seq) pairs in
    b-major order; row r writes cache position base + r % seq of batch
    r // seq. The whole (S, hd) block per (batch, head) lands in ONE
    store — the real prefill path the round-1 decode chain lacked."""
    arena, k_cache, v_cache = (refs["arena"], refs["k_cache"],
                               refs["v_cache"])
    va, vb, vsq = refs["va"], refs["vb"], refs["vsq"]
    k_off, v_off, layer, knorm_off = args[0], args[1], args[2], args[3]
    rows, hd, w = cfg.batch, cfg.hd, cfg.w
    seq = cfg.seq
    nb = rows // seq
    base = len_s[0]
    heads_per_tile = w // hd
    kv_tiles = pl.cdiv(cfg.kv_loc * hd, w)
    row_pos = base + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), seq)

    pltpu.sync_copy(arena.at[pl.ds(knorm_off, 1)], vb.at[pl.ds(0, 1)])
    wrow = vb[0, :hd].astype(jnp.float32)

    # Static head/batch loops with static column slices — Mosaic has
    # no lowering for value-level dynamic_slice with traced starts.
    def per_tile(j, _):
        pltpu.sync_copy(arena.at[pl.ds(k_off + j * rows, rows)], va)
        kt = va[...].astype(jnp.float32)

        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh

            @pl.when(kv_head < cfg.kv_loc)
            def _():
                head = kt[:, hh * hd:(hh + 1) * hd]
                head = _rms_rows(head, wrow, cfg.rms_eps)
                head = _rope_rows(head, row_pos, hd, cfg.rope_theta)
                for bb in range(nb):  # static batch
                    vsq[...] = head[bb * seq:(bb + 1) * seq].astype(
                        vsq.dtype)
                    pltpu.sync_copy(
                        vsq, _kv_slice(k_cache, refs, cfg, layer, bb,
                                       base, seq, kv_head))

        pltpu.sync_copy(arena.at[pl.ds(v_off + j * rows, rows)], va)
        vt = va[...]

        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh

            @pl.when(kv_head < cfg.kv_loc)
            def _():
                for bb in range(nb):
                    vsq[...] = vt[bb * seq:(bb + 1) * seq,
                                  hh * hd:(hh + 1) * hd].astype(vsq.dtype)
                    pltpu.sync_copy(
                        vsq, _kv_slice(v_cache, refs, cfg, layer, bb,
                                       base, seq, kv_head))
        return 0

    jax.lax.fori_loop(0, kv_tiles, per_tile, 0)


def attn_prefill_body(cfg, args, refs, len_s):
    """Batched causal prefill attention over the just-appended cache.

    Rows are (batch, seq) pairs; row s of batch b attends cache
    positions <= base + s. Each (batch, head) pair runs a (S, t_tile)
    blocked online softmax — S query rows per MXU pass instead of the
    decode body's single row (reference megakernel flash_attn task)."""
    arena, k_cache, v_cache, va, vkt = (refs["arena"], refs["k_cache"],
                                        refs["v_cache"], refs["va"],
                                        refs["vkt"])
    q_off, out_off, layer, qnorm_off = args[0], args[1], args[2], args[3]
    rows, hd, w = cfg.batch, cfg.hd, cfg.w
    seq = cfg.seq
    nb = rows // seq
    t_tile = vkt.shape[0]
    base = len_s[0]
    kv_len = base + seq
    n_tiles_t = pl.cdiv(kv_len, t_tile)
    group = cfg.h_loc // cfg.kv_loc
    heads_per_tile = w // hd
    row_pos = base + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), seq)

    pltpu.sync_copy(arena.at[pl.ds(qnorm_off, 1)],
                    refs["vb"].at[pl.ds(0, 1)])
    qn_row = refs["vb"][0, :hd].astype(jnp.float32)

    def per_qtile(j, _):
        pltpu.sync_copy(arena.at[pl.ds(q_off + j * rows, rows)], va)
        qtile = va[...].astype(jnp.float32)
        col_blocks = []

        for hh in range(heads_per_tile):
            h_idx = j * heads_per_tile + hh
            kv_head = jnp.minimum(h_idx // group, cfg.kv_loc - 1)
            q = qtile[:, hh * hd:(hh + 1) * hd]
            q = _rms_rows(q, qn_row, cfg.rms_eps)
            q = _rope_rows(q, row_pos, hd, cfg.rope_theta)
            q = q / jnp.sqrt(jnp.float32(hd))
            row_blocks = []

            for bb in range(nb):
                qb = q[bb * seq:(bb + 1) * seq]
                srow = jax.lax.broadcasted_iota(jnp.int32, (seq, 1), 0)

                def tstep(tt, carry, bb=bb, qb=qb, kv_head=kv_head):
                    m, l, acc = carry
                    pltpu.sync_copy(
                        _kv_slice(k_cache, refs, cfg, layer, bb,
                                  tt * t_tile, t_tile, kv_head), vkt)
                    kt = vkt[...].astype(jnp.float32)   # (t_tile, hd)
                    s = jnp.dot(qb, kt.T,
                                preferred_element_type=jnp.float32)
                    tpos = tt * t_tile + jax.lax.broadcasted_iota(
                        jnp.int32, (1, t_tile), 1)
                    mask = tpos <= (base + srow)        # causal
                    s = jnp.where(mask, s, -jnp.inf)
                    m_new = jnp.maximum(
                        m, jnp.max(s, axis=1, keepdims=True))
                    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe),
                                  0.0)
                    corr = jnp.where(jnp.isfinite(m),
                                     jnp.exp(m - m_safe), 0.0)
                    pltpu.sync_copy(
                        _kv_slice(v_cache, refs, cfg, layer, bb,
                                  tt * t_tile, t_tile, kv_head), vkt)
                    vt = vkt[...].astype(jnp.float32)
                    acc = acc * corr + jnp.dot(
                        p, vt, preferred_element_type=jnp.float32)
                    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                    return (m_new, l, acc)

                m0 = jnp.full((seq, 1), -jnp.inf, jnp.float32)
                l0 = jnp.zeros((seq, 1), jnp.float32)
                acc0 = jnp.zeros((seq, hd), jnp.float32)
                m, l, acc = jax.lax.fori_loop(0, n_tiles_t, tstep,
                                              (m0, l0, acc0))
                row_blocks.append(acc / jnp.maximum(l, 1e-30))

            blk = jnp.concatenate(row_blocks, axis=0)   # (rows, hd)
            col_blocks.append(jnp.where(h_idx < cfg.h_loc, blk, 0.0))

        refs["acc"][...] = jnp.concatenate(col_blocks, axis=1)
        pltpu.sync_copy(refs["acc"],
                        arena.at[pl.ds(out_off + j * rows, rows)])
        return 0

    q_tiles = pl.cdiv(cfg.h_loc * hd, w)
    jax.lax.fori_loop(0, q_tiles, per_qtile, 0)


def _chunk_apos(enc):
    """Decode one chunk row's sign-encoded position to its ATTEND
    position (clamped ≥ 0 for rope/mask arithmetic). The encoding —
    shared with :func:`ops.chunked_prefill.chunk_row_codes` — packs the
    chunk task's three row kinds into the existing per-row cache_len
    vector, so no extra prefetch operand exists:

    - ``enc >= 0``       write + attend at position ``enc``;
    - ``enc <= -2``      attend-only at position ``-enc - 2`` (prefix-
      resident positions below ``wfrom`` — their K/V was written by the
      first sharer and is never re-blitted, exactly the
      ``chunk_write_ids`` scratch-routing rule);
    - ``enc == -1``      dead row (bucket padding) — decodes to
      position 0, computes garbage the host discards, and the write
      body's ``enc >= 0`` store mask keeps it out of every page.
    """
    return jnp.maximum(jnp.where(enc >= 0, enc, -enc - 2), 0)


def write_kv_qblock_body(cfg, args, refs, len_s):
    """Q-block (speculative verification) cache append: batch rows are
    (slot, j) pairs in slot-major order (``cfg.seq`` = K rows per
    slot); row r appends K/V at its OWN position ``len_s[r]`` through
    slot ``r // K``'s block-table row. ``len_s[r] < 0`` MASKS the row
    entirely (over-budget candidates near a request's token budget,
    parked slots) — masked rows write nothing, so real pages and, on
    quantized pools, their scales are never touched. Math is
    op-for-op :func:`write_kv_body`'s per-row path, so on UNQUANTIZED
    pools a committed candidate's stored K/V is bit-identical to what
    the sequential decode lane would have written at that position
    (greedy spec exactness). Quantized pools are token-AGREEING only:
    an in-budget draft that is later rejected can have grown a page's
    running scale (rescaling committed tokens once) — exactly the
    layer path's merge behaviour, bounded by the quantization
    contract."""
    arena, k_cache, v_cache = (refs["arena"], refs["k_cache"],
                               refs["v_cache"])
    va, vb, vhd = refs["va"], refs["vb"], refs["vhd"]
    tbl_s = refs["tbl_s"]
    k_off, v_off, layer, knorm_off = args[0], args[1], args[2], args[3]
    rows, hd, kv_loc, w = cfg.batch, cfg.hd, cfg.kv_loc, cfg.w
    kq = cfg.seq
    heads_per_tile = w // hd
    kv_tiles = -(-(kv_loc * hd) // w)
    pos_rows = jnp.concatenate(
        [jnp.full((1, 1), jnp.maximum(len_s[r], 0), jnp.int32)
         for r in range(rows)], axis=0)

    pltpu.sync_copy(arena.at[pl.ds(knorm_off, 1)], vb.at[pl.ds(0, 1)])
    wrow = vb[0, :hd].astype(jnp.float32)

    def _store(cache, scale_name, r, kv_head, head_row):
        slot = r // kq
        pos = jnp.maximum(len_s[r], 0)
        pid = tbl_s[slot * cfg.p_max + pos // cfg.page]
        off = jax.lax.rem(pos, cfg.page)

        @pl.when(len_s[r] >= 0)
        def _():
            if cfg.kv_quant:
                _quant_store_token(cfg, refs, cache, scale_name, layer,
                                   pid, off, kv_head, head_row)
            else:
                vhd[pl.ds(0, 1), :] = head_row.astype(vhd.dtype)
                pltpu.sync_copy(
                    vhd.at[pl.ds(0, 1)],
                    cache.at[layer, pid, pl.ds(off, 1), kv_head, :])

    for j in range(kv_tiles):                      # static tile loop
        pltpu.sync_copy(arena.at[pl.ds(k_off + j * rows, rows)], va)
        kt = va[...].astype(jnp.float32)
        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh      # static head index
            if kv_head >= kv_loc:
                continue
            head = kt[:, hh * hd:(hh + 1) * hd]
            head = _rms_rows(head, wrow, cfg.rms_eps)
            head = _rope_rows(head, pos_rows, hd, cfg.rope_theta)
            for r in range(rows):
                _store(k_cache, "k_scale", r, kv_head, head[r:r + 1])
        pltpu.sync_copy(arena.at[pl.ds(v_off + j * rows, rows)], va)
        vt = va[...].astype(jnp.float32)
        for hh in range(heads_per_tile):
            kv_head = j * heads_per_tile + hh
            if kv_head >= kv_loc:
                continue
            for r in range(rows):
                _store(v_cache, "v_scale", r, kv_head,
                       vt[r:r + 1, hh * hd:(hh + 1) * hd])


def write_kv_chunk_body(cfg, args, refs, len_s):
    """Prefill-chunk cache append: store row r's K/V iff its encoded
    position is non-negative — which under the :func:`_chunk_apos`
    encoding is EXACTLY the Q-block body's ``len_s[r] >= 0`` store
    mask, so this delegates verbatim. Attend-only rows (prefix-resident
    positions, encoded ``<= -2``) and dead padding rows (``-1``) never
    touch a page or, on quantized pools, a scale — the in-kernel form
    of ``chunk_write_ids``'s scratch routing. Rows store one token
    each, in ascending-position row order per (layer, page, kv_head),
    so a quantized page's running-scale evolution (and the ``off == 0``
    page-start reset that handles ragged chunk tails reusing freed
    pages) is the same per-head sequence the one-token lane produces.
    """
    write_kv_qblock_body(cfg, args, refs, len_s)


def attn_qblock_body(cfg, args, refs, len_s):
    """Q-block verification attention: each slot's K query rows attend
    the (just-appended) cache under the PER-QUERY causal mask
    ``key_pos <= len_s[row]`` — the ``ops/paged_flash_qblock`` mask as
    a megakernel task. One task covers a whole K-token verification
    chain's attention for one layer; each query row runs the SAME
    (1, hd) online-softmax stream as :func:`attn_decode_body`, so a
    committed candidate's logits are bit-identical to the sequential
    decode's (the greedy-acceptance exactness contract). Rows with
    ``len_s[row] < 0`` compute garbage the host discards."""
    _attn_rowpos_body(cfg, args, refs,
                      [jnp.maximum(len_s[r], 0)
                       for r in range(cfg.batch)])


def attn_chunk_body(cfg, args, refs, len_s):
    """Prefill-chunk attention: the Q-block per-query causal stream
    over one C-token prompt chunk, row positions decoded from the
    sign-encoded cache_len vector (:func:`_chunk_apos`). Row r attends
    keys at positions ``<= apos[r]`` — :func:`ops.chunked_prefill.
    chunk_attend`'s global causal mask — which covers earlier chunks,
    the shared prefix, AND this chunk's own earlier rows (the paired
    WRITE_KV_CHUNK task already appended them; the task dep enforces
    the order), so chunk boundaries are invisible to the math. Dead
    (padding) rows compute garbage the host discards."""
    _attn_rowpos_body(cfg, args, refs,
                      [_chunk_apos(len_s[r]) for r in range(cfg.batch)])


def _attn_rowpos_body(cfg, args, refs, row_pos):
    """Shared per-row-position attention core of
    :func:`attn_qblock_body` / :func:`attn_chunk_body`: ``row_pos`` is
    a python list of ``cfg.batch`` traced int32 scalars (≥ 0), row r's
    query rope position and causal horizon (``kv_len = row_pos[r]+1``).
    """
    arena, k_cache, v_cache, va, vkt = (refs["arena"], refs["k_cache"],
                                        refs["v_cache"], refs["va"],
                                        refs["vkt"])
    tbl_s = refs["tbl_s"]
    q_off, out_off, layer, qnorm_off = args[0], args[1], args[2], args[3]
    rows, hd, w = cfg.batch, cfg.hd, cfg.w
    h_loc, kv_loc = cfg.h_loc, cfg.kv_loc
    kq = cfg.seq
    t_tile = (refs["vqt"].shape[0] if cfg.kv_quant else vkt.shape[0])
    group = h_loc // kv_loc
    heads_per_tile = w // hd
    pos_rows = jnp.concatenate(
        [jnp.full((1, 1), row_pos[r], jnp.int32)
         for r in range(rows)], axis=0)

    pltpu.sync_copy(arena.at[pl.ds(qnorm_off, 1)],
                    refs["vb"].at[pl.ds(0, 1)])
    qn_row = refs["vb"][0, :hd].astype(jnp.float32)

    q_tiles = -(-(h_loc * hd) // w)
    for j in range(q_tiles):                       # static tile loop
        pltpu.sync_copy(arena.at[pl.ds(q_off + j * rows, rows)], va)
        qtile = va[...].astype(jnp.float32)
        col_blocks = []
        for hh in range(heads_per_tile):
            h_idx = j * heads_per_tile + hh        # static head index
            if h_idx >= h_loc:
                col_blocks.append(jnp.zeros((rows, hd), jnp.float32))
                continue
            kv_head = h_idx // group
            q = qtile[:, hh * hd:(hh + 1) * hd]
            q = _rms_rows(q, qn_row, cfg.rms_eps)
            q = _rope_rows(q, pos_rows, hd, cfg.rope_theta)
            q = q / jnp.sqrt(jnp.float32(hd))
            row_blocks = []
            for r in range(rows):
                slot = r // kq
                kv_len = row_pos[r] + 1
                n_tiles_t = pl.cdiv(kv_len, t_tile)

                def tstep(tt, carry, slot=slot, r=r, q=q,
                          kv_head=kv_head, kv_len=kv_len):
                    m, l, acc = carry
                    if cfg.kv_quant:
                        pid = tbl_s[slot * cfg.p_max
                                    + (tt * t_tile) // cfg.page]
                        start = jax.lax.rem(tt * t_tile, cfg.page)
                        kt = _dequant_tile(cfg, refs, k_cache,
                                           "k_scale", layer, pid,
                                           start, kv_head)
                    else:
                        pltpu.sync_copy(
                            _kv_slice(k_cache, refs, cfg, layer, slot,
                                      tt * t_tile, t_tile, kv_head),
                            vkt)
                        kt = vkt[...].astype(jnp.float32)
                    s = jnp.dot(q[r:r + 1], kt.T,
                                preferred_element_type=jnp.float32)
                    tpos = tt * t_tile + jax.lax.broadcasted_iota(
                        jnp.int32, (1, t_tile), 1)
                    s = jnp.where(tpos < kv_len, s, -jnp.inf)
                    m_new = jnp.maximum(
                        m, jnp.max(s, axis=1, keepdims=True))
                    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                    p = jnp.where(jnp.isfinite(s),
                                  jnp.exp(s - m_safe), 0.0)
                    corr = jnp.where(jnp.isfinite(m),
                                     jnp.exp(m - m_safe), 0.0)
                    if cfg.kv_quant:
                        vt = _dequant_tile(cfg, refs, v_cache,
                                           "v_scale", layer, pid,
                                           start, kv_head)
                    else:
                        pltpu.sync_copy(
                            _kv_slice(v_cache, refs, cfg, layer, slot,
                                      tt * t_tile, t_tile, kv_head),
                            vkt)
                        vt = vkt[...].astype(jnp.float32)
                    acc = acc * corr + jnp.dot(
                        p, vt, preferred_element_type=jnp.float32)
                    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                    return (m_new, l, acc)

                m0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
                l0 = jnp.zeros((1, 1), jnp.float32)
                acc0 = jnp.zeros((1, hd), jnp.float32)
                m, l, acc = jax.lax.fori_loop(0, n_tiles_t, tstep,
                                              (m0, l0, acc0))
                row_blocks.append(acc / jnp.maximum(l, 1e-30))
            col_blocks.append(jnp.concatenate(row_blocks, axis=0))
        refs["acc"][...] = jnp.concatenate(col_blocks, axis=1)
        pltpu.sync_copy(refs["acc"],
                        arena.at[pl.ds(out_off + j * rows, rows)])


def gdn_decode_body(cfg, args, refs):
    """Gated-delta-rule decode step for one GDN layer, all (batch,
    local-head) pairs: S ← exp(g)·S + β·k(v − Sᵀk)ᵀ; o = Sᵀq
    (``ops/gdn.gdn_decode_step`` math, normalize_qk on). Head slices
    live inside lane tiles (w % dk == 0, w % dv == 0 — builder
    contract); per-(b, h) scalars are extracted with masked reduces
    (no dynamic vector indexing). Row DMAs are grouped per lane tile —
    each q/k row loads once per batch entry, each v/output row once
    per v-tile — and the recurrent state rides the ``states`` buffer,
    the hybrid family's KV-cache analogue."""
    arena, states = refs["arena"], refs["states"]
    va, vb = refs["va"], refs["vb"]
    vrow, vrow2, vS = refs["vrow"], refs["vrow2"], refs["vS"]
    q_off, k_off, v_off = args[0], args[1], args[2]
    graw_off, braw_off, gbias_off = args[3], args[4], args[5]
    out_off, gl = args[6], args[7]
    b, w = cfg.batch, cfg.w
    h_loc, dk, dv = cfg.gdn_h_loc, cfg.gdn_dk, cfg.gdn_dv

    pltpu.sync_copy(arena.at[pl.ds(graw_off, b)], va)     # g raw (b, w)
    pltpu.sync_copy(arena.at[pl.ds(braw_off, b)], vb)     # beta raw
    pltpu.sync_copy(arena.at[pl.ds(gbias_off, 1)], vrow)  # bias (1, w)
    g_all = -jax.nn.softplus(va[...].astype(jnp.float32)
                             + vrow[...].astype(jnp.float32))
    beta_all = jax.nn.sigmoid(vb[...].astype(jnp.float32))
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (b, w), 0)
    cols_i = jax.lax.broadcasted_iota(jnp.int32, (b, w), 1)

    # Static DMA plan: heads grouped by their q-tile; within a group,
    # v/output rows reload only when the v-tile changes (heads are
    # ascending, so v-tiles are nondecreasing). g_all/beta_all live in
    # registers, freeing va/vb as the q/k row buffers.
    gq_tiles = -(-(h_loc * dk) // w)

    def bstep(bb, _):
        qrow = va.at[0:1]
        krow = vb.at[0:1]
        cur_jv = [None]

        def flush_out():
            if cur_jv[0] is not None:
                pltpu.sync_copy(
                    vrow2, arena.at[pl.ds(out_off + cur_jv[0] * b + bb,
                                          1)])

        for jq in range(gq_tiles):
            heads = [hh for hh in range(h_loc) if (hh * dk) // w == jq]
            if not heads:
                continue
            pltpu.sync_copy(arena.at[pl.ds(q_off + jq * b + bb, 1)],
                            qrow)
            pltpu.sync_copy(arena.at[pl.ds(k_off + jq * b + bb, 1)],
                            krow)
            for h in heads:
                cq = (h * dk) % w
                jv, cv = (h * dv) // w, (h * dv) % w
                if jv != cur_jv[0]:
                    flush_out()
                    pltpu.sync_copy(
                        arena.at[pl.ds(v_off + jv * b + bb, 1)], vrow)
                    pltpu.sync_copy(
                        arena.at[pl.ds(out_off + jv * b + bb, 1)],
                        vrow2)
                    cur_jv[0] = jv
                sel = jnp.logical_and(rows_i == bb, cols_i == h)
                g_s = jnp.exp(jnp.sum(jnp.where(sel, g_all, 0.0)))
                b_s = jnp.sum(jnp.where(sel, beta_all, 0.0))
                q = qrow[0:1, cq:cq + dk].astype(jnp.float32)
                k = krow[0:1, cq:cq + dk].astype(jnp.float32)
                # FLA-convention L2 norm — must track ops/gdn._l2norm
                # (the layer oracle this kernel is tested against).
                q = q * jax.lax.rsqrt(
                    jnp.sum(q * q, axis=1, keepdims=True) + 1e-6)
                k = k * jax.lax.rsqrt(
                    jnp.sum(k * k, axis=1, keepdims=True) + 1e-6)
                v = vrow[0:1, cv:cv + dv].astype(jnp.float32)

                pltpu.sync_copy(states.at[gl, bb, h], vS)
                S = vS[...] * g_s
                pred = jnp.dot(k, S,
                               preferred_element_type=jnp.float32)
                delta = (v - pred) * b_s
                S = S + jnp.dot(k.reshape(dk, 1), delta,
                                preferred_element_type=jnp.float32)
                o = jnp.dot(q, S, preferred_element_type=jnp.float32)
                vS[...] = S
                pltpu.sync_copy(vS, states.at[gl, bb, h])
                vrow2[0:1, cv:cv + dv] = o.astype(vrow2.dtype)
        flush_out()
        return 0

    jax.lax.fori_loop(0, b, bstep, 0)
