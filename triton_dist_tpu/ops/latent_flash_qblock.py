"""Latent flash Q-BLOCK attention as a Pallas kernel: a prefill chunk's
rows over their slot's pages of the LATENT pool.

What :func:`triton_dist_tpu.models.latent_moe._attend_expanded` computes
(and stays the oracle for), with no score array outside VMEM. The pool
is :class:`~triton_dist_tpu.serving.blocks.LatentPagedCache`'s: ``(L,
pages, r_kv + d_r, page)``, a page lying ``(width, page)``, so a fetched
page's rows ``[:r_kv]`` are the latent ``(r_kv, keys)`` and its rows
``[r_kv:]`` the roped key part ``(d_r, keys)``, both as a product wants
them. :mod:`ops.paged_flash_qblock` gives the DMA idiom (pages streamed
through VMEM by the table row, double-buffered, the layer an operand)
and nothing else: a key here is in two parts, one of them shared by
every head, and there is no expanded pool to fetch from.

Per grid step ``(row block, head group)`` the kernel walks the pages the
row block can see, ``ppb`` pages (``kb`` keys) a step:

- the step's pages reach VMEM by DMA through the table row while the
  step before computes; a row block never fetches a page past its OWN
  last visible position, so a chunk's causal triangle is skipped
  page-wise;
- the group's keys and values are expanded there, ``[k_n | v]^T =
  w_ukv^T c`` (bf16 operands, float32 accumulation, rounded to the
  pool's type as the XLA walk rounds them), once for all of the row
  block's rows;
- the rows attend in sub-tiles of ``tq``: scores ``q [k_n | k_r]`` and
  the products with the values on the MXU with float32 accumulation, the
  softmax (running maximum and sum a row) in float32, the probabilities
  cast to the values' type before the second product: the XLA walk's
  arithmetic, in other block sizes. A step every row of the block sees
  whole (most of a long context) runs unmasked; on the chunk's own
  diagonal a sub-tile that sees nothing of the step skips it, and the
  others mask.

Sized for its set-up as well as its speed. A step program is traced and
lowered at every start of the process (no compile cache keeps either),
and Mosaic compiles the kernel wherever that cache is empty. So every
loop here (pages of a step, heads of a group, sub-tiles, steps) is a
``pl.loop`` (a ``lax.fori_loop``), and the body holds ONE unmasked and ONE masked
attention of a sub-tile and a head whatever the block sizes: 231
equations traced where the loops written in Python counted 1,345 at 2048
rows (``tests/test_latent_moe.py`` holds the count). Only the heads of a
group are unrolled, at lowering: one basic block of four independent
chains costs 8 % of the kernel's time against all 32 of a step unrolled,
a rolled loop 27 % (PERF.md, PR 39). The layer is an operand and the
call sits behind one ``jax.jit``, so the layers of a program share one
trace and one Mosaic kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import core_call

_NEG = -1e30
_LANES = 128

# Scoped VMEM the kernel asks Mosaic for (a v5e holds 128 MiB; its
# default of 16 is for kernels that state nothing), and the part of it a
# row block's own buffers may take (the rest: the step's pages, the
# expanded keys and values, the compiler's score tiles).
VMEM_LIMIT = 48 * 1024 * 1024
VMEM_BUDGET = 24 * 1024 * 1024
# Pages a step: the running maximum, sum and accumulator are touched
# once a step, so few keys a step leave the vector units that work and
# little else (128 keys a step ran 1.6x slower than 512 on a v5e,
# PERF.md PR 38).
PAGES_A_STEP = 4


def legal(rows: int, dqk: int, dv: int, r: int, width: int,
          page: int) -> bool:
    """Whether Mosaic can tile the kernel at these sizes: whole lanes of
    keys a page, of query and value a head, of latent a key; whole
    sublane tiles (of bf16, the narrowest pool) where a page or the
    expanded keys are cut by rows; rows in whole sub-tiles. Pure host
    arithmetic on shapes."""
    dn = dqk - (width - r)
    return (page % _LANES == 0 and dqk % _LANES == 0 and dv % _LANES == 0
            and r % _LANES == 0 and 0 < dn < dqk and dn % 16 == 0
            and width % 16 == 0 and rows % _LANES == 0)


def block_sizes(rows: int, heads: int, dqk: int, dv: int, itemsize: int,
                budget: int = VMEM_BUDGET):
    """``(rows a block, heads a group, rows a sub-tile, pages a step)``.
    The heads of a group share each fetched page; a row block's rows
    share each expansion of it, so the block is as many rows as
    ``budget`` holds: per row and head the float32 accumulator, the
    lane-wide running maximum and sum, and the double-buffered query and
    output blocks; per row its position, a lane-padded column twice.
    Pure host arithmetic."""
    group = next(g for g in (4, 2, 1) if heads % g == 0)
    tq = 256 if rows % 256 == 0 else _LANES
    per_row = (group * ((dv + 2 * _LANES) * 4 + 2 * (dqk + dv) * itemsize)
               + 2 * _LANES * 4)
    bq = rows
    while bq * per_row > budget and bq % (2 * tq) == 0:
        bq //= 2
    return bq, group, tq, PAGES_A_STEP


def _kernel(layer_ref, table_ref, hi_ref, first_ref, end_ref, qpos_ref,
            q_ref, w_ref, pool_ref, o_ref, lat, kf, vf, m_s, l_s, acc_s,
            sem, *, page: int, ppb: int, tq: int, group: int, dn: int,
            dv: int, r: int, sigma: float):
    """Grid (row blocks, head groups). SMEM: the layer, the slot's table
    row, each sub-tile's greatest last visible position, each row
    block's least and greatest. VMEM blocks: the block's positions
    ``(bq, 1)`` and queries ``(bq, group * dqk)``, the group's
    ``w_ukv^T`` ``(group * (dn + dv), r)``; the pool stays in HBM.
    Scratch: two steps' pages ``(2, width, kb)``, the group's expanded
    keys ``(group, dqk, kb)`` and values ``(group, dv, kb)``, the
    running maximum, sum and accumulator.

    Every loop (pages of a step, heads of a group, sub-tiles of a row
    block, steps of the walk) is a ``pl.loop``: the traced body
    and the Mosaic module hold ONE unmasked and ONE masked attention of
    a sub-tile and a head, whatever the block sizes (module docstring,
    "Sized for its set-up")."""
    i = pl.program_id(0)
    bq = q_ref.shape[0]
    dqk = q_ref.shape[1] // group
    kb = ppb * page
    nt = bq // tq
    li = layer_ref[0]
    n_pages = end_ref[i] // page + 1
    n_steps = (n_pages + ppb - 1) // ppb
    # A partly filled last step multiplies probabilities of 0 with what
    # its unfetched columns hold: make that finite once (afterwards they
    # hold zeros or pages some row block could see).
    @pl.when(jnp.logical_and(i == 0, pl.program_id(1) == 0))
    def _():
        lat[...] = jnp.zeros_like(lat)

    def pages_of(step, slot, go):
        @pl.loop(0, ppb)
        def _(jj):
            pg = step * ppb + jj

            @pl.when(pg < n_pages)
            def _():
                go(pltpu.make_async_copy(
                    pool_ref.at[li, table_ref[pg]],
                    lat.at[slot, :, pl.ds(pl.multiple_of(jj * page, page),
                                          page)],
                    sem.at[slot]))

    pages_of(0, 0, lambda c: c.start())
    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    def lanes(x, n):
        """A lane-wide ``(rows, 128)`` statistic over ``n`` lanes."""
        return x if n == _LANES else jnp.tile(x, (1, n // _LANES))

    def head(j, width):
        return pl.ds(pl.multiple_of(j * width, width), width)

    @pl.loop(0, n_steps)
    def _(k):
        slot = jax.lax.rem(k, 2)
        pages_of(k, slot, lambda c: c.wait())

        @pl.when(k + 1 < n_steps)
        def _():
            pages_of(k + 1, 1 - slot, lambda c: c.start())

        @pl.loop(0, group)
        def _(j):
            kv = jnp.dot(w_ref[head(j, dn + dv), :], lat[slot, :r, :],
                         preferred_element_type=jnp.float32
                         ).astype(kf.dtype)
            kf[j, :dn, :] = kv[:dn]
            kf[j, dn:, :] = lat[slot, r:, :]
            vf[j] = kv[dn:]

        key0 = k * kb

        def attend(t, masked: bool):
            rows = pl.ds(pl.multiple_of(t * tq, tq), tq)

            # The group's heads as ONE basic block (traced once, lowered
            # ``group`` times): their independent chains (product,
            # reduction, exponential, product) overlap.
            @pl.loop(0, group, unroll=True)
            def _(j):
                s = jnp.dot(q_ref[rows, head(j, dqk)], kf[j],
                            preferred_element_type=jnp.float32) * sigma
                if masked:
                    kpos = key0 + jax.lax.broadcasted_iota(
                        jnp.int32, (1, kb), 1)
                    s = jnp.where(kpos <= qpos_ref[rows, :], s, _NEG)
                m_prev = m_s[j, rows, :]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - lanes(m_new, kb))
                l_s[j, rows, :] = (alpha * l_s[j, rows, :]
                                   + jnp.sum(p, axis=-1, keepdims=True))
                m_s[j, rows, :] = m_new
                pv = jax.lax.dot_general(
                    p.astype(vf.dtype), vf[j], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_s[j, rows, :] = (acc_s[j, rows, :] * lanes(alpha, dv)
                                     + pv)

        # Most steps of a long context lie before the block's first
        # row: no mask, no question asked of a sub-tile. On the chunk's
        # own diagonal a sub-tile that sees nothing of the step skips
        # it, and the others mask.
        every = first_ref[i] >= key0 + kb - 1

        @pl.when(every)
        def _():
            pl.loop(0, nt)(lambda t: attend(t, False))

        @pl.when(jnp.logical_not(every))
        def _():
            @pl.loop(0, nt)
            def _(t):
                pl.when(hi_ref[i * nt + t] >= key0)(
                    lambda: attend(t, True))

    # Position 0 is visible to every row, so l holds a key's mass.
    @pl.loop(0, group)
    def _(j):
        o_ref[:, head(j, dv)] = (
            acc_s[j] / lanes(l_s[j], dv)).astype(o_ref.dtype)


def latent_flash_qblock(q, pool, table_row, qpos, w_ukv, *, layer,
                        sigma: float):
    """A chunk's rows of ONE slot over the latent pool.

    q: (C, H, d_n + d_r), roped and scaled; pool: every layer's pages
    whole, (L, num_pages, r_kv + d_r, page), read as ``pool.at[layer,
    pid]`` with ``layer`` an int or int32 scalar (an operand: the layers
    of a step program share one trace and one Mosaic kernel); table_row:
    (p_max,) int32 page ids of the slot; qpos: (C,) int32, the last
    position each row sees (clamped to the row's capacity); w_ukv:
    (r_kv, H, d_n + d_v); ``sigma`` the softmax scale. Every attended
    key is already resident. Sizes must be :func:`legal`.
    Returns (C, H * d_v), in ``q``'s type."""
    c, h, dqk = q.shape
    r = w_ukv.shape[0]
    width, page = pool.shape[2:]
    dv = w_ukv.shape[2] - (dqk - (width - r))
    if not legal(c, dqk, dv, r, width, page):
        raise ValueError(
            f"latent_flash_qblock cannot tile rows={c} heads={h} "
            f"dqk={dqk} dv={dv} r={r} width={width} page={page} "
            f"{pool.dtype}: see latent_flash_qblock.legal")
    return _latent_qblock_call(
        q, pool, jnp.asarray(table_row, jnp.int32),
        jnp.asarray(qpos, jnp.int32), w_ukv,
        jnp.asarray(layer, jnp.int32).reshape(1), sigma=float(sigma))


@functools.partial(jax.jit, static_argnames=("sigma", "sizes"))
def _latent_qblock_call(q, pool, table_row, qpos, w_ukv, layer, *,
                        sigma: float, sizes=None):
    """:func:`latent_flash_qblock` behind one jit: the layers of a step
    program share its trace and its lowering. ``sizes`` overrides
    :func:`block_sizes`, for tests of a chunk cut into row blocks at
    sizes the interpreter can hold."""
    c, h, dqk = q.shape
    r = w_ukv.shape[0]
    width, page = pool.shape[2:]
    dn = dqk - (width - r)
    dv = w_ukv.shape[2] - dn
    bq, group, tq, ppb = sizes or block_sizes(c, h, dqk, dv,
                                              q.dtype.itemsize)
    kb = ppb * page
    keys = table_row.shape[0] * page            # the row's capacity
    qpos = jnp.clip(qpos, 0, keys - 1)
    blocks = qpos.reshape(c // bq, bq)
    # [k_n | v]^T by head: the expansion is then a plain product with a
    # page as it lies.
    w_t = w_ukv.transpose(1, 2, 0).reshape(h * (dn + dv), r)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _kernel, page=page, ppb=ppb, tq=tq, group=group, dn=dn, dv=dv,
        r=r, sigma=sigma)
    return core_call(
        kernel,
        name="latent_flash_qblock",
        grid=(c // bq, h // group),
        out_shape=jax.ShapeDtypeStruct((c, h * dv), q.dtype),
        in_specs=[
            smem,                                       # layer
            smem,                                       # table row
            smem,                                       # sub-tiles' hi
            smem, smem,                                 # row blocks' lo, hi
            pl.BlockSpec((bq, 1), lambda i, g: (i, 0),
                         memory_space=pltpu.VMEM),      # positions
            pl.BlockSpec((bq, group * dqk), lambda i, g: (i, g),
                         memory_space=pltpu.VMEM),      # queries
            pl.BlockSpec((group * (dn + dv), r), lambda i, g: (g, 0),
                         memory_space=pltpu.VMEM),      # w_ukv^T
            pl.BlockSpec(memory_space=pl.ANY),          # the pool
        ],
        out_specs=pl.BlockSpec((bq, group * dv), lambda i, g: (i, g),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, width, kb), pool.dtype),
            pltpu.VMEM((group, dqk, kb), pool.dtype),
            pltpu.VMEM((group, dv, kb), pool.dtype),
            pltpu.VMEM((group, bq, _LANES), jnp.float32),
            pltpu.VMEM((group, bq, _LANES), jnp.float32),
            pltpu.VMEM((group, bq, dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        # For XLA's scheduler: a walk over half the row's capacity.
        cost_estimate=pl.CostEstimate(
            flops=2 * c * h * (dqk + dv) * keys // 2
            + 2 * (c // bq) * h * (dn + dv) * r * keys // 2,
            bytes_accessed=(c // bq) * (h // group) * width * keys
            * pool.dtype.itemsize // 2,
            transcendentals=c * h * keys // 2),
    )(layer, table_row, jnp.max(qpos.reshape(c // tq, tq), axis=1),
      jnp.min(blocks, axis=1), jnp.max(blocks, axis=1), qpos[:, None],
      q.reshape(c, h * dqk), w_t, pool)
