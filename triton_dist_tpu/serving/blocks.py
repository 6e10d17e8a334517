"""Paged KV block manager — the serving layer's memory system.

Reference: the paged block_table/workspace host APIs of
``flash_decode.py:763-1095`` (``gqa_fwd_batch_decode*``) manage pages
implicitly per call; vLLM-style serving needs an explicit allocator so
requests can join, append, and leave a persistent decode batch without
ever materializing a dense (B, max_len) cache per request.

Two halves:

- :class:`PagedKVCache` — the DEVICE pytree: per-layer page pools
  ``(L, num_pages, KV_loc, page, hd)`` (KV heads sharded along ``tp``,
  same placement as the dense :class:`~triton_dist_tpu.models.KVCache`)
  plus the per-slot ``block_table``, ``lens``, and ``live`` mask that
  ride into every decode dispatch. Consumed by
  :func:`~triton_dist_tpu.models.dense.decode_step_paged` and
  :func:`~triton_dist_tpu.ops.paged_flash_decode.paged_flash_decode`.
- :class:`BlockManager` — the HOST allocator: free-list of page ids,
  per-slot page lists, append-time page growth, fragmentation stats,
  and optional prefix-block reuse (identical full prompt pages are
  refcounted and shared across requests — content-addressed, so the
  hit is exact).

Page id 0 is RESERVED as the scratch page: parked (non-live) slots keep
an all-zero table row, so the fixed-shape decode step's appends for
dead slots land there instead of corrupting a reused page.
"""

from __future__ import annotations

import dataclasses
import types
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


SCRATCH_PAGE = 0

# Per-page KV quantization (the ll_a2a wire-quantization move applied
# to the pools): pools stored at a narrow dtype with one fp32 scale per
# (layer, page, kv_head) alongside. Symmetric max-abs: scale =
# amax/QMAX, stored = round/cast(x/scale), dequant = stored·scale.
# "bf16" is the UNQUANTIZED native path (pool at the engine's param
# dtype, no scales, bit-identical to the pre-quantization code).
KV_DTYPES = ("bf16", "int8", "fp8")
_KV_QUANT = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}


def kv_quant_spec(kv_dtype: str):
    """→ (storage dtype | None, qmax | None) for a ``kv_dtype`` knob
    value; None means the unquantized native path."""
    if kv_dtype in (None, "bf16", "native"):
        return None, None
    if kv_dtype not in _KV_QUANT:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got "
                         f"{kv_dtype!r}")
    return _KV_QUANT[kv_dtype]


def _quantize(x, scale, qdtype, qmax):
    """x fp32 → storage dtype under per-broadcast ``scale`` (fp32,
    broadcastable). int8 rounds-to-nearest; fp8 is a saturating cast."""
    y = x / scale
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        return jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    return jnp.clip(y, -qmax, qmax).astype(qdtype)


def _safe_scale(amax, qmax):
    """amax → scale with the zero guard (an all-zero page stores zeros
    under scale 1 instead of dividing by zero)."""
    return jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)


def _quant_range_write(pool, scales, layer, pids, loc, toks, tok_mask,
                       had_prior, qmax):
    """Merge a consecutive token range into QUANTIZED pages under fresh
    per-page max-abs scales — the shared core of every partial-page
    quantized write (decode append, the speculative K-token block, the
    prefill chunk).

    pool: (L, N, KV, page, hd) storage; scales: (L, N, KV) fp32;
    pids: (S, n_t) touched page ids per slot (scratch-substituted rows
    write garbage by contract); loc: (S, K) each token's position
    inside the touched window [0, n_t·page) (tokens with ``tok_mask``
    False are dumped past the window); toks: (S, K, KV, hd);
    had_prior: (S, n_t) — pages holding earlier valid tokens keep
    their running amax (scale·qmax) through the merge, pages whose
    first token lands now get a FRESH scale (stale garbage from a
    freed-and-reused pool slot never leaks into the new scale).
    Returns (pool, scales). Pages a token never lands in requantize to
    themselves exactly (unchanged scale ⇒ dequant·requant identity).
    """
    s, n_t = pids.shape
    _, _, kvh, page, hd = pool.shape
    toks = toks.astype(jnp.float32)
    old_scale = scales[layer, pids]                    # (S, n_t, KV)
    gathered = pool[layer, pids]                       # (S, n_t, KV, pg, hd)
    deq = gathered.astype(jnp.float32) * old_scale[..., None, None]
    dense = deq.transpose(0, 1, 3, 2, 4).reshape(s, n_t * page, kvh, hd)
    # One dump row past the window swallows masked (padding/resident)
    # tokens without branching.
    dense = jnp.concatenate(
        [dense, jnp.zeros((s, 1, kvh, hd), jnp.float32)], axis=1)
    loc_w = jnp.where(tok_mask, loc, n_t * page)
    dense = dense.at[jnp.arange(s)[:, None], loc_w].set(toks)
    dense = dense[:, :n_t * page]
    tok_amax = jnp.max(jnp.abs(toks), axis=-1)       # (S, K, KV)
    tok_amax = jnp.where(tok_mask[..., None], tok_amax, 0.0)
    tpage = jnp.clip(loc // page, 0, n_t - 1)
    amax_new = jnp.zeros((s, n_t, kvh), jnp.float32).at[
        jnp.arange(s)[:, None], tpage].max(tok_amax)
    amax = jnp.maximum(
        jnp.where(had_prior[..., None], old_scale * qmax, 0.0),
        amax_new)
    new_scale = _safe_scale(amax, qmax)
    blocks = dense.reshape(s, n_t, page, kvh, hd).transpose(0, 1, 3, 2, 4)
    q = _quantize(blocks, new_scale[..., None, None], pool.dtype, qmax)
    return (pool.at[layer, pids].set(q),
            scales.at[layer, pids].set(new_scale))


def _put_rows(pool, layer: int, pids, offs, toks):
    """``pool[layer, pids[n], :, offs[n], :] = toks[n]``, one row at a
    time, in place in the pool's own layout.

    pool: (L, N, KV, page, hd), donated by every serving dispatch;
    pids/offs: (n,) int32; toks: (n, KV, hd). Each row is one
    ``lax.dynamic_update_slice`` of a ``(1, 1, KV, 1, hd)`` block — NOT
    ``pool.at[layer, pids, :, offs, :].set(toks)``: that scatter's
    window is (KV, hd), for which XLA's layout assignment wants the
    pool with KV and the page offset swapped, so every program that
    held one began with a relayout of the whole pool and ended with
    one back (PERF.md section 6, PR 26). Rows with the same target land
    in order (the last wins), as the scatter's did."""
    rows = toks.astype(pool.dtype)[:, None, None, :, None, :]
    for n in range(rows.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, rows[n], (layer, pids[n], 0, offs[n], 0))
    return pool


def _merge_pages(pool, layer: int, pids, toks, write):
    """Merge a window of whole pages into the pool, a page at a time,
    in place in the pool's own layout: page ``pids[j]`` of ``layer``
    takes ``toks[:, j·page:(j+1)·page]`` in the rows where ``write`` is
    True and stays as it is in the others.

    pool: (L, N, KV, page, hd); pids: (n_t,) int32; toks: (KV,
    n_t·page, hd); write: (n_t·page,) bool. One dynamic slice, select
    and ``lax.dynamic_update_slice`` of a ``(1, 1, KV, page, hd)`` block
    a page (see :func:`_put_rows` for why not a scatter of rows)."""
    kvh, page, hd = pool.shape[2:]
    toks = toks.astype(pool.dtype)
    for j in range(pids.shape[0]):
        at = (layer, pids[j], 0, 0, 0)
        rows = slice(j * page, (j + 1) * page)
        old = jax.lax.dynamic_slice(pool, at, (1, 1, kvh, page, hd))
        new = jnp.where(write[rows][:, None], toks[None, None, :, rows], old)
        pool = jax.lax.dynamic_update_slice(pool, new, at)
    return pool


def _append_targets(block_table, lens, page: int, k: int, budget=None,
                    ring: bool = False):
    """Where ``k`` consecutive tokens a slot land, from each slot's own
    length on: ``(pids, offs)``, each ``(num_slots * k,)``. A token past
    its slot's table row, or past its slot's ``budget`` (S,), lands in
    the scratch page, as a parked slot's (all-zero row) does. ``ring``:
    the table is a window layer's ring, page ``i`` of a slot in entry
    ``i % entries``, and no position lies past it."""
    pos = lens[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
    rows_raw = pos // page
    if ring:
        rows_raw = rows_raw % block_table.shape[1]
    valid = rows_raw < block_table.shape[1]
    if budget is not None:
        valid = jnp.logical_and(
            valid, jnp.arange(k, dtype=jnp.int32)[None]
            < budget[:, None])
    rows = jnp.clip(rows_raw, 0, block_table.shape[1] - 1)
    pids = jnp.where(
        valid, jnp.take_along_axis(block_table, rows, axis=1),
        SCRATCH_PAGE)
    return pids.reshape(-1), (pos % page).reshape(-1)


def _chunk_window(table_row, positions, valid, wfrom, page: int,
                  ring: bool = False):
    """The window of whole pages a chunk of consecutive ``positions``
    lies in: ``(pids (n_t,), write (n_t * page,) bool, loc0)``, the
    chunk's first row at offset ``loc0`` of the window. A row is
    written where it is below ``valid`` and at or past ``wfrom``; a
    page none of whose rows is written (bucket padding, a prefix-shared
    page, past the table row) is the scratch page. ``ring``: the row is
    a window layer's ring (see :func:`_append_targets`)."""
    c = positions.shape[0]
    n_t = (c - 1) // page + 2
    row0 = positions[0] // page
    loc0 = positions[0] - row0 * page
    i = jnp.arange(c, dtype=jnp.int32)
    write = jax.lax.dynamic_update_slice(
        jnp.zeros((n_t * page,), bool),
        jnp.logical_and(i < valid, positions >= wfrom), (loc0,))
    rows = row0 + jnp.arange(n_t, dtype=jnp.int32)
    if ring:
        rows = rows % table_row.shape[0]
    written = jnp.any(write.reshape(n_t, page), axis=1)
    pids = jnp.where(
        jnp.logical_and(written, rows < table_row.shape[0]),
        table_row[jnp.clip(rows, 0, table_row.shape[0] - 1)],
        SCRATCH_PAGE)
    return pids, write, loc0


def pool_shardings(mesh, spec_tree):
    """NamedShardings for a :class:`PagedKVCache` spec pytree, with
    trailing-``None`` dims dropped from every spec — the spelling jit
    canonicalizes OUTPUT shardings to. Pinning writers (prompt blit,
    chunk steps, migration scatter) to THESE shardings makes their
    output pools compare jit-cache-equal to pools emitted by unpinned
    dispatches (``P(None, None, 'tp', None, None)`` and
    ``P(None, None, 'tp')`` place identically but are different cache
    keys — a one-entry-per-producer leak otherwise)."""
    from jax.sharding import NamedSharding, PartitionSpec

    def canon(spec):
        parts = tuple(spec)
        while parts and parts[-1] is None:
            parts = parts[:-1]
        return NamedSharding(mesh, PartitionSpec(*parts))

    return jax.tree.map(canon, spec_tree,
                        is_leaf=lambda s: isinstance(s, PartitionSpec))


class OutOfPagesError(RuntimeError):
    """The pool has no free page (and nothing evictable) — the caller
    should apply backpressure (reject or queue the request)."""


class BlockTableOverflowError(RuntimeError):
    """A request needs more pages than one block-table row holds
    (``p_max``) — i.e. it outgrew ``max_len``; fail the request, not
    the server."""


class SeqArray(NamedTuple):
    """One array a SEQUENCE keeps beside its pages, as a model's
    ``paged_pool`` states it: ``shape`` a slot (the layer first, as it
    leads the pages), always in the pool's type, which is the
    parameters'; the array the server allocates has the slots' axis
    inserted at ``slot_axis`` (the model says where, as it says how a
    page lies: the layout is what its step programs slice). Not
    position-addressed: nothing of it is found through the block table
    or cut back by a length."""
    shape: Tuple[int, ...]
    slot_axis: int = 0

    def zeros(self, num_slots: int, pool_dtype):
        full = (self.shape[:self.slot_axis] + (num_slots,)
                + self.shape[self.slot_axis:])
        return jnp.zeros(full, pool_dtype)


class WindowLayers(NamedTuple):
    """The window layers of a model, as its ``paged_pool`` states them
    (``window=WindowLayers(layers, window)``: how many of its paged
    layers read only the last ``window`` positions) and as the server
    completes them from its own sizes (:meth:`sized`): ``ring``, the
    pages a slot holds in each such layer, and ``num_pages``, the
    window pool's pages, the scratch page among them."""
    layers: int
    window: int
    ring: int = 0
    num_pages: int = 0

    def sized(self, page: int, largest_chunk: int,
              num_slots: int) -> "WindowLayers":
        """With the ring a slot needs: the pages that ``window`` keys
        and the rows of the largest chunk program span, and one more
        (a chunk's rows are all written before any of them reads, so
        the first row's oldest key and the last row's own must lie in
        different entries whatever the chunk's offset in its page); and
        pages for every slot's ring beside the scratch page."""
        ring = -(-(self.window + max(largest_chunk, 1)) // page) + 1
        return self._replace(ring=ring, num_pages=1 + num_slots * ring)

    def require_sized(self) -> "WindowLayers":
        if self.ring < 1 or self.num_pages < 2:
            raise ValueError(f"window={self}: not sized "
                             "(WindowLayers.sized)")
        return self


@dataclasses.dataclass
class PagedKVCache:
    """Device half of the paged cache (see module docstring).

    ``k_pages``/``v_pages``: (L, num_pages, KV_loc, page, hd) pools;
    ``block_table``: (num_slots, p_max) int32 page ids;
    ``lens``: (num_slots,) int32 valid tokens per slot;
    ``live``: (num_slots,) int32 0/1 — the live slot mask (parked slots
    keep shape but neither advance nor persist their appends).

    The pools keep ONE layout, row-major, inside every paged program:
    the kernels take them whole with a ``layer`` index (nothing cuts
    ``k_pages[layer]`` out) and the unquantized writers update them in
    place (:func:`_put_rows`, :func:`_merge_pages`). A program that
    asks for the pool in another layout, as a row scatter does, pays a
    relayout of the whole pool at both ends.

    Quantized pools (``kv_dtype="int8"|"fp8"``) additionally carry
    ``k_scale``/``v_scale``: (L, num_pages, KV_loc) fp32 per-page
    per-head dequant scales. Every write path quantizes in place
    (partial-page writes dequant→merge→requant the touched pages under
    a fresh max-abs scale; a page's scale RESETS when its first token
    lands, so a freed-and-reused pool slot never inherits a stale
    scale) and every read path (``dense_row``/``dense_layer``, the
    fused kernel prefetch) dequantizes. The unquantized path keeps the
    scales ``None`` and runs the original code bit-identically.

    ``seq``: what a sequence keeps that is no page of keys (a recurrent
    state, a convolution's tail), name -> array over the decode slots
    (:class:`SeqArray`); empty for a model that keeps none, and then no
    leaf of the tree. The model's step programs reset a slot's at its
    first chunk, carry it chunk to chunk and advance it for live decode
    rows only; the server allocates it and never reads it. Whatever
    moves PAGES (tiers, migration, prefix sharing) knows nothing of it:
    the server refuses those for a pool that states one.

    ``win``: the pools of the model's WINDOW layers, ``"k"`` and ``"v"``
    (L_w, window pages, KV_loc, page, hd), for a model some of whose
    layers read only the last ``w`` positions (:class:`WindowLayers`);
    empty for a model that states none, and then no leaf of the tree. A
    slot holds ``ring`` pages there, a static count, used as a RING:
    position ``p`` lies in entry ``(p // page) % ring``, and a page
    behind the window is written over as the sequence grows. The slot's
    ring is the last ``ring`` entries of its ``block_table`` row, behind
    the ``p_max`` pages of the other layers (one upload a tick carries
    both tables, :meth:`table_of` cuts a kind's part out); a window
    layer writes and reads under ``window=True``, its index counted
    among the window layers. What reads a ring bounds its keys from
    below as well as above: an entry's page may hold an older page's
    positions, or another request's.
    """

    k_pages: jax.Array
    v_pages: jax.Array
    block_table: jax.Array
    lens: jax.Array
    live: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    seq: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    win: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    ring: int = 0

    @classmethod
    def empty(cls, num_layers: int, num_pages: int, page: int,
              kv_heads_loc: int, head_dim: int, *, num_slots: int,
              p_max: int, dtype=jnp.float32, kv_dtype: str = "bf16",
              seq_state: Optional[Dict[str, SeqArray]] = None,
              window: Optional["WindowLayers"] = None
              ) -> "PagedKVCache":
        shape = (num_layers, num_pages, kv_heads_loc, page, head_dim)
        qdtype, _ = kv_quant_spec(kv_dtype)
        pool_dtype = dtype if qdtype is None else qdtype
        win, ring = {}, 0
        if window is not None:
            if qdtype is not None:
                raise ValueError("window layers keep an unquantized "
                                 f"pool: kv_dtype={kv_dtype!r}")
            ring = window.require_sized().ring
            wshape = (window.layers, window.num_pages) + shape[2:]
            win = {"k": jnp.zeros(wshape, dtype),
                   "v": jnp.zeros(wshape, dtype)}
        scale = (None if qdtype is None else jnp.ones(
            (num_layers, num_pages, kv_heads_loc), jnp.float32))
        return cls(
            k_pages=jnp.zeros(shape, pool_dtype),
            v_pages=jnp.zeros(shape, pool_dtype),
            block_table=jnp.zeros((num_slots, p_max + ring), jnp.int32),
            lens=jnp.zeros((num_slots,), jnp.int32),
            live=jnp.zeros((num_slots,), jnp.int32),
            k_scale=scale, v_scale=(None if scale is None
                                    else jnp.ones_like(scale)),
            seq={name: a.zeros(num_slots, dtype)
                 for name, a in (seq_state or {}).items()},
            win=win, ring=ring)

    @classmethod
    def empty_sharded(cls, mesh, spec_fn, axis: str, *shape_args,
                      kv_dtype: str = "bf16", **kw):
        """:meth:`empty` allocated directly under its target sharding:
        every device zero-fills only its own KV-head shard of the pool
        (an unsharded pool at a realistic size is gigabytes on device 0
        before the first ``device_put``). ``spec_fn`` is the model's
        ``paged_cache_specs``; ``shape_args`` are GLOBAL shapes (all KV
        heads). Returns ``(cache, shardings)`` — the pool and the pinned
        :func:`pool_shardings` every writer into it must emit."""
        quantized = kv_quant_spec(kv_dtype)[0] is not None
        shardings = pool_shardings(mesh, spec_fn(axis, quantized=quantized))
        cache = jax.jit(
            lambda: cls.empty(*shape_args, kv_dtype=kv_dtype, **kw),
            out_shardings=shardings)()
        return cache, shardings

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def qmax(self) -> float:
        return 127.0 if self.k_pages.dtype == jnp.int8 else 448.0

    @property
    def page(self) -> int:
        return self.k_pages.shape[3]

    def layer_scales(self, layer: int):
        """One layer's ``(k_scale, v_scale)`` dequant planes, or
        ``(None, None)`` on an unquantized pool — the ONE spelling of
        "scales accompany int8/fp8 storage" every paged-kernel call
        site reads (the kernels' ``_require_pool_scales`` contract)."""
        if not self.quantized:
            return None, None
        return self.k_scale[layer], self.v_scale[layer]

    @property
    def capacity(self) -> int:
        """Tokens one block-table row can address (p_max · page)."""
        return (self.block_table.shape[1] - self.ring) * self.page

    def table_of(self, table, window: bool = False):
        """The part of ``table`` (a slot's row, or the batch's rows)
        that one kind of layer reads: the ``p_max`` pages first, the
        window layers' ring behind them. A pool with no window layers
        has one kind, and ``table`` is returned as it is."""
        if not self.ring:
            return table
        cut = table.shape[-1] - self.ring
        return table[..., cut:] if window else table[..., :cut]

    def _pools(self, window: bool):
        if window:
            return self.win["k"], self.win["v"]
        return self.k_pages, self.v_pages

    def _with_pools(self, window: bool, k, v) -> "PagedKVCache":
        if window:
            return dataclasses.replace(self, win={"k": k, "v": v})
        return dataclasses.replace(self, k_pages=k, v_pages=v)

    def append_decode(self, layer: int, k_tok, v_tok,
                      window: bool = False) -> "PagedKVCache":
        """Append one decode token's K/V per slot at each slot's own
        length — the paged half of the shared cache-update contract
        (:meth:`~triton_dist_tpu.models.kv_cache.KVCache.append_decode`
        is the dense half). k_tok/v_tok: (num_slots, 1, KV_loc, hd).
        Parked slots (all-zero table row) write the scratch page.
        Lengths advance once per step via :meth:`advance`, not here.
        The one-token case of :meth:`append_block`: an unquantized pool
        is updated in place, a row a slot (:func:`_put_rows`).
        """
        return self.append_block(layer, k_tok, v_tok, window=window)

    def append_block(self, layer: int, k_tok, v_tok, budget=None,
                     window: bool = False) -> "PagedKVCache":
        """Write K consecutive tokens per slot at each slot's own
        length — the speculative-verification form of
        :meth:`append_decode` (positions ``lens[s]..lens[s]+K-1``; the
        host commits only the accepted prefix by not advancing the
        length mirrors past it). k_tok/v_tok: (num_slots, K, KV_loc,
        hd). Parked slots' writes land in the scratch page, and so do
        tokens past a slot's block-table row or past its ``budget``
        (S,) — a fixed-K dispatch near a request's token budget must
        not let its over-budget candidates corrupt a real page's
        contents (or, quantized, inflate its scale). An unquantized
        pool is updated in place in its own layout, a row a token
        (:func:`_put_rows`); a quantized one requantizes whole pages
        (:meth:`_quant_append`). ``window``: ``layer`` is a window
        layer's, and the tokens go into its pool through the slots'
        rings."""
        if self.quantized:
            return self._quant_append(layer, k_tok, v_tok, budget)
        pids, off = _append_targets(
            self.table_of(self.block_table, window), self.lens,
            self.page, k_tok.shape[1], budget, ring=window)
        kvl, hd = k_tok.shape[2:]
        kp, vp = self._pools(window)
        return self._with_pools(
            window,
            _put_rows(kp, layer, pids, off, k_tok.reshape(-1, kvl, hd)),
            _put_rows(vp, layer, pids, off, v_tok.reshape(-1, kvl, hd)))

    def _quant_append(self, layer: int, k_tok, v_tok,
                      budget=None) -> "PagedKVCache":
        """Quantized slot-range write shared by :meth:`append_decode`
        (K=1) and :meth:`append_block`: dequant→merge→requant the
        touched pages; a page whose first token lands now (its start
        position reaches ``lens``) gets a fresh scale."""
        page = self.page
        s, k = k_tok.shape[:2]
        p_max = self.block_table.shape[1]
        n_t = (k - 1) // page + 2
        row0 = self.lens // page
        rows = row0[:, None] + jnp.arange(n_t, dtype=jnp.int32)[None]
        rows_c = jnp.clip(rows, 0, p_max - 1)
        pids = jnp.where(
            rows < p_max,
            jnp.take_along_axis(self.block_table, rows_c, axis=1),
            SCRATCH_PAGE)
        loc = (self.lens % page)[:, None] + jnp.arange(
            k, dtype=jnp.int32)[None]
        pos = self.lens[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
        mask = pos // page < p_max
        if budget is not None:
            mask = jnp.logical_and(
                mask, jnp.arange(k, dtype=jnp.int32)[None]
                < budget[:, None])
        had_prior = rows * page < self.lens[:, None]
        kp, ks = _quant_range_write(self.k_pages, self.k_scale, layer,
                                    pids, loc, k_tok, mask, had_prior,
                                    self.qmax)
        vp, vs = _quant_range_write(self.v_pages, self.v_scale, layer,
                                    pids, loc, v_tok, mask, had_prior,
                                    self.qmax)
        return dataclasses.replace(self, k_pages=kp, v_pages=vp,
                                   k_scale=ks, v_scale=vs)

    def advance(self) -> "PagedKVCache":
        """Bump live slots' lengths after all layers appended."""
        return dataclasses.replace(
            self, lens=self.lens + self.live.astype(jnp.int32))

    def write_chunk(self, layer: int, k_tok, v_tok, table_row,
                    positions, valid, wfrom,
                    window: bool = False) -> "PagedKVCache":
        """Write one prefill CHUNK's K/V into a slot's pages — the
        chunked-prefill half of the cache-update contract
        (:meth:`append_decode` is the one-token decode half).

        k_tok/v_tok: (C, 1, KV_loc, hd) — one row per chunk token;
        ``table_row``: (p_max,) int32 — the slot's block-table row;
        ``positions``: (C,) int32 global positions, consecutive;
        ``valid``/``wfrom``: bucket padding and already-resident
        (prefix-shared) positions are not written — the rule of
        :func:`~triton_dist_tpu.ops.chunked_prefill.chunk_write_ids` —
        so a chunk can never corrupt a page a live reader holds. An
        unquantized pool is updated in place in its own layout, a whole
        page at a time (:func:`_merge_pages`: 2 to 5 pages a chunk, not
        one scatter row a token); a quantized one requantizes the
        touched pages (:meth:`_quant_write_chunk`). ``window``:
        ``layer`` is a window layer's, and the rows go into its pool
        through the slot's ring, which ``table_row`` ends in.
        """
        if self.quantized:
            return self._quant_write_chunk(layer, k_tok, v_tok,
                                           table_row, positions, valid,
                                           wfrom)
        # Positions are consecutive (start + arange(C): the chunk
        # contract), so the chunk lies in a window of n_t whole pages
        # that begins at the start's page. Lay the rows out on that
        # window, and merge it into the pool a page at a time.
        page = self.page
        pids, write, loc0 = _chunk_window(
            self.table_of(table_row, window), positions, valid, wfrom,
            page, ring=window)
        n_t = pids.shape[0]

        def pages_of(tok):
            kvl, hd = tok.shape[2:]
            return jax.lax.dynamic_update_slice(
                jnp.zeros((kvl, n_t * page, hd), tok.dtype),
                tok[:, 0].transpose(1, 0, 2), (0, loc0, 0))

        kp, vp = self._pools(window)
        return self._with_pools(
            window,
            _merge_pages(kp, layer, pids, pages_of(k_tok), write),
            _merge_pages(vp, layer, pids, pages_of(v_tok), write))

    def _quant_write_chunk(self, layer, k_tok, v_tok, table_row,
                           positions, valid, wfrom) -> "PagedKVCache":
        """Quantized chunk write. Positions are consecutive
        (``start + arange(C)`` — the chunk contract), so the touched
        pages are a bounded window. Prefix-resident pages (below the
        page-aligned ``wfrom``) are scratch-substituted — their bytes
        AND scales a live reader holds are never rewritten; a page
        whose first token lands in an earlier chunk keeps its running
        amax through this merge."""
        page = self.page
        c = positions.shape[0]
        start = positions[0]
        n_t = (c - 1) // page + 2
        row0 = start // page
        rows = row0 + jnp.arange(n_t, dtype=jnp.int32)
        rows_c = jnp.clip(rows, 0, table_row.shape[0] - 1)
        writable_page = rows >= wfrom // page
        pids = jnp.where(writable_page, table_row[rows_c],
                         SCRATCH_PAGE)[None]
        i = jnp.arange(c, dtype=jnp.int32)
        tok_mask = jnp.logical_and(i < valid, positions >= wfrom)[None]
        loc = (positions - row0 * page)[None]
        had_prior = jnp.logical_and(rows * page < start,
                                    writable_page)[None]
        kp, ks = _quant_range_write(self.k_pages, self.k_scale, layer,
                                    pids, loc, k_tok[:, 0][None],
                                    tok_mask, had_prior, self.qmax)
        vp, vs = _quant_range_write(self.v_pages, self.v_scale, layer,
                                    pids, loc, v_tok[:, 0][None],
                                    tok_mask, had_prior, self.qmax)
        return dataclasses.replace(self, k_pages=kp, v_pages=vp,
                                   k_scale=ks, v_scale=vs)

    def dense_row(self, layer: int, table_row) -> Tuple[jax.Array,
                                                        jax.Array]:
        """Gather ONE slot's pages to the dense position-major view
        (p_max·page, KV_loc, hd) — the per-slot form of
        :meth:`dense_layer`, consumed by the chunked-prefill attention
        (positions past the slot's written region are garbage the
        causal mask hides)."""
        from triton_dist_tpu.ops.chunked_prefill import gather_pages_dense

        def gather(pool, scale):
            return gather_pages_dense(
                pool[layer], table_row,
                None if scale is None else scale[layer])

        return (gather(self.k_pages, self.k_scale),
                gather(self.v_pages, self.v_scale))

    def gather_pages(self, page_ids):
        """Extract whole pages as a migration payload: page_ids (n,)
        int32 pool slots (pad with the scratch page for a fixed-shape
        transfer) → (K, V) each (L, n, KV_loc, page, hd) — plus
        (K_scale, V_scale) each (L, n, KV_loc) on a quantized pool
        (pages migrate as their STORED bytes; the scales ride along so
        the receiver's dequant is bit-exact with the source). The
        disaggregated serving handoff's source half."""
        k, v = self.k_pages[:, page_ids], self.v_pages[:, page_ids]
        if not self.quantized:
            return k, v
        return (k, v, self.k_scale[:, page_ids],
                self.v_scale[:, page_ids])

    def scatter_pages(self, k_payload, v_payload, page_ids,
                      k_scale=None, v_scale=None) -> "PagedKVCache":
        """Blit a migration payload into this pool's pages: the
        receiver half of the disaggregated KV handoff. ``page_ids``
        rows the caller wants dropped (padding, prefix-resident pages a
        live reader holds) should point at the scratch page — duplicate
        scratch writes are benign garbage. A quantized pool requires
        the payload's scales (a scaleless scatter would silently pair
        this pool's stale scales with the new bytes)."""
        repl = dict(
            k_pages=self.k_pages.at[:, page_ids].set(
                k_payload.astype(self.k_pages.dtype)),
            v_pages=self.v_pages.at[:, page_ids].set(
                v_payload.astype(self.v_pages.dtype)))
        if self.quantized:
            if k_scale is None or v_scale is None:
                raise ValueError(
                    "scatter_pages into a quantized pool needs the "
                    "payload's k_scale/v_scale (gather_pages returns "
                    "them) — bytes without scales are unreadable")
            repl.update(
                k_scale=self.k_scale.at[:, page_ids].set(k_scale),
                v_scale=self.v_scale.at[:, page_ids].set(v_scale))
        elif k_scale is not None or v_scale is not None:
            raise ValueError(
                "scatter_pages got quantization scales but this pool "
                "is unquantized (kv_dtype mismatch between roles?)")
        return dataclasses.replace(self, **repl)

    def dense_layer(self, layer: int) -> Tuple[jax.Array, jax.Array]:
        """Gather one layer's pages to the dense position-major view
        (num_slots, p_max·page, KV_loc, hd) — the reference-attention
        path (token-exact with the dense cache; positions past a slot's
        length are garbage the kv_len mask hides)."""
        from triton_dist_tpu.ops.chunked_prefill import gather_pages_dense

        def gather(pool, scale):
            return gather_pages_dense(
                pool[layer], self.table_of(self.block_table),
                None if scale is None else scale[layer])

        return (gather(self.k_pages, self.k_scale),
                gather(self.v_pages, self.v_scale))

    def write_prompt(self, k_prompt, v_prompt, page_ids) -> "PagedKVCache":
        """Blit a prefilled prompt's K/V into this cache's pages.

        k_prompt/v_prompt: (L, S_pad, KV_loc, hd) with S_pad a multiple
        of ``page`` (pad the tail with anything — positions past the
        slot's length are masked); ``page_ids``: (S_pad // page,) int32
        pool slots, one per page block of the prompt slice. The caller
        passes only the NON-prefix-shared suffix of its allocation
        (:meth:`BlockManager.prefix_hits`): shared pages keep the first
        sharer's bytes.
        """
        num_l, s_pad, kvh, hd = k_prompt.shape
        page = self.page
        n_p = s_pad // page

        def blit(pool, scales, prompt):
            blocks = prompt.reshape(num_l, n_p, page, kvh, hd)
            blocks = blocks.transpose(0, 1, 3, 2, 4)
            if scales is None:
                return pool.at[:, page_ids].set(
                    blocks.astype(pool.dtype)), None
            # Whole-page quantize: one fresh max-abs scale per
            # (layer, page, kv_head). The blit's tail padding is the
            # prefill cache's zeros, so it never inflates the ragged
            # final page's scale.
            b32 = blocks.astype(jnp.float32)
            sc = _safe_scale(jnp.max(jnp.abs(b32), axis=(3, 4)),
                             self.qmax)
            q = _quantize(b32, sc[..., None, None], pool.dtype,
                          self.qmax)
            return (pool.at[:, page_ids].set(q),
                    scales.at[:, page_ids].set(sc))

        kp, ks = blit(self.k_pages, self.k_scale, k_prompt)
        vp, vs = blit(self.v_pages, self.v_scale, v_prompt)
        return dataclasses.replace(self, k_pages=kp, v_pages=vp,
                                   k_scale=ks, v_scale=vs)

    def tree_flatten(self):
        return (self.k_pages, self.v_pages, self.block_table, self.lens,
                self.live, self.k_scale, self.v_scale, self.seq,
                self.win), self.ring

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, ring=aux)


jax.tree_util.register_pytree_node(
    PagedKVCache, PagedKVCache.tree_flatten, PagedKVCache.tree_unflatten)


@dataclasses.dataclass
class LatentPagedCache:
    """The paged pool of a latent-attention model: ONE array, ``pages``
    (L, num_pages, width, page), ``width`` values a token a layer (the
    latent beside the roped key every head shares), no heads;
    ``block_table``, ``lens`` and ``live`` as :class:`PagedKVCache`'s,
    and the same page accounting (:class:`BlockManager` counts pages,
    not what a page holds). Every rank keeps the whole pool: there is
    no head to divide it by.

    A page lies ``(width, page)``, a token a COLUMN: the page's 128
    positions fill the lanes of a tile whole, where 320 values would
    leave its last half empty (the TPU's own choice for the array the
    other way round, which it then relaid before every read: PERF.md,
    PR 37). Attention contracts over ``width`` as the pages lie.
    Written in place, one layout, as :class:`PagedKVCache` is: a
    ``lax.dynamic_update_slice`` of a column a token or of a whole page.
    Not quantized, not tiered, not migrated: the first slice of a pool
    stated by the model, as wide as its one model needs
    (docs/serving.md, "The pool a model states")."""

    pages: jax.Array
    block_table: jax.Array
    lens: jax.Array
    live: jax.Array

    quantized = False
    # What a sequence keeps beside its pages (:class:`PagedKVCache` has
    # the field): nothing, for every model of this pool.
    seq = types.MappingProxyType({})

    @classmethod
    def empty(cls, num_layers: int, num_pages: int, page: int,
              width: int, *, num_slots: int, p_max: int,
              dtype=jnp.float32, kv_dtype: str = "bf16"):
        if kv_quant_spec(kv_dtype)[0] is not None:
            raise ValueError(f"the latent pool is not quantized: "
                             f"kv_dtype={kv_dtype!r}")
        return cls(
            pages=jnp.zeros((num_layers, num_pages, width, page), dtype),
            block_table=jnp.zeros((num_slots, p_max), jnp.int32),
            lens=jnp.zeros((num_slots,), jnp.int32),
            live=jnp.zeros((num_slots,), jnp.int32))

    empty_sharded = classmethod(PagedKVCache.empty_sharded.__func__)

    @property
    def page(self) -> int:
        return self.pages.shape[3]

    @property
    def width(self) -> int:
        return self.pages.shape[2]

    def append_block(self, layer: int, rows,
                     budget=None) -> "LatentPagedCache":
        """Write K consecutive tokens a slot at each slot's own length
        (:meth:`PagedKVCache.append_block`'s rule for parked slots, the
        table row's end and ``budget``), a column a token. rows:
        (num_slots, K, width)."""
        pids, off = _append_targets(self.block_table, self.lens,
                                    self.page, rows.shape[1], budget)
        cols = rows.astype(self.pages.dtype).reshape(
            -1, 1, 1, self.width, 1)
        pool = self.pages
        for n in range(cols.shape[0]):
            pool = jax.lax.dynamic_update_slice(
                pool, cols[n], (layer, pids[n], 0, off[n]))
        return dataclasses.replace(self, pages=pool)

    def append_decode(self, layer: int, rows) -> "LatentPagedCache":
        """One decode token a slot: rows (num_slots, width)."""
        return self.append_block(layer, rows[:, None])

    def advance(self) -> "LatentPagedCache":
        return dataclasses.replace(
            self, lens=self.lens + self.live.astype(jnp.int32))

    def write_chunk(self, layer: int, rows, table_row, positions, valid,
                    wfrom) -> "LatentPagedCache":
        """Write one prefill chunk's rows (C, width) into a slot's
        pages, a whole page at a time
        (:meth:`PagedKVCache.write_chunk`'s rule for padding and
        resident positions)."""
        page, width = self.page, self.width
        pids, write, loc0 = _chunk_window(table_row, positions, valid,
                                          wfrom, page)
        window = jax.lax.dynamic_update_slice(
            jnp.zeros((width, write.shape[0]), self.pages.dtype),
            rows.astype(self.pages.dtype).T, (0, loc0))
        pool = self.pages
        for j in range(pids.shape[0]):
            at = (layer, pids[j], 0, 0)
            cols = slice(j * page, (j + 1) * page)
            old = jax.lax.dynamic_slice(pool, at, (1, 1, width, page))
            new = jnp.where(write[cols], window[None, None, :, cols], old)
            pool = jax.lax.dynamic_update_slice(pool, new, at)
        return dataclasses.replace(self, pages=pool)

    def gather(self, layer: int, page_ids):
        """Pages ``page_ids`` (..., n) of ``layer`` as they lie:
        (..., n, width, page)."""
        # Out of the whole pool, the layer in the index: nothing cuts
        # ``pages[layer]`` out (a walk's loop would hoist that copy).
        n = self.pages.shape[1]
        return jnp.take(self.pages.reshape((-1,) + self.pages.shape[2:]),
                        layer * n + page_ids, axis=0)

    def tree_flatten(self):
        return (self.pages, self.block_table, self.lens, self.live), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    LatentPagedCache, LatentPagedCache.tree_flatten,
    LatentPagedCache.tree_unflatten)


class BlockManager:
    """Host-side page allocator over a fixed pool (see module
    docstring). All bookkeeping is plain Python — no device syncs; the
    scheduler mirrors slot lengths host-side exactly like the Engine's
    ``_host_len`` overflow guard.

    ``prefix_reuse=True`` content-addresses FULL prompt pages: a second
    request whose prompt shares a page-aligned prefix re-uses those
    page ids (refcounted) instead of new pages. Shared pages are always
    full, so decode appends (which only ever touch a slot's last,
    private page) can never mutate them. The cache itself holds one
    reference per shared page; when the free list runs dry,
    unreferenced prefix pages are evicted by SCORE — an EWMA of hit
    frequency/recency per committed block (every hit bumps the score,
    every allocation tick decays it by ``score_decay``), so the cold
    tail leaves first and the hot set stays HBM-resident. ``on_demote``
    (installed by the serving engine when a
    :class:`~triton_dist_tpu.serving.tiers.KVTierStore` is configured)
    fires per victim BEFORE its page is freed: the hook offloads the
    page's bytes to the tier below, turning eviction from
    drop-and-recompute into demote-and-prefetch.

    ``window`` (a sized :class:`WindowLayers`): the model's window
    layers keep a pool of their own, and this manager its free list. A
    slot takes its whole RING there at admission (``window.ring`` pages,
    whatever its prompt's length: the ring is what the step programs
    index modulo) and hands it back at :meth:`free_slot`; while the
    request runs nothing is allocated there, a page behind the window is
    written over in place (``window_pages_recycled`` counts those, at
    :meth:`free_slot`). A ring's pages are never shared: a manager with
    both ``window`` and ``prefix_reuse`` is refused.
    """

    def __init__(self, num_pages: int, page: int, p_max: int, *,
                 prefix_reuse: bool = False,
                 page_bytes: Optional[int] = None,
                 native_page_bytes: Optional[int] = None,
                 score_decay: float = 0.9,
                 on_demote=None,
                 window: Optional[WindowLayers] = None):
        if num_pages < 2:
            raise ValueError(f"num_pages={num_pages} < 2 (page 0 is the "
                             "reserved scratch page)")
        if window is not None:
            if prefix_reuse:
                raise ValueError(
                    "prefix_reuse over window layers: a ring's pages "
                    "are written over while their request runs, so none "
                    "of them can be another request's prefix")
            window.require_sized()
        self.window = window
        self.ring = window.ring if window is not None else 0
        self._wfree: deque = deque(
            range(1, window.num_pages if window is not None else 1))
        self._slot_ring: Dict[int, List[int]] = {}
        self.num_pages = num_pages
        self.page = page
        self.p_max = p_max
        self.prefix_reuse = prefix_reuse
        # Capacity accounting (from ModelConfig.kv_cache_plan): bytes
        # one page costs at the pool's storage dtype, and what it
        # would cost at the engine's native dtype — the pair the
        # quantization capacity win is measured against in stats.
        self.page_bytes = page_bytes
        self.native_page_bytes = native_page_bytes
        self._free: deque = deque(range(1, num_pages))
        self._refs: Dict[int, int] = {}
        self._slot_pages: Dict[int, List[int]] = {}
        self._slot_tokens: Dict[int, int] = {}
        self._slot_hits: Dict[int, int] = {}
        # prefix cache: chained content key -> page id (insertion order
        # doubles as the eviction order). Entries are PUBLISHED in two
        # phases: alloc_prefill stages a slot's prefix-eligible pages
        # in _pending_prefix, and commit_prefix moves them into _prefix
        # once their KV content is actually resident — a hit hands
        # other requests these bytes, so registering at allocation time
        # would share unwritten pages (the multi-tick chunk stream and
        # the migration handoff both write AFTER allocating).
        self._prefix: Dict[Tuple, int] = {}
        self._pending_prefix: Dict[int, List[Tuple[Tuple, int]]] = {}
        # Eviction scoring: committed key -> (score, last-touch tick).
        # The tick advances per alloc_prefill; a hit folds +1 into the
        # geometrically-decayed running score, so frequency AND
        # recency both count (a once-hot-now-cold prefix decays below
        # a steadily-warm one).
        if not (0.0 < score_decay <= 1.0):
            raise ValueError(f"score_decay must be in (0, 1], got "
                             f"{score_decay}")
        self.score_decay = float(score_decay)
        self.on_demote = on_demote
        # Publication hook, the demote hook's dual: fires per key the
        # moment it COMMITS into the HBM prefix cache. The serving
        # engine uses it to drop any stale tier copy of the same
        # content (a faulted prefetch falls back to recompute; once
        # the recomputed pages publish, HBM is the one authoritative
        # tier again and the tier entry must go).
        self.on_commit = None
        self._score: Dict[Tuple, Tuple[float, int]] = {}
        self._tick = 0
        self.stats = {"allocs": 0, "frees": 0, "prefix_hits": 0,
                      "prefix_misses": 0, "evictions": 0,
                      "demotions": 0}
        if self.ring:
            self.stats["window_pages_recycled"] = 0

    # -- the window layers' pool -------------------------------------

    def _take_ring(self, slot: int):
        """``slot``'s ring out of the window pool's free list, whole or
        not at all."""
        if len(self._wfree) < self.ring:
            raise OutOfPagesError(
                f"window page pool exhausted ({len(self._wfree)} of "
                f"{self.window.num_pages - 1} usable pages free, a slot's "
                f"ring takes {self.ring})")
        self._slot_ring[slot] = [self._wfree.popleft()
                                 for _ in range(self.ring)]

    def _drop_ring(self, slot: int):
        ring = self._slot_ring.pop(slot, None)
        if ring is None:
            return
        self._wfree.extend(ring)
        # Every page of the sequence past the ring's first round was
        # written over an older one's entry.
        pages = -(-self._slot_tokens.get(slot, 0) // self.page)
        self.stats["window_pages_recycled"] += max(pages - self.ring, 0)

    def window_pages(self, slot: int) -> int:
        """Pages ``slot`` holds in a window layer: its ring, or 0."""
        return len(self._slot_ring.get(slot, ()))

    # -- raw pool ----------------------------------------------------

    def _take_page(self) -> int:
        if not self._free:
            self._evict_prefix()
        if not self._free:
            raise OutOfPagesError(
                f"page pool exhausted ({self.num_pages - 1} usable "
                f"pages, {len(self._prefix)} pinned by live prefixes)")
        pid = self._free.popleft()
        self._refs[pid] = self._refs.get(pid, 0) + 1
        self.stats["allocs"] += 1
        return pid

    def _drop_ref(self, pid: int):
        self._refs[pid] -= 1
        if self._refs[pid] == 0:
            del self._refs[pid]
            self._free.append(pid)
            self.stats["frees"] += 1

    def _evict_prefix(self):
        """Free ONE unreferenced prefix-cache page — incremental, so a
        transient pool-dry tick reclaims exactly what it needs instead
        of wiping the whole warm prefix cache. Victim choice and the
        demote hook live in :meth:`evict`."""
        self.evict(1)

    def _decayed_score(self, key: Tuple) -> float:
        score, last = self._score.get(key, (0.0, self._tick))
        return score * self.score_decay ** (self._tick - last)

    def _touch_score(self, key: Tuple):
        self._score[key] = (self._decayed_score(key) + 1.0, self._tick)

    def evict(self, n: int = 1) -> List[Tuple[Tuple, int]]:
        """Evict up to ``n`` UNREFERENCED committed prefix pages, the
        lowest frequency/recency score first (ties break in insertion
        order). Each victim runs the ``on_demote(key, pid)`` hook —
        while it runs, the page is still HBM-resident and still out of
        the free list (the two-phase tier transition: the hook stages
        + commits the payload into the tier below, and only then does
        the page free here) — a True return counts a demotion, False
        (or no hook) drops the content (recomputable by contract).
        Pages a live slot still references are never candidates.
        Returns the evicted ``(key, pid)`` pairs.

        The victim scan is a deliberate linear pass: every committed
        entry pins a distinct pool page, so it is bounded by
        ``num_pages`` — O(pool) per pool-dry eviction, with exact
        decayed scores under arbitrary refcount churn (a heap would
        trade that exactness for staleness-invalidation machinery)."""
        out: List[Tuple[Tuple, int]] = []
        for _ in range(n):
            victim, best = None, None
            for key, pid in self._prefix.items():
                if self._refs.get(pid, 0) != 1:   # a slot still reads it
                    continue
                s = self._decayed_score(key)
                if best is None or s < best:
                    victim, best = (key, pid), s
            if victim is None:
                break
            key, pid = victim
            if self.on_demote is not None and self.on_demote(key, pid):
                self.stats["demotions"] += 1
            del self._prefix[key]
            self._score.pop(key, None)
            self._drop_ref(pid)
            self.stats["evictions"] += 1
            out.append(victim)
        return out

    # -- per-slot API ------------------------------------------------

    def iter_prefix_keys(self, tokens: Sequence[int]):
        """Successive chained content keys for ``tokens``'s FULL
        pages — THE one definition of the prefix-key algebra.
        :meth:`alloc_prefill`, the fleet router's affinity walk, and
        the router-time tier prefetch all consume this iterator, so a
        change to the key shape moves them together (an affinity hit
        stays a prefix hit at admission by construction)."""
        key: Tuple = ()
        for i in range(len(tokens) // self.page):
            key = (key, tuple(tokens[i * self.page:
                                     (i + 1) * self.page]))
            yield key

    def alloc_prefill(self, slot: int, tokens: Sequence[int]) -> List[int]:
        """Allocate the page list for a prompt entering ``slot``:
        shared full-prefix pages (when ``prefix_reuse``) + private
        pages for the remainder. Returns the slot's page ids in
        position order. Raises :class:`BlockTableOverflowError` when
        the prompt alone outgrows one table row, and
        :class:`OutOfPagesError` (allocation rolled back) when the
        pool is dry."""
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already allocated; free it "
                             "before reuse")
        self._tick += 1            # the eviction score's decay clock
        n_tok = len(tokens)
        n_pages = max((n_tok + self.page - 1) // self.page, 1)
        if n_pages > self.p_max:
            raise BlockTableOverflowError(
                f"prompt of {n_tok} tokens needs {n_pages} pages > one "
                f"block-table row ({self.p_max} x {self.page})")
        pages: List[int] = []
        hits = 0
        try:
            full = n_tok // self.page
            keys = self.iter_prefix_keys(tokens)
            for i in range(n_pages):
                if self.prefix_reuse and i < full:
                    key = next(keys)
                    pid = self._prefix.get(key)
                    if pid is not None:
                        self._refs[pid] += 1
                        self.stats["prefix_hits"] += 1
                        self._touch_score(key)
                        if hits == i:     # hits are always a prefix run
                            hits += 1
                        pages.append(pid)
                        continue
                    self.stats["prefix_misses"] += 1
                    pid = self._take_page()
                    # Staged, not published: the page holds no KV yet.
                    self._pending_prefix.setdefault(slot, []).append(
                        (key, pid))
                    pages.append(pid)
                else:
                    pages.append(self._take_page())
            if self.ring:
                self._take_ring(slot)
        except OutOfPagesError:
            self._pending_prefix.pop(slot, None)
            for pid in pages:
                self._drop_ref(pid)
            raise
        self._slot_pages[slot] = pages
        self._slot_tokens[slot] = n_tok
        self._slot_hits[slot] = hits
        return list(pages)   # copy: appends must not mutate the result

    def commit_prefix(self, slot: int):
        """Publish ``slot``'s staged prefix pages into the
        content-addressed cache — call exactly when their KV content is
        RESIDENT (end of the monolithic blit, the last chunk of a
        chunk stream — layer `prefill_chunk_paged` or the megakernel
        WRITE_KV_CHUNK lane, whose sharers then ride attend-only
        position codes over these pages — the one-token mk lane's
        final token, or the migration scatter on a receiving pool).
        Until then a same-prefix request
        simply misses and computes its own copy — losing the sharing
        for the overlap window, never reading unwritten pages. If
        another sharer committed the same content first, its entry
        wins and this slot's copy stays private."""
        self.commit_pages(slot, [pid for _, pid in
                                 self._pending_prefix.get(slot, [])])

    def commit_pages(self, slot: int, pids) -> None:
        """Publish only the staged prefix entries whose page is in
        ``pids`` (the rest stay staged) — the tier-prefetch commit
        point: a page whose bytes just scattered in FROM THE TIER is
        content-resident (and shareable) immediately, while the rest
        of the slot's prompt is still streaming through prefill."""
        pids = set(int(p) for p in pids)
        keep: List[Tuple[Tuple, int]] = []
        for key, pid in self._pending_prefix.get(slot, []):
            if pid not in pids:
                keep.append((key, pid))
                continue
            if key in self._prefix:
                continue
            self._refs[pid] += 1
            self._prefix[key] = pid
            self._score[key] = (1.0, self._tick)
            if self.on_commit is not None:
                self.on_commit(key)
        if keep:
            self._pending_prefix[slot] = keep
        else:
            self._pending_prefix.pop(slot, None)

    def note_tier_hits(self, slot: int, upto_pages: int) -> None:
        """Extend ``slot``'s resident leading-page run to
        ``upto_pages`` — the tier-prefetch form of a prefix hit: the
        pages' KV bytes just arrived from the tier store, so the blit
        / chunk stream must skip them exactly like first-sharer
        pages (and :meth:`truncate_to`'s keep-floor protects them)."""
        self._slot_hits[slot] = max(self._slot_hits.get(slot, 0),
                                    int(upto_pages))

    def alloc_resume(self, slot: int, n_tokens: int) -> List[int]:
        """Allocate PRIVATE pages for a parked session re-entering
        with ``n_tokens`` of tier-resident KV (no prefix lookup: the
        payload scatter rewrites every page, and writing into a
        shared page a live reader holds is exactly what the prefix
        protocol forbids). Same rollback contract as
        :meth:`alloc_prefill`."""
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already allocated; free it "
                             "before reuse")
        self._tick += 1
        n_pages = max((n_tokens + self.page - 1) // self.page, 1)
        if n_pages > self.p_max:
            raise BlockTableOverflowError(
                f"resume of {n_tokens} tokens needs {n_pages} pages > "
                f"one block-table row ({self.p_max} x {self.page})")
        pages: List[int] = []
        try:
            for _ in range(n_pages):
                pages.append(self._take_page())
            if self.ring:
                self._take_ring(slot)
        except OutOfPagesError:
            for pid in pages:
                self._drop_ref(pid)
            raise
        self._slot_pages[slot] = pages
        self._slot_tokens[slot] = int(n_tokens)
        self._slot_hits[slot] = 0
        return list(pages)

    def prefix_hits(self, slot: int) -> int:
        """Leading page count of ``slot``'s allocation that came from
        the prefix cache (always a prefix RUN of the page list: a hit
        after a miss is impossible — the chained key of the later page
        embeds the earlier miss). The server skips blitting these: their
        KV bytes were written by the first sharer, and rewriting them
        from a differently-shaped prefill while another request attends
        to them has no cross-shape bit-exactness guarantee."""
        return self._slot_hits.get(slot, 0)

    def append(self, slot: int, pos: Optional[int] = None) -> Optional[int]:
        """Account one appended token for ``slot``; allocates (and
        returns) a fresh page when the token starts a new page, else
        returns None. Raises :class:`BlockTableOverflowError` when the
        request outgrows its table row.

        ``pos`` (the position being written) makes the call IDEMPOTENT
        per position: a serving step that failed mid-dispatch (comm
        timeout) re-appends the same position on retry, and the
        bookkeeping must not drift."""
        n = self._slot_tokens[slot]
        if pos is not None and pos < n:
            return None          # retry of an already-accounted token
        if n % self.page == 0 and n // self.page >= len(
                self._slot_pages[slot]):
            if len(self._slot_pages[slot]) >= self.p_max:
                raise BlockTableOverflowError(
                    f"slot {slot} at {n} tokens needs page "
                    f"{n // self.page + 1} > row capacity "
                    f"{self.p_max} x {self.page}")
            pid = self._take_page()
            self._slot_pages[slot].append(pid)
            self._slot_tokens[slot] = n + 1
            return pid
        self._slot_tokens[slot] = n + 1
        return None

    def truncate_to(self, slot: int, n_tokens: int):
        """Roll ``slot``'s token accounting back to ``n_tokens`` and
        free now-unused TRAILING pages — the speculative-decode
        rollback (a rejected draft suffix releases the page growth its
        pre-allocation claimed). Page-level only: the partially-filled
        final page stays; a PREFIX-SHARED page is never freed — the
        keep-floor is the slot's prefix-hit run, and even past it a
        drop only releases this slot's ref (the cache's own ref keeps
        a published page's bytes alive for its other readers)."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise KeyError(f"slot {slot} has no allocation to truncate")
        cur = self._slot_tokens[slot]
        if n_tokens > cur:
            raise ValueError(f"truncate_to({n_tokens}) beyond slot "
                             f"{slot}'s {cur} accounted tokens")
        keep = max((n_tokens + self.page - 1) // self.page, 1,
                   self._slot_hits.get(slot, 0))
        while len(pages) > keep:
            self._drop_ref(pages.pop())
        self._slot_tokens[slot] = n_tokens

    def free_slot(self, slot: int):
        """Release a finished request's pages (COMMITTED shared pages
        survive in the prefix cache until evicted; staged-but-never-
        committed ones — a request that failed before its content
        landed — are dropped, so a later same-prefix request can never
        hit an unwritten page)."""
        self._pending_prefix.pop(slot, None)
        for pid in self._slot_pages.pop(slot, []):
            self._drop_ref(pid)
        self._drop_ring(slot)
        self._slot_tokens.pop(slot, None)
        self._slot_hits.pop(slot, None)

    # -- checkpoint/restore ------------------------------------------

    def snapshot(self) -> dict:
        """Plain-data copy of the FULL allocator state (free list,
        refcounts, per-slot pages/tokens/hits, committed + staged
        prefix entries, stats) — the host half of a serving
        checkpoint. Deep-copied: mutating the manager afterwards never
        mutates the snapshot, and vice versa. Round-trips through
        :meth:`load_snapshot` (pickle-safe: tuples/lists/dicts/ints
        only)."""
        return {
            "num_pages": self.num_pages, "page": self.page,
            "p_max": self.p_max, "prefix_reuse": self.prefix_reuse,
            "free": list(self._free),
            "refs": dict(self._refs),
            "slot_pages": {s: list(p)
                           for s, p in self._slot_pages.items()},
            "slot_tokens": dict(self._slot_tokens),
            "slot_hits": dict(self._slot_hits),
            "prefix": list(self._prefix.items()),
            "prefix_score": [(k, s, t) for k, (s, t) in
                             self._score.items()],
            "tick": self._tick,
            "pending_prefix": {s: list(v) for s, v in
                               self._pending_prefix.items()},
            "stats": dict(self.stats),
            "window_free": list(self._wfree),
            "slot_ring": {s: list(p) for s, p in self._slot_ring.items()},
        }

    def load_snapshot(self, snap: dict) -> None:
        """Adopt a :meth:`snapshot` wholesale (geometry must match —
        the pool the snapshot's page ids index into must be the pool
        being restored alongside)."""
        for key in ("num_pages", "page", "p_max"):
            if snap[key] != getattr(self, key):
                raise ValueError(
                    f"snapshot {key}={snap[key]} != this manager's "
                    f"{getattr(self, key)} — restore needs an "
                    "identically-planned pool")
        self.prefix_reuse = bool(snap["prefix_reuse"])
        self._free = deque(snap["free"])
        self._refs = {int(k): int(v) for k, v in snap["refs"].items()}
        self._slot_pages = {int(s): list(p) for s, p in
                            snap["slot_pages"].items()}
        self._slot_tokens = {int(s): int(n) for s, n in
                             snap["slot_tokens"].items()}
        self._slot_hits = {int(s): int(n) for s, n in
                           snap["slot_hits"].items()}
        self._prefix = {k: int(v) for k, v in snap["prefix"]}
        self._score = {k: (float(s), int(t)) for k, s, t in
                       snap.get("prefix_score", [])}
        self._tick = int(snap.get("tick", 0))
        self._pending_prefix = {int(s): [(k, int(p)) for k, p in v]
                                for s, v in
                                snap["pending_prefix"].items()}
        self.stats = dict(snap["stats"])
        self.stats.setdefault("demotions", 0)
        if self.ring:
            self._wfree = deque(snap["window_free"])
            self._slot_ring = {int(s): list(p) for s, p in
                               snap["slot_ring"].items()}

    @property
    def table_width(self) -> int:
        """Entries of a slot's table row: ``p_max`` pages, and behind
        them the window layers' ring where the pool has any."""
        return self.p_max + self.ring

    def table_row(self, slot: int):
        """This slot's block-table row, scratch-padded to p_max; behind
        it the slot's ring in the window layers' pool, entry by entry,
        where the pool has window layers."""
        row = [SCRATCH_PAGE] * self.p_max
        for i, pid in enumerate(self._slot_pages.get(slot, [])):
            row[i] = pid
        if self.ring:
            row += self._slot_ring.get(slot, [SCRATCH_PAGE] * self.ring)
        return row

    def fragmentation(self) -> dict:
        """Pool health: page accounting + internal fragmentation
        (used-token fraction of allocated page capacity)."""
        used_pages = self.num_pages - 1 - len(self._free)
        used_tokens = sum(self._slot_tokens.values())
        held_pages = sum(len(p) for p in self._slot_pages.values())
        shared = max(held_pages - len(
            set(p for ps in self._slot_pages.values() for p in ps)), 0)
        cap = max(held_pages, 1) * self.page
        out = {
            "num_pages": self.num_pages, "page": self.page,
            "free_pages": len(self._free), "used_pages": used_pages,
            "prefix_pages": len(self._prefix),
            "shared_page_refs": shared,
            "used_tokens": used_tokens,
            "utilization": used_tokens / cap if held_pages else 1.0,
            **self.stats,
        }
        if self.ring:
            out.update(
                window_num_pages=self.window.num_pages,
                window_free_pages=len(self._wfree),
                window_used_pages=(self.window.num_pages - 1
                                   - len(self._wfree)),
                window_pages_a_slot=self.ring)
        if self.page_bytes:
            # The quantization capacity surface: HBM cost per resident
            # token, and how many MORE pages the same pool bytes buy
            # vs the native dtype (int8 ≈ 2–4x depending on the
            # native width and the per-page scale overhead).
            out["bytes_per_token"] = self.page_bytes / self.page
            if self.native_page_bytes:
                ratio = self.native_page_bytes / self.page_bytes
                out["capacity_ratio_vs_native"] = round(ratio, 4)
                out["pages_at_native_bytes"] = int(
                    (self.num_pages - 1) * ratio)
        return out
