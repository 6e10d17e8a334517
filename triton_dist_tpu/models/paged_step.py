"""The paged step contract, once: what the ROWS of a step program are,
the K/V pool's two halves, and the four step functions the serving
engine calls, built from a family's trunk.

A step program carries up to two kinds of rows through one trunk
(:class:`Rows`): ``C`` rows of a prefill CHUNK of one slot and one
DECODE row for each of the batch's ``S`` slots, or, alone, ``S x K``
VERIFICATION rows. They share embedding, projections, FFN, final norm
and head as one row-concatenated activation, chunk rows first; only
where a row's cache entry is written and what its query reads differs,
and there each half runs its own code (:meth:`Rows.split`), both writes
before both reads, so that each kernel takes the pool as the layer's
last writer left it, in place.

A family writes a pool statement, its parameters and a TRUNK,
``trunk(params, rows, cache, cfg, *, mode, axis, attn_impl,
decode_attn_impl, **own) -> (x (n, d) normed, cache[, stats])``, which
embeds ``rows.tokens()`` (:func:`embed_rows`) and runs its layers,
handing each layer's cache halves to ``rows.split`` or taking the K/V
pool's from :func:`kv_attend`; :func:`build` makes the step functions of
it (docs/serving.md, "What a new model family writes").
"""

from __future__ import annotations

import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp

from triton_dist_tpu.layers import tp_attn
from triton_dist_tpu.obs import scope

# The blocks of a chunk program that a rule on sizes may give to a Pallas
# kernel. A model's ``step_kernels(cfg, rows, *, decode_rows, page,
# dtype)`` returns those its program of ``rows`` chunk rows does, by the
# rules the program decides by inside; the serving engine counts its
# chunk dispatches by it (``chunk_dispatches_kernel_<block>``).
STEP_KERNELS = ("walk", "scan", "experts")


@scope("embed")
def embed_rows(params, token_ids):
    """The table's rows of ``token_ids`` (n,), replicated: (n, d)."""
    return params["embed"][token_ids]


@scope("head")
def last_valid_row(x, valid):
    """Row ``valid - 1`` of a chunk's (C, ...) rows, as (1, ...)."""
    return jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(jnp.asarray(valid, jnp.int32) - 1, 0), 1, axis=0)


@scope("head")
def lm_head(params, x, axis):
    """Logits of rows ``x`` over the whole vocabulary, the head's rows
    sharded along ``axis``."""
    logits_loc = jnp.dot(x, params["lm_head"].T,
                         preferred_element_type=jnp.float32)
    return jax.lax.all_gather(logits_loc, axis, axis=x.ndim - 1,
                              tiled=True)


@dataclasses.dataclass
class Rows:
    """The rows of one step program, as its caller gave them.

    Chunk rows: ``chunk_toks`` (C,) int32, padded past ``valid``;
    ``table_row`` (p_max,) int32, the slot's block-table row; ``start``,
    the global position of the chunk's first token; ``wfrom``: positions
    below it are already resident (prefix-shared pages: computed, never
    rewritten); ``valid``, the real tokens in this chunk; ``slot``, the
    decode slot whose per-sequence state the rows carry, where the pool
    keeps one. All ride as data: the trace keys on ``C`` alone.

    Decode rows: ``token_ids`` (S,), one for each batch slot, at the
    slot's OWN length (``cache.lens``), so requests of different ages
    share one fixed-shape dispatch. A parked slot (``live == 0``: free,
    or mid-prefill, as the chunk's own slot is until its prompt is
    resident) still flows through the math, but its entries land in the
    manager's scratch page, its length does not advance and its logits
    are garbage the scheduler ignores; its reads clamp to one position,
    so that a fully masked row stays finite.

    Verification rows: ``token_ids`` (S, K), slot ``s``'s candidates at
    positions ``lens[s] .. lens[s] + K - 1`` (K is static: one program
    however many are accepted); ``budget`` (S,) caps how many may WRITE
    real pages a slot (rows past it, near a request's token limit, land
    in scratch). Lengths are NOT advanced: the host commits the prefix
    it accepts and rolls page accounting back
    (``BlockManager.truncate_to``); a rejected suffix stays masked
    garbage the next block overwrites.
    """
    chunk_toks: object = None
    token_ids: object = None
    table_row: object = None
    start: object = None
    wfrom: object = None
    valid: object = None
    slot: object = None
    budget: object = None

    def __post_init__(self):
        chunk, toks = self.chunk_toks, self.token_ids
        self.c = 0 if chunk is None else chunk.shape[0]    # chunk rows
        self.s = 0 if toks is None else toks.shape[0]      # batch slots
        # Verification rows a slot; 0 where the batch's rows decode.
        self.k = toks.shape[1] if self.s and len(toks.shape) == 2 else 0

    # The two position arrays are made where they are first asked for
    # and kept: ask outside any inner ``jit`` (a family's once-traced
    # layer), as the trunks do before their first layer.

    @functools.cached_property
    def chunk_pos(self):
        """(C,) the chunk rows' global positions."""
        return (jnp.asarray(self.start, jnp.int32)
                + jnp.arange(self.c, dtype=jnp.int32))

    @functools.cached_property
    def steps(self):
        """(1, K) a verification row's place among its slot's."""
        return jnp.arange(self.k, dtype=jnp.int32)[None]

    def _join(self, chunk, batch):
        """``chunk()`` for the chunk rows, ``batch()`` for the batch's,
        concatenated where both ride. HERE is the rule that an absent
        half costs nothing: its thunk is not called, and nothing is
        concatenated."""
        if not self.s:
            return chunk()
        if not self.c:
            return batch()
        return jnp.concatenate([chunk(), batch()])

    def tokens(self):
        """(n,) the ids to embed, chunk rows first."""
        toks = self.token_ids
        return self._join(lambda: self.chunk_toks,
                          lambda: toks.reshape(-1) if self.k else toks)

    def positions(self, cache):
        """(n,) each row's position: a chunk row's global one, a decode
        row's slot's length, a verification row's length + place."""
        return self._join(
            lambda: self.chunk_pos,
            lambda: ((cache.lens[:, None] + self.steps).reshape(-1)
                     if self.k else cache.lens))

    def head_rows(self, x):
        """The rows of ``x`` (n, ...) the head reads: the chunk's last
        valid row first, then every decode or verification row."""
        c = self.c
        return self._join(
            lambda: last_valid_row(x[:c] if self.s else x, self.valid),
            lambda: x[c:] if c else x)

    def split(self, chunk_fn, decode_fn, cache, *arrays,
              per_slot: bool = False):
        """``chunk_fn(cache, *chunk halves)`` then ``decode_fn(cache,
        *decode halves)`` over row-major ``arrays`` (n, ...), each ``->
        (out, cache)``, the cache threaded through both. Returns ``(the
        outs as (n, -1), cache)``; ``out`` None for a half that only
        writes. ``per_slot``: the decode halves as ``(S, 1, ...)``, one
        row a slot, the layout of verification rows. With one half
        absent the other's function gets the arrays whole (no slice, no
        concatenation) and its result is returned as it is."""
        c = self.c
        if not self.s:
            return chunk_fn(cache, *arrays)
        if per_slot:    # one indexing step: the rows and the new axis
            half = lambda x: x[c or None:, None]
        else:
            half = lambda x: x[c:] if c else x
        if not c:
            return decode_fn(cache, *map(half, arrays))
        a, cache = chunk_fn(cache, *(x[:c] for x in arrays))
        b, cache = decode_fn(cache, *map(half, arrays))
        if a is None:
            return None, cache
        return jnp.concatenate([a.reshape(c, -1),
                                b.reshape(self.s, -1)]), cache


# -- the K/V pool's halves --------------------------------------------------

def _valid_qpos(positions, start, valid):
    """A chunk's query positions with the bucket-padding rows clamped
    to the last VALID position: their outputs are discarded garbage
    either way, but unclamped they would stretch a kernel's page walk
    to the padded tail (8x the DMA traffic for exactly the
    short-prompt-in-a-big-bucket case the kernel exists to make
    cheap)."""
    i = jnp.arange(positions.shape[0], dtype=jnp.int32)
    last_valid = (jnp.asarray(start, jnp.int32)
                  + jnp.maximum(jnp.asarray(valid, jnp.int32)
                                - 1, 0))
    return jnp.where(i < valid, positions, last_valid)


@scope("attn_chunk")
def chunk_attend(li, q, cache, table_row, positions, start, valid,
                 attn_impl):
    """A prefill chunk's queries (C, 1, H_loc, hd) over its slot's
    pages, causal by global position, after the chunk's own K/V were
    written: earlier chunks and a shared prefix are attended exactly,
    chunk boundaries are invisible to the math. ``"ref"`` gathers the
    slot's dense row (O(p_max x page) HBM traffic whatever the prompt's
    length), ``"flash"`` streams only the RESIDENT pages through the
    Q-block kernel. Returns (C, H_loc, hd)."""
    if attn_impl == "flash":
        from triton_dist_tpu.ops.paged_flash_qblock import (
            paged_flash_qblock)

        qpos = _valid_qpos(positions, start, valid)
        ksc, vsc = cache.layer_scales(li)
        return paged_flash_qblock(
            q[:, 0][None], cache.k_pages, cache.v_pages,
            table_row[None], qpos[None], layer=li,
            k_scale=ksc, v_scale=vsc)[0]
    from triton_dist_tpu.ops.chunked_prefill import chunk_attend

    kd, vd = cache.dense_row(li, table_row)
    return chunk_attend(q[:, 0], kd, vd, positions)


@scope("attn_chunk_window")
def chunk_attend_window(li, q, cache, ring_row, positions, start, valid,
                        attn_impl, window: int):
    """:func:`chunk_attend` for a WINDOW layer (``li`` its index among
    them): row ``i`` reads keys ``j`` with ``i - window < j <= i`` out
    of the slot's ring ``ring_row`` in the window layers' pool.
    ``"flash"`` walks, a row block, the pages that hold a key in its
    reach (``paged_flash_qblock(window=...)``); ``"ref"`` gathers the
    ring whole, in position order from the first row's oldest key."""
    qpos = _valid_qpos(positions, start, valid)
    if attn_impl == "flash":
        from triton_dist_tpu.ops.paged_flash_qblock import (
            paged_flash_qblock)

        return paged_flash_qblock(
            q[:, 0][None], cache.win["k"], cache.win["v"], ring_row[None],
            qpos[None], layer=li, window=window)[0]
    from triton_dist_tpu.ops.chunked_prefill import (gather_ring_dense,
                                                     window_attend)

    first = jnp.maximum(positions[0] - (window - 1), 0) // cache.page
    ring = ring_row.shape[0]
    kd, key_pos = gather_ring_dense(cache.win["k"][li], ring_row, first,
                                    ring)
    vd, _ = gather_ring_dense(cache.win["v"][li], ring_row, first, ring)
    return window_attend(q[:, 0][None], kd[None], vd[None], qpos[None],
                         key_pos[None], window)[0]


@scope("attn_decode")
def decode_attend(li, q, cache, attn_impl):
    """One query a slot (S, 1, H_loc, hd) over the slot's pages at its
    own length, after the step's token was appended. ``"ref"`` gathers
    the layer's pages to a dense (S, cap, KV_loc, hd) view for
    :func:`tp_attn.sdpa` (token-exact with ``Engine.serve``; the CPU
    default); ``"kernel"`` streams them through ``paged_flash_decode``;
    ``"flash"`` is the same kernel, so that one value spells "Pallas
    paged attention" for decode, chunks and verification."""
    # Active slots attend including the token appended this step;
    # parked slots clamp to 1 so a fully-masked row cannot NaN the
    # softmax (their output is discarded anyway).
    kv_len = jnp.maximum(cache.lens + cache.live, 1).astype(jnp.int32)
    if attn_impl in ("kernel", "flash"):
        from triton_dist_tpu.ops.paged_flash_decode import (
            paged_flash_decode)

        ksc, vsc = cache.layer_scales(li)
        return paged_flash_decode(
            q[:, 0], cache.k_pages, cache.v_pages,
            cache.table_of(cache.block_table), kv_len, layer=li,
            axis=None, k_scale=ksc, v_scale=vsc)
    kd, vd = cache.dense_layer(li)
    return tp_attn.sdpa(q, kd, vd, causal=False, kv_len=kv_len)


@scope("attn_decode_window")
def decode_attend_window(li, q, cache, attn_impl, window: int):
    """:func:`decode_attend` for a WINDOW layer (``li`` its index among
    them): a slot's query reads its last ``window`` keys, the token
    appended this step among them, out of the slot's ring.
    ``"kernel"`` / ``"flash"`` walk the pages those keys span
    (``paged_flash_decode(window=...)``); ``"ref"`` gathers them."""
    kv_len = jnp.maximum(cache.lens + cache.live, 1).astype(jnp.int32)
    rings = cache.table_of(cache.block_table, window=True)
    if attn_impl in ("kernel", "flash"):
        from triton_dist_tpu.ops.paged_flash_decode import (
            paged_flash_decode)

        return paged_flash_decode(
            q[:, 0], cache.win["k"], cache.win["v"], rings, kv_len,
            layer=li, axis=None, window=window)
    from triton_dist_tpu.ops.chunked_prefill import (gather_ring_dense,
                                                     window_attend)

    from triton_dist_tpu.ops.paged_flash_decode import window_walk_pages

    page = cache.page
    n_pages = min(window_walk_pages(1, window, page), rings.shape[1])
    first = jnp.maximum(kv_len - window, 0) // page
    kd, key_pos = gather_ring_dense(cache.win["k"][li], rings, first,
                                    n_pages)
    vd, _ = gather_ring_dense(cache.win["v"][li], rings, first, n_pages)
    return window_attend(q, kd, vd, (kv_len - 1)[:, None], key_pos,
                         window)


def _verify_attend(li, q, k_tok, v_tok, cache, rows, attn_impl):
    """The verification rows' half: every candidate's K/V written
    (``append_block``), then candidate ``j`` of a slot attends its paged
    history and the candidates through itself, what a sequential decode
    of the accepted prefix would see: ``"ref"`` by ``block_attend`` over
    every slot's dense row, ``"flash"`` by the Q-block kernel, the
    per-query positions riding as data."""
    s, k = rows.s, rows.k
    hl, hd = q.shape[2], q.shape[3]
    kvl = k_tok.shape[2]
    lens = cache.lens
    with scope("cache_write"):
        cache = cache.append_block(
            li, k_tok[:, 0].reshape(s, k, kvl, hd),
            v_tok[:, 0].reshape(s, k, kvl, hd), budget=rows.budget)
    with scope("attn_decode"):
        q = q[:, 0].reshape(s, k, hl, hd)
        if attn_impl == "flash":
            from triton_dist_tpu.ops.paged_flash_qblock import (
                paged_flash_qblock)

            # Candidate j of a live slot attends positions <= lens[s]+j
            # (block_attend's kv_len-1); parked slots clamp to position
            # 0 (garbage the scheduler ignores).
            qpos = jnp.maximum(
                lens[:, None] + cache.live[:, None]
                * (jnp.arange(k, dtype=jnp.int32)[None] + 1), 1) - 1
            ksc, vsc = cache.layer_scales(li)
            return paged_flash_qblock(
                q, cache.k_pages, cache.v_pages, cache.block_table,
                qpos, layer=li, k_scale=ksc, v_scale=vsc), cache
        from triton_dist_tpu.ops.chunked_prefill import block_attend

        kd, vd = cache.dense_layer(li)
        return block_attend(q, kd, vd, lens, cache.live), cache


def kv_attend(rows: Rows, attn_impl: str, decode_attn_impl: str,
              window: int = 0):
    """What a layer over a ``PagedKVCache`` does between its
    projections: ``attend(li, q, k_tok, v_tok, cache) -> (o, cache)``
    for rows ``(n, 1, heads, hd)``, ``li`` the POOL's layer (an int, or
    an int32 operand). Chunk rows write through ``rows.table_row``
    (``write_chunk``) and read by :func:`chunk_attend` under
    ``attn_impl``; decode rows append through ``cache.block_table`` and
    read by :func:`decode_attend` under ``decode_attn_impl``;
    verification rows take ``attn_impl``. ``o`` reshapes to (n, -1).

    ``window`` > 0: the halves of a WINDOW layer, which reads a row's
    last ``window`` keys alone. ``li`` counts among the window layers;
    both kinds of rows write and read the window layers' pool through
    the slot's ring (``PagedKVCache.win``), by
    :func:`chunk_attend_window` and :func:`decode_attend_window`. A
    model of both kinds of layer makes one ``attend`` a kind."""
    if rows.k:
        if window:
            raise NotImplementedError(
                "verification rows over window layers: a refused "
                "candidate's entry would have written over a key the "
                "ring still needs")
        return functools.partial(_verify_attend, rows=rows,
                                 attn_impl=attn_impl)
    pos = rows.chunk_pos if rows.c else None
    kind = {"window": True} if window else {}
    if window:
        def read_chunk(li, q, cache):
            return chunk_attend_window(
                li, q, cache, cache.table_of(rows.table_row, window=True),
                pos, rows.start, rows.valid, attn_impl, window)

        def read_decode(li, q, cache):
            return decode_attend_window(li, q, cache, decode_attn_impl,
                                        window)
    else:
        def read_chunk(li, q, cache):
            return chunk_attend(li, q, cache, cache.table_of(rows.table_row),
                                pos, rows.start, rows.valid, attn_impl)

        def read_decode(li, q, cache):
            return decode_attend(li, q, cache, decode_attn_impl)

    def attend(li, q, k_tok, v_tok, cache):
        with scope("cache_write"):
            _, cache = rows.split(
                lambda cache, k, v: (None, cache.write_chunk(
                    li, k, v, rows.table_row, pos, rows.valid, rows.wfrom,
                    **kind)),
                lambda cache, k, v: (None, cache.append_decode(li, k, v,
                                                               **kind)),
                cache, k_tok, v_tok)
        return rows.split(
            lambda cache, q: (read_chunk(li, q, cache), cache),
            lambda cache, q: (read_decode(li, q, cache), cache),
            cache, q)

    return attend


# -- the four step functions ------------------------------------------------

def _publish(step, trunk, slotted):
    """``step`` under the signature it serves: its ``**own`` spelled out
    as the keyword arguments ``trunk`` gives defaults to (the family's
    own; the others are the builder's to pass), and ``slot`` required
    where the pool keeps per-sequence state, else absent."""
    mine = [p.replace(default=p.empty) if p.name == "slot" else p
            for p in inspect.signature(step).parameters.values()
            if p.kind is not p.VAR_KEYWORD and (slotted or p.name != "slot")]
    own = [p for p in inspect.signature(trunk).parameters.values()
           if p.kind is p.KEYWORD_ONLY and p.default is not p.empty]
    step.__signature__ = inspect.Signature(mine + own)
    return step


def build(trunk, *, slotted: bool = False, row_stats: bool = False,
          xla_only: str = ""):
    """``(prefill_chunk_paged, decode_step_paged, chunk_decode_paged,
    verify_step_paged)`` over ``trunk`` (the module's docstring has its
    form). Each states its rows (:class:`Rows`, which has what the
    arguments mean), runs the trunk, reads the head over
    ``rows.head_rows`` and hands out last what the trunk returns third:
    one vector a step (``STEP_STATS``) as it is, or with ``row_stats``
    one number a row (``ROW_STATS``) for the head's rows. ``slotted``:
    the chunk's steps take ``slot``. ``xla_only``: why the family runs
    under ``mode="xla"`` alone, for the error another mode gets. The
    keyword arguments ``trunk`` gives defaults to are the steps' too. A
    family that cannot roll a sequence's state back binds three of the
    four."""

    def run(params, rows, cache, cfg, mode, axis, own, **impls):
        if xla_only and mode != "xla":
            raise ValueError(f"mode={mode!r}: {xla_only}; serve it with "
                             "mode='xla'")
        if rows.c and slotted == (rows.slot is None):
            raise TypeError("slot: the decode slot a chunk's rows belong "
                            "to, for a pool that keeps a sequence's "
                            "state, and for no other")
        x, cache, *stats = trunk(params, rows, cache, cfg, mode=mode,
                                 axis=axis, **impls, **own)
        return x, cache, stats

    def head_stats(rows, stats):
        return [rows.head_rows(st) if row_stats else st for st in stats]

    def prefill_chunk_paged(params, chunk_toks, cache, table_row, cfg, *,
                            start, wfrom, valid, slot=None,
                            mode: str = "xla", axis: str = "tp",
                            attn_impl: str = "ref", **own):
        """One FIXED-SHAPE chunk of a bucketed paged prefill
        (per-shard): a prompt streams through in chunks of a few bucket
        lengths, so the trace keys on ``C`` alone and the jit cache is
        bounded by the bucket count; the residual stays replicated (no
        divisibility ties C to the mesh). Returns ``(logits (vocab,) of
        the LAST VALID token, cache[, stats])``: the final chunk's
        logits seed the first generated token."""
        rows = Rows(chunk_toks=chunk_toks, table_row=table_row,
                    start=start, wfrom=wfrom, valid=valid, slot=slot)
        x, cache, stats = run(params, rows, cache, cfg, mode, axis, own,
                              attn_impl=attn_impl, decode_attn_impl="ref")
        logits = lm_head(params, rows.head_rows(x), axis)
        return (logits[0], cache, *head_stats(rows, stats))

    def decode_step_paged(params, token_ids, cache, cfg, *,
                          mode: str = "xla", axis: str = "tp",
                          attn_impl: str = "ref", **own):
        """One CONTINUOUS-BATCHING decode step over the paged pool, no
        recompilation as requests join and leave; ``attn_impl`` is the
        decode rows'. Returns ``(logits (S, vocab), cache.advance()[,
        stats])``."""
        rows = Rows(token_ids=token_ids)
        x, cache, stats = run(params, rows, cache, cfg, mode, axis, own,
                              attn_impl="ref", decode_attn_impl=attn_impl)
        return (lm_head(params, x, axis), cache.advance(),
                *head_stats(rows, stats))

    def chunk_decode_paged(params, chunk_toks, token_ids, cache, table_row,
                           cfg, *, start, wfrom, valid, slot=None,
                           mode: str = "xla", axis: str = "tp",
                           attn_impl: str = "ref",
                           decode_attn_impl: str = "ref", **own):
        """One prefill chunk of one slot AND one decode step of the
        whole batch in ONE program: the two steps above on the same
        pool, every weight read once for both, the head once over the
        chunk's last valid row and the decode rows. ``attn_impl`` is the
        chunk rows', ``decode_attn_impl`` the decode rows'. Returns
        ``(chunk logits (vocab,), decode logits (S, vocab),
        cache.advance()[, stats])``."""
        rows = Rows(chunk_toks=chunk_toks, token_ids=token_ids,
                    table_row=table_row, start=start, wfrom=wfrom,
                    valid=valid, slot=slot)
        x, cache, stats = run(params, rows, cache, cfg, mode, axis, own,
                              attn_impl=attn_impl,
                              decode_attn_impl=decode_attn_impl)
        with scope("head"):
            logits = lm_head(params, rows.head_rows(x), axis)
            stats = head_stats(rows, stats)
            chunk_logits, decode_logits = logits[0], logits[1:]
        return (chunk_logits, decode_logits, cache.advance(), *stats)

    def verify_step_paged(params, token_ids, cache, cfg, *, budget=None,
                          mode: str = "xla", axis: str = "tp",
                          attn_impl: str = "ref", **own):
        """One SPECULATIVE-VERIFICATION step: K candidate tokens a slot
        through one fixed-shape dispatch (``attn_impl`` "ref" |
        "flash"). Returns ``(logits (S, K, vocab), cache)``: ``logits[s,
        j]`` is the next-token distribution AFTER candidates 0..j,
        token-exact with greedy decode of the accepted prefix."""
        rows = Rows(token_ids=token_ids, budget=budget)
        x, cache, _ = run(params, rows, cache, cfg, mode, axis, own,
                          attn_impl=attn_impl, decode_attn_impl="ref")
        return lm_head(params, x, axis).reshape(*token_ids.shape, -1), cache

    return tuple(_publish(step, trunk, slotted) for step in (
        prefill_chunk_paged, decode_step_paged, chunk_decode_paged,
        verify_step_paged))
