"""Fixed-shape chunked-prefill building blocks (paged KV).

Reference: the serving split of the source paper's Engine (PAPER.md
L7/L7′) assumes prefill work can be fed to a persistent decode batch
without respecializing it; the megakernel-decode serving analysis of
arXiv 2605.00686 makes the cost of violating that explicit. The layer
path used to run one monolithic prefill dispatch per request, which
XLA specializes per prompt length — so a mixed-length trace burns its
time in compiles. Chunked prefill fixes the shape instead: prompts are
split into a small set of BUCKETED chunk lengths (padded to bucket),
each chunk streamed into the slot's ``PagedKVCache`` pages through one
jitted per-bucket step, so the prefill jit cache is bounded by the
bucket count — never by the distinct-prompt-length count.

This module holds the pure math both the dense and MoE chunk steps
share (:func:`triton_dist_tpu.models.dense.prefill_chunk_paged` is the
model-level driver):

- :func:`chunk_write_ids` — which pool page / offset each chunk token
  writes, with padding and already-resident (prefix-shared) positions
  routed to the reserved scratch page, so a chunk can never corrupt a
  page a live reader holds.
- :func:`chunk_attend` — causal attention of the chunk's queries over
  the slot's gathered position-major page view (the
  ``paged_flash_decode_ref`` gather path generalized from one query
  per slot to a chunk of queries), masked by each query's GLOBAL
  position so earlier chunks and the shared prefix are attended
  exactly.
- :func:`gather_pages_dense` — THE dense-row gather (pool pages →
  position-major view, dequant fused for quantized pools). One
  definition shared by ``PagedKVCache.dense_row``/``dense_layer``,
  ``paged_flash_decode_ref``, and ``paged_flash_qblock_ref`` — the
  oracle the Pallas paged kernels are tested against has exactly one
  spelling of its gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


SCRATCH_PAGE = 0


def gather_pages_dense(pool, table, scale=None):
    """Gather block-table pages into the dense position-major view.

    pool: (num_pages, KV, page, hd) — ONE layer's page pool; table:
    (..., P) int32 page ids (any leading batch shape: ``(p_max,)`` for
    one slot's row, ``(S, p_max)`` for a whole decode batch); scale:
    (num_pages, KV) fp32 per-page per-head dequant scales of a
    QUANTIZED pool (dequant fuses into the gather), or None for the
    native path. Returns (..., P·page, KV, hd) — positions past the
    written region are garbage the caller's mask hides.
    """
    kvh, page, hd = pool.shape[1:]
    g = pool[table]                     # (..., P, KV, page, hd)
    if scale is not None:               # fused dequant on gather
        g = g.astype(jnp.float32) * scale[table][..., None, None]
    g = jnp.moveaxis(g, -2, -3)         # (..., P, page, KV, hd)
    return g.reshape(*table.shape[:-1], table.shape[-1] * page, kvh, hd)


def gather_ring_dense(pool, ring_table, first_page, n_pages: int):
    """Pages ``first_page .. first_page + n_pages - 1`` of each slot out
    of a window layer's RING, position-major, with the positions they
    hold if they are the slot's newest: the gather-path view of a pool
    whose table entry ``n % ring`` holds page ``n``.

    pool: (num_pages, KV, page, hd), ONE layer's; ring_table: (...,
    ring) int32; first_page: (...) int32. Returns ``(dense (..., n_pages
    * page, KV, hd), key_pos (..., n_pages * page) int32)``. An entry
    may hold an older page (or another request's): the caller's mask
    bounds the keys on both sides."""
    page = pool.shape[2]
    pages = (jnp.asarray(first_page, jnp.int32)[..., None]
             + jnp.arange(n_pages, dtype=jnp.int32))
    ids = jnp.take_along_axis(ring_table, pages % ring_table.shape[-1],
                              axis=-1)
    key_pos = (pages[..., None] * page
               + jnp.arange(page, dtype=jnp.int32)).reshape(
                   *pages.shape[:-1], n_pages * page)
    return gather_pages_dense(pool, ids), key_pos


def window_attend(q, k_dense, v_dense, positions, key_pos, window: int):
    """Attention of queries that read the last ``window`` keys through
    their own, over a gathered view whose keys carry their positions
    (:func:`gather_ring_dense`).

    q: (B, Cq, H, hd); k_dense/v_dense: (B, T, KV, hd); positions: (B,
    Cq) int32; key_pos: (B, T) int32. Query ``(b, i)`` reads keys with
    ``positions[b, i] - window < key_pos <= positions[b, i]``. GQA by
    head repeat, fp32 softmax (:func:`chunk_attend`'s numerics).
    Returns (B, Cq, H, hd)."""
    h, hd = q.shape[2:]
    rep = h // k_dense.shape[2]
    k = jnp.repeat(k_dense, rep, axis=2)
    v = jnp.repeat(v_dense, rep, axis=2)
    scores = jnp.einsum("bchd,bthd->bhct", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    kp, qp = key_pos[:, None, :], positions[:, :, None]
    mask = jnp.logical_and(kp <= qp, kp > qp - window)
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhct,bthd->bchd", probs, v)


def _greedy_step(rem: int, bs) -> tuple:
    """One step of the greedy cover over sorted buckets ``bs``: the
    largest bucket that fits in ``rem`` whole, else the smallest one
    that covers it, padded."""
    fit = [b for b in bs if b <= rem]
    if fit:
        return fit[-1], fit[-1]
    return next(b for b in bs if b >= rem), rem


def _greedy_cover(rem: int, bs) -> list:
    """The greedy steps that cover ``rem``."""
    out = []
    while rem > 0:
        out.append(_greedy_step(rem, bs))
        rem -= out[-1][1]
    return out


def padded_up(bucket: int, valid: int, buckets) -> bool:
    """Whether a chunk ``(bucket, valid)`` of :func:`plan_chunks` took a
    larger bucket than the greedy step would have: its rows stop short
    of the bucket and a smaller bucket fits in them whole."""
    return valid < bucket and min(buckets) <= valid


def plan_chunks(n_tokens: int, buckets) -> list:
    """Deterministic bucket cover of ``n_tokens``, a pure function of
    ``(n_tokens, buckets)``. Returns ``[(bucket, valid), ...]`` with
    ``sum(valid) == n_tokens``; only the last chunk is padded.

    At each step, with ``rem`` tokens left, the GREEDY step is the
    largest bucket that fits, else the smallest bucket covering the
    remainder (padded). A chunk program pays a fixed price whatever its
    rows (the weights' read, a launch a layer), so where the greedy
    cover ``G`` of ``rem`` takes THREE OR MORE programs and the
    smallest bucket ``B >= rem`` has at most a third more rows than
    they (``3 B <= 4 rows(G)``), the one padded chunk ``(B, rem)``
    takes their place; a tail of two programs stays two. With
    ``(128, 512)``: a tail of 257-511 tokens is one 512-row program,
    one of 1-256 is one or two 128-row programs as before.

    The rule reads sizes alone, step by step, so
    ``plan_chunks(n, b)[i:]`` is ``plan_chunks`` of what is left after
    ``i`` chunks: a stream that asks for its next chunk by the tokens
    left walks this cover, and the resume path re-prefills through the
    SAME sequence for the same length, which is what makes preemption
    recovery deterministic."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
    bs = sorted(set(int(b) for b in buckets))
    if not bs or bs[0] < 1:
        raise ValueError(f"buckets must be positive ints, got {buckets}")
    out = []
    rem = int(n_tokens)
    while rem > 0:
        step = _greedy_step(rem, bs)
        if step[0] < rem <= bs[-1]:       # a tail of several programs
            b = next(x for x in bs if x >= rem)
            g = _greedy_cover(rem, bs)
            if len(g) >= 3 and 3 * b <= 4 * sum(x for x, _ in g):
                step = (b, rem)
        out.append(step)
        rem -= step[1]
    return out


def chunk_write_ids(positions, table_row, valid, wfrom, *, page: int):
    """Scatter targets for one chunk's K/V tokens.

    ``positions``: (C,) int32 global positions of the chunk tokens;
    ``table_row``: (p_max,) int32 — the slot's block-table row;
    ``valid``: scalar — tokens past it are bucket padding;
    ``wfrom``: scalar — positions below it are already resident
    (prefix-shared pages another request may be attending; rewriting
    them with this prefill's floats has no cross-shape bit-exactness
    guarantee, so they are never re-blitted).

    Returns ``(pids, offsets)``: padding / resident positions map to
    the reserved scratch page (id 0) — their writes are garbage the
    masks hide; real positions map to ``table_row[pos // page]``.
    The rule as a row scatter would apply it: the megakernel's chunk
    codes encode it, and ``PagedKVCache.write_chunk`` applies it a
    whole page at a time (a row scatter into the pool costs a relayout
    of the pool; ``tests/test_paged_decode.py`` holds the two equal).
    """
    c = positions.shape[0]
    i = jnp.arange(c, dtype=jnp.int32)
    row = jnp.clip(positions // page, 0, table_row.shape[0] - 1)
    writable = jnp.logical_and(i < valid, positions >= wfrom)
    pids = jnp.where(writable, table_row[row], SCRATCH_PAGE)
    return pids, positions % page


def chunk_row_codes(start: int, bucket: int, valid, wfrom):
    """Sign-encoded per-row positions for ONE megakernel prefill chunk
    (host-side numpy — the codes ride the chunk step as data, so the
    trace is keyed only on the bucket length).

    The encoding packs :func:`chunk_write_ids`'s write rule and
    :func:`chunk_attend`'s mask positions into one (bucket,) int32
    vector (decoded in-kernel by ``megakernel.kernels._chunk_apos``):
    row i's global position is ``start + i``; rows ``>= valid`` are
    bucket padding (code ``-1`` — dead); positions ``< wfrom`` are
    already resident (prefix-shared pages — attend-only, code
    ``-(pos + 2)``, never re-blitted); the rest write + attend at
    their position (code ``pos``).
    """
    import numpy as np

    i = np.arange(int(bucket), dtype=np.int64)
    pos = int(start) + i
    codes = np.where(pos >= int(wfrom), pos, -(pos + 2))
    codes = np.where(i < int(valid), codes, -1)
    return codes.astype(np.int32)


def chunk_attend(q, k_dense, v_dense, positions):
    """Causal chunk attention over a gathered position-major KV view.

    q: (C, H, hd) — the chunk's queries (head-major, this rank's
    heads); k_dense/v_dense: (T, KV, hd) — the slot's pages gathered
    position-major (T = p_max·page; positions past the written region
    are garbage the mask hides); positions: (C,) int32 global query
    positions. Query ``i`` attends keys at positions
    ``<= positions[i]`` — exactly the monolithic causal mask restricted
    to this chunk's rows, so chunk boundaries are invisible to the
    math. GQA by head repeat; fp32 softmax (the :func:`tp_attn.sdpa`
    numerics). Returns (C, H, hd).
    """
    c, h, hd = q.shape
    t, kvh, _ = k_dense.shape
    rep = h // kvh
    k = jnp.repeat(k_dense, rep, axis=1)      # (T, H, hd)
    v = jnp.repeat(v_dense, rep, axis=1)
    scores = jnp.einsum("chd,thd->hct", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    mask = jnp.arange(t, dtype=jnp.int32)[None, :] <= positions[:, None]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hct,thd->chd", probs, v)


def block_attend(q, k_dense, v_dense, lens, live):
    """Causal K-token VERIFICATION attention over gathered page views —
    :func:`chunk_attend` generalized from one slot's chunk to the whole
    fixed-shape decode batch, one K-candidate block per slot (the
    speculative-decode verification dispatch's attention).

    q: (S, K, H, hd) — K candidate queries per slot (head-major, this
    rank's heads); k_dense/v_dense: (S, T, KV, hd) — each slot's pages
    gathered position-major, candidate K/V already appended at
    ``lens[s]..lens[s]+K-1`` (positions past that are garbage the mask
    hides); lens: (S,) int32 pre-block lengths; live: (S,) int32 0/1.
    Query j of a live slot attends positions ``< lens[s]+j+1`` — its
    paged history plus the candidate prefix through itself, exactly
    the mask a sequential decode of those candidates would apply, so
    accepted tokens are token-exact with non-speculative decode.
    Parked slots clamp to 1 (garbage the scheduler ignores).
    Returns (S, K, H, hd).

    Delegates to :func:`tp_attn.sdpa`'s per-query ``(B, Sq)`` kv_len
    form — the one masked-attention implementation the decode step
    already uses, so verification shares its numerics exactly.
    """
    from triton_dist_tpu.layers.tp_attn import sdpa

    kq = q.shape[1]
    kv_len = jnp.maximum(
        lens[:, None] + live[:, None]
        * (jnp.arange(kq, dtype=jnp.int32)[None] + 1), 1)
    return sdpa(q, k_dense, v_dense, causal=False, kv_len=kv_len,
                use_flash=False)
