"""Paged split-KV flash decode tests (kernel form).

Oracle: dense-cache attention (``flash_decode_ref``), the reference's
torch oracle pattern for ``gqa_fwd_batch_decode`` (paged, ragged
lengths, shuffled page tables).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.flash_decode import flash_decode_ref
from triton_dist_tpu.ops.paged_flash_decode import (
    paged_flash_decode, paged_flash_decode_ref,
)
from triton_dist_tpu.utils.testing import pool_copies, spmd

N = 8          # ranks
B = 2          # batch
PAGE = 8       # tokens per page
P_MAX = 2      # pages per (rank, sequence)
KVH = 2        # kv heads
REP = 2        # GQA ratio → H = 4
HD = 8         # head dim
H = KVH * REP
SHARD = PAGE * P_MAX
T = N * SHARD  # global max context


def _build(seed, n_ranks, dense=None):
    """Dense cache + per-rank shuffled page pools covering it."""
    rng = np.random.RandomState(seed)
    if dense is None:
        k_dense = rng.randn(B, T, KVH, HD).astype(np.float32)
        v_dense = rng.randn(B, T, KVH, HD).astype(np.float32)
    else:
        k_dense, v_dense = dense
    num_pages = B * P_MAX
    kp = np.zeros((n_ranks, num_pages, KVH, PAGE, HD), np.float32)
    vp = np.zeros_like(kp)
    tbl = np.zeros((n_ranks, B, P_MAX), np.int32)
    for r in range(n_ranks):
        perm = rng.permutation(num_pages)
        slot = 0
        for b in range(B):
            for p in range(P_MAX):
                pid = perm[slot]; slot += 1
                lo = r * SHARD + p * PAGE
                kp[r, pid] = k_dense[b, lo:lo + PAGE].transpose(1, 0, 2)
                vp[r, pid] = v_dense[b, lo:lo + PAGE].transpose(1, 0, 2)
                tbl[r, b, p] = pid
    return k_dense, v_dense, kp, vp, tbl


def test_paged_decode_single_rank():
    """1 rank: paged kernel == dense oracle on ragged lengths."""
    k_dense, v_dense, kp, vp, tbl = _build(0, 1)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, HD))
    kv_len = jnp.array([SHARD - 3, 5], jnp.int32)

    out = jax.jit(lambda *a: paged_flash_decode(*a))(
        q, jnp.asarray(kp[0]), jnp.asarray(vp[0]),
        jnp.asarray(tbl[0]), kv_len)
    want = flash_decode_ref(q, jnp.asarray(k_dense[:, :SHARD]),
                            jnp.asarray(v_dense[:, :SHARD]), kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_8_ranks_ragged(tp8_mesh, tp8_ctx):
    """8 ranks: KV sharded by position; ragged global lengths hit
    different subsets of ranks (some ranks fully masked)."""
    k_dense, v_dense, kp, vp, tbl = _build(2, N)
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, HD))
    # Batch 0 spans ~6.5 shards; batch 1 only 1.5 (ranks 2..7 masked).
    kv_len = jnp.array([6 * SHARD + 5, SHARD + PAGE - 2], jnp.int32)

    def run(kp_r, vp_r, tbl_r, q_, len_):
        return paged_flash_decode(q_, kp_r[0], vp_r[0], tbl_r[0], len_,
                                  ctx=tp8_ctx, axis="tp")

    f = spmd(tp8_mesh, run,
             (P("tp", None, None, None, None),
              P("tp", None, None, None, None),
              P("tp", None, None), P(None, None, None), P(None)),
             P(None, None, None))
    out = f(jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl), q,
            kv_len)
    want = flash_decode_ref(q, jnp.asarray(k_dense),
                            jnp.asarray(v_dense), kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_ragged_final_page():
    """Serving edge: a slot whose length ends mid-page (neither at a
    page boundary nor filling its final table entry)."""
    k_dense, v_dense, kp, vp, tbl = _build(10, 1)
    q = jax.random.normal(jax.random.PRNGKey(11), (B, H, HD))
    # Batch 0: one full page + 3 tokens into the ragged final page;
    # batch 1: 1 token (first page barely started).
    kv_len = jnp.array([PAGE + 3, 1], jnp.int32)
    out = jax.jit(lambda *a: paged_flash_decode(*a))(
        q, jnp.asarray(kp[0]), jnp.asarray(vp[0]),
        jnp.asarray(tbl[0]), kv_len)
    want = flash_decode_ref(q, jnp.asarray(k_dense[:, :SHARD]),
                            jnp.asarray(v_dense[:, :SHARD]), kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_zero_length_slot():
    """A freed/parked batch slot (kv_len 0) must stay finite and not
    perturb live rows — the fixed-shape serving batch's empty lane."""
    k_dense, v_dense, kp, vp, tbl = _build(12, 1)
    q = jax.random.normal(jax.random.PRNGKey(13), (B, H, HD))
    kv_len = jnp.array([0, PAGE + 2], jnp.int32)
    out = np.asarray(jax.jit(lambda *a: paged_flash_decode(*a))(
        q, jnp.asarray(kp[0]), jnp.asarray(vp[0]),
        jnp.asarray(tbl[0]), kv_len))
    assert np.isfinite(out).all(), "parked slot produced non-finite"
    want = flash_decode_ref(q, jnp.asarray(k_dense[:, :SHARD]),
                            jnp.asarray(v_dense[:, :SHARD]), kv_len)
    np.testing.assert_allclose(out[1], np.asarray(want)[1],
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_freed_and_reused_slot():
    """Recycling: free batch 0's pages, hand the SAME pool slots to a
    new sequence (new contents, new table) — results must track only
    the table, with no leakage from the freed request's data."""
    rng = np.random.RandomState(14)
    k_dense, v_dense, kp, vp, tbl = _build(14, 1)
    q = jax.random.normal(jax.random.PRNGKey(15), (B, H, HD))
    kv_len = jnp.array([SHARD - 2, SHARD - 5], jnp.int32)
    f = jax.jit(lambda kp_, vp_, tbl_: paged_flash_decode(
        q, kp_, vp_, tbl_, kv_len))
    o1 = np.asarray(f(jnp.asarray(kp[0]), jnp.asarray(vp[0]),
                      jnp.asarray(tbl[0])))

    # "Free" batch 0's pages and re-fill those pool slots with a new
    # request's KV (batch 0 becomes a fresh sequence in-place).
    k_new = rng.randn(SHARD, KVH, HD).astype(np.float32)
    v_new = rng.randn(SHARD, KVH, HD).astype(np.float32)
    kp2, vp2 = kp.copy(), vp.copy()
    for p in range(P_MAX):
        pid = tbl[0, 0, p]
        kp2[0, pid] = k_new[p * PAGE:(p + 1) * PAGE].transpose(1, 0, 2)
        vp2[0, pid] = v_new[p * PAGE:(p + 1) * PAGE].transpose(1, 0, 2)
    o2 = np.asarray(f(jnp.asarray(kp2[0]), jnp.asarray(vp2[0]),
                      jnp.asarray(tbl[0])))
    want0 = flash_decode_ref(q[0:1], jnp.asarray(k_new[None]),
                             jnp.asarray(v_new[None]), kv_len[0:1])
    np.testing.assert_allclose(o2[0], np.asarray(want0)[0],
                               rtol=2e-4, atol=2e-4)
    # Batch 1 (untouched pages) is bit-identical across the reuse.
    np.testing.assert_array_equal(o1[1], o2[1])


def test_paged_decode_longer_than_table_row_raises():
    """A request longer than one block-table row (kv_len beyond
    p_max·page) must fail loudly, naming the offending slot."""
    _, _, kp, vp, tbl = _build(16, 1)
    q = jax.random.normal(jax.random.PRNGKey(17), (B, H, HD))
    kv_len = jnp.array([SHARD + 1, 3], jnp.int32)
    with pytest.raises(ValueError, match="slot 0.*table row"):
        paged_flash_decode(q, jnp.asarray(kp[0]), jnp.asarray(vp[0]),
                           jnp.asarray(tbl[0]), kv_len)


def test_paged_decode_ref_matches_kernel():
    """The XLA gather oracle (the serving engine's attn_impl='ref')
    agrees with the Pallas kernel on ragged lengths."""
    _, _, kp, vp, tbl = _build(18, 1)
    q = jax.random.normal(jax.random.PRNGKey(19), (B, H, HD))
    kv_len = jnp.array([SHARD - 3, PAGE + 1], jnp.int32)
    out = jax.jit(lambda *a: paged_flash_decode(*a))(
        q, jnp.asarray(kp[0]), jnp.asarray(vp[0]),
        jnp.asarray(tbl[0]), kv_len)
    ref = paged_flash_decode_ref(q, jnp.asarray(kp[0]),
                                 jnp.asarray(vp[0]),
                                 jnp.asarray(tbl[0]), kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# quantized pools (int8/fp8 per-page scales)
# ---------------------------------------------------------------------------

def _quantize_pool(kp, vp, qdtype, qmax):
    """Whole-page max-abs quantization of a (N, KV, page, hd) pool →
    (k_q, v_q, k_scale, v_scale) — the write_prompt blit's math."""
    ks = np.abs(kp).max(axis=(2, 3)) / qmax
    vs = np.abs(vp).max(axis=(2, 3)) / qmax
    ks = np.where(ks > 0, ks, 1.0).astype(np.float32)
    vs = np.where(vs > 0, vs, 1.0).astype(np.float32)
    kq = kp / ks[:, :, None, None]
    vq = vp / vs[:, :, None, None]
    if qdtype == jnp.int8:
        kq, vq = np.round(kq), np.round(vq)
    return (jnp.asarray(kq).astype(qdtype), jnp.asarray(vq).astype(qdtype),
            jnp.asarray(ks), jnp.asarray(vs))


@pytest.mark.parametrize("qdtype,qmax,tol", [
    (jnp.int8, 127.0, 5e-2),
    (jnp.float8_e4m3fn, 448.0, 2e-1),
])
def test_paged_decode_quantized_fused_dequant(qdtype, qmax, tol):
    """int8/fp8 pools through the kernel's FUSED page-prefetch dequant
    == the dequantizing gather oracle (float-exact), and both within
    the quantization tolerance of the fp32 ground truth."""
    k_dense, v_dense, kp, vp, tbl = _build(30, 1)
    kq, vq, ks, vs = _quantize_pool(kp[0], vp[0], qdtype, qmax)
    q = jax.random.normal(jax.random.PRNGKey(31), (B, H, HD))
    kv_len = jnp.array([SHARD - 3, PAGE + 1], jnp.int32)
    out = jax.jit(lambda *a: paged_flash_decode(
        *a, k_scale=ks, v_scale=vs))(q, kq, vq, jnp.asarray(tbl[0]),
                                     kv_len)
    ref = paged_flash_decode_ref(q, kq, vq, jnp.asarray(tbl[0]),
                                 kv_len, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    exact = flash_decode_ref(q, jnp.asarray(k_dense[:, :SHARD]),
                             jnp.asarray(v_dense[:, :SHARD]), kv_len)
    assert np.abs(np.asarray(out) - np.asarray(exact)).max() < tol


def test_quantized_ragged_final_page_scale():
    """A slot ending mid-page: the ragged final page's scale comes
    from its VALID tokens (zero padding never inflates it), so the
    partial page reconstructs as accurately as a full one."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    rng = np.random.RandomState(40)
    c = PagedKVCache.empty(1, 4, PAGE, KVH, HD, num_slots=1, p_max=2,
                           kv_dtype="int8")
    import dataclasses
    c = dataclasses.replace(
        c, block_table=jnp.asarray([[1, 2]], jnp.int32),
        live=jnp.ones((1,), jnp.int32))
    # 3 tokens of a tiny magnitude — if padding (or stale garbage)
    # leaked into the scale, round(x/scale) would collapse to zero.
    toks = 1e-3 * rng.randn(3, KVH, HD).astype(np.float32)
    for t in range(3):
        c = c.append_decode(0, jnp.asarray(toks[t][None, None]),
                            jnp.asarray(toks[t][None, None]))
        c = c.advance()
    kd, _ = c.dense_layer(0)
    err = np.abs(np.asarray(kd)[0, :3] - toks).max()
    assert err < 1e-3 * 2 / 127, f"ragged-page scale inflated: {err}"


def test_quantized_freed_and_reused_page_fresh_scale():
    """Pool-slot recycling: a page that held LARGE values, freed and
    reused by a small-valued sequence, must re-quantize under a fresh
    scale — no precision inherited from the dead request."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    rng = np.random.RandomState(41)
    import dataclasses
    c = PagedKVCache.empty(1, 3, PAGE, KVH, HD, num_slots=1, p_max=1,
                           kv_dtype="int8")
    c = dataclasses.replace(
        c, block_table=jnp.asarray([[1]], jnp.int32),
        live=jnp.ones((1,), jnp.int32))
    big = 100.0 * rng.randn(1, 1, KVH, HD).astype(np.float32)
    c = c.append_decode(0, jnp.asarray(big), jnp.asarray(big)).advance()
    big_scale = float(np.asarray(c.k_scale)[0, 1].max())
    # "Free" the slot: lens reset to 0, same pool page reused.
    c = dataclasses.replace(c, lens=jnp.zeros((1,), jnp.int32))
    small = 1e-2 * rng.randn(1, 1, KVH, HD).astype(np.float32)
    c = c.append_decode(0, jnp.asarray(small),
                        jnp.asarray(small)).advance()
    new_scale = float(np.asarray(c.k_scale)[0, 1].max())
    assert new_scale < big_scale / 100, (new_scale, big_scale)
    kd, _ = c.dense_layer(0)
    err = np.abs(np.asarray(kd)[0, 0] - small[0, 0]).max()
    assert err < 1e-2 * 2 / 127, f"stale scale survived reuse: {err}"


def test_quantized_pool_scaleless_reader_fails_loudly():
    """A quantized pool handed to a bf16-era reader (no scales — e.g.
    a prefix page shared across mismatched kv_dtype configs) raises
    instead of attending raw quantized bytes."""
    _, _, kp, vp, tbl = _build(42, 1)
    kq, vq, ks, vs = _quantize_pool(kp[0], vp[0], jnp.int8, 127.0)
    q = jax.random.normal(jax.random.PRNGKey(43), (B, H, HD))
    kv_len = jnp.array([PAGE, 2], jnp.int32)
    with pytest.raises(ValueError, match="QUANTIZED pool"):
        paged_flash_decode(q, kq, vq, jnp.asarray(tbl[0]), kv_len)
    with pytest.raises(ValueError, match="QUANTIZED pool"):
        paged_flash_decode_ref(q, kq, vq, jnp.asarray(tbl[0]), kv_len)
    # And the reverse mismatch: scales with an unquantized pool.
    with pytest.raises(ValueError, match="unquantized"):
        paged_flash_decode(q, jnp.asarray(kp[0]), jnp.asarray(vp[0]),
                           jnp.asarray(tbl[0]), kv_len,
                           k_scale=ks, v_scale=vs)


def test_paged_decode_page_shuffle_invariance():
    """The block table fully decouples pool layout from positions: two
    different pool permutations give identical results."""
    k_dense, v_dense, kp1, vp1, tbl1 = _build(4, 1)
    # Pool 2: same dense cache, different page permutation.
    _, _, kp2, vp2, tbl2 = _build(5, 1, dense=(k_dense, v_dense))
    q = jax.random.normal(jax.random.PRNGKey(6), (B, H, HD))
    kv_len = jnp.array([SHARD, SHARD - 7], jnp.int32)
    f = jax.jit(lambda kp, vp, tbl: paged_flash_decode(
        q, kp, vp, tbl, kv_len))
    o1 = f(jnp.asarray(kp1[0]), jnp.asarray(vp1[0]), jnp.asarray(tbl1[0]))
    o2 = f(jnp.asarray(kp2[0]), jnp.asarray(vp2[0]), jnp.asarray(tbl2[0]))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the pool keeps one layout (PR 26): the kernels take every layer's pool
# whole, the writers update it in place, and the compiled step programs
# hold no copy of the pool or of a layer
# ---------------------------------------------------------------------------

def _layer_pool_call(kernel, quantized):
    """One paged kernel on a two-layer pool: → call(pool_k, pool_v,
    layer, k_scale, v_scale) and the arguments of the 4-D form."""
    from triton_dist_tpu.ops.paged_flash_qblock import paged_flash_qblock

    rng = np.random.RandomState(40)
    kp = rng.randn(2, B * P_MAX + 1, KVH, PAGE, HD).astype(np.float32)
    vp = rng.randn(*kp.shape).astype(np.float32)
    tbl = jnp.asarray(
        1 + rng.permutation(B * P_MAX).reshape(B, P_MAX), jnp.int32)
    if quantized:
        parts = [_quantize_pool(kp[l], vp[l], jnp.int8, 127.0)
                 for l in range(2)]
        kp, vp, ks, vs = (jnp.stack([p[i] for p in parts])
                          for i in range(4))
    else:
        kp, vp = (jnp.asarray(a, jnp.bfloat16) for a in (kp, vp))
        ks = vs = None
    if kernel == "decode":
        q = jax.random.normal(jax.random.PRNGKey(41), (B, H, HD))
        arg = jnp.array([SHARD - 3, PAGE + 1], jnp.int32)      # kv_len
        fn = lambda k, v, **kw: paged_flash_decode(
            q, k, v, tbl, arg, axis=None, **kw)
    else:
        q = jax.random.normal(jax.random.PRNGKey(41), (B, 3, H, HD))
        arg = jnp.array([[2, 3, 4], [PAGE - 1, PAGE, PAGE + 1]],
                        jnp.int32)                             # positions
        fn = lambda k, v, **kw: paged_flash_qblock(q, k, v, tbl, arg, **kw)
    return fn, kp, vp, ks, vs


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "qblock"])
def test_kernel_on_whole_pool_equals_layer_slice(kernel, quantized):
    """The 5-D (L, N, KV, page, hd) pool with a ``layer`` reads the
    same pages as the 4-D call on ``pool[layer]``: bit for bit (layer 1
    of two layers of random pages, so layer 0 would show). The layer is
    data to the kernel: a Python int and a traced scalar read alike."""
    fn, kp, vp, ks, vs = _layer_pool_call(kernel, quantized)
    layer = 1
    scales = ({} if ks is None
              else dict(k_scale=ks[layer], v_scale=vs[layer]))
    whole = jax.jit(lambda k, v: fn(k, v, layer=layer, **scales))(kp, vp)
    traced = jax.jit(lambda k, v, li: fn(k, v, layer=li, **scales))(
        kp, vp, jnp.int32(layer))
    sliced = jax.jit(lambda k, v: fn(k, v, **scales))(kp[layer], vp[layer])
    assert np.array_equal(np.asarray(whole, np.float32),
                          np.asarray(sliced, np.float32))
    assert np.array_equal(np.asarray(traced, np.float32),
                          np.asarray(sliced, np.float32))


def test_whole_pool_needs_a_layer():
    fn, kp, vp, _, _ = _layer_pool_call("decode", False)
    with pytest.raises(ValueError, match="needs a layer"):
        fn(kp, vp)
    with pytest.raises(ValueError, match="only the whole 5-D pool"):
        fn(kp[0], vp[0], layer=0)


# -- the writers: in place, and bit-identical to the scatter they replace --

W_PAGE, W_PMAX, W_SLOTS, W_KV, W_HD = 4, 3, 3, 2, 8


def _write_cache(lens, tables, live):
    """A two-layer bf16 pool of random bytes (so a write to the wrong
    layer or page shows), page 0 the scratch page."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    rng = np.random.RandomState(50)
    shape = (2, 1 + W_SLOTS * W_PMAX, W_KV, W_PAGE, W_HD)
    return PagedKVCache(
        k_pages=jnp.asarray(rng.randn(*shape), jnp.bfloat16),
        v_pages=jnp.asarray(rng.randn(*shape), jnp.bfloat16),
        block_table=jnp.asarray(tables, jnp.int32),
        lens=jnp.asarray(lens, jnp.int32),
        live=jnp.asarray(live, jnp.int32))


def _scatter_rows(cache, layer, pids, off, k_rows, v_rows):
    """The plain reference: the row scatter the writers were before."""
    return dataclasses.replace(
        cache,
        k_pages=cache.k_pages.at[layer, pids, :, off, :].set(
            k_rows.astype(cache.k_pages.dtype)),
        v_pages=cache.v_pages.at[layer, pids, :, off, :].set(
            v_rows.astype(cache.v_pages.dtype)))


def _ref_append(cache, layer, k_tok, v_tok, budget=None):
    page, p_max = cache.page, cache.block_table.shape[1]
    k = k_tok.shape[1]
    pos = cache.lens[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
    valid = pos // page < p_max
    if budget is not None:
        valid &= jnp.arange(k, dtype=jnp.int32)[None] < budget[:, None]
    rows = jnp.clip(pos // page, 0, p_max - 1)
    pids = jnp.where(
        valid, jnp.take_along_axis(cache.block_table, rows, axis=1), 0)
    return _scatter_rows(cache, layer, pids, pos % page, k_tok, v_tok)


def _ref_chunk(cache, layer, k_tok, v_tok, table_row, positions, valid,
               wfrom):
    from triton_dist_tpu.ops.chunked_prefill import chunk_write_ids

    pids, off = chunk_write_ids(positions, table_row, valid, wfrom,
                                page=cache.page)
    return _scatter_rows(cache, layer, pids, off, k_tok[:, 0], v_tok[:, 0])


_TABLES = [[1, 2, 3], [4, 5, 6], [7, 8, 9]]
_PARKED = [[1, 2, 3], [0, 0, 0], [7, 8, 9]]
_WRITES = {
    # name: (writer, lens, tables, live, arguments of the writer)
    "decode-parked-slot": ("decode", [5, 0, 2], _PARKED, [1, 0, 1], {}),
    "decode-two-parked-slots": (
        "decode", [0, 0, 7], [[0, 0, 0], [0, 0, 0], [7, 8, 9]],
        [0, 0, 1], {}),
    "decode-page-boundary": ("decode", [3, 4, 8], _TABLES, [1, 1, 1], {}),
    "block-crosses-a-page": ("block", [2, 3, 7], _TABLES, [1, 1, 1], {}),
    "block-budget": ("block", [2, 3, 7], _TABLES, [1, 1, 1],
                     {"budget": [1, 3, 0]}),
    "block-past-the-table-row": ("block", [10, 11, 0], _PARKED, [1, 0, 1],
                                 {"budget": [3, 3, 3]}),
    "chunk-ends-inside-a-page": ("chunk", None, _TABLES, None,
                                 dict(start=0, valid=6, wfrom=0)),
    "chunk-valid-below-bucket": ("chunk", None, _TABLES, None,
                                 dict(start=4, valid=1, wfrom=0)),
    "chunk-wfrom-on-a-shared-page": ("chunk", None, _TABLES, None,
                                     dict(start=0, valid=8, wfrom=6)),
    "chunk-unaligned-start": ("chunk", None, _TABLES, None,
                              dict(start=3, valid=7, wfrom=5)),
    "chunk-padding-past-the-table-row": ("chunk", None, _TABLES, None,
                                         dict(start=8, valid=4, wfrom=0)),
    "chunk-nothing-to-write": ("chunk", None, _TABLES, None,
                               dict(start=0, valid=0, wfrom=0)),
}


@pytest.mark.parametrize("name", list(_WRITES))
def test_writers_equal_the_row_scatter(name):
    """``append_decode``, ``append_block`` and ``write_chunk`` leave
    every page but the scratch page bit-identical to the
    ``.at[layer, pids, :, off, :].set`` form they replace (the scratch
    page holds what nobody reads: padding, parked slots, rows past a
    budget). Two layers, written at layer 1, then at layer 0."""
    writer, lens, tables, live, kw = _WRITES[name]
    cache = _write_cache(lens if lens is not None else [0] * W_SLOTS,
                         tables, live if live is not None else [1] * W_SLOTS)
    rng = np.random.RandomState(51)
    n_tok = {"decode": 1, "block": 3, "chunk": 8}[writer]
    shape = ((n_tok, 1, W_KV, W_HD) if writer == "chunk"
             else (W_SLOTS, n_tok, W_KV, W_HD))
    k_tok, v_tok = (jnp.asarray(rng.randn(*shape), jnp.float32)
                    for _ in range(2))

    def run(ref):
        def step(cache):
            for layer in (1, 0):
                if writer == "chunk":
                    row = cache.block_table[1]
                    pos = kw["start"] + jnp.arange(n_tok, dtype=jnp.int32)
                    fn = _ref_chunk if ref else type(cache).write_chunk
                    cache = fn(cache, layer, k_tok, v_tok, row, pos,
                               jnp.int32(kw["valid"]), jnp.int32(kw["wfrom"]))
                elif ref:
                    budget = kw.get("budget")
                    cache = _ref_append(
                        cache, layer, k_tok, v_tok,
                        None if budget is None
                        else jnp.asarray(budget, jnp.int32))
                elif writer == "decode":
                    cache = cache.append_decode(layer, k_tok, v_tok)
                else:
                    budget = kw.get("budget")
                    cache = cache.append_block(
                        layer, k_tok, v_tok,
                        None if budget is None
                        else jnp.asarray(budget, jnp.int32))
            return cache.k_pages, cache.v_pages
        return jax.jit(step)(cache)

    for got, want, before in zip(run(False), run(True),
                                 (cache.k_pages, cache.v_pages)):
        got, want, before = (np.asarray(a[:, 1:]).view(np.uint16)
                             for a in (got, want, before))
        assert np.array_equal(got, want)
        if "nothing" not in name:
            assert not np.array_equal(got, before)


# -- the compiled step programs: no copy of the pool, none of a layer ------

@pytest.fixture(scope="module")
def v5e():
    """libtpu's description of a v5e:2x2 (nothing attached, nothing
    run), or skip. Inside the fixture: only the worker that is given one
    of these tests may load the TPU's library."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — libtpu says why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("program", ["decode", "chunk-128", "chunk-512",
                                     "verify", "fused-128", "fused-512"])
def test_compiled_step_keeps_the_pool_in_place(v5e, program, monkeypatch):
    """Each paged step of ``models.dense``, lowered for one v5e chip
    as the serving engine jits it (pool donated, output shardings
    pinned), compiles to a program whose entry computation neither
    relayouts the pool nor cuts a layer out of it, and whose
    temporaries are smaller than one layer (PR 26: they were the pool
    twice), and which holds each Pallas kernel once, not once a layer.
    ``fused-*`` is the chunk program with the decode batch
    aboard (``chunk_decode_paged``): two writers into the pool a layer,
    the case in which XLA's layout assignment turned before. Compile
    only: nothing runs, nothing is timed."""
    import triton_dist_tpu as tdt
    from jax.sharding import NamedSharding
    from triton_dist_tpu.models import ModelConfig, dense
    from triton_dist_tpu.serving.blocks import PagedKVCache, pool_shardings
    from triton_dist_tpu.utils import distributed

    # Lower the kernels for Mosaic, as on the chip, not for the
    # interpreter this process's CPU backend would choose.
    monkeypatch.setattr(distributed, "platform", lambda: "tpu")
    cfg = ModelConfig(vocab_size=1024, hidden_size=512,
                      intermediate_size=1024, num_hidden_layers=2,
                      num_attention_heads=8, num_key_value_heads=4,
                      head_dim=128, attention_bias=True, qk_norm=False)
    # A pool larger than the chip's 128 MiB of VMEM: a small one XLA
    # prefetches there whole, which no serving pool gives it room for.
    pages, page, slots, p_max, spec_k = 1025, 128, 4, 8, 4
    mesh = tdt.make_mesh(tp=1, devices=v5e.devices[:1])
    axis, dt = "tp", jnp.bfloat16

    def on_mesh(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=(
                    s if isinstance(s, NamedSharding)
                    else NamedSharding(mesh, s))),
            tree, specs, is_leaf=lambda s: isinstance(s, P))

    specs = dense.param_specs(cfg, axis)
    params = on_mesh(jax.eval_shape(
        lambda: dense.init_params(jax.random.PRNGKey(0), cfg, dt)), specs)
    kv_spec = dense.paged_cache_specs(axis)
    kv_sh = pool_shardings(mesh, kv_spec)
    cache = on_mesh(jax.eval_shape(lambda: PagedKVCache.empty(
        cfg.num_hidden_layers, pages, page, cfg.num_key_value_heads,
        cfg.head_dim, num_slots=slots, p_max=p_max, dtype=dt)), kv_sh)
    ints = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=NamedSharding(mesh, P()))
    kw = dict(mode="fused", axis=axis, attn_impl="flash",
              ctxs=dense.make_fwd_contexts(
                  tdt.MeshContext.from_mesh(mesh), axis))

    if program == "decode":
        step = lambda p, t, c: dense.decode_step_paged(p, t, c, cfg, **kw)
        in_specs, out_specs = (specs, P(None), kv_spec), (P(None, None),
                                                          kv_spec)
        args, donate = (params, ints(slots), cache), 2
    elif program == "verify":
        step = lambda p, t, b, c: dense.verify_step_paged(
            p, t, c, cfg, budget=b, **kw)
        in_specs = (specs, P(None, None), P(None), kv_spec)
        out_specs = (P(None, None, None), kv_spec)
        args, donate = (params, ints(slots, spec_k), ints(slots), cache), 3
    elif program.startswith("fused"):
        step = lambda p, t, c, row, start, wfrom, valid, d: (
            dense.chunk_decode_paged(
                p, t, d, c, row, cfg, start=start, wfrom=wfrom,
                valid=valid, decode_attn_impl="flash", **kw))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P(),
                    P(None))
        out_specs = (P(None), P(None, None), kv_spec)
        args = (params, ints(int(program[6:])), cache, ints(p_max),
                ints(), ints(), ints(), ints(slots))
        donate = 2
    else:
        step = lambda p, t, c, row, start, wfrom, valid: (
            dense.prefill_chunk_paged(p, t, c, row, cfg, start=start,
                                      wfrom=wfrom, valid=valid, **kw))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P())
        out_specs = (P(None), kv_spec)
        args = (params, ints(int(program[6:])), cache, ints(p_max),
                ints(), ints(), ints())
        donate = 2
    lowered = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(donate,),
        out_shardings=tuple(NamedSharding(mesh, s)
                            for s in out_specs[:-1]) + (kv_sh,),
    ).lower(*args)
    # The layer is an operand of the kernels, so a program's layers
    # share ONE lowering of each: set-up's seconds are these (a layer
    # baked into the kernel made it one a layer, PR 30).
    assert lowered.as_text().count("tpu_custom_call") == (
        2 if program.startswith("fused") else 1)
    compiled = lowered.compile()

    pool_shape = cache.k_pages.shape
    assert pool_copies(compiled.as_text(), pool_shape) == []
    layer_bytes = 2 * int(np.prod(pool_shape[1:]))
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
