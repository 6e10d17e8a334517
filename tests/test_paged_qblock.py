"""Paged flash Q-BLOCK attention battery (kernel + serving wiring).

The contract under test: ``paged_flash_qblock`` — one Pallas kernel
for BOTH chunked prefill (C consecutive queries of one slot) and
speculative verification (K candidate queries per slot) — agrees with
the gather oracle on every pool dtype and edge shape, and switching
the serving engine to ``attn_impl="flash"`` changes TRAFFIC, never
tokens: greedy outputs stay exact vs ``Engine.serve`` across chunk
boundaries and speculative rollback, and no jit cache grows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.ops.chunked_prefill import gather_pages_dense
from triton_dist_tpu.ops.paged_flash_qblock import (
    paged_flash_qblock, paged_flash_qblock_ref,
)
from triton_dist_tpu.serving import ServingEngine
from triton_dist_tpu.serving.blocks import PagedKVCache

KVH = 2        # kv heads
REP = 2        # GQA ratio -> H = 4
HD = 8         # head dim
PAGE = 8       # tokens per page
P_MAX = 4      # pages per table row
H = KVH * REP
CAP = P_MAX * PAGE

TP = 4
CFG = ModelConfig.tiny()
MAX_LEN = 64
SRV_PAGE = 8


def _build(seed, b, num_pages=None):
    """Random pool + shuffled per-slot tables (page 0 = scratch)."""
    rng = np.random.RandomState(seed)
    num_pages = num_pages or (b * P_MAX + 1)
    kp = rng.randn(num_pages, KVH, PAGE, HD).astype(np.float32)
    vp = rng.randn(num_pages, KVH, PAGE, HD).astype(np.float32)
    perm = 1 + rng.permutation(num_pages - 1)[:b * P_MAX]
    tbl = perm.reshape(b, P_MAX).astype(np.int32)
    return kp, vp, tbl


def _quantize_pool(kp, vp, qdtype, qmax):
    """Whole-page max-abs quantization — the write_prompt blit's math."""
    ks = np.abs(kp).max(axis=(2, 3)) / qmax
    vs = np.abs(vp).max(axis=(2, 3)) / qmax
    ks = np.where(ks > 0, ks, 1.0).astype(np.float32)
    vs = np.where(vs > 0, vs, 1.0).astype(np.float32)
    kq = kp / ks[:, :, None, None]
    vq = vp / vs[:, :, None, None]
    if qdtype == jnp.int8:
        kq, vq = np.round(kq), np.round(vq)
    return (jnp.asarray(kq).astype(qdtype),
            jnp.asarray(vq).astype(qdtype),
            jnp.asarray(ks), jnp.asarray(vs))


def _run_both(q, kp, vp, tbl, pos, scales=()):
    kw = {}
    if scales:
        kw = dict(k_scale=scales[0], v_scale=scales[1])
    out = jax.jit(lambda *a: paged_flash_qblock(*a, **kw))(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tbl), jnp.asarray(pos))
    ref = paged_flash_qblock_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tbl), jnp.asarray(pos), *scales)
    return np.asarray(out), np.asarray(ref)


# ---------------------------------------------------------------------------
# kernel == gather oracle
# ---------------------------------------------------------------------------

def test_qblock_matches_oracle_chunk_and_verify_shapes():
    """Both serving masks through one call: chunk-style consecutive
    positions (one slot mid-prompt) and verify-style lens+j positions,
    ragged across slots."""
    rng = np.random.RandomState(0)
    b, cq = 3, 5
    kp, vp, tbl = _build(1, b)
    q = rng.randn(b, cq, H, HD).astype(np.float32)
    pos = np.zeros((b, cq), np.int32)
    pos[0] = 9 + np.arange(cq)           # chunk at start=9
    pos[1] = 17 + np.arange(cq)          # verify at lens=17
    pos[2] = 2 + np.arange(cq)           # short history
    out, ref = _run_both(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("qdtype,qmax", [
    (jnp.int8, 127.0),
    (jnp.float8_e4m3fn, 448.0),
])
def test_qblock_quantized_fused_dequant(qdtype, qmax):
    """int8/fp8 pools through the kernel's fused page-prefetch dequant
    == the dequantizing gather oracle, and both within quantization
    tolerance of the fp32 ground truth."""
    rng = np.random.RandomState(2)
    b, cq = 2, 4
    kp, vp, tbl = _build(3, b)
    kq, vq, ks, vs = _quantize_pool(kp, vp, qdtype, qmax)
    q = rng.randn(b, cq, H, HD).astype(np.float32)
    pos = np.stack([11 + np.arange(cq), 23 + np.arange(cq)]
                   ).astype(np.int32)
    out, ref = _run_both(q, kq, vq, tbl, pos, scales=(ks, vs))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    exact, _ = _run_both(q, kp, vp, tbl, pos)
    tol = 5e-2 if qdtype == jnp.int8 else 2e-1
    assert np.abs(out - exact).max() < tol


def test_qblock_ragged_final_page():
    """Positions ending mid-page (neither page-aligned nor filling the
    final table entry) mask the page's tail exactly."""
    rng = np.random.RandomState(4)
    b, cq = 2, 3
    kp, vp, tbl = _build(5, b)
    q = rng.randn(b, cq, H, HD).astype(np.float32)
    pos = np.stack([PAGE + np.arange(cq),       # 1 page + partial
                    np.arange(cq)]).astype(np.int32)   # first page only
    out, ref = _run_both(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_qblock_zero_len_parked_slot():
    """A parked slot (positions 0, scratch table row) stays finite and
    never perturbs live rows — the fixed-shape batch's empty lane."""
    rng = np.random.RandomState(6)
    b, cq = 2, 4
    kp, vp, tbl = _build(7, b)
    tbl[1] = 0                            # parked: all-scratch row
    q = rng.randn(b, cq, H, HD).astype(np.float32)
    pos = np.zeros((b, cq), np.int32)
    pos[0] = 13 + np.arange(cq)
    out, ref = _run_both(q, kp, vp, tbl, pos)
    assert np.isfinite(out).all(), "parked slot produced non-finite"
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    # Live row unchanged when the parked slot's queries change.
    q2 = q.copy()
    q2[1] = rng.randn(cq, H, HD)
    out2, _ = _run_both(q2, kp, vp, tbl, pos)
    np.testing.assert_array_equal(out[0], out2[0])


def test_qblock_prefix_shared_pages():
    """Two slots whose tables share leading (prefix) pages: each
    attends the shared bytes plus its own private suffix — results
    match a pool where the prefix is duplicated."""
    rng = np.random.RandomState(8)
    b, cq = 2, 4
    kp, vp, tbl = _build(9, b)
    tbl[1, :2] = tbl[0, :2]               # share the first two pages
    q = rng.randn(b, cq, H, HD).astype(np.float32)
    pos = np.stack([2 * PAGE + 3 + np.arange(cq),
                    3 * PAGE + 1 + np.arange(cq)]).astype(np.int32)
    out, ref = _run_both(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_qblock_position_beyond_capacity_raises():
    """A concrete position beyond one table row's capacity fails
    loudly, naming the slot (same contract as paged_flash_decode)."""
    rng = np.random.RandomState(10)
    kp, vp, tbl = _build(11, 1)
    q = rng.randn(1, 2, H, HD).astype(np.float32)
    pos = np.asarray([[CAP - 1, CAP]], np.int32)
    with pytest.raises(ValueError, match="slot 0.*capacity"):
        paged_flash_qblock(jnp.asarray(q), jnp.asarray(kp),
                           jnp.asarray(vp), jnp.asarray(tbl),
                           jnp.asarray(pos))


def test_qblock_scaleless_quantized_pool_raises():
    """A quantized pool without scales fails loudly in BOTH the kernel
    and the oracle instead of attending raw quantized bytes."""
    kp, vp, tbl = _build(12, 1)
    kq, vq, ks, vs = _quantize_pool(kp, vp, jnp.int8, 127.0)
    q = np.random.RandomState(13).randn(1, 2, H, HD).astype(np.float32)
    pos = np.asarray([[3, 4]], np.int32)
    with pytest.raises(ValueError, match="QUANTIZED pool"):
        paged_flash_qblock(jnp.asarray(q), kq, vq, jnp.asarray(tbl),
                           jnp.asarray(pos))
    with pytest.raises(ValueError, match="QUANTIZED pool"):
        paged_flash_qblock_ref(jnp.asarray(q), kq, vq,
                               jnp.asarray(tbl), jnp.asarray(pos))
    with pytest.raises(ValueError, match="unquantized"):
        paged_flash_qblock(jnp.asarray(q), jnp.asarray(kp),
                           jnp.asarray(vp), jnp.asarray(tbl),
                           jnp.asarray(pos), k_scale=ks, v_scale=vs)


def test_gather_pages_dense_one_definition():
    """The shared gather helper reproduces the PagedKVCache views it
    replaced — one definition for the oracle every paged kernel is
    tested against."""
    kp, vp, tbl = _build(14, 2)
    c = PagedKVCache(
        k_pages=jnp.asarray(kp)[None], v_pages=jnp.asarray(vp)[None],
        block_table=jnp.asarray(tbl),
        lens=jnp.asarray([5, 9], jnp.int32),
        live=jnp.ones((2,), jnp.int32))
    kd, vd = c.dense_layer(0)
    np.testing.assert_array_equal(
        np.asarray(kd),
        np.asarray(gather_pages_dense(jnp.asarray(kp),
                                      jnp.asarray(tbl))))
    kr, _ = c.dense_row(0, jnp.asarray(tbl[1]))
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kd)[1])


# ---------------------------------------------------------------------------
# serving wiring: flash changes traffic, never tokens
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    mesh = Mesh(np.array(jax.devices()[:TP]), ("tp",))
    return Engine(CFG, mesh, mode="xla", max_len=MAX_LEN, seed=3)


def _baseline(engine, prompt, gen_len):
    ids = jnp.asarray(np.tile(np.asarray([prompt], np.int32), (TP, 1)))
    return np.asarray(engine.serve(ids, gen_len=gen_len))[0].tolist()


def test_chunk_boundary_token_exact_flash(engine):
    """Prompt lengths at b-1 / b / b+1 for bucket b through the FLASH
    chunk path: greedy tokens equal the monolithic Engine.serve run
    (chunk boundaries invisible to the math, kernel or gather)."""
    bucket = 8
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(0, CFG.vocab_size, n)]
               for n in (bucket - 1, bucket, bucket + 1)]
    # The boundaries are the prompt's: four tokens after it are enough.
    want = [_baseline(engine, p, 4) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=SRV_PAGE,
                        prefill_buckets=(4, bucket),
                        attn_impl="flash")
    got = srv.generate(prompts, max_new_tokens=4)
    assert got == want
    assert srv.stats()["chunk_attn"] == "flash"


def test_spec_rollback_token_exact_flash(engine):
    """Speculative decode through the FLASH verification kernel:
    rejected draft suffixes roll back page accounting and greedy
    outputs stay bit-identical to Engine.serve — acceptance is data,
    whichever kernel scored it."""
    prompts = [[1, 2, 3, 1, 2, 3], [4, 5], [5, 5, 5]]
    want = [_baseline(engine, p, 6) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=SRV_PAGE, spec_k=4,
                        chunk_attn="flash")
    got = srv.generate(prompts, max_new_tokens=6)
    assert got == want
    st = srv.stats()
    # Mixed accept/reject actually exercised the rollback path.
    assert st["spec"]["drafted"] > st["spec"]["accepted"] > 0
    # Rollback left the pool clean: every page back on the free list.
    frag = st["pool"]
    assert frag["used_pages"] == 0, frag


def test_flash_matches_ref_tokens_quantized(engine):
    """attn_impl='flash' over an int8 pool produces the SAME tokens as
    the gather ref over the same int8 pool — the fused dequant and the
    gather dequant are the same math."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7]]
    kw = dict(num_slots=2, page=SRV_PAGE, prefill_buckets=(4, 8),
              spec_k=3, kv_dtype="int8")
    got_f = ServingEngine(engine, attn_impl="flash", **kw).generate(
        prompts, max_new_tokens=4)
    got_r = ServingEngine(engine, attn_impl="ref", **kw).generate(
        prompts, max_new_tokens=4)
    assert got_f == got_r


def test_no_recompile_gates_with_flash(engine):
    """The serving no-growth gates hold with every flash path active:
    ONE decode(-side) jit entry after warmup and the chunk cache
    bounded by the bucket count — positions ride as data through the
    kernel exactly as through the gather."""
    rng = np.random.RandomState(1)
    srv = ServingEngine(engine, num_slots=2, page=SRV_PAGE,
                        prefill_buckets=(4, 8), spec_k=4,
                        attn_impl="flash")
    # The warm-up meets both buckets (3 -> 4; 9 -> 8 + 4; 13 -> 8 + 8),
    # the verify program and the decode program; the lengths after it
    # are new to all of them.
    prompts = [[int(t) for t in rng.randint(0, CFG.vocab_size, n)]
               for n in (3, 9, 13)]
    srv.generate(prompts, max_new_tokens=4)
    assert srv.decode_cache_size() == 1, srv.decode_cache_size()
    assert srv.prefill_cache_size() <= 2
    more = [[int(t) for t in rng.randint(0, CFG.vocab_size, n)]
            for n in (2, 10)]                    # unseen lengths
    srv.generate(more, max_new_tokens=2)
    assert srv.decode_cache_size() == 1
    assert srv.prefill_cache_size() <= 2


def test_bad_attn_impl_values_raise(engine):
    with pytest.raises(ValueError, match="attn_impl"):
        ServingEngine(engine, num_slots=2, page=SRV_PAGE,
                      attn_impl="pallas")
    with pytest.raises(ValueError, match="chunk_attn"):
        ServingEngine(engine, num_slots=2, page=SRV_PAGE,
                      chunk_attn="kernel")


# ---------------------------------------------------------------------------
# the decode batch rides the chunk's program: one program, the same math
# ---------------------------------------------------------------------------

FUSED_SLOTS = 4
FUSED_BUCKETS = (4, 8)
# (bucket, start, wfrom, valid) of the chunk, and the decode batch's
# live mask: the chunk's own slot (the last) is parked in that batch.
FUSED_CASES = {
    "padded_chunk": (8, 0, 0, 5, (1, 1, 0, 0)),
    # Two pages resident from a prefix hit, the cursor clamped one
    # below them: position 15 is computed, never written; the start is
    # not page-aligned.
    "prefix_hit_unaligned": (4, 15, 16, 4, (1, 1, 1, 0)),
    "start_mid_page": (8, 5, 0, 8, (1, 0, 1, 0)),
    "every_row_parked": (8, 8, 0, 8, (0, 0, 0, 0)),
}


@pytest.fixture(scope="module")
def fused_engines(engine):
    """A dense engine and one that serves through the MoE ``ffn_fn``
    hook (TP expert regime), float32."""
    from triton_dist_tpu.models import qwen_moe

    cfg = ModelConfig.tiny_moe(num_experts=8)
    moe = Engine(cfg, engine.mesh, mode="xla", max_len=MAX_LEN,
                 model=qwen_moe,
                 params=qwen_moe.init_params(jax.random.PRNGKey(4), cfg))
    return {"dense": engine, "moe": moe}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
# The attention does not know the FFN: the kernels once, on dense.
@pytest.mark.parametrize("family,attn", [("dense", "ref"), ("moe", "ref"),
                                         ("dense", "flash")])
def test_fused_step_equals_chunk_then_decode(fused_engines, family, attn,
                                             case):
    """``chunk_decode_paged`` against the pair it replaces, the chunk
    program and then the decode program on the same pool: the same pool
    contents, chunk logits row, decode logits and lengths."""
    from triton_dist_tpu.serving.chunked import ChunkedPrefill

    eng = fused_engines[family]
    srv = ServingEngine(eng, num_slots=FUSED_SLOTS, page=SRV_PAGE,
                        prefill_buckets=FUSED_BUCKETS, attn_impl=attn)
    fused = srv.chunker
    assert fused.decode_rows == FUSED_SLOTS
    plain = ChunkedPrefill(eng, srv._cache_shardings, FUSED_BUCKETS,
                           attn_impl=srv.chunk_attn)
    bucket, start, wfrom, valid, live = FUSED_CASES[case]
    rng = np.random.RandomState(7)
    p_max = srv.p_max
    tbl = (1 + np.arange(FUSED_SLOTS * p_max, dtype=np.int32)
           ).reshape(FUSED_SLOTS, p_max)
    row = tbl[-1].copy()
    live = np.asarray(live, np.int32)
    lens = np.asarray([5, 11, 16, 0], np.int32) * live
    tbl = tbl * live[:, None]
    toks = rng.randint(0, eng.cfg.vocab_size, bucket).astype(np.int32)
    dec_toks = rng.randint(0, eng.cfg.vocab_size,
                           FUSED_SLOTS).astype(np.int32)

    def pool():
        """The same random pool twice: every program donates its own."""
        r = np.random.RandomState(11)
        c = srv.cache
        fill = lambda a, sh: jax.device_put(
            r.randn(*a.shape).astype(a.dtype), sh)
        return dataclasses.replace(
            c, k_pages=fill(c.k_pages, srv._cache_shardings.k_pages),
            v_pages=fill(c.v_pages, srv._cache_shardings.v_pages))

    def batch(c):
        return dataclasses.replace(
            c, block_table=jnp.asarray(tbl), lens=jnp.asarray(lens),
            live=jnp.asarray(live))

    _, chunk_ref, c = plain.step(eng.params, toks, pool(), row, start,
                                 wfrom, valid)
    _, dec_ref, c_ref = srv._decode(eng.params, jnp.asarray(dec_toks),
                                    batch(c))
    if live.any():
        picked, chunk_got, dec_got, c_got = fused.step_decode(
            eng.params, toks, batch(pool()), row, start, wfrom, valid,
            jnp.asarray(dec_toks))
        np.testing.assert_allclose(np.asarray(dec_got)[live == 1],
                                   np.asarray(dec_ref)[live == 1],
                                   rtol=1e-5, atol=1e-5)
        # The program's own picks are its own rows' arg-maxes.
        np.testing.assert_array_equal(
            np.asarray(picked)[1:], np.argmax(np.asarray(dec_got), -1))
    else:
        picked, chunk_got, c_got = fused.step(
            eng.params, toks, pool(), row, start, wfrom, valid)
    assert np.asarray(picked)[0] == np.argmax(np.asarray(chunk_got))
    np.testing.assert_allclose(np.asarray(chunk_got),
                               np.asarray(chunk_ref), rtol=1e-5,
                               atol=1e-5)
    # Every page but the scratch one (page 0: what padding and parked
    # rows write, and nobody reads).
    for got, ref in ((c_got.k_pages, c_ref.k_pages),
                     (c_got.v_pages, c_ref.v_pages)):
        np.testing.assert_allclose(np.asarray(got)[:, 1:],
                                   np.asarray(ref)[:, 1:], rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_got.lens), lens + live)
    np.testing.assert_array_equal(np.asarray(c_ref.lens), lens + live)
    assert fused.cache_size() <= len(FUSED_BUCKETS)


# The interpreted kernels take seconds a dispatch: fewer, shorter prompts
# and fewer tokens after them. ``rode``: the least share of decode
# dispatches aboard a chunk program. Here 10 of 20 (one chunk a slot a
# tick: 10 of 22), and 2 of 7 (3 of 8: the two-chunk prompt's chunks now
# share a tick, which is one tick and one ride fewer).
@pytest.mark.parametrize("attn,lens,rode", [
    ("ref", (13, 3, 21, 8, 5, 17, 2, 11), 0.5), ("flash", (9, 3, 6), 0.25)])
def test_decode_rides_chunks_token_exact(engine, attn, lens, rode):
    """Mixed lengths over few slots, so that ticks hold a chunk and
    live decoders: the tokens are ``Engine.serve``'s, what ``step()``
    returns adds up to ``decode_tokens``, some decode dispatches rode a
    chunk program, and each bucket still has ONE compiled program."""
    rng = np.random.RandomState(5)
    prompts = [[int(t) for t in rng.randint(0, CFG.vocab_size, n)]
               for n in lens]
    gen = 6 if attn == "ref" else 4
    want = [_baseline(engine, p, gen) for p in prompts]
    srv = ServingEngine(engine, num_slots=3 if attn == "ref" else 2,
                        page=SRV_PAGE, prefill_buckets=(4, 8),
                        attn_impl=attn)
    hs = [srv.submit(p, max_new_tokens=gen) for p in prompts]
    decoded = 0
    for _ in range(400):
        if srv.sched.idle:
            break
        decoded += srv.step()
    assert [h.tokens for h in hs] == want
    st = srv.stats()
    assert decoded == st["decode_tokens"]
    assert 0 < st["decode_dispatches_fused"] <= st["decode_dispatches"]
    assert st["decode_dispatches_fused"] < st["decode_dispatches"], (
        "ticks with no chunk run the decode program")
    assert st["decode_dispatches_fused"] >= rode * st["decode_dispatches"]
    assert 0 < st["chunk_dispatches_parked"] < st["prefill_chunks"]
    assert srv.prefill_cache_size() <= 2
    assert srv.decode_cache_size() == 1
    assert st["pool"]["used_pages"] == 0


# Buckets (4, 16): a riding tick holds 16 bucket rows. A decoder (3
# tokens) is live when A (24 = 16 + 4 + 4), B (7 = 4 + 3) and C (27 =
# 16 + 11, the tail ONE padded program of 16 where the greedy cover
# took 4 + 4 + 3: ``plan_chunks``) are admitted together, in that order.
TICK_BUCKETS = (4, 16)
TICK_PROMPTS = {"D": 3, "A": 24, "B": 7, "C": 27}
ONE_CHUNK_A_SLOT = [["A16", "B4", "C16"], ["A4", "B4", "C16"], ["A4"]]
# case: (engine arguments, a decoder live before A, B and C arrive, the
# decode batch rides, the chunks of each tick from then on)
TICK_CASES = {
    # Oldest first, a prompt's tail chunks in one tick, the next
    # prompt's behind them while the tick's rows stay within 16; a
    # padded tail is the tick's whole room.
    "riding": ({}, True, True, [["A16"], ["A4", "A4", "B4", "B4"],
                                ["C16"], ["C16"]]),
    # The same engine held to the old rule: the schedule whose tokens
    # the riding one must equal.
    "one_chunk_a_slot": ({}, True, False, ONE_CHUNK_A_SLOT),
    # Speculation's decode dispatch is not the plain step: nothing
    # rides, so nothing is saved by waiting.
    "speculation": ({"spec_k": 1}, True, False, ONE_CHUNK_A_SLOT),
    # No decoder live in the first tick: its chunk programs pipeline
    # back to back, one a slot. From the next tick on D decodes, and
    # C's padded tail does not fit behind A's and B's.
    "no_live_decoder": ({}, False, True, [
        ["D4", "A16", "B4", "C16"], ["A4", "A4", "B4"], ["C16"]]),
}


@pytest.mark.parametrize("case", sorted(TICK_CASES))
def test_riding_tick_prefills_one_programs_worth(engine, case):
    """Which chunks each tick runs, read from the ``prefill_chunk``
    spans: a tick whose decode batch rides holds at most
    ``max(prefill_buckets)`` bucket rows, oldest prompt first; an
    engine that does not ride, and a tick with no live decoder, keep
    one chunk a prefilling slot. Every schedule serves
    ``Engine.serve``'s tokens from the same two chunk programs."""
    kw, decoder_first, rides, want_ticks = TICK_CASES[case]
    rng = np.random.RandomState(9)
    prompts = {k: [int(t) for t in rng.randint(0, CFG.vocab_size, n)]
               for k, n in TICK_PROMPTS.items()}
    gen = {"D": 12, "A": 3, "B": 3, "C": 3}
    srv = ServingEngine(engine, num_slots=4, page=SRV_PAGE,
                        prefill_buckets=TICK_BUCKETS, telemetry="spans",
                        **kw)
    if not rides:
        srv._rides = False       # parked chunks, the decode tick apart
    hs = {"D": srv.submit(prompts["D"], max_new_tokens=gen["D"])}
    if decoder_first:
        srv.step()
        assert hs["D"].status == "running"
    for k in "ABC":
        hs[k] = srv.submit(prompts[k], max_new_tokens=gen[k])
    srv.run()
    for k, h in hs.items():
        assert h.tokens == _baseline(engine, prompts[k], gen[k]), k
    name = {h.request.request_id: k for k, h in hs.items()}
    spans = srv.obs.log.spans()
    ticks = {}
    for s in spans:
        if s.kind == "prefill_chunk":
            ticks.setdefault(s.attrs["tick"], []).append(
                f"{name[s.request_id]}{s.attrs['bucket']}")
    got = [ticks[t] for t in sorted(ticks)]
    if decoder_first:
        assert got[0] == ["D4"]
        got = got[1:]
    assert got == want_ticks
    # A ``decode`` span is the step's LANDING; ``launched`` names the
    # tick whose chunk program carried it.
    rode = {s.attrs["launched"] for s in spans
            if s.kind == "decode" and s.attrs["fused"]}
    for t in rode:
        assert sum(int(c[1:]) for c in ticks[t]) <= max(TICK_BUCKETS)
    st = srv.stats()
    assert bool(rode) == rides
    assert st["decode_dispatches_fused"] == len(rode)
    assert st["prefill_chunks"] == sum(len(t) for t in ticks.values())
    # Every chunk program but the one a tick's batch rode ran parked.
    assert st["chunk_dispatches_parked"] == (
        st["prefill_chunks"] - len(rode) if rides else 0)
    # C's tail alone took a larger bucket than the greedy step; the
    # padding rows are D's 1, B's 1 and C's 5.
    assert (st["chunk_dispatches_padded_up"],
            st["prefill_rows_padded"]) == (1, 7)
    assert srv.chunker.cache_size() <= len(TICK_BUCKETS)
    assert st["pool"]["used_pages"] == 0


class _Recorded:
    """A jitted step program that notes, at every call, the tick, the
    tokens it picked and the ``np.argmax`` of the logits it returned
    beside them (``n_logits`` outputs after the first: the chunk's row,
    then the decode rows)."""

    def __init__(self, srv, fn, kind, n_logits, calls):
        self.srv, self.fn, self.kind = srv, fn, kind
        self.n_logits, self.calls = n_logits, calls

    def __call__(self, *args):
        out = self.fn(*args)
        want = np.concatenate([
            np.argmax(np.atleast_2d(np.asarray(rows)), axis=-1)
            for rows in out[1:1 + self.n_logits]])
        self.calls.append((self.srv.stats_counters["ticks"] - 1,
                           self.kind, np.asarray(out[0]), want))
        return out

    def _cache_size(self):
        return self.fn._cache_size()


# Buckets (4, 16) for the cell's (128, 512), 8 slots. A decoder D is
# live when A arrives, or nothing is. case: (A's prompt length, D
# first, which program A's last chunk is, by its place among the
# chunks of a tick whose batch rides: 0 the one the batch is aboard).
PICK_CASES = {
    # A16 carries the batch: A's first token comes in the batch's array.
    "riding_tick": (16, True, 0),
    # A4 carries the batch, A's last chunk A4 runs behind it, parked:
    # the token is that program's own 4-byte read.
    "parked_program": (8, True, 1),
    # No decoder: A's chunk runs parked, then every token is the decode
    # program's.
    "decode_only": (16, False, None),
}


@pytest.mark.parametrize("case", sorted(PICK_CASES))
def test_greedy_tokens_are_the_programs_own_picks(engine, case):
    """A greedy run emits, token for token, the ``np.argmax`` of the
    logits the same programs return, without copying a logits row:
    every program's picked tokens are its rows' arg-maxes, every array
    the tick reads is a program's picked tokens, and each served token
    is the pick of the row that produced it (``Engine.serve``'s, too).
    Every token counts as picked on the device; the programs are the
    same three."""
    n_a, decoder_first, last_place = PICK_CASES[case]
    rng = np.random.RandomState(13)
    prompts = {"D": [int(t) for t in rng.randint(0, CFG.vocab_size, 3)],
               "A": [int(t) for t in rng.randint(0, CFG.vocab_size, n_a)]}
    gen = {"D": 8, "A": 4}
    srv = ServingEngine(engine, num_slots=8, page=SRV_PAGE,
                        prefill_buckets=TICK_BUCKETS, telemetry="spans")
    calls, read, real = [], [], srv._read
    srv._decode = _Recorded(srv, srv._decode, "decode", 1, calls)
    srv.chunker._chunk = _Recorded(srv, srv.chunker._chunk, "chunk", 2,
                                   calls)

    def counting(out):
        read.append(real(out))
        return read[-1]

    srv._read = counting
    hs = {}
    if decoder_first:
        hs["D"] = srv.submit(prompts["D"], max_new_tokens=gen["D"])
        srv.step()
        assert hs["D"].status == "running"
    hs["A"] = srv.submit(prompts["A"], max_new_tokens=gen["A"])
    srv.run()

    for k, h in hs.items():
        assert h.tokens == _baseline(engine, prompts[k], gen[k]), k
    for _, kind, picked, want in calls:
        np.testing.assert_array_equal(picked, want, err_msg=kind)
    picks = {picked.tobytes() for _, _, picked, _ in calls}
    assert read and all(a.dtype == np.int32 and a.tobytes() in picks
                        for a in read), "a tick read more than tokens"

    # Each served token, from the row of the program that produced it.
    spans = srv.obs.log.spans()
    chunk_spans = [s for s in spans if s.kind == "prefill_chunk"]
    chunk_calls = [c for c in calls if c[1] == "chunk"]
    assert len(chunk_spans) == len(chunk_calls)
    served = {h.request.request_id: [] for h in hs.values()}
    # A token is sampled in the tick that LANDS its step; the ``decode``
    # span of that tick names the one that launched its program.
    launched = {s.attrs["tick"]: s.attrs["launched"] for s in spans
                if s.kind == "decode"}
    for s in (s for s in spans if s.kind == "sample"):
        assert s.attrs["device"] == 1
        toks = served[s.request_id]
        tick = launched.get(s.attrs["tick"])
        if not toks:     # the prompt's token: its last chunk's row 0
            i = max(i for i, c in enumerate(chunk_spans)
                    if c.request_id == s.request_id)
            toks.append(int(chunk_calls[i][3][0]))
            continue
        of_tick = [c for c in calls if c[0] == tick]
        dec = [c for c in of_tick if c[1] == "decode"]
        toks.append(int(dec[0][3][s.slot] if dec
                        else of_tick[0][3][1 + s.slot]))
    for h in hs.values():
        assert h.tokens == served[h.request.request_id]

    # The path the case is named for.
    a_id = hs["A"].request.request_id
    last = [s for s in chunk_spans if s.request_id == a_id][-1]
    in_tick = [s for s in chunk_spans
               if s.attrs["tick"] == last.attrs["tick"]]
    rode = [s for s in spans if s.kind == "decode" and s.attrs["fused"]
            and s.attrs["launched"] == last.attrs["tick"]]
    if last_place is None:
        assert not rode
        assert any(s.kind == "decode" and not s.attrs["fused"]
                   for s in spans)
    else:
        assert rode and in_tick.index(last) == last_place
    st = srv.stats()
    assert st["tokens_picked_on_device"] == st["tokens_generated"] == sum(
        len(h.tokens) for h in hs.values())
    assert srv.decode_cache_size() == 1
    assert srv.prefill_cache_size() <= len(TICK_BUCKETS)
    assert st["pool"]["used_pages"] == 0
