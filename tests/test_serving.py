"""Serving subsystem battery: block manager, continuous batching,
token-exactness vs ``Engine.serve`` under churn, backpressure,
deadlines, and the CommTimeoutError containment path.

Everything is seeded and clock-injected — no wall-clock anywhere; the
randomized arrival schedule is a fixed RandomState so the admission /
EOS-recycle interleavings are reproducible.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.serving import (
    BlockManager, BlockTableOverflowError, OutOfPagesError, PagedKVCache,
    QueueFullError, Request, ServingEngine,
)
from triton_dist_tpu.resilience.watchdog import CommTimeoutError

TP = 4
CFG = ModelConfig.tiny()
MAX_LEN = 64
PAGE = 8
VOCAB = CFG.vocab_size


@pytest.fixture(scope="module")
def engine():
    mesh = Mesh(np.array(jax.devices()[:TP]), ("tp",))
    return Engine(CFG, mesh, mode="xla", max_len=MAX_LEN, seed=3)


def _baseline(engine, prompt, gen_len, eos_id=None):
    """Sequential oracle: Engine.serve on the tiled prompt (row 0),
    truncated at EOS inclusively — the per-request ground truth."""
    ids = jnp.asarray(np.tile(np.asarray([prompt], np.int32), (TP, 1)))
    toks = np.asarray(engine.serve(ids, gen_len=gen_len))[0].tolist()
    if eos_id is not None and eos_id in toks:
        toks = toks[:toks.index(eos_id) + 1]
    return toks


# ---------------------------------------------------------------------------
# block manager (pure host logic)
# ---------------------------------------------------------------------------

def test_block_manager_alloc_append_free():
    m = BlockManager(num_pages=6, page=4, p_max=4)
    pages = m.alloc_prefill(0, list(range(6)))   # 6 tokens -> 2 pages
    assert len(pages) == 2 and 0 not in pages    # scratch reserved
    # appends fill page 2 (tokens 6, 7), then a third page at token 8.
    assert m.append(0) is None and m.append(0) is None
    new = m.append(0)
    assert new is not None and new not in pages
    frag = m.fragmentation()
    assert frag["used_pages"] == 3 and frag["free_pages"] == 2
    assert 0.0 < frag["utilization"] <= 1.0
    m.free_slot(0)
    assert m.fragmentation()["free_pages"] == 5


def test_block_manager_backpressure_and_rollback():
    m = BlockManager(num_pages=3, page=4, p_max=4)   # 2 usable pages
    m.alloc_prefill(0, list(range(8)))               # takes both
    with pytest.raises(OutOfPagesError):
        m.alloc_prefill(1, [1, 2, 3])
    # failed alloc must not leak pages
    m.free_slot(0)
    assert m.fragmentation()["free_pages"] == 2


def test_block_manager_row_overflow():
    m = BlockManager(num_pages=8, page=4, p_max=2)
    with pytest.raises(BlockTableOverflowError):
        m.alloc_prefill(0, list(range(12)))          # 3 pages > p_max
    m.alloc_prefill(1, list(range(8)))               # fills the row
    with pytest.raises(BlockTableOverflowError):
        m.append(1)                                  # token 9 needs row 3


def test_block_manager_prefix_reuse():
    m = BlockManager(num_pages=10, page=4, p_max=6, prefix_reuse=True)
    p0 = m.alloc_prefill(0, [1, 2, 3, 4, 5, 6, 7, 8, 9])  # 2 full + 1
    # Two-phase publication: until the content-resident commit, a
    # same-prefix alloc must MISS (the pages hold no KV yet).
    probe = m.alloc_prefill(7, [1, 2, 3, 4, 5, 6, 7, 8])
    assert probe[:2] != p0[:2], "uncommitted prefix pages were shared"
    m.free_slot(7)
    m.commit_prefix(0)
    p1 = m.alloc_prefill(1, [1, 2, 3, 4, 5, 6, 7, 8, 42])
    m.commit_prefix(1)
    assert p0[:2] == p1[:2], "full prefix pages must be shared"
    assert p0[2] != p1[2], "ragged tails stay private"
    assert m.stats["prefix_hits"] == 2
    # different first page -> no sharing
    p2 = m.alloc_prefill(2, [9, 9, 9, 9, 5, 6, 7, 8])
    assert p2[0] not in (p0[0],)
    # freeing both sharers keeps prefix pages cached until eviction
    m.free_slot(0)
    m.free_slot(1)
    before = m.fragmentation()["prefix_pages"]
    assert before >= 2
    # exhaust the pool: eviction reclaims unreferenced prefix pages
    got = m.alloc_prefill(3, list(range(100, 124)))  # 6 pages
    assert len(got) == 6
    assert m.stats["evictions"] >= 1


def test_paged_cache_append_and_gather():
    """PagedKVCache.append_decode + dense_layer against a hand scatter."""
    rng = np.random.RandomState(0)
    cache = PagedKVCache.empty(1, 5, 4, 2, 3, num_slots=2, p_max=2)
    tbl = np.array([[1, 2], [0, 0]], np.int32)   # parked row = scratch
    lens = np.array([5, 0], np.int32)    # slot0 mid page 2; slot1 parked
    live = np.array([1, 0], np.int32)
    cache = dataclasses.replace(
        cache, block_table=jnp.asarray(tbl), lens=jnp.asarray(lens),
        live=jnp.asarray(live))
    k = rng.randn(2, 1, 2, 3).astype(np.float32)
    v = rng.randn(2, 1, 2, 3).astype(np.float32)
    cache = cache.append_decode(0, jnp.asarray(k), jnp.asarray(v))
    kp = np.asarray(cache.k_pages)
    # slot0: position 5 -> row 1 (page id 2), offset 1
    np.testing.assert_array_equal(kp[0, 2, :, 1, :], k[0, 0])
    # slot1 parked: its append landed in the scratch page (0), off 0
    np.testing.assert_array_equal(kp[0, 0, :, 0, :], k[1, 0])
    kd, _ = cache.dense_layer(0)
    np.testing.assert_array_equal(np.asarray(kd)[0, 5], k[0, 0])
    cache = cache.advance()
    np.testing.assert_array_equal(np.asarray(cache.lens), [6, 0])


# ---------------------------------------------------------------------------
# continuous batching vs the sequential baseline
# ---------------------------------------------------------------------------

def test_continuous_token_exact_random_churn(engine):
    """Admission → prefill → joined decode → EOS recycle under a
    seeded randomized arrival schedule: every request's tokens equal
    its solo Engine.serve run."""
    rng = np.random.RandomState(7)
    reqs = []
    for _ in range(6):
        plen = int(rng.randint(1, 9))
        prompt = [int(t) for t in rng.randint(0, VOCAB, plen)]
        gen = int(rng.randint(1, 7))
        reqs.append((prompt, gen))
    # Derive an EOS for two requests from their own baseline output so
    # early-stop (slot recycle mid-run) actually triggers.
    base_plain = [_baseline(engine, p, g) for p, g in reqs]
    eos = [None] * len(reqs)
    for i in (1, 4):
        toks = base_plain[i]
        if len(toks) > 1:
            eos[i] = toks[len(toks) // 2]
    want = [_baseline(engine, p, g, e)
            for (p, g), e in zip(reqs, eos)]

    srv = ServingEngine(engine, num_slots=2, page=PAGE)
    handles = []
    pending = list(zip(reqs, eos))
    rng2 = np.random.RandomState(8)
    while pending or not srv.sched.idle:
        # randomized arrivals: 0-2 submissions per tick
        for _ in range(int(rng2.randint(0, 3))):
            if pending:
                (prompt, gen), e = pending.pop(0)
                handles.append(srv.submit(prompt, max_new_tokens=gen,
                                          eos_id=e))
        srv.step()
    assert [h.tokens for h in handles] == want
    assert all(h.status == "done" for h in handles)
    st = srv.stats()
    assert st["completed"] == len(reqs)
    assert st["pool"]["used_pages"] == 0, "all pages recycled"


def test_static_policy_gang_batching(engine):
    """policy='static' is still token-exact but needs more decode
    dispatches than continuous batching on a skewed workload — the
    bench's serving_tokens_per_s comparison in miniature."""
    prompts = [[1, 2, 3], [4, 5], [6], [7, 8]]
    gens = [2, 6, 2, 6]
    want = [_baseline(engine, p, g) for p, g in zip(prompts, gens)]

    def run(policy):
        srv = ServingEngine(engine, num_slots=2, page=PAGE,
                            policy=policy)
        hs = [srv.submit(p, max_new_tokens=g)
              for p, g in zip(prompts, gens)]
        srv.run()
        return [h.tokens for h in hs], srv.stats()["decode_dispatches"]

    out_c, steps_c = run("continuous")
    out_s, steps_s = run("static")
    assert out_c == want and out_s == want
    assert steps_c <= steps_s


def test_admission_backpressure(engine):
    srv = ServingEngine(engine, num_slots=1, page=PAGE, max_queue=2)
    srv.submit([1, 2], max_new_tokens=2)
    srv.submit([3, 4], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        srv.submit([5, 6], max_new_tokens=2)
    assert srv.stats()["rejected"] == 1
    srv.run()
    assert srv.stats()["completed"] == 2


def test_out_of_pages_stalls_then_completes(engine):
    """An undersized pool stalls admission (requeue, not failure) until
    a finishing request frees pages."""
    # ONE usable page + scratch: the second request must wait for the
    # first to finish and free it.
    srv = ServingEngine(engine, num_slots=2, page=PAGE, num_pages=2)
    h1 = srv.submit([1, 2, 3], max_new_tokens=3)
    h2 = srv.submit([4, 5, 6], max_new_tokens=3)
    srv.run()
    assert h1.status == "done" and h2.status == "done"
    assert srv.stats()["admit_stalls"] >= 1
    want = [_baseline(engine, [1, 2, 3], 3),
            _baseline(engine, [4, 5, 6], 3)]
    assert [h1.tokens, h2.tokens] == want


def test_mid_decode_preemption_token_exact(engine):
    """Pool exhaustion while GROWING a running request preempts it
    (pages freed, requeued at the head, resumed via re-prefill of
    prompt + generated-so-far) — never crashes the loop, and the
    preempted request's final tokens still match its solo baseline."""
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]]
    want = [_baseline(engine, p, 4) for p in prompts]
    # 2 usable pages: one per slot at prefill; the first page-boundary
    # crossing (position 8) finds the pool dry.
    srv = ServingEngine(engine, num_slots=2, page=PAGE, num_pages=3)
    hs = [srv.submit(p, max_new_tokens=4) for p in prompts]
    srv.run()
    assert [h.status for h in hs] == ["done", "done"]
    assert [h.tokens for h in hs] == want
    assert srv.stats()["preemptions"] >= 1


def test_pool_never_satisfiable_fails_fast(engine):
    """A request whose pages can NEVER be freed by anyone (empty
    server, pool smaller than the prompt) fails instead of spinning."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE, num_pages=2)
    h = srv.submit(list(range(PAGE + 1)), max_new_tokens=2)  # 2 pages
    srv.run()
    assert h.status == "failed"
    assert isinstance(h.error, OutOfPagesError)


def test_capacity_validation(engine):
    srv = ServingEngine(engine, num_slots=1, page=PAGE)
    with pytest.raises(ValueError, match="exceeds capacity"):
        srv.submit(list(range(60)), max_new_tokens=10)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], max_new_tokens=2)


def test_streaming_callbacks(engine):
    seen = []
    srv = ServingEngine(engine, num_slots=1, page=PAGE)
    h = srv.submit([1, 2, 3], max_new_tokens=4,
                   stream_cb=lambda tok, hh: seen.append(
                       (tok, len(hh.tokens))))
    srv.run()
    assert [t for t, _ in seen] == h.tokens
    # streamed as generated: callback i fires when i+1 tokens exist
    assert [n for _, n in seen] == [1, 2, 3, 4]


def test_deadline_fails_one_request(engine):
    """A deadline miss (injected clock) fails that request only; the
    survivor's tokens stay exact."""
    clock = [0.0]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        clock=lambda: clock[0])
    slow = srv.submit([1, 2], max_new_tokens=8, deadline=3.0)
    fast = srv.submit([3, 4], max_new_tokens=8)
    srv.step()                    # both admitted, first decode
    clock[0] = 5.0                # past slow's deadline
    srv.run()
    assert slow.status == "timeout"
    assert isinstance(slow.error, TimeoutError)
    assert fast.status == "done"
    assert fast.tokens == _baseline(engine, [3, 4], 8)
    assert srv.stats()["timed_out"] == 1


def test_comm_timeout_fails_victim_not_server(engine):
    """A hung collective (CommTimeoutError on the shared dispatch)
    fails the scheduler's victim; the server keeps serving and the
    survivor stays token-exact."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE)
    eldest = srv.submit([1, 2, 3], max_new_tokens=6)
    srv.step()                    # eldest admitted + first decode
    younger = srv.submit([4, 5], max_new_tokens=4)
    real = srv._decode
    state = {"armed": False}

    def flaky(*a, **kw):
        if state["armed"]:
            state["armed"] = False
            raise CommTimeoutError(op="serving.decode", rank=0,
                                   timeout_s=0.1, progress=None)
        return real(*a, **kw)

    srv._decode = flaky
    srv.step()                    # younger admitted this tick
    state["armed"] = True
    srv.step()                    # wedged dispatch -> eldest fails
    srv.run()
    assert eldest.status == "timeout"
    assert isinstance(eldest.error, CommTimeoutError)
    assert younger.status == "done"
    assert younger.tokens == _baseline(engine, [4, 5], 4)
    assert srv.stats()["comm_timeouts"] == 1


def test_prefill_timeout_fails_admitting_request_only(engine):
    """A wedged PREFILL dispatch fails the admitting request (slot and
    pages released — no leaked half-admitted state); requests already
    decoding are untouched and stay exact."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE)
    ok = srv.submit([1, 2, 3], max_new_tokens=5)
    srv.step()                    # ok admitted + decoding
    real = srv.engine.prefill
    state = {"armed": True}

    def flaky(ids):
        if state["armed"]:
            state["armed"] = False
            raise CommTimeoutError(op="engine.prefill", rank=0,
                                   timeout_s=0.1, progress=None)
        return real(ids)

    srv.engine.prefill = flaky
    doomed = srv.submit([4, 5], max_new_tokens=3)
    try:
        srv.run()
    finally:
        srv.engine.prefill = real
    assert doomed.status == "timeout"
    assert isinstance(doomed.error, CommTimeoutError)
    assert ok.status == "done"
    assert ok.tokens == _baseline(engine, [1, 2, 3], 5)
    assert srv.stats()["pool"]["used_pages"] == 0, "pages leaked"
    assert not srv.sched.slots, "slot leaked"


def test_no_recompile_after_warmup(engine):
    """Fixed decode-batch shape: the decode jit cache stops growing
    after warmup, over arrivals, EOS recycles, and parked slots."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE)
    srv.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)   # warmup
    warm = srv.decode_cache_size()
    rng = np.random.RandomState(21)
    for _ in range(5):
        plen = int(rng.randint(1, 8))
        srv.submit([int(t) for t in rng.randint(0, VOCAB, plen)],
                   max_new_tokens=int(rng.randint(1, 5)))
        srv.step()
    srv.run()
    assert srv.decode_cache_size() == warm, (
        "decode dispatch re-specialized after warmup")


def test_kernel_attn_impl_matches_baseline(engine):
    """attn_impl='kernel' (the in-kernel paged flash decode, axis=None
    local form) greedy-matches the sequential baseline too."""
    prompts = [[1, 2, 3], [7, 8]]
    want = [_baseline(engine, p, 3) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        attn_impl="kernel")
    assert srv.generate(prompts, max_new_tokens=3) == want


def test_prefix_reuse_serving(engine):
    """Shared page-aligned prompt prefixes: fewer pages, same tokens."""
    shared = list(range(1, 17))            # two full pages at PAGE=8
    p1 = shared + [30, 31]
    p2 = shared + [40]
    want = [_baseline(engine, p1, 3), _baseline(engine, p2, 3)]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefix_reuse=True)
    out = srv.generate([p1, p2], max_new_tokens=3)
    assert out == want
    assert srv.manager.stats["prefix_hits"] >= 2


# ---------------------------------------------------------------------------
# the token picked inside the step program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", ["tied_maxima", "nan_row"])
def test_greedy_tokens_first_index_rule(rows):
    """The program's pick and ``np.argmax`` agree where the maximum is
    not alone: the FIRST index of the maximum, a NaN counting as the
    maximum. Pinned on both sides, so neither may drift."""
    from triton_dist_tpu.serving.chunked import greedy_tokens

    v = 257
    x = np.random.RandomState(3).randn(6, v).astype(np.float32)
    if rows == "tied_maxima":
        x[0, [5, 200]] = 9.0               # two maxima
        x[1, :] = 0.25                     # every entry the maximum
        x[2, :] = -np.inf
        x[3, [0, v - 1]] = np.inf
        x[4, [v - 2, v - 1]] = 7.0         # the tie at the row's end
        want = [5, 0, 0, 0, v - 2]
    else:
        x[0, 17] = np.nan                  # a NaN below a larger value
        x[0, 3] = 50.0
        x[1, [40, 41, 250]] = np.nan       # several: the first
        x[2, :] = np.nan
        x[3, 9] = np.nan
        x[3, 2] = np.inf                   # NaN outranks +inf
        x[4, v - 1] = np.nan
        want = [17, 40, 0, 9, v - 1]
    got = np.asarray(jax.jit(greedy_tokens)(jnp.asarray(x)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, np.argmax(x, axis=-1))
    assert got[:5].tolist() == want


@pytest.mark.parametrize("buckets", [None, (4, 8)],
                         ids=["monolithic", "chunked"])
def test_sampled_request_among_greedy_rows(engine, buckets):
    """One sampled request in a batch of greedy ones: it samples the
    tokens it samples served alone, the greedy rows take the device's
    token (monolithic admission picks a prompt's token on the host, as
    ever), and only ticks with the sampled row aboard copy logits."""
    req = dict(max_new_tokens=3, temperature=0.7, top_k=40, seed=5)
    sampled = [9, 1, 4, 7, 2]
    alone = ServingEngine(engine, num_slots=3, page=PAGE,
                          prefill_buckets=buckets)
    ha = alone.submit(sampled, **req)
    alone.run()
    greedy = [[1, 2, 3], list(range(20, 31))]
    want = [_baseline(engine, p, 8) for p in greedy]

    srv = ServingEngine(engine, num_slots=3, page=PAGE,
                        prefill_buckets=buckets)
    read, real = [], srv._read

    def counting(out):
        read.append((srv.stats_counters["ticks"] - 1, real(out)))
        return read[-1][1]

    srv._read = counting
    hg = [srv.submit(p, max_new_tokens=8) for p in greedy]
    hs = srv.submit(sampled, **req)
    aboard = set()           # ticks that began with the sampled row live
    while not srv.sched.idle:
        if hs.status == "running":
            aboard.add(srv.stats_counters["ticks"])
        srv.step()
    assert hs.tokens == ha.tokens and len(hs.tokens) == 3
    assert [h.tokens for h in hg] == want
    on_host = 0 if buckets else len(greedy)    # admission's own picks
    st = srv.stats()
    assert st["tokens_picked_on_device"] == 16 - on_host
    assert st["tokens_generated"] == 16 + 3
    wide = {t for t, a in read if a.dtype != np.int32}
    assert wide and wide <= aboard | {min(aboard) - 1}, (wide, aboard)
    assert aboard <= wide, "a sampled row's tick copied no logits"
    assert max(t for t, _ in read) > max(wide), (
        "the greedy ticks after it copied tokens alone")
    for _, a in read:
        if a.dtype == np.int32:
            assert a.nbytes <= 4 * (1 + srv.num_slots)
        else:
            assert a.shape[-1] == VOCAB
    assert srv.decode_cache_size() == 1


# ---------------------------------------------------------------------------
# megakernel path (prefill lane + live slot mask)
# ---------------------------------------------------------------------------

# An interpreted step costs in proportion to the tasks in its queue, and
# the heads and the FFN's width add theirs: tests/test_megakernel.py's
# micro config.
MK_CFG = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                          intermediate_size=32, num_attention_heads=4,
                          num_key_value_heads=2, head_dim=8)


def test_megakernel_paged_serving_token_exact():
    """PAGED megakernel serving: the manager's block table is installed
    on the engine each tick (parked rows hit the scratch page), and
    staggered requests through allocator-assigned pages match solo runs
    on the identity-table engine."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kw = dict(batch=2, max_len=32, tile_w=16, t_tile=16, paged=True,
              page=16)
    prompts = [[5, 6, 7], [3, 4]]
    gen = 3

    e = MegaKernelEngine(MK_CFG, mesh, **kw)    # stale rows are masked

    def solo(prompt):
        tiled = jnp.asarray(np.tile(np.asarray([prompt], np.int32),
                                    (2, 1)))
        seed = e.prefill_chain(tiled)
        return np.asarray(e.generate(
            seed, steps=gen, start_pos=len(prompt) - 1))[0].tolist()

    want = [solo(p) for p in prompts]
    mk = MegaKernelEngine(MK_CFG, mesh, num_pages=2 * 2 + 1, **kw)
    srv = ServingEngine(mk)
    assert srv.manager is not None
    h0 = srv.submit(prompts[0], max_new_tokens=gen)
    srv.step()                       # slot 0 mid-prefill-lane
    # The allocator's table (slot 0 -> a manager page, parked slot 1 ->
    # scratch row of zeros) must actually be installed on the engine —
    # NOT its construction-time identity table.
    installed = np.asarray(mk.block_table).reshape(2, -1)
    assert installed[0, 0] != 0, "slot 0 should map to a manager page"
    np.testing.assert_array_equal(installed[1], 0)   # parked -> scratch
    h1 = srv.submit(prompts[1], max_new_tokens=gen)
    srv.run()
    assert [h0.tokens, h1.tokens] == want
    assert srv.stats()["pool"]["used_pages"] == 0


def test_megakernel_hybrid_timeout_fails_all_in_flight():
    """Hybrid GDN megakernel: the recurrent state cannot be rewound, so
    a decode timeout fails EVERY in-flight request; fresh requests
    (slots reset) still serve fine afterwards."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    cfg = ModelConfig.tiny_next(vocab_size=128, num_key_value_heads=4,
                                full_attn_interval=2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mk = MegaKernelEngine(cfg, mesh, batch=2, max_len=16,
                          tile_w=16, t_tile=16)
    srv = ServingEngine(mk)
    a = srv.submit([5, 6], max_new_tokens=4)
    b = srv.submit([7], max_new_tokens=4)
    srv.step()
    real = mk.decode_step
    state = {"armed": True}

    def flaky(toks, lens):
        if state["armed"]:
            state["armed"] = False
            raise CommTimeoutError(op="megakernel.decode_step", rank=0,
                                   timeout_s=0.1, progress=None)
        return real(toks, lens)

    mk.decode_step = flaky
    srv.step()
    mk.decode_step = real
    assert a.status == "timeout" and b.status == "timeout"
    fresh = srv.submit([9, 10], max_new_tokens=2)
    srv.run()
    assert fresh.status == "done" and len(fresh.tokens) == 2


def test_megakernel_serving_token_exact():
    """Continuous batching over the persistent megakernel: staggered
    requests through the prefill lane match solo runs."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    prompts = [[5, 6, 7], [3], [11, 12]]
    gen = 3

    # One engine for the solo runs and the serving run: a slot's stale
    # rows are masked beyond its length, which is what recycling a slot
    # relies on.
    mk = MegaKernelEngine(MK_CFG, mesh, batch=2, max_len=16,
                          tile_w=16, t_tile=16)

    def solo(prompt):
        tiled = jnp.asarray(np.tile(np.asarray([prompt], np.int32),
                                    (2, 1)))
        seed = mk.prefill_chain(tiled)
        return np.asarray(mk.generate(
            seed, steps=gen, start_pos=len(prompt) - 1))[0].tolist()

    want = [solo(p) for p in prompts]
    srv = ServingEngine(mk)
    h0 = srv.submit(prompts[0], max_new_tokens=gen)
    srv.step()                       # slot 0 mid-prefill-lane
    h1 = srv.submit(prompts[1], max_new_tokens=gen)
    h2 = srv.submit(prompts[2], max_new_tokens=gen)
    srv.run()
    assert [h0.tokens, h1.tokens, h2.tokens] == want
    # This lane's step returns host rows: every token is _pick's.
    st = srv.stats()
    assert st["tokens_picked_on_device"] == 0 < st["tokens_generated"]
