"""Chunked-prefill + disaggregated-serving battery.

Covers the serving split of ROADMAP Open item 1: fixed-shape bucketed
chunked prefill (jit cache bounded by the bucket count — never by the
distinct-prompt-length count), the prefill-worker/decode-worker role
split with whole-page KV migration over the one-sided p2p path, and
the containment story (a dropped or wedged migration fails one
request, never the server). Everything token-exact against the
sequential ``Engine.serve`` oracle; everything seeded.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig, dense
from triton_dist_tpu.ops.chunked_prefill import plan_chunks
from triton_dist_tpu.resilience import faults
from triton_dist_tpu.resilience.watchdog import CommTimeoutError
from triton_dist_tpu.serving import (
    DisaggServingEngine, OutOfPagesError, PagedKVCache, ServingEngine,
)

TP = 4
CFG = ModelConfig.tiny()
MAX_LEN = 64
PAGE = 8
BUCKETS = (4, 16)
VOCAB = CFG.vocab_size


@pytest.fixture(scope="module")
def engine():
    mesh = Mesh(np.array(jax.devices()[:TP]), ("tp",))
    return Engine(CFG, mesh, mode="xla", max_len=MAX_LEN, seed=3)


@pytest.fixture(scope="module")
def role_engines():
    """Disjoint mesh slices sharing ONE weight pytree — the
    prefill-worker / decode-worker pair."""
    params = dense.init_params(jax.random.PRNGKey(3), CFG)
    devs = jax.devices()
    pf = Engine(CFG, Mesh(np.array(devs[:2]), ("tp",)), mode="xla",
                max_len=MAX_LEN, params=params)
    dec = Engine(CFG, Mesh(np.array(devs[2:4]), ("tp",)), mode="xla",
                 max_len=MAX_LEN, params=params)
    return pf, dec


def _baseline(engine, prompt, gen_len):
    n = engine.mesh.shape[engine.axis]
    ids = jnp.asarray(np.tile(np.asarray([prompt], np.int32), (n, 1)))
    return np.asarray(engine.serve(ids, gen_len=gen_len))[0].tolist()


# ---------------------------------------------------------------------------
# chunk planning (pure host logic)
# ---------------------------------------------------------------------------

def test_plan_chunks_deterministic_cover():
    for n in range(1, 40):
        plan = plan_chunks(n, BUCKETS)
        assert sum(v for _, v in plan) == n
        assert all(b in BUCKETS and 1 <= v <= b for b, v in plan)
        assert plan == plan_chunks(n, BUCKETS), "must be deterministic"
    # largest-fit greedy with a padded tail
    assert plan_chunks(21, BUCKETS) == [(16, 16), (4, 4), (4, 1)]
    assert plan_chunks(3, BUCKETS) == [(4, 3)]
    with pytest.raises(ValueError):
        plan_chunks(4, ())


# ---------------------------------------------------------------------------
# fixed-shape chunked prefill (in-place, single engine)
# ---------------------------------------------------------------------------

def test_chunked_token_exact_across_bucket_edges(engine):
    """Prompt lengths straddling every bucket edge (b-1 / b / b+1):
    greedy tokens equal the monolithic Engine.serve run — chunk
    boundaries are invisible."""
    lens = sorted({max(b + d, 1) for b in BUCKETS for d in (-1, 0, 1)})
    prompts = [[int(t) for t in
                np.random.RandomState(n).randint(0, VOCAB, n)]
               for n in lens]
    want = [_baseline(engine, p, 4) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=BUCKETS)
    assert srv.generate(prompts, max_new_tokens=4) == want


def test_chunked_jit_cache_bounded_by_buckets(engine):
    """The compile-count gate: after warmup over the buckets, UNSEEN
    prompt lengths cause zero new prefill or decode compilations (the
    prefill cache is bounded by the bucket count; monolithic prefill
    grows per distinct length)."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=BUCKETS)
    rng = np.random.RandomState(11)
    srv.generate([[1, 2, 3], list(range(20))], max_new_tokens=2)
    pre, dec = srv.prefill_cache_size(), srv.decode_cache_size()
    assert pre <= len(BUCKETS)
    for n in (2, 6, 9, 13, 19, 23):        # unseen lengths + a resume mix
        srv.submit([int(t) for t in rng.randint(0, VOCAB, n)],
                   max_new_tokens=2)
        srv.step()
    srv.run()
    assert srv.prefill_cache_size() == pre, "prefill re-specialized"
    assert srv.decode_cache_size() == dec, "decode re-specialized"
    st = srv.stats()
    assert st["prefill_cache_size"] == pre
    assert st["prefill_chunks"] > 0 and st["prefill_buckets"] == list(
        BUCKETS)


def test_chunked_interleaves_with_decode(engine):
    """A long prompt no longer monopolizes the dispatch: while it
    chunk-streams, an already-running request keeps decoding (decode
    dispatches happen between its chunks)."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=(4,))
    short = srv.submit([1, 2], max_new_tokens=8)
    srv.step()                       # short admitted + decoding
    long = srv.submit(list(range(17)), max_new_tokens=2)  # 5 chunks
    progress = []
    while long.status in ("queued", "prefill"):
        srv.step()
        progress.append(len(short.tokens))
    assert progress[-1] > progress[0], (
        "short request made no decode progress during the long "
        "prompt's chunk stream")
    srv.run()
    assert short.tokens == _baseline(engine, [1, 2], 8)
    assert long.tokens == _baseline(engine, list(range(17)), 2)


def test_chunked_prefix_reuse_skips_resident_pages(engine):
    """Chunked × prefix-reuse: the second sharer's chunk stream starts
    at the first non-shared page (fewer chunks), shared pages are
    never re-blitted while a live reader holds them, and tokens stay
    exact. The BlockManager.prefix_hits assertion of satellite 2."""
    shared = list(range(1, 17))              # two full pages
    p1, p2 = shared + [30, 31], shared + [40]
    want = [_baseline(engine, p1, 3), _baseline(engine, p2, 3)]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=BUCKETS, prefix_reuse=True)
    h1 = srv.submit(p1, max_new_tokens=3)
    srv.step()
    srv.step()                               # p1 fully prefilled (16+4)
    h2 = srv.submit(p2, max_new_tokens=3)    # while h1 still decodes
    srv.step()
    assert srv.manager.prefix_hits(h2.slot) == 2, (
        "second sharer must hit both full prefix pages")
    srv.run()
    assert [h1.tokens, h2.tokens] == want
    assert srv.manager.stats["prefix_hits"] >= 2
    # h2 computed only its non-shared tail: ONE bucket-4 chunk starting
    # at the first non-shared page, vs h1's full 16+4 stream.
    assert h1.chunks == [(0, 16, 16), (16, 4, 2)], h1.chunks
    assert h2.chunks == [(16, 4, 1)], h2.chunks


def test_chunked_prefix_concurrent_admission_no_unwritten_share(engine):
    """Two same-prefix requests admitted in ONE tick: the second must
    not attend the first's still-unwritten prefix pages (prefix
    entries publish only at content-resident commit). Both stay
    token-exact; the second computes its own copy (no hits) because it
    admitted inside the first's chunk-stream window."""
    shared = list(range(1, 17))
    p1, p2 = shared + [30, 31], shared + [40]
    want = [_baseline(engine, p1, 3), _baseline(engine, p2, 3)]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=BUCKETS, prefix_reuse=True)
    h1 = srv.submit(p1, max_new_tokens=3)
    h2 = srv.submit(p2, max_new_tokens=3)   # same tick — mid-stream
    srv.run()
    assert [h1.tokens, h2.tokens] == want
    # Both full streams ran (no premature sharing): 16+4 chunks each.
    assert h1.chunks[0] == (0, 16, 16) and h2.chunks[0] == (0, 16, 16)
    # A THIRD same-prefix request after commit does share.
    h3 = srv.submit(shared + [50], max_new_tokens=3)
    srv.run()
    assert h3.tokens == _baseline(engine, shared + [50], 3)
    assert h3.chunks[0][0] == 16, "post-commit sharer should skip"


def test_chunked_preempt_resume_deterministic(engine):
    """A preempted request re-prefills prompt + generated-so-far
    through the SAME deterministic bucket plan and ends token-exact."""
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]]
    want = [_baseline(engine, p, 4) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=PAGE, num_pages=3,
                        prefill_buckets=BUCKETS)
    hs = [srv.submit(p, max_new_tokens=4) for p in prompts]
    srv.run()
    assert [h.tokens for h in hs] == want
    assert srv.stats()["preemptions"] >= 1
    # The last chunk stream (the resume) followed the deterministic
    # plan of its lane (prompt + generated-so-far at preemption time).
    resumed = max(hs, key=lambda h: len(h.lane))
    assert len(resumed.lane) > len(resumed.request.prompt), (
        "expected a resumed lane carrying generated tokens")
    start = resumed.chunks[0][0]
    assert [(b, v) for _, b, v in resumed.chunks] == plan_chunks(
        len(resumed.lane) - start, BUCKETS), (
        "resume deviated from the plan")


@pytest.mark.parametrize("lane", ["disaggregated", "spec_k", "megakernel",
                                  "in_place"])
def test_only_the_in_place_chunker_carries_decode_rows(engine,
                                                       role_engines, lane):
    """The decode batch rides a chunk program only where the chunks
    write the pool the decoders read and the decode dispatch is the
    plain step: a disaggregated prefill worker (its own pool), the
    speculative lane (K tokens a slot) and the megakernel lane (chunk
    tasks in its own kernel) build today's chunk program, with no
    decode rows; the in-place layer path carries ``num_slots``. None
    is chosen by an argument."""
    if lane == "disaggregated":
        pf, dec = role_engines
        srv = DisaggServingEngine(dec, prefill_engine=pf, num_slots=2,
                                  page=PAGE, prefill_buckets=BUCKETS)
        chunker = srv._prefiller.chunker
    elif lane == "spec_k":
        srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=1,
                            prefill_buckets=BUCKETS)
        chunker = srv.chunker
    elif lane == "megakernel":
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine

        cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                               intermediate_size=32, num_hidden_layers=2,
                               num_attention_heads=4,
                               num_key_value_heads=2, head_dim=8)
        mk = MegaKernelEngine(
            cfg, Mesh(np.array(jax.devices()[:1]), ("tp",)), batch=2,
            max_len=64, tile_w=16, t_tile=16, paged=True, page=16,
            num_pages=9, prefill_buckets=BUCKETS)
        srv = ServingEngine(mk, prefill_buckets=BUCKETS)
        chunker = srv.chunker
    else:
        srv = ServingEngine(engine, num_slots=2, page=PAGE,
                            prefill_buckets=BUCKETS)
        chunker = srv.chunker
    rows = getattr(chunker, "decode_rows", 0)
    assert rows == (2 if lane == "in_place" else 0)
    assert srv._rides == (lane == "in_place")
    # One chunk, then two: the second prompt's last chunk runs in a
    # tick in which the first request decodes.
    hs = [srv.submit([1, 2, 3], max_new_tokens=4),
          srv.submit(list(range(1, 20)), max_new_tokens=3)]
    srv.run()
    assert [h.status for h in hs] == ["done", "done"]
    st = srv.stats()
    assert (st["decode_dispatches_fused"] > 0) == (lane == "in_place")
    assert st["decode_dispatches_fused"] <= st["decode_dispatches"]


def test_chunked_wedged_chunk_fails_one_request(engine):
    """A dropped chunk dispatch (fault plan) fails the admitting
    request only; the running survivor stays token-exact."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=BUCKETS)
    ok = srv.submit([1, 2, 3], max_new_tokens=5)
    srv.step()
    doomed = srv.submit([4, 5], max_new_tokens=3)
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="chunked_prefill", k=0)):
        srv.run()
    assert doomed.status == "failed"
    assert isinstance(doomed.error, faults.InjectedFault)
    assert ok.status == "done"
    assert ok.tokens == _baseline(engine, [1, 2, 3], 5)
    assert srv.stats()["pool"]["used_pages"] == 0, "pages leaked"


# ---------------------------------------------------------------------------
# page-migration building blocks
# ---------------------------------------------------------------------------

def test_page_gather_scatter_bit_exact():
    """PagedKVCache.gather_pages → scatter_pages round-trips page
    bytes exactly under a REWRITTEN block table (different dst ids),
    with padding rows dumped into scratch."""
    rng = np.random.RandomState(0)
    src = PagedKVCache.empty(2, 6, 4, 2, 3, num_slots=1, p_max=3)
    src = dataclasses.replace(
        src,
        k_pages=jnp.asarray(rng.randn(2, 6, 2, 4, 3), jnp.float32),
        v_pages=jnp.asarray(rng.randn(2, 6, 2, 4, 3), jnp.float32))
    dst = PagedKVCache.empty(2, 6, 4, 2, 3, num_slots=1, p_max=3)
    src_ids = jnp.asarray([1, 3, 0], jnp.int32)       # pad -> scratch
    dst_ids = jnp.asarray([4, 2, 0], jnp.int32)       # rewritten table
    k_pay, v_pay = src.gather_pages(src_ids)
    dst = dst.scatter_pages(k_pay, v_pay, dst_ids)
    np.testing.assert_array_equal(np.asarray(dst.k_pages)[:, 4],
                                  np.asarray(src.k_pages)[:, 1])
    np.testing.assert_array_equal(np.asarray(dst.v_pages)[:, 2],
                                  np.asarray(src.v_pages)[:, 3])
    # untouched pages stay zero
    np.testing.assert_array_equal(np.asarray(dst.k_pages)[:, 5], 0.0)


def test_migrate_pages_host_bridge_put(role_engines):
    """ops/p2p.migrate_pages_host carries a page payload bit-exactly
    from the prefill role's rank to the decode role's over the bridge
    mesh."""
    pf, dec = role_engines
    bridge = Mesh(np.array([pf.mesh.devices.flat[0],
                            dec.mesh.devices.flat[0]]), ("role",))
    from triton_dist_tpu.ops.p2p import migrate_pages_host

    rng = np.random.RandomState(1)
    k = rng.randn(2, 3, 2, 4, 5).astype(np.float32)
    v = rng.randn(2, 3, 2, 4, 5).astype(np.float32)
    k2, v2 = migrate_pages_host(jnp.asarray(k), jnp.asarray(v), bridge)
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)


# ---------------------------------------------------------------------------
# disaggregated serving (prefill worker | decode worker)
# ---------------------------------------------------------------------------

def _disagg(pf, dec, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page", PAGE)
    kw.setdefault("prefill_buckets", BUCKETS)
    return DisaggServingEngine(dec, prefill_engine=pf, **kw)


def test_disagg_token_exact_vs_solo(role_engines):
    """Disjoint-role serving with p2p page migration: every request's
    greedy tokens equal its solo Engine.serve run (bucket edges
    included)."""
    pf, dec = role_engines
    lens = sorted({max(b + d, 1) for b in BUCKETS for d in (-1, 0, 1)})
    prompts = [[int(t) for t in
                np.random.RandomState(100 + n).randint(0, VOCAB, n)]
               for n in lens]
    want = [_baseline(dec, p, 4) for p in prompts]
    srv = _disagg(pf, dec)
    assert srv.migration == "p2p"
    assert srv.generate(prompts, max_new_tokens=4) == want
    st = srv.stats()
    assert st["roles"] == "prefill|decode/disjoint"
    assert st["migrated_pages"] == sum(
        -(-len(p) // PAGE) for p in prompts)
    assert st["pool"]["used_pages"] == 0
    assert st["prefill_pool"]["used_pages"] == 0, "staging leaked"
    # A prompt's token is its last chunk's own pick, read on the decode
    # side after the migration; a step's is the decode program's.
    assert st["tokens_picked_on_device"] == st["tokens_generated"] == (
        4 * len(prompts))


def test_disagg_migration_bit_exact_rewritten_tables(role_engines):
    """The decode pool's migrated pages hold byte-identical KV to the
    prefill worker's staging pages, under a REWRITTEN (receiver-side)
    block table."""
    pf, dec = role_engines
    srv = _disagg(pf, dec)
    record = {}
    orig = srv._scatter

    def spy(cache, k_pay, v_pay, ids):
        record["k"], record["ids"] = np.asarray(k_pay), np.asarray(ids)
        return orig(cache, k_pay, v_pay, ids)

    srv._scatter = spy
    # Shift the decode allocator (a parked reservation outside the
    # scheduler's slot range) so src and dst page ids must differ.
    srv.manager.alloc_prefill(99, list(range(PAGE)))
    prompt = list(range(1, 14))                       # 2 pages
    h = srv.submit(prompt, max_new_tokens=2)
    while h.status in ("queued", "prefill"):
        srv.step()
    assert h.status == "migrating"
    src_ids = np.asarray(
        srv.prefill_worker.manager.table_row(h.slot), np.int32)
    k_src, _ = srv.prefill_worker.extract(src_ids)
    record["src"], record["src_ids"] = np.asarray(k_src), src_ids
    # Complete the handoff WITHOUT a decode tick, so the pool still
    # holds exactly the migrated bytes when inspected.
    srv._complete_migrations()
    assert h.status == "running"
    n_pages = -(-len(prompt) // PAGE)
    dst_ids = record["ids"][:n_pages]
    assert not np.array_equal(dst_ids, record["src_ids"][:n_pages]), (
        "block table was not rewritten on the receiver")
    np.testing.assert_array_equal(record["k"], record["src"],
                                  err_msg="migrated payload drifted")
    dec_pool = np.asarray(srv.cache.k_pages)
    for i in range(n_pages):
        np.testing.assert_array_equal(
            dec_pool[:, dst_ids[i]], record["src"][:, i],
            err_msg=f"page {i} bytes differ after scatter")
    srv.manager.free_slot(99)
    srv.run()
    assert h.tokens == _baseline(dec, prompt, 2)


def test_disagg_prefix_migrates_once(role_engines):
    """Refcounted prefix pages migrate ONCE: the second sharer's
    handoff skips decode-side-resident pages (and its chunk stream
    skips computing them)."""
    pf, dec = role_engines
    srv = _disagg(pf, dec, prefix_reuse=True)
    shared = list(range(1, 17))                       # two full pages
    p1, p2 = shared + [30, 31], shared + [40]
    want = [_baseline(dec, p1, 3), _baseline(dec, p2, 3)]
    h1 = srv.submit(p1, max_new_tokens=3)
    srv.run()
    first = srv.stats()["migrated_pages"]
    assert first == 3
    h2 = srv.submit(p2, max_new_tokens=3)
    srv.run()
    assert [h1.tokens, h2.tokens] == want
    assert srv.stats()["migrated_pages"] == first + 1, (
        "shared prefix pages re-migrated")


def test_disagg_preempt_resume(role_engines):
    """Mid-decode preemption on the decode worker resumes through the
    prefill worker deterministically — and re-migrates."""
    pf, dec = role_engines
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]]
    want = [_baseline(dec, p, 4) for p in prompts]
    srv = _disagg(pf, dec, num_pages=3)
    hs = [srv.submit(p, max_new_tokens=4) for p in prompts]
    srv.run()
    assert [h.tokens for h in hs] == want
    assert srv.stats()["preemptions"] >= 1
    resumed = max(hs, key=lambda h: len(h.lane))
    start = resumed.chunks[0][0]
    assert [(b, v) for _, b, v in resumed.chunks] == plan_chunks(
        len(resumed.lane) - start, BUCKETS)


def test_disagg_dropped_migration_fails_one_request(role_engines):
    """Fault-plan dropped migration: one request fails, survivors stay
    token-exact, no page leaks on either pool — the server outlives
    its transport."""
    pf, dec = role_engines
    srv = _disagg(pf, dec)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]]
    want = [_baseline(dec, p, 3) for p in prompts]
    hs = [srv.submit(p, max_new_tokens=3) for p in prompts]
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="page_migration", k=0)):
        srv.run()
    statuses = [h.status for h in hs]
    assert statuses.count("failed") == 1, statuses
    for h, w in zip(hs, want):
        if h.status == "failed":
            assert isinstance(h.error, faults.InjectedFault)
        else:
            assert h.status == "done" and h.tokens == w
    st = srv.stats()
    assert st["pool"]["used_pages"] == 0
    assert st["prefill_pool"]["used_pages"] == 0


def test_disagg_dropped_migration_no_prefix_poison(role_engines):
    """A dropped migration must NOT leave decode-side prefix entries
    for pages whose payload never arrived: a later same-prefix request
    migrates its own copy and stays token-exact."""
    pf, dec = role_engines
    srv = _disagg(pf, dec, prefix_reuse=True)
    shared = list(range(1, 17))                       # two full pages
    doomed = srv.submit(shared + [30], max_new_tokens=3)
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="page_migration", k=0)):
        srv.run()
    assert doomed.status == "failed"
    later = srv.submit(shared + [40], max_new_tokens=3)
    srv.run()
    assert later.status == "done"
    assert later.tokens == _baseline(dec, shared + [40], 3)
    # All 3 of later's pages migrated: nothing stale to hit.
    assert srv.stats()["migrated_pages"] == 3


def test_disagg_wedged_migration_times_out_one_request(role_engines):
    """A migration that never completes (watchdog timeout) fails its
    request with CommTimeoutError; the server keeps serving."""
    pf, dec = role_engines
    srv = _disagg(pf, dec, timeout_s=60.0)
    real = srv._scatter

    def wedged(cache, k, v, ids):
        raise CommTimeoutError(op="serving.page_migration", rank=0,
                               timeout_s=0.1, progress=None)

    doomed = srv.submit([1, 2, 3], max_new_tokens=3)
    srv._scatter = wedged
    while doomed.status in ("queued", "prefill"):
        srv.step()
    srv.step()                     # the migration tick — wedged
    srv._scatter = real
    fresh = srv.submit([4, 5], max_new_tokens=2)
    srv.run()
    assert doomed.status == "timeout"
    assert isinstance(doomed.error, CommTimeoutError)
    assert fresh.status == "done"
    assert fresh.tokens == _baseline(dec, [4, 5], 2)
    assert srv.stats()["comm_timeouts"] == 1


def test_disagg_degenerate_single_mesh(engine):
    """Single-role degenerate mode: one engine plays both roles on one
    mesh — chunked prefill + local page migration, same exactness and
    cache bounds."""
    srv = DisaggServingEngine(engine, num_slots=2, page=PAGE,
                              prefill_buckets=BUCKETS)
    assert srv.migration == "local"
    prompts = [[1, 2, 3], list(range(1, 19))]
    want = [_baseline(engine, p, 3) for p in prompts]
    assert srv.generate(prompts, max_new_tokens=3) == want
    st = srv.stats()
    assert st["roles"] == "prefill+decode/colocated"
    assert st["migrated_pages"] == 4
    assert srv.prefill_cache_size() <= len(BUCKETS)
    assert srv.decode_cache_size() == 1


def test_disagg_decode_pool_backpressure(role_engines):
    """A dry DECODE pool at handoff requeues (staging released), and
    the request completes once pages free — no deadlock, no leak."""
    pf, dec = role_engines
    srv = _disagg(pf, dec, num_pages=2)       # one usable decode page
    h1 = srv.submit([1, 2, 3], max_new_tokens=3)
    h2 = srv.submit([4, 5, 6], max_new_tokens=3)
    srv.run()
    assert h1.status == "done" and h2.status == "done"
    assert srv.stats()["admit_stalls"] >= 1
    want = [_baseline(dec, [1, 2, 3], 3), _baseline(dec, [4, 5, 6], 3)]
    assert [h1.tokens, h2.tokens] == want


def test_disagg_rejects_megakernel():
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    cfg = ModelConfig.tiny(vocab_size=128)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mk = MegaKernelEngine(cfg, mesh, batch=2, max_len=16, tile_w=16,
                          t_tile=16)
    with pytest.raises(ValueError, match="megakernel"):
        DisaggServingEngine(mk)
    with pytest.raises(ValueError, match="prefill_buckets mismatch"):
        ServingEngine(mk, prefill_buckets=(4,))
