"""Fault-tolerant serving battery: retry/backoff, prefill-worker
failover, and checkpoint/restore.

The escalation ladder under test (docs/resilience.md, "Failure
semantics"): a transient migration/chunk fault is RETRIED (absorbed,
request unaffected); exhausted retries FAIL ONE request with zero
leaked pages; consecutive post-retry failures declare the prefill
worker dead and FAIL OVER — in-flight requests requeue and finish
token-exact on the surviving role. checkpoint()/restore() round-trips
the full serving state (pools + scales bit-exact, allocator,
queue/slots, counters) and resumes decode token-exact mid-stream.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig, dense
from triton_dist_tpu.resilience import chaos, faults
from triton_dist_tpu.resilience.policy import RetryPolicy
from triton_dist_tpu.resilience.watchdog import (
    CommTimeoutError, HealthTracker,
)
from triton_dist_tpu.serving import DisaggServingEngine, ServingEngine
from triton_dist_tpu.serving.server import (
    load_checkpoint, save_checkpoint,
)

CFG = ModelConfig.tiny()
TINY = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                        intermediate_size=32, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=4,
                        head_dim=8)
MAX_LEN = 64
PAGE = 8
BUCKETS = (4, 16)


@pytest.fixture(scope="module")
def role_engines():
    params = dense.init_params(jax.random.PRNGKey(3), CFG)
    devs = jax.devices()
    pf = Engine(CFG, Mesh(np.array(devs[:2]), ("tp",)), mode="xla",
                max_len=MAX_LEN, params=params)
    dec = Engine(CFG, Mesh(np.array(devs[2:4]), ("tp",)), mode="xla",
                 max_len=MAX_LEN, params=params)
    return pf, dec


@pytest.fixture(scope="module")
def tiny_engine():
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    return Engine(TINY, mesh, mode="xla", max_len=96, seed=0)


def _baseline(engine, prompt, gen_len):
    n = engine.mesh.shape[engine.axis]
    ids = jnp.asarray(np.tile(np.asarray([prompt], np.int32), (n, 1)))
    return np.asarray(engine.serve(ids, gen_len=gen_len))[0].tolist()


def _disagg(role_engines, **kw):
    pf, dec = role_engines
    kw.setdefault("num_slots", 2)
    kw.setdefault("page", PAGE)
    kw.setdefault("prefill_buckets", BUCKETS)
    return DisaggServingEngine(dec, prefill_engine=pf, **kw)


# ---------------------------------------------------------------------------
# RetryPolicy units (pure host logic)
# ---------------------------------------------------------------------------

def test_retry_policy_deterministic_schedule():
    pol = RetryPolicy(max_attempts=4, base_delay_s=0.5, multiplier=2.0,
                      max_delay_s=1.5, jitter=0.5, seed=9)
    assert pol.delays() == pol.delays(), "seeded jitter must replay"
    assert len(pol.delays()) == 3
    nj = RetryPolicy(max_attempts=4, base_delay_s=0.5, multiplier=2.0,
                     max_delay_s=1.5)
    assert nj.delays() == (0.5, 1.0, 1.5)   # capped at max_delay_s
    for got, base in zip(pol.delays(), nj.delays()):
        assert base <= got <= base * 1.5    # jitter in [0, 50%]


def test_retry_policy_absorbs_then_exhausts():
    calls = []

    def flaky(fail_n):
        def fn():
            calls.append(1)
            if len(calls) <= fail_n:
                raise TimeoutError("transient")
            return "ok"
        return fn

    pol = RetryPolicy(max_attempts=3)
    out, n = pol.call(flaky(2), retry_on=(TimeoutError,),
                      sleep=lambda d: None)
    assert (out, n) == ("ok", 3)
    calls.clear()
    with pytest.raises(TimeoutError):
        pol.call(flaky(99), retry_on=(TimeoutError,),
                 sleep=lambda d: None)
    assert len(calls) == 3, "max_attempts bounds total tries"


def test_retry_policy_non_retryable_propagates():
    pol = RetryPolicy(max_attempts=5)
    calls = []

    def fn():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        pol.call(fn, retry_on=(TimeoutError,), sleep=lambda d: None)
    assert len(calls) == 1, "a non-transient must not be retried"


def test_retry_policy_deadline_bounds_wall_clock():
    pol = RetryPolicy(max_attempts=100, base_delay_s=10.0)
    calls = []

    def fn():
        calls.append(1)
        raise TimeoutError("down")

    with pytest.raises(TimeoutError):
        pol.call(fn, retry_on=(TimeoutError,), deadline_s=1.0,
                 sleep=lambda d: None)
    assert len(calls) == 1, ("the next 10s backoff would exceed the "
                             "1s deadline — stop immediately")


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)
    # engine-side validation of the retry knob
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(TINY, mesh, mode="xla", max_len=32, seed=0)
    with pytest.raises(TypeError):
        ServingEngine(eng, num_slots=2, page=8, retry="3 times")
    with pytest.raises(TypeError):
        ServingEngine(eng, num_slots=2, page=8,
                      retry={"page_migration": 3})


def test_health_tracker_thresholds():
    t = [0.0]
    ht = HealthTracker(fail_threshold=2, dead_after_s=5.0,
                       clock=lambda: t[0])
    assert not ht.fail("a")
    ht.beat()                      # progress resets the streak
    assert not ht.fail("b")
    assert ht.fail("c"), "2 consecutive failures cross the threshold"
    assert ht.dead and not ht.fail("d"), "death fires exactly once"
    ht2 = HealthTracker(fail_threshold=3, dead_after_s=5.0,
                        clock=lambda: t[0])
    t[0] = 6.0
    assert ht2.stalled()
    assert ht2.declare_dead("stall") and not ht2.declare_dead("again")


# ---------------------------------------------------------------------------
# Migration/chunk retry through the serving loop
# ---------------------------------------------------------------------------

def test_transient_migration_retried_token_exact(role_engines):
    pf, dec = role_engines
    srv = _disagg(role_engines, retry=RetryPolicy(max_attempts=3))
    h = srv.submit([1, 2, 3, 4, 5], max_new_tokens=4)
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="page_migration", k=0)):
        srv.run()
    assert h.status == "done", (h.status, h.error)
    assert h.tokens == _baseline(dec, [1, 2, 3, 4, 5], 4)
    st = srv.stats()
    assert st["retries"] >= 1 and st["failovers"] == 0
    chaos.check_invariants(srv)


def test_transient_wedged_chunk_retried(role_engines):
    pf, dec = role_engines
    srv = _disagg(role_engines, retry=RetryPolicy(max_attempts=2))
    h = srv.submit(list(range(1, 10)), max_new_tokens=3)
    with faults.inject(faults.get_plan("wedge_kth_call",
                                       op="chunked_prefill", k=0)):
        srv.run()
    assert h.status == "done" and h.tokens == _baseline(
        dec, list(range(1, 10)), 3)
    st = srv.stats()
    assert st["retries"] >= 1
    assert st["comm_timeouts"] >= 1, ("a timeout_call wedge surfaces "
                                      "as a CommTimeoutError")
    chaos.check_invariants(srv)


def test_no_retry_configured_keeps_fail_one(role_engines):
    """Without a policy the pre-existing containment is untouched:
    one dropped migration fails one request, zero retries."""
    srv = _disagg(role_engines, failover=False)
    h = srv.submit([7, 7, 7], max_new_tokens=3)
    with faults.inject(faults.FaultPlan(
            name="hard", faults=(faults.Fault(
                "fail_call", op="page_migration", k=None),))):
        for _ in range(20):
            if h.done:
                break
            srv.step()
    assert h.status == "failed" and srv.stats()["retries"] == 0
    # the server survives: a fresh request serves normally
    ok = srv.submit([5, 5], max_new_tokens=3)
    srv.run()
    assert ok.status == "done"
    chaos.check_invariants(srv)


def test_retry_exhausted_retires_with_zero_leaked_pages(role_engines):
    """The _retire audit: 3 consecutive failed migrations (retries
    exhausted each time) must release decode pages, staging pages AND
    the prefill-worker slot — both pools fully free afterwards."""
    srv = _disagg(role_engines, retry=RetryPolicy(max_attempts=2),
                  failover=False, prefix_reuse=False)
    hs = [srv.submit([i + 1, i + 2, i + 3], max_new_tokens=3)
          for i in range(3)]
    with faults.inject(faults.FaultPlan(
            name="hard", faults=(faults.Fault(
                "fail_call", op="page_migration", k=None),))):
        for _ in range(60):
            if all(h.done for h in hs):
                break
            srv.step()
    assert [h.status for h in hs] == ["failed"] * 3
    st = srv.stats()
    assert st["pool"]["free_pages"] == st["pool"]["num_pages"] - 1, (
        f"decode pages leaked: {st['pool']}")
    assert (st["prefill_pool"]["free_pages"]
            == st["prefill_pool"]["num_pages"] - 1), (
        f"staging pages leaked: {st['prefill_pool']}")
    assert st["retries"] == 3, "one retry per request before giving up"
    assert not srv.sched.slots, "prefill-worker slots all recycled"
    chaos.check_invariants(srv)


# ---------------------------------------------------------------------------
# The decode batch aboard a chunk program: one dispatch, both fault scopes
# ---------------------------------------------------------------------------

def _riding(tiny_engine, **kw):
    """A server with one request decoding and a second just submitted,
    so that the next tick's chunk program carries the decode batch."""
    srv = ServingEngine(tiny_engine, num_slots=2, page=PAGE,
                        prefill_buckets=BUCKETS, **kw)
    ok = srv.submit([1, 2, 3], max_new_tokens=6)
    srv.step()
    assert ok.status == "running"
    new = srv.submit(list(range(4, 13)), max_new_tokens=3)
    return srv, ok, new


@pytest.mark.parametrize("op", ["chunked_prefill", "serving_decode"])
def test_dropped_fused_dispatch_retried_token_exact(tiny_engine, op):
    """The dispatch that carries both a chunk and the decode batch
    opens both fault scopes; a transient drop at either is absorbed by
    one retry, and both requests serve the tokens of a clean run."""
    srv, ok, new = _riding(tiny_engine,
                           retry=RetryPolicy(max_attempts=2))
    with faults.inject(faults.get_plan("fail_kth_call", op=op, k=0)):
        srv.run()
    assert (ok.status, new.status) == ("done", "done")
    assert ok.tokens == _baseline(tiny_engine, [1, 2, 3], 6)
    assert new.tokens == _baseline(tiny_engine, list(range(4, 13)), 3)
    st = srv.stats()
    assert st["retries"] == 1
    assert 0 < st["decode_dispatches_fused"] <= st["decode_dispatches"]
    chaos.check_invariants(srv)


@pytest.mark.parametrize("op,failed", [("chunked_prefill", "new"),
                                       ("serving_decode", "both")])
def test_dropped_fused_dispatch_contained_by_scope(tiny_engine, op,
                                                   failed):
    """No retry armed: a drop at the chunk's scope fails the chunk's
    request alone and the decoder stays token-exact; a drop at the
    decode's scope is the decode tick's containment (its victim), and
    the chunk aboard the same dispatch goes with it. The server
    survives either."""
    srv, ok, new = _riding(tiny_engine)
    with faults.inject(faults.get_plan("fail_kth_call", op=op, k=0)):
        srv.run()
    assert new.status == "failed"
    assert isinstance(new.error, faults.InjectedFault)
    if failed == "new":
        assert ok.status == "done"
        assert ok.tokens == _baseline(tiny_engine, [1, 2, 3], 6)
    else:
        assert ok.status == "failed"
    assert srv.stats()["retries"] == 0
    assert srv.stats()["pool"]["used_pages"] == 0, "pages leaked"
    again = srv.submit([5, 5], max_new_tokens=3)
    srv.run()
    assert again.tokens == _baseline(tiny_engine, [5, 5], 3)
    chaos.check_invariants(srv)


def test_dropped_second_chunk_of_a_riding_tick_fails_its_request_alone(
        tiny_engine):
    """A riding tick runs the oldest prompt's consecutive chunks: a
    drop at the second of them (its decode rows parked) fails that
    request alone, the decoder's step stands, and the tick's rows go
    on to the next-oldest prompt."""
    srv = ServingEngine(tiny_engine, num_slots=3, page=PAGE,
                        prefill_buckets=BUCKETS)
    ok = srv.submit([1, 2, 3], max_new_tokens=6)
    srv.step()
    assert ok.status == "running"
    # A two-program tail stays two programs (4+4+3 would be one of 16).
    doomed = srv.submit(list(range(4, 11)), max_new_tokens=3)  # 4+3
    nxt = srv.submit(list(range(20, 26)), max_new_tokens=3)    # 4+2
    # The first tick is still in flight (nothing forbade launching
    # ahead of it): the tokens it owes count, and the armed plan lands
    # it before the faulted tick is launched.
    lens, told = int(srv._lens[ok.slot]), len(ok.tokens) + ok.in_flight
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="chunked_prefill", k=1)):
        assert srv.step() == 1
    assert doomed.status == "failed"
    assert isinstance(doomed.error, faults.InjectedFault)
    assert doomed.chunks == [(0, 4, 4)], "its first chunk carried the batch"
    # As after a sound tick: one token more, one position further.
    assert (int(srv._lens[ok.slot]), len(ok.tokens)) == (lens + 1, told + 1)
    assert nxt.chunks == [(0, 4, 4), (4, 4, 2)] and nxt.status == "running"
    srv.run()
    assert ok.tokens == _baseline(tiny_engine, [1, 2, 3], 6)
    assert nxt.tokens == _baseline(tiny_engine, list(range(20, 26)), 3)
    st = srv.stats()
    assert st["decode_dispatches_fused"] == 1 and st["retries"] == 0
    assert st["pool"]["used_pages"] == 0, "pages leaked"
    chaos.check_invariants(srv)


# ---------------------------------------------------------------------------
# Prefill-worker failover
# ---------------------------------------------------------------------------

def test_hard_faults_declare_worker_dead_and_fail_over(role_engines):
    pf, dec = role_engines
    srv = _disagg(role_engines, retry=RetryPolicy(max_attempts=2),
                  worker_fail_threshold=1)
    h = srv.submit([9, 8, 7, 6, 5, 4], max_new_tokens=4)
    with faults.inject(faults.FaultPlan(
            name="hard", faults=(faults.Fault(
                "fail_call", op="page_migration", k=None),))):
        for _ in range(30):
            if srv._drained():
                break
            srv.step()
    srv.run()
    st = srv.stats()
    assert st["failovers"] == 1
    assert st["roles"] == "prefill+decode/failover-local"
    assert srv.prefill_worker is None and srv.migration == "local"
    # The request the final failure hit was REQUEUED, not failed, and
    # finished token-exact on the local path.
    assert h.status == "done"
    assert h.tokens == _baseline(dec, [9, 8, 7, 6, 5, 4], 4)
    chaos.check_invariants(srv)


def test_operator_kill_mid_stream_token_exact(role_engines):
    pf, dec = role_engines
    srv = _disagg(role_engines)
    long_p = list(range(1, 12))
    h1 = srv.submit(long_p, max_new_tokens=5)
    h2 = srv.submit([5, 5], max_new_tokens=5)
    srv.step()
    srv.step()      # h1 mid-chunk-stream / mid-migration
    assert srv.fail_prefill_worker()
    assert not srv.fail_prefill_worker(), "second kill is a no-op"
    srv.run()
    assert h1.tokens == _baseline(dec, long_p, 5)
    assert h2.tokens == _baseline(dec, [5, 5], 5)
    assert srv.stats()["failovers"] == 1
    assert srv.stats()["dead_prefill_workers"] == 1
    chaos.check_invariants(srv)


def test_failover_to_surviving_standby_worker():
    """N>1 prefill workers: killing the active one moves prefill to
    the standby (still a WORKER role, not the local path), then
    killing that one degrades to local."""
    params = dense.init_params(jax.random.PRNGKey(3), CFG)
    devs = jax.devices()
    pf_a = Engine(CFG, Mesh(np.array(devs[:2]), ("tp",)), mode="xla",
                  max_len=MAX_LEN, params=params)
    pf_b = Engine(CFG, Mesh(np.array(devs[4:6]), ("tp",)), mode="xla",
                  max_len=MAX_LEN, params=params)
    dec = Engine(CFG, Mesh(np.array(devs[2:4]), ("tp",)), mode="xla",
                 max_len=MAX_LEN, params=params)
    srv = DisaggServingEngine(dec, prefill_engines=[pf_a, pf_b],
                              num_slots=2, page=PAGE,
                              prefill_buckets=BUCKETS)
    assert srv.stats()["prefill_workers"] == 2
    h1 = srv.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=4)
    srv.step()
    assert srv.fail_prefill_worker()
    assert srv.prefill_worker is srv.prefill_workers[1], (
        "standby worker takes over")
    srv.run()
    assert h1.tokens == _baseline(dec, [1, 2, 3, 4, 5, 6, 7], 4)
    h2 = srv.submit([9, 9, 2], max_new_tokens=4)
    assert srv.fail_prefill_worker()
    srv.run()
    assert srv.prefill_worker is None, "no survivors -> local path"
    assert h2.tokens == _baseline(dec, [9, 9, 2], 4)
    assert srv.stats()["failovers"] == 2
    assert srv.stats()["dead_prefill_workers"] == 2
    chaos.check_invariants(srv)


def test_prefill_engine_and_engines_mutually_exclusive(role_engines):
    pf, dec = role_engines
    with pytest.raises(ValueError):
        DisaggServingEngine(dec, prefill_engine=pf,
                            prefill_engines=[pf], num_slots=2,
                            page=PAGE, prefill_buckets=BUCKETS)


# ---------------------------------------------------------------------------
# Checkpoint / restore
# ---------------------------------------------------------------------------

def test_checkpoint_restore_mid_run_token_exact(tiny_engine):
    """The kill/restore drill: snapshot mid-decode, rebuild a fresh
    engine, restore, finish — every request token-exact vs the
    uninterrupted run."""
    eng = tiny_engine
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    want = [_baseline(eng, p, 6) for p in prompts]
    srv = ServingEngine(eng, num_slots=2, page=8)
    hs = [srv.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):
        srv.step()      # two running mid-stream, one still queued
    snap = srv.checkpoint()
    fresh = ServingEngine(eng, num_slots=2, page=8)
    revived = fresh.restore(snap)
    assert len(revived) == 3
    assert fresh.stats()["restored_requests"] == 3
    fresh.run()
    got = {h.request.request_id: h.tokens for h in revived}
    for h, w in zip(hs, want):
        assert got[h.request.request_id] == w
    chaos.check_invariants(fresh)


def test_checkpoint_is_side_effect_free(tiny_engine):
    """checkpoint() observes; the live engine must finish exactly as
    if it had never been called."""
    eng = tiny_engine
    srv = ServingEngine(eng, num_slots=2, page=8)
    h = srv.submit([3, 1, 4, 1], max_new_tokens=6)
    srv.step()
    before = srv.manager.snapshot()
    srv.checkpoint()
    assert srv.manager.snapshot() == before
    srv.run()
    assert h.tokens == _baseline(eng, [3, 1, 4, 1], 6)


def test_restore_prefix_shared_pages_and_refcounts(tiny_engine):
    """Prefix-shared pages restore with their LIVE refcounts: two
    sharers + the cache ref survive the round-trip, and a post-restore
    third sharer still hits the warm prefix cache."""
    eng = tiny_engine
    pre = list(range(1, 9))                    # one full shared page
    srv = ServingEngine(eng, num_slots=2, page=8, prefix_reuse=True)
    h1 = srv.submit(pre + [20, 21], max_new_tokens=6)
    h2 = srv.submit(pre + [30], max_new_tokens=6)
    for _ in range(3):
        srv.step()
    assert srv.manager.prefix_hits(h2.slot) == 1
    snap = srv.checkpoint()
    fresh = ServingEngine(eng, num_slots=2, page=8, prefix_reuse=True)
    revived = fresh.restore(snap)
    assert fresh.manager._refs == srv.manager._refs
    assert fresh.manager._prefix == srv.manager._prefix
    fresh.run()
    got = {h.request.request_id: h.tokens for h in revived}
    ref = ServingEngine(eng, num_slots=2, page=8, prefix_reuse=True)
    want = ref.generate([pre + [20, 21], pre + [30]], max_new_tokens=6)
    assert [got[h1.request.request_id],
            got[h2.request.request_id]] == want
    # warm cache: a new same-prefix request hits without recompute
    hits0 = fresh.manager.stats["prefix_hits"]
    h3 = fresh.submit(pre + [40], max_new_tokens=2)
    fresh.run()
    assert fresh.manager.stats["prefix_hits"] > hits0
    assert h3.status == "done"
    chaos.check_invariants(fresh)


@pytest.mark.parametrize("kvd", ["int8", "fp8"])
def test_restore_quantized_pool_scales_bit_exact(tiny_engine, kvd):
    eng = tiny_engine
    srv = ServingEngine(eng, num_slots=2, page=8, kv_dtype=kvd)
    hs = [srv.submit([1, 2, 3, 4, 5], max_new_tokens=6),
          srv.submit([9, 8], max_new_tokens=6)]
    for _ in range(2):
        srv.step()
    snap = srv.checkpoint()
    # cross-process fidelity: the snapshot must survive pickling
    # (ml_dtypes fp8 pools included)
    import pickle

    snap = pickle.loads(pickle.dumps(snap))
    fresh = ServingEngine(eng, num_slots=2, page=8, kv_dtype=kvd)
    revived = fresh.restore(snap)
    np.testing.assert_array_equal(np.asarray(fresh.cache.k_scale),
                                  np.asarray(srv.cache.k_scale))
    np.testing.assert_array_equal(np.asarray(fresh.cache.v_scale),
                                  np.asarray(srv.cache.v_scale))
    np.testing.assert_array_equal(
        np.asarray(fresh.cache.k_pages).view(np.uint8),
        np.asarray(srv.cache.k_pages).view(np.uint8))
    fresh.run()
    ref = ServingEngine(eng, num_slots=2, page=8, kv_dtype=kvd)
    want = ref.generate([[1, 2, 3, 4, 5], [9, 8]], max_new_tokens=6)
    got = {h.request.request_id: h.tokens for h in revived}
    assert [got[h.request.request_id] for h in hs] == want
    chaos.check_invariants(fresh)


def test_restore_mid_speculative_draft(tiny_engine):
    """Checkpoint with spec_k active (rollback mirrors mid-flight):
    the restored engine's spec loop continues token-exact vs the
    non-spec greedy oracle."""
    eng = tiny_engine
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    srv = ServingEngine(eng, num_slots=2, page=8, spec_k=3)
    h = srv.submit(prompt, max_new_tokens=10)
    for _ in range(2):
        srv.step()
    snap = srv.checkpoint()
    fresh = ServingEngine(eng, num_slots=2, page=8, spec_k=3)
    revived = fresh.restore(snap)
    fresh.run()
    assert revived[0].tokens == _baseline(eng, prompt, 10)
    assert fresh.decode_cache_size() == 1
    chaos.check_invariants(fresh)


def test_restore_rejects_mismatched_plan(tiny_engine):
    eng = tiny_engine
    srv = ServingEngine(eng, num_slots=2, page=8)
    srv.submit([1, 2], max_new_tokens=2)
    srv.step()
    snap = srv.checkpoint()
    with pytest.raises(ValueError, match="mismatch"):
        ServingEngine(eng, num_slots=4, page=8).restore(snap)
    with pytest.raises(ValueError, match="mismatch"):
        ServingEngine(eng, num_slots=2, page=8,
                      kv_dtype="int8").restore(snap)
    with pytest.raises(ValueError, match="not a serving checkpoint"):
        ServingEngine(eng, num_slots=2, page=8).restore({"meta": {}})
    busy = ServingEngine(eng, num_slots=2, page=8)
    busy.submit([1], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="idle"):
        busy.restore(snap)
    srv.run()


def test_checkpoint_file_roundtrip_atomic(tiny_engine, tmp_path):
    eng = tiny_engine
    srv = ServingEngine(eng, num_slots=2, page=8)
    srv.submit([1, 2, 3], max_new_tokens=4)
    srv.step()
    path = str(tmp_path / "serving.ckpt")
    save_checkpoint(srv.checkpoint(), path)
    snap = load_checkpoint(path)
    fresh = ServingEngine(eng, num_slots=2, page=8)
    revived = fresh.restore(snap)
    fresh.run()
    assert revived[0].tokens == _baseline(eng, [1, 2, 3], 4)
    leftovers = [p for p in tmp_path.iterdir() if "tmp" in p.name]
    assert not leftovers, "atomic save must not strand temp files"
    srv.run()


def test_disagg_checkpoint_requeues_inflight(role_engines):
    """Disaggregated checkpoint: mid-prefill / mid-migration work
    snapshots as QUEUED (partial staging dropped), restores into a
    fresh two-role engine, finishes token-exact."""
    pf, dec = role_engines
    srv = _disagg(role_engines, prefix_reuse=True)
    long_p = list(range(1, 12))
    h1 = srv.submit(long_p, max_new_tokens=4)
    h2 = srv.submit([5, 5], max_new_tokens=4)
    srv.step()          # h1 mid-chunk-stream
    snap = srv.checkpoint()
    fresh = _disagg(role_engines, prefix_reuse=True)
    revived = fresh.restore(snap)
    fresh.run()
    got = {h.request.request_id: h.tokens for h in revived}
    assert got[h1.request.request_id] == _baseline(dec, long_p, 4)
    assert got[h2.request.request_id] == _baseline(dec, [5, 5], 4)
    chaos.check_invariants(fresh)
    srv2_stats = fresh.stats()
    assert srv2_stats["restored_requests"] == 2


# One megakernel engine per kv_dtype for the module: restore()
# overwrites pools/scales wholesale, so even the "fresh process"
# half of the round-trip can share the engine (what a real fresh
# process repacks — the weights — is identical by construction).
_MK_ENGINES: dict = {}


def _mk_serving(kv_dtype="bf16"):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    if kv_dtype not in _MK_ENGINES:
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        _MK_ENGINES[kv_dtype] = MegaKernelEngine(
            TINY, mesh, batch=2, max_len=32, tile_w=16, t_tile=16,
            paged=True, page=16, num_pages=5, kv_dtype=kv_dtype)
    return ServingEngine(_MK_ENGINES[kv_dtype], kv_dtype=kv_dtype)


@pytest.mark.parametrize("kvd", ["bf16", "int8"])
def test_megakernel_checkpoint_restore_token_exact(kvd):
    """The converted mk-reject: a schema-driven checkpoint (KV pools +
    scale tables + counters by arena-region name) restores into a
    FRESH megakernel engine and resumes mid-stream decode token-exact
    — bit-exact pools at bf16 AND int8. A mid-prefill-LANE request
    snapshots as queued and re-prefills deterministically."""
    prompts = [[5, 6, 7], [3, 4]]
    want = _mk_serving(kvd).generate(prompts, max_new_tokens=4)
    srv = _mk_serving(kvd)
    h0 = srv.submit(prompts[0], max_new_tokens=4)
    for _ in range(4):       # h0 mid-decode: its prompt, and one step on
        srv.step()
    h1 = srv.submit(prompts[1], max_new_tokens=4)
    srv.step()               # h1 mid-prefill-lane
    assert h0.status == "running" and h0.tokens
    snap = srv.checkpoint()
    fresh = _mk_serving(kvd)
    revived = {h.request.request_id: h for h in fresh.restore(snap)}
    fresh.run()
    got = [revived[h0.request.request_id].tokens,
           revived[h1.request.request_id].tokens]
    assert got == want, (kvd, got, want)
    assert fresh.stats()["restored_requests"] == 2
    assert fresh.stats()["mk_checkpointable"] is True
    chaos.check_invariants(fresh)


def test_megakernel_checkpoint_file_roundtrip(tmp_path):
    """The pickle path carries the mk snapshot too (int8 pool bytes
    view-round-trip through numpy, scale planes exact)."""
    from triton_dist_tpu.serving.server import (load_checkpoint,
                                                save_checkpoint)

    srv = _mk_serving("int8")
    srv.submit([5, 6, 7], max_new_tokens=6)
    for _ in range(5):
        srv.step()
    snap = srv.checkpoint()
    p = save_checkpoint(snap, str(tmp_path / "mk.ckpt"))
    snap2 = load_checkpoint(p)
    np.testing.assert_array_equal(
        snap["cache"]["k_cache"].view(np.uint8),
        snap2["cache"]["k_cache"].view(np.uint8))
    np.testing.assert_array_equal(snap["cache"]["k_scale"],
                                  snap2["cache"]["k_scale"])
    fresh = _mk_serving("int8")
    revived = fresh.restore(snap2)
    fresh.run()
    assert all(h.status == "done" for h in revived)


def test_megakernel_checkpoint_meta_mismatch_rejected():
    """A layer-path snapshot cannot restore into an mk engine (and
    vice versa): the engine_kind meta key fails the plan check."""
    srv = _mk_serving()
    snap = srv.checkpoint()
    snap["meta"]["engine_kind"] = "layer"
    fresh = _mk_serving()
    with pytest.raises(ValueError, match="plan mismatch"):
        fresh.restore(snap)


# ---------------------------------------------------------------------------
# migrate_pages_host's own retry knob (ops/p2p.py surface)
# ---------------------------------------------------------------------------

def test_migrate_pages_host_retry_param():
    """The op-level retry knob: same bit-exact payload through the
    bridge put whether or not a policy wraps it."""
    from triton_dist_tpu.ops.p2p import migrate_pages_host

    devs = jax.devices()
    bridge = Mesh(np.array(devs[:2]), ("role",))
    k = np.arange(2 * 3 * 2 * 4 * 2, dtype=np.float32).reshape(
        2, 3, 2, 4, 2)
    v = k + 100.0
    kk, vv = migrate_pages_host(k, v, bridge, axis="role", src=0,
                                dst=1, retry=RetryPolicy(max_attempts=2))
    np.testing.assert_array_equal(kk, k)
    np.testing.assert_array_equal(vv, v)
