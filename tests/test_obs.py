"""Observability battery: span timelines, latency histograms, and the
merged Perfetto export.

Everything timeline-shaped runs under an injected fake clock (the
scheduler's clock IS the telemetry clock), so span orderings and
TTFT/ITL values are deterministic. The bit-exactness block is the
subsystem's core contract: telemetry="spans" is pure host-side
bookkeeping — token outputs and every jit no-growth gate are identical
to telemetry="off".
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig, dense
from triton_dist_tpu.obs import (
    SPAN_KINDS, EventLog, HistogramSet, LatencyHistogram, Span,
    Telemetry,
)
from triton_dist_tpu.resilience import chaos, faults
from triton_dist_tpu.resilience.policy import RetryPolicy
from triton_dist_tpu.resilience.watchdog import HealthTracker
from triton_dist_tpu.serving import DisaggServingEngine, ServingEngine

CFG = ModelConfig.tiny()
MAX_LEN = 64
PAGE = 8
TP = 4


@pytest.fixture(scope="module")
def engine():
    mesh = Mesh(np.array(jax.devices()[:TP]), ("tp",))
    return Engine(CFG, mesh, mode="xla", max_len=MAX_LEN, seed=3)


@pytest.fixture(scope="module")
def role_engines():
    params = dense.init_params(jax.random.PRNGKey(3), CFG)
    devs = jax.devices()
    pf = Engine(CFG, Mesh(np.array(devs[:2]), ("tp",)), mode="xla",
                max_len=MAX_LEN, params=params)
    dec = Engine(CFG, Mesh(np.array(devs[2:4]), ("tp",)), mode="xla",
                 max_len=MAX_LEN, params=params)
    return pf, dec


def _kinds(srv, request_id=None):
    """Ordered span kinds from the engine's event log (optionally
    filtered to one request's timeline)."""
    return [s.kind for s in srv.obs.log.spans()
            if request_id is None or s.request_id == request_id]


# ---------------------------------------------------------------------------
# Histogram bucket math + percentile summaries (pure host units)
# ---------------------------------------------------------------------------

def test_histogram_bucket_boundaries_geometric():
    h = LatencyHistogram(lo=1e-3, hi=1e3, buckets_per_decade=6)
    ratios = [b2 / b1 for b1, b2 in zip(h.bounds, h.bounds[1:])]
    assert all(abs(r - h.ratio) < 1e-9 for r in ratios)
    assert abs(h.bounds[0] - 1e-3) < 1e-12
    assert abs(h.bounds[-1] - 1e3) < 1e-9
    # 6 decades x 6 buckets/decade = 36 buckets -> 37 bounds.
    assert len(h.bounds) == 37


def test_histogram_bucket_index_edges():
    h = LatencyHistogram(lo=1e-3, hi=1e3, buckets_per_decade=6)
    assert h.bucket_index(1e-4) == 0          # underflow
    assert h.bucket_index(1e-3) == 1          # exactly lo -> bucket 1
    assert h.bucket_index(2e3) == len(h.bounds)   # overflow
    # A value inside bucket i sits in [bounds[i-1], bounds[i]).
    for v in (0.002, 0.5, 7.0, 999.0):
        i = h.bucket_index(v)
        assert h.bounds[i - 1] <= v < h.bounds[i]


def test_histogram_percentiles_bounded_relative_error():
    h = LatencyHistogram()
    vals = [0.001 * (1.3 ** i) for i in range(40)]   # 1ms .. ~36s
    for v in vals:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 40
    exact = sorted(vals)
    for q, key in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
        want = exact[max(0, math.ceil(q * 40) - 1)] * 1e3
        got = s[key]
        assert want / h.ratio <= got <= want * h.ratio, (
            f"{key}: {got} vs exact {want} (ratio {h.ratio})")
    assert s["min"] == pytest.approx(min(vals) * 1e3, rel=1e-6)
    assert s["max"] == pytest.approx(max(vals) * 1e3, rel=1e-6)
    assert s["mean"] == pytest.approx(
        sum(vals) / 40 * 1e3, rel=1e-4)


def test_histogram_single_value_clamped():
    h = LatencyHistogram()
    h.observe(0.0075)
    s = h.summary()
    # The bucket midpoint is clamped to the observed min/max, so a
    # 1-sample histogram answers exactly.
    assert s["p50"] == s["p99"] == pytest.approx(7.5, rel=1e-6)
    assert h.summary()["count"] == 1
    assert LatencyHistogram().summary() is None


def test_histogram_set_tenant_grouping():
    hs = HistogramSet()
    hs.observe("ttft", 0.010, tenant="a")
    hs.observe("ttft", 0.020, tenant="b")
    hs.observe("ttft", 0.030)                 # untagged
    s = hs.summary()
    assert s["ttft"]["count"] == 3, "aggregate counts every observation"
    assert s["per_tenant"]["a"]["ttft"]["count"] == 1
    assert s["per_tenant"]["b"]["ttft"]["count"] == 1


# ---------------------------------------------------------------------------
# Event ring + JSONL round-trip
# ---------------------------------------------------------------------------

def test_event_log_ring_bounding():
    log = EventLog(capacity=8)
    for i in range(20):
        log.append(Span(kind="submit", t0=float(i)))
    assert len(log) == 8 and log.total == 20 and log.dropped == 12
    assert [s.t0 for s in log.spans()] == [float(i) for i in
                                           range(12, 20)]


def test_event_log_jsonl_roundtrip(tmp_path):
    log = EventLog(capacity=16)
    log.append(Span(kind="decode", t0=1.0, t1=2.5, step=3,
                    attrs={"batch": 2}))
    log.append(Span(kind="retry", t0=3.0, request_id="req-1",
                    slot=1, tenant="t0", attrs={"op": "x"}))
    p = log.to_jsonl(str(tmp_path / "log.jsonl"))
    back = EventLog.from_jsonl(p)
    assert [s.to_dict() for s in back.spans()] == [
        s.to_dict() for s in log.spans()]
    # and the lines are plain JSON (one span per line)
    lines = open(p).read().splitlines()
    assert len(lines) == 2 and json.loads(lines[0])["kind"] == "decode"


def test_span_taxonomy_well_formed():
    assert len(set(SPAN_KINDS)) == len(SPAN_KINDS)
    for k in ("queue_wait", "prefill_chunk", "migration", "decode",
              "spec_verify", "retry", "failover", "preempt",
              "checkpoint", "restore", "chaos_fault"):
        assert k in SPAN_KINDS


# The tick's own spans: the root, the leaves that tile it, and submit.
TICK_KINDS = ("tick", "schedule", "decode_prep", "decode_enqueue",
              "decode_wait", "decode_fetch", "prefill_fetch", "sample",
              "emit", "submit")


@pytest.mark.parametrize("kind", TICK_KINDS)
def test_tick_span_kinds_in_taxonomy_and_op_histograms(kind):
    from triton_dist_tpu.obs.telemetry import _OP_HIST_KINDS

    assert kind in SPAN_KINDS
    assert kind in _OP_HIST_KINDS, (
        f"{kind} must reach stats()['latency']['ops'] in untraced runs")


# ---------------------------------------------------------------------------
# Telemetry facade modes
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what a
    profiler capture would be handed."""
    seen = []

    def __init__(self, name, **stats):
        self.seen.append((name, stats))

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("mode", ["off", "counters", "spans"])
def test_every_span_and_event_is_a_profiler_annotation(mode, monkeypatch):
    from triton_dist_tpu.obs import telemetry

    seen = []
    tel = Telemetry(mode)
    # Outside a capture (TraceMe's own switch) nothing is built.
    assert tel._annotation("decode", {"step": 1}) is telemetry._NULL
    monkeypatch.setattr(_FakeAnnotation, "seen", seen)
    monkeypatch.setattr(telemetry, "TraceAnnotation", _FakeAnnotation)
    with tel.span("tick", tick=4):
        with tel.span("decode", step=9, batch=2, tenant="acme"):
            pass
        tel.event("admit", request_id="r1", slot=0, waited_ms=1.5)
    tel.event("retry", op="migration")
    tel.complete_span("queue_wait", 0.0, 1.0, request_id="r1")
    if mode == "off":
        assert seen == [], "off mode hands the profiler nothing"
        assert tel.span("decode") is tel.span("tick", tick=0), (
            "off mode allocates no span object")
        return
    # Correlation keys and the two counts ride as stats; the open
    # tick's index reaches everything inside it and nothing after it;
    # other attrs (tenant, op) stay out; a back-dated span is none.
    assert seen == [
        ("tdt.tick", {"tick": 4}),
        ("tdt.decode", {"step": 9, "batch": 2, "tick": 4}),
        ("tdt.admit", {"request_id": "r1", "slot": 0, "waited_ms": 1.5,
                       "tick": 4}),
        ("tdt.retry", {})]
    assert tel.tick is None
    if mode == "spans":
        by_kind = {s.kind: s for s in tel.log.spans()}
        assert by_kind["decode"].attrs["tick"] == 4
        assert by_kind["admit"].attrs["tick"] == 4
        assert "tick" not in by_kind["retry"].attrs
    else:
        assert len(tel.log) == 0

def test_telemetry_mode_gating():
    t = [0.0]
    off = Telemetry("off", clock=lambda: t[0])
    with off.span("decode"):
        t[0] += 1.0
    off.event("retry")
    off.observe("ttft", 1.0)
    assert off.latency_summary() is None and len(off.log) == 0

    cnt = Telemetry("counters", clock=lambda: t[0])
    with cnt.span("decode"):
        t[0] += 2.0
    cnt.event("retry")
    assert len(cnt.log) == 0, "counters mode allocates no spans"
    s = cnt.latency_summary()
    assert s["ops"]["decode"]["count"] == 1
    assert s["ops"]["decode"]["min"] == pytest.approx(2000.0)
    assert s["counters"]["retry"] == 1

    sp = Telemetry("spans", clock=lambda: t[0])
    with sp.span("decode", step=7):
        t[0] += 1.0
    sp.event("retry", op="migration")
    spans = sp.log.spans()
    assert [x.kind for x in spans] == ["decode", "retry"]
    assert spans[0].step == 7 and spans[0].duration == 1.0
    assert spans[1].instant and spans[1].attrs["op"] == "migration"
    with pytest.raises(ValueError):
        Telemetry("verbose")


def test_span_records_error_kind():
    sp = Telemetry("spans")
    with pytest.raises(RuntimeError):
        with sp.span("migration"):
            raise RuntimeError("boom")
    (s,) = sp.log.spans()
    assert s.attrs["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# Deterministic serving timelines under a fake clock
# ---------------------------------------------------------------------------

def test_request_timeline_ordering_and_ttft(engine):
    t = [10.0]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        telemetry="spans", clock=lambda: t[0])
    h = srv.submit([1, 2, 3], max_new_tokens=3, tenant="acme")
    t[0] = 12.0
    srv.run()
    ks = _kinds(srv)
    # Lifecycle ordering: submit -> queue_wait -> admit -> prefill ->
    # first_token -> decode... -> request(terminal).
    for a, b in (("submit", "queue_wait"), ("queue_wait", "admit"),
                 ("admit", "prefill"), ("prefill", "first_token"),
                 ("first_token", "decode"), ("decode", "request")):
        assert ks.index(a) < ks.index(b), ks
    by_kind = {s.kind: s for s in srv.obs.log.spans()}
    qw = by_kind["queue_wait"]
    assert (qw.t0, qw.t1) == (10.0, 12.0)
    assert qw.request_id == h.request.request_id
    assert qw.tenant == "acme"
    req = by_kind["request"]
    assert req.attrs["status"] == "done"
    assert req.attrs["tokens"] == 3
    # TTFT on the fake clock: submit at 10, first token at 12 -> 2s,
    # exact in the histogram's min/max fields.
    lat = srv.stats()["latency"]
    assert lat["ttft_ms"]["count"] == 1
    assert lat["ttft_ms"]["min"] == pytest.approx(2000.0)
    assert lat["per_tenant"]["acme"]["ttft_ms"]["count"] == 1


def test_chunked_prefill_timeline(engine):
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=(4, 8), telemetry="spans",
                        clock=lambda: 0.0)
    h = srv.submit(list(range(1, 11)), max_new_tokens=2)
    srv.run()
    ks = _kinds(srv, h.request.request_id)
    chunk_spans = [s for s in srv.obs.log.spans()
                   if s.kind == "prefill_chunk"]
    # 10 tokens over (4, 8) buckets: plan_chunks covers it in >= 2
    # chunks, each span carrying its (start, bucket, valid) triple.
    assert len(chunk_spans) == len(h.chunks) >= 2
    assert [(s.attrs["start"], s.attrs["bucket"], s.attrs["valid"])
            for s in chunk_spans] == [tuple(c) for c in h.chunks]
    assert "prefill" not in ks, "chunked admission has no monolithic span"
    assert ks.index("prefill_chunk") < ks.index("first_token")
    # per-bucket counters from the chunk driver
    counters = srv.stats()["latency"]["counters"]
    assert sum(v for k, v in counters.items()
               if k.startswith("chunk_bucket_")) == len(chunk_spans)


def test_disagg_migration_timeline(role_engines):
    pf, dec = role_engines
    srv = DisaggServingEngine(dec, prefill_engine=pf, num_slots=2,
                              page=PAGE, prefill_buckets=(4, 16),
                              telemetry="spans", clock=lambda: 0.0)
    h = srv.submit([5, 6, 7, 8, 9], max_new_tokens=2)
    srv.run()
    ks = _kinds(srv)
    assert "migration" in ks and "prefill_chunk" in ks
    mig = next(s for s in srv.obs.log.spans() if s.kind == "migration")
    assert mig.request_id == h.request.request_id
    assert mig.attrs["pages"] >= 1
    assert mig.attrs["transport"] in ("local", "p2p")
    assert ks.index("prefill_chunk") < ks.index("migration")
    assert ks.index("migration") < ks.index("request")
    chaos.check_invariants(srv)


def test_spec_timeline_draft_verify_rollback(engine):
    srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=4,
                        telemetry="spans", clock=lambda: 0.0)
    # A sampled request commits exactly one token per K-token dispatch
    # (greedy acceptance does not apply), so its rejected suffix rolls
    # back every tick — a deterministic rollback source. The greedy
    # companion exercises the n-gram proposer (sampled requests never
    # draft).
    h = srv.submit([1, 9, 4, 2], max_new_tokens=6, temperature=0.5,
                   seed=7)
    srv.submit([1, 2, 3, 1, 2, 3], max_new_tokens=4)
    srv.run()
    ks = _kinds(srv)
    assert "spec_draft" in ks and "spec_verify" in ks
    assert ks.index("spec_draft") < ks.index("spec_verify")
    verify = [s for s in srv.obs.log.spans() if s.kind == "spec_verify"]
    assert all(s.attrs["k"] == 4 for s in verify)
    rollbacks = [s for s in srv.obs.log.spans()
                 if s.kind == "spec_rollback"]
    assert rollbacks, "a mispredicting draft must roll back"
    assert all(s.attrs["accepted"] + s.attrs["rolled"] <= 4
               for s in rollbacks)
    # draft-quality counters from the n-gram proposer
    counters = srv.stats()["latency"]["counters"]
    assert any(k.startswith("draft_ngram_") for k in counters)
    assert h.status == "done"


def test_retry_events_interleave_with_attempt_spans(role_engines):
    pf, dec = role_engines
    srv = DisaggServingEngine(
        dec, prefill_engine=pf, num_slots=2, page=PAGE,
        prefill_buckets=(4, 16), retry=RetryPolicy(max_attempts=3),
        telemetry="spans", clock=lambda: 0.0)
    h = srv.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="page_migration", k=0)):
        srv.run()
    assert h.status == "done"
    spans = srv.obs.log.spans()
    migs = [s for s in spans if s.kind == "migration"]
    assert len(migs) >= 2, "one failed + one successful attempt"
    assert migs[0].attrs.get("error") == "InjectedFault"
    assert "error" not in migs[-1].attrs
    retries = [s for s in spans if s.kind == "retry"]
    assert retries and retries[0].attrs["op"] == "page_migration"
    # the policy's own backoff event rides the same log
    assert any(s.kind == "retry_backoff" for s in spans)
    # ...and the timeline interleaves: failed attempt -> retry ->
    # successful attempt.
    i_fail = spans.index(migs[0])
    i_ok = spans.index(migs[-1])
    i_retry = spans.index(retries[0])
    assert i_fail < i_retry < i_ok


def test_failover_events_in_timeline(role_engines):
    pf, dec = role_engines
    srv = DisaggServingEngine(dec, prefill_engine=pf, num_slots=2,
                              page=PAGE, prefill_buckets=(4, 16),
                              retry=RetryPolicy(max_attempts=2),
                              worker_fail_threshold=1,
                              telemetry="spans", clock=lambda: 0.0)
    srv.submit([9, 8, 7, 6, 5, 4], max_new_tokens=3)
    with faults.inject(faults.FaultPlan(
            name="hard", faults=(faults.Fault(
                "fail_call", op="page_migration", k=None),))):
        for _ in range(30):
            if srv._drained():
                break
            srv.step()
    srv.run()
    ks = _kinds(srv)
    assert "role_fail" in ks and "role_dead" in ks and "failover" in ks
    fo = next(s for s in srv.obs.log.spans() if s.kind == "failover")
    assert fo.attrs["requeued"] >= 1
    assert fo.attrs["target"] == "local"
    assert ks.index("role_dead") < ks.index("failover")
    assert srv.stats()["failovers"] == 1


def test_preempt_event_in_timeline(engine):
    srv = ServingEngine(engine, num_slots=2, page=PAGE, num_pages=3,
                        telemetry="spans", clock=lambda: 0.0)
    hs = [srv.submit(p, max_new_tokens=4)
          for p in ([1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12])]
    srv.run()
    assert [h.status for h in hs] == ["done", "done"]
    pre = [s for s in srv.obs.log.spans() if s.kind == "preempt"]
    assert len(pre) == srv.stats()["preemptions"] >= 1
    assert pre[0].request_id is not None


def test_checkpoint_restore_spans(engine):
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        telemetry="spans", clock=lambda: 0.0)
    srv.submit([1, 2, 3], max_new_tokens=6)
    for _ in range(3):
        srv.step()
    snap = srv.checkpoint()
    assert "checkpoint" in _kinds(srv)
    srv2 = ServingEngine(engine, num_slots=2, page=PAGE,
                         telemetry="spans", clock=lambda: 0.0)
    srv2.restore(snap)
    ks = _kinds(srv2)
    assert "restore" in ks
    rs = next(s for s in srv2.obs.log.spans() if s.kind == "restore")
    assert rs.attrs["requests"] == 1
    srv.run()
    srv2.run()
    # A mid-stream revival records NO second TTFT (its first token
    # happened in the previous process) and no duplicate first_token
    # event — only the ITL chain restarts.
    lat = srv2.stats()["latency"]
    assert lat["ttft_ms"] is None
    assert "first_token" not in _kinds(srv2)
    assert lat["itl_ms"]["count"] >= 1


def test_chaos_events_carry_clock_stamps(role_engines):
    pf, dec = role_engines

    def factory():
        return DisaggServingEngine(
            dec, prefill_engine=pf, num_slots=2, page=PAGE,
            prefill_buckets=(4, 16), retry=RetryPolicy(max_attempts=2),
            worker_fail_threshold=2, telemetry="spans")

    rep = chaos.run_soak(factory, seed=5, ticks=25, n_faults=4)
    fired = [e for e in rep.events if e.fired]
    assert fired, "the soak must fire at least one fault"
    assert all(e.at is not None for e in fired), (
        "fired chaos events must carry engine-clock timestamps")
    assert all(e.at is None for e in rep.events if not e.fired)


# ---------------------------------------------------------------------------
# Bit-exactness + no-growth with spans active (the core contract)
# ---------------------------------------------------------------------------

def test_spans_bit_identical_and_jit_no_growth(engine):
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    runs = {}
    for mode in ("off", "counters", "spans"):
        srv = ServingEngine(engine, num_slots=2, page=PAGE,
                            prefill_buckets=(4, 8), telemetry=mode)
        runs[mode] = srv.generate(prompts, max_new_tokens=4)
        assert srv.decode_cache_size() == 1, (
            f"telemetry={mode} grew the decode jit cache")
        assert srv.prefill_cache_size() <= 2, (
            f"telemetry={mode} leaked a prefill shape")
        stats = srv.stats()
        assert stats["ticks"] > 0
        if mode == "off":
            assert stats["latency"] is None and len(srv.obs.log) == 0
            continue
        # The tick's phases as histograms, where no profiler runs.
        ops = stats["latency"]["ops"]
        assert set(TICK_KINDS) <= set(ops), sorted(ops)
        assert ops["tick"]["count"] == stats["ticks"]
        assert (ops["decode_enqueue"]["count"]
                == ops["decode_wait"]["count"]
                == ops["decode_fetch"]["count"]
                == ops["decode"]["count"] == stats["decode_dispatches"])
        assert ops["sample"]["count"] == ops["emit"]["count"] == 12
        assert ops["submit"]["count"] == ops["prefill_fetch"]["count"] == 3
    assert runs["off"] == runs["counters"] == runs["spans"], (
        "span recording changed token outputs")


def test_tick_spans_tile_the_tick(engine):
    """On a clock that moves at every reading: the leaf spans of a tick
    lie inside it, carry its index, and do not overlap; what one tick
    holds is what the tick did."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        prefill_buckets=(4, 8), telemetry="spans",
                        clock=clock)
    srv.generate([list(range(1, 11)), [4, 5]], max_new_tokens=3)
    spans = srv.obs.log.spans()
    ticks = [s for s in spans if s.kind == "tick"]
    assert [s.attrs["tick"] for s in ticks] == list(range(len(ticks)))
    assert len(ticks) == srv.stats()["ticks"]
    parents, back_dated = ("tick", "decode"), ("queue_wait", "request")
    seen, launches = set(), {}     # tick -> its batch rode a chunk
    for tick in ticks:
        idx = tick.attrs["tick"]
        inside = [s for s in spans if s is not tick
                  and s.attrs.get("tick") == idx]
        # queue_wait and request are back-dated: they CLOSE in the tick.
        for s in inside:
            assert s.kind in back_dated or tick.t0 < s.t0, s
            assert (s.t1 or s.t0) < tick.t1, (
                f"{s.kind} of tick {idx} escapes it")
        leaves = sorted((s for s in inside if not s.instant
                         and s.kind not in parents + back_dated),
                        key=lambda s: s.t0)
        for a, b in zip(leaves, leaves[1:]):
            assert a.t1 < b.t0, f"{a.kind} overlaps {b.kind} in tick {idx}"
        kinds = [s.kind for s in leaves]
        seen.update(kinds)
        assert kinds[0] == "schedule"
        # The launch: the batch's upload follows its prep, and the
        # chunk program it is aboard (if any) follows the upload.
        if "decode_enqueue" in kinds:
            at = kinds.index("decode_enqueue")
            assert kinds[at - 1] == "decode_prep"
            launches[idx] = kinds[at + 1:at + 2] == ["prefill_chunk"]
        # The landing: ``decode`` holds the wait and the fetch and
        # nothing else, and lies behind everything the tick launched
        # (the tick it lands is the LAST one's: ``launched``).
        for dec in (s for s in inside if s.kind == "decode"):
            kids = [s.kind for s in leaves if dec.t0 < s.t0 < dec.t1]
            assert kids == ["decode_wait", "decode_fetch"]
            assert all(s.t1 < dec.t0 for s in leaves if s.kind in (
                "decode_prep", "decode_enqueue", "prefill_chunk"))
            assert dec.attrs["launched"] == idx - 1
            assert launches[idx - 1] == bool(dec.attrs["fused"])
            assert tick.attrs.get("ahead") == 1 or (
                "decode_enqueue" not in kinds)
    assert seen == set(TICK_KINDS) - {"tick", "submit"} | {"prefill_chunk"}
    # What is recorded between two ticks carries no index.
    submits = [s for s in spans if s.kind == "submit"]
    assert len(submits) == 2
    assert all("tick" not in s.attrs for s in submits)


def test_fused_tick_holds_every_leaf_kind(engine):
    """A tick whose decode batch rides a chunk program: every leaf kind
    a tick can hold occurs in it where its work does, the leaves tile
    the tick, and its one ``decode`` span says how many sequences it
    served and that it rode (``fused``); a tick with no chunk says 0."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    srv = ServingEngine(engine, num_slots=3, page=PAGE,
                        prefill_buckets=(4, 8), telemetry="spans",
                        clock=clock)
    # One chunk, two chunks, three: in the second tick one request
    # decodes while two prefill, and one of those runs its last chunk.
    srv.generate([[7, 8], list(range(1, 11)), list(range(1, 20))],
                 max_new_tokens=4)
    spans = srv.obs.log.spans()
    decodes = [s for s in spans if s.kind == "decode"]
    assert all({"batch", "fused"} <= set(s.attrs) for s in decodes)
    assert [s.step for s in decodes] == list(range(len(decodes)))
    rode = [s for s in decodes if s.attrs["fused"]]
    st = srv.stats()
    assert len(rode) == st["decode_dispatches_fused"] > 0
    assert len(decodes) == st["decode_dispatches"] > len(rode)
    leaf_kinds = {"schedule", "decode_prep", "prefill_chunk",
                  "decode_enqueue", "decode_wait", "decode_fetch",
                  "prefill_fetch", "sample", "emit"}
    full = 0

    def leaves_of(idx):
        tick = next(s for s in spans if s.kind == "tick"
                    and s.attrs["tick"] == idx)
        leaves = sorted((s for s in spans if s.attrs.get("tick") == idx
                         and not s.instant and s.kind in leaf_kinds),
                        key=lambda s: s.t0)
        assert all(tick.t0 < s.t0 and s.t1 < tick.t1 for s in leaves)
        for a, b in zip(leaves, leaves[1:]):
            assert a.t1 < b.t0, f"{a.kind} overlaps {b.kind} in tick {idx}"
        return [s.kind for s in leaves]

    for dec in rode:
        # The tick that LAUNCHED the riding batch: its upload, then the
        # chunk program it is aboard, before anything is waited for.
        assert dec.attrs["launched"] == dec.attrs["tick"] - 1
        kinds = leaves_of(dec.attrs["launched"])
        assert kinds[:4] == ["schedule", "decode_prep", "decode_enqueue",
                             "prefill_chunk"]
        # The tick that LANDS it, the next one, launches its own
        # programs first: the wait comes after the tick's LAST chunk
        # was enqueued, and a slot whose prompt became resident is
        # fetched after the decode rows' tokens.
        kinds = leaves_of(dec.attrs["tick"])
        launch = [i for i, k in enumerate(kinds) if k in (
            "decode_prep", "decode_enqueue", "prefill_chunk")]
        assert kinds.index("decode_wait") > max(launch, default=0)
        assert dec.attrs["batch"] == kinds.count("sample") - kinds.count(
            "prefill_fetch")
        if "prefill_fetch" in kinds:
            assert kinds.index("prefill_fetch") > kinds.index("sample")
        full += set(kinds) == leaf_kinds
    assert full >= 1, "no tick that landed a riding batch held every leaf"


def test_greedy_tick_fetches_tokens_and_keeps_its_spans(engine):
    """What the benchmark's fetch and sample metrics read of a greedy
    chunked run: ``decode_fetch``, ``prefill_fetch``, ``sample`` and
    ``emit`` all occur, every ``sample`` says the device picked its
    token, and every array the tick copies from a program is the
    picked tokens, never a logits row (counted at ``_read``, the one
    place the engine reads a program's output)."""
    srv = ServingEngine(engine, num_slots=3, page=PAGE,
                        prefill_buckets=(4, 8), telemetry="spans")
    read, real = [], srv._read

    def counting(out):
        read.append(real(out))
        return read[-1]

    srv._read = counting
    # One chunk, two, three: ticks that ride, parked last chunks and
    # decode-only ticks (test_fused_tick_holds_every_leaf_kind).
    srv.generate([[7, 8], list(range(1, 11)), list(range(1, 20))],
                 max_new_tokens=4)
    spans = srv.obs.log.spans()
    samples = [s for s in spans if s.kind == "sample"]
    assert {"decode_fetch", "prefill_fetch", "sample", "emit"} <= {
        s.kind for s in spans}
    assert samples and all(s.attrs["device"] == 1 for s in samples)
    st = srv.stats()
    assert (st["tokens_picked_on_device"] == st["tokens_generated"]
            == len(samples) == 12)
    assert len(read) >= st["decode_dispatches"]
    assert all(a.dtype == np.int32 and a.nbytes <= 4 * (1 + srv.num_slots)
               for a in read), [(a.dtype, a.shape) for a in read]


def test_spans_reach_a_profiler_capture(engine, tmp_path):
    """A capture started by any means holds the spans, on its clock and
    with their keys as stats (on the chip: beside the device's ops)."""
    from jax.profiler import ProfileData

    srv = ServingEngine(engine, num_slots=2, page=PAGE)
    srv.generate([[1, 2]], max_new_tokens=1)        # compile outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        srv.generate([[1, 2, 3]], max_new_tokens=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("tdt.")]
    by_name = {}
    for ev in events:
        by_name.setdefault(ev.name, []).append(dict(ev.stats))
    ticks = [st["tick"] for st in by_name["tdt.tick"]]
    assert ticks == sorted(ticks) and len(ticks) >= 2
    assert [st["tick"] for st in by_name["tdt.decode_fetch"]] == [
        st["tick"] for st in by_name["tdt.decode"]]
    assert all(st["batch"] == 1 for st in by_name["tdt.decode"])
    (admit,) = by_name["tdt.admit"]
    assert admit["waited_ms"] >= 0 and admit["slot"] == 0
    assert admit["request_id"] == by_name["tdt.emit"][0]["request_id"]
    tick = next(ev for ev in events if ev.name == "tdt.tick")
    fetch = next(ev for ev in events if ev.name == "tdt.decode_fetch")
    assert tick.duration_ns > 0 and fetch.duration_ns > 0


def test_a_tick_launched_ahead_keeps_its_span_tree_in_a_capture(
        engine, tmp_path):
    """What the benchmark's serving-tick metrics read, out of a real
    capture of ticks launched ahead: every leaf of the tick occurs once
    a tick that lands a riding batch, ``tdt.decode`` carries ``batch``,
    ``fused`` and ``step`` and holds the wait and the fetch,
    ``tdt.decode_enqueue`` lies under ``tdt.tick`` and outside it, and
    ``tdt.tick`` says whether its launch stayed in flight (``ahead``)."""
    from jax.profiler import ProfileData

    srv = ServingEngine(engine, num_slots=3, page=PAGE,
                        prefill_buckets=(4, 8))
    prompts = [[7, 8], list(range(1, 11)), list(range(1, 20))]
    srv.generate(prompts, max_new_tokens=4)         # compile outside
    before = srv.stats()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        srv.generate(prompts, max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    st = srv.stats()
    ahead = st["ticks_launched_ahead"] - before["ticks_launched_ahead"]
    assert ahead > 0 and st["ticks_in_order"] == 0
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("tdt.")]
    rows = [(ev.name[4:], ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats)) for ev in events]
    ticks = {st["tick"]: (t0, t1, st) for k, t0, t1, st in rows
             if k == "tick"}
    assert sum(st.get("ahead", 0) for _, _, st in ticks.values()) == ahead
    assert all("ahead" in st for _, _, st in ticks.values()
               if any(k == "decode_enqueue" and s["tick"] == st["tick"]
                      for k, _, _, s in rows))

    def inside(outer, kind):
        return [(k, t0, t1, st) for k, t0, t1, st in rows
                if k == kind and outer[0] <= t0 and t1 <= outer[1]]

    decodes = [r for r in rows if r[0] == "decode"]
    assert decodes and all({"batch", "fused", "step", "tick"} <= set(
        st) for _, _, _, st in decodes)
    assert [st["step"] for _, _, _, st in decodes] == list(range(
        before["decode_dispatches"], st["decode_dispatches"]))
    leaf_kinds = ("schedule", "decode_prep", "decode_enqueue",
                  "prefill_chunk", "decode_wait", "decode_fetch",
                  "prefill_fetch", "sample", "emit")
    full = 0
    for _, t0, t1, dst in decodes:
        tick = ticks[dst["tick"]]
        assert tick[0] <= t0 and t1 <= tick[1]
        assert len(inside((t0, t1), "decode_wait")) == 1
        assert len(inside((t0, t1), "decode_fetch")) == 1
        assert not inside((t0, t1), "decode_enqueue")
        assert not inside((t0, t1), "prefill_chunk")
        held = {k: len(inside(tick, k)) for k in leaf_kinds}
        assert held["decode_wait"] == held["decode_fetch"] == 1
        assert held["decode_enqueue"] <= 1 and held["schedule"] == 1
        assert held["sample"] == held["emit"] >= dst["batch"]
        full += all(held.values())
    assert full >= 1, "no tick of the capture held every leaf kind"
    assert sum(dst["fused"] for _, _, _, dst in decodes) == (
        st["decode_dispatches_fused"] - before["decode_dispatches_fused"])


def test_spec_spans_bit_identical(engine):
    prompts = [[1, 2, 3, 1, 2, 3, 1, 2], [5, 5, 5, 5]]
    runs = {}
    for mode in ("off", "spans"):
        srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=3,
                            telemetry=mode)
        runs[mode] = srv.generate(prompts, max_new_tokens=6)
        assert srv.decode_cache_size() == 1
    assert runs["off"] == runs["spans"]


# ---------------------------------------------------------------------------
# Perfetto export well-formedness + the shared trace session
# ---------------------------------------------------------------------------

def test_merged_perfetto_export_well_formed(engine, tmp_path):
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        telemetry="spans")
    with srv.trace("obs-test", out_dir=str(tmp_path / "sess"),
                   xprof=False) as sess:
        srv.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)
    path = sess.export()
    trace = json.load(open(path))          # json loads
    evs = trace["traceEvents"]
    host = [e for e in evs if e["pid"] == 1 and e.get("ph") in ("X", "i")]
    assert host, "host spans missing from the merged trace"
    # pid/tid stable: every host event on pid 1; slot-correlated spans
    # keep one tid per slot; numeric ts/dur everywhere.
    for e in host:
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        assert isinstance(e["ts"], (int, float))
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # one tid per slot, stable across the file
    tid_by_slot = {}
    for e in host:
        slot = e["args"].get("slot")
        if slot is not None:
            tid_by_slot.setdefault(slot, set()).add(e["tid"])
    assert tid_by_slot and all(len(tids) == 1
                               for tids in tid_by_slot.values())
    # spans nested: each request's queue_wait and decode-side work sits
    # inside its request span on the same clock. Its two edges are
    # spans themselves: submit opens before the scheduler stamps the
    # request, and the emit that retires it closes after.
    reqs = {e["args"]["request_id"]: e for e in host
            if e["args"]["kind"] == "request"}
    for e in host:
        rid = e["args"].get("request_id")
        if rid in reqs and e["ph"] == "X" and e is not reqs[rid]:
            r, kind = reqs[rid], e["args"]["kind"]
            assert kind == "submit" or r["ts"] <= e["ts"] + 1e-6
            assert (kind == "emit" or e["ts"] + e.get("dur", 0)
                    <= r["ts"] + r["dur"] + 1e-6), (
                f"{kind} escapes its request span")
            assert (e["ts"] <= r["ts"] + r["dur"]
                    and r["ts"] <= e["ts"] + e.get("dur", 0) + 1e-6)
    # metrics snapshot rides the same session dir
    mp = sess.export_metrics(srv.stats())
    m = json.load(open(mp))
    assert m["stats"]["latency"]["ttft_ms"]["count"] == 2
    # old-signature compatibility: the session IS the directory path
    import os

    assert os.fspath(sess) == str(tmp_path / "sess")


def test_megakernel_slot_records_in_merged_trace(tmp_path):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mk = MegaKernelEngine(cfg, mesh, batch=2, max_len=32, tile_w=16,
                          t_tile=16, num_cores=2, profile=True)
    srv = ServingEngine(mk, telemetry="spans")
    with srv.trace("mk-obs", out_dir=str(tmp_path / "mk"),
                   xprof=False, mk_keep=2) as sess:
        srv.generate([[1, 2, 3], [4, 5]], max_new_tokens=2)
    trace = json.load(open(sess.export()))
    evs = trace["traceEvents"]
    mk_evs = [e for e in evs if e["pid"] == 2 and "args" in e
              and "value" in e.get("args", {})]
    assert mk_evs, "megakernel slot records missing"
    steps = {e["args"]["step"] for e in mk_evs}
    assert len(steps) == 2, "mk_keep=2 retains two decode steps"
    names = {e["name"] for e in mk_evs}
    assert "LINEAR" in names or "RMSNORM" in names
    host = [e for e in evs if e["pid"] == 1]
    assert host, "host spans must ride the same file"


def test_trace_old_signature_still_works(engine):
    srv = ServingEngine(engine, num_slots=2, page=PAGE)
    # the pre-obs call shape: positional name, expert_histograms kw,
    # no interest in the yielded value.
    with srv.trace("compat-check", expert_histograms=False):
        srv.generate([[1, 2]], max_new_tokens=2)


# ---------------------------------------------------------------------------
# Resilience-layer units (event hooks)
# ---------------------------------------------------------------------------

def test_retry_policy_event_cb():
    events = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TimeoutError("transient")
        return "ok"

    pol = RetryPolicy(max_attempts=3)
    out, n = pol.call(flaky, op="x", retry_on=(TimeoutError,),
                      event_cb=lambda kind, **a: events.append(
                          (kind, a)),
                      sleep=lambda d: None)
    assert (out, n) == ("ok", 3)
    assert [k for k, _ in events] == ["retry_backoff", "retry_backoff"]
    assert events[0][1]["attempt"] == 1 and events[0][1]["op"] == "x"
    events.clear()
    calls.clear()
    with pytest.raises(TimeoutError):
        pol.call(lambda: (_ for _ in ()).throw(TimeoutError("t")),
                 op="y", retry_on=(TimeoutError,),
                 event_cb=lambda kind, **a: events.append((kind, a)),
                 sleep=lambda d: None)
    assert events[-1][0] == "retry_giveup"
    assert events[-1][1]["attempts"] == 3


def test_health_tracker_history_and_on_event():
    t = [100.0]
    events = []
    ht = HealthTracker(fail_threshold=2, clock=lambda: t[0],
                       on_event=lambda k, at, c: events.append(
                           (k, at, c)))
    ht.beat()                      # beats are not forwarded
    t[0] = 101.0
    ht.fail("first")
    t[0] = 102.0
    ht.fail("second")
    kinds = [k for k, _, _ in events]
    assert kinds == ["fail", "fail", "dead"]
    assert events[0][1] == 101.0 and events[1][1] == 102.0
    assert [h[1] for h in ht.history] == ["fail", "fail", "dead"]
    assert ht.history[0][0] == 101.0
