"""Benchmark entry point — prints ONE JSON line.

Metric: AG+GEMM overlap efficiency versus compute-only GEMM (the
north-star from BASELINE.json: >=0.90 of compute-only on a TP mesh).

- With >=2 TPU chips: the full measurement — overlapped ``ag_gemm``
  wall time vs (pure XLA dot on pre-gathered A).
- With 1 chip: the SELF-SIMULATED RING — A is split into SIM_RANKS
  chunks and the full multi-chip ring schedule runs with self-targeted
  RDMA puts (``ag_gemm(sim_ranks=8)``): identical control flow,
  semaphore waits, staging, and per-step compute:comm ratio; only the
  wire is HBM instead of ICI. A ring that does not compile is an error
  carrying the compiler's message, never a demotion to a kernel without
  the ring.
- With no TPU: exits non-zero naming the platform JAX found.
  ``BENCH_BACKEND=cpu`` asks for the interpreter pass instead (a check
  that the schedules run; its record says ``platform: cpu`` and none of
  its wall times is a device number).

Timing: dispatch is asynchronous and carries a fixed host overhead, so
each measurement runs dependency-chained iterations inside one jit (a
numerically *visible* bump keeps XLA from hoisting the op out of the
loop), fetches the result (forcing device completion), and takes the
slope between two chain lengths — the fixed overhead cancels exactly.

``vs_baseline`` is value / 0.90 (the reference-implied H800 target).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ITERS_LO, ITERS_HI = 8, 72
ITERS_HI_FINAL = 200   # long final chains: slope error ~ noise / (hi-lo)
REPEATS = 5
SWEEP_REPEATS = 3

# Self-simulated ring size for the single-chip overlap measurement
# (chunks = the v5p-8 TP degree the kernels are designed for).
SIM_RANKS = 8

# Config space swept at bench time (ADVICE r1: a single hardcoded config
# left the metric at the mercy of one noise sample). The round-1 winner
# leads; the others bracket it in block_n / block_k, plus the pipelined
# (BlockSpec-A) variant at both granularities.
AG_GEMM_CONFIGS = (
    {"block_m": 1024, "block_n": 128, "block_k": 4096},
    {"block_m": 1024, "block_n": 256, "block_k": 4096},
    {"block_m": 512, "block_n": 128, "block_k": 4096},
    {"block_m": 1024, "block_n": 128, "block_k": 2048},
    {"block_m": 256, "block_n": 512, "block_k": 1024},
    # Double-buffered panels (block_m <= 512 fits two (tm, K) panels in
    # the VMEM budget): the cross-chunk prefetch path — no cold panel
    # load or arrival stall at ring boundaries (r5 kernel change).
    {"block_m": 512, "block_n": 256, "block_k": 4096},
    {"block_m": 256, "block_n": 128, "block_k": 4096},
    {"variant": "pipelined", "block_m": 256, "block_n": 256,
     "block_k": 1024},
    {"variant": "pipelined", "block_m": 128, "block_n": 512,
     "block_k": 2048},
    # Variant-crossover pairs: both variants measured at block_m
    # {128, 256, 512} so the panel-vs-streamed crossover is read off
    # ONE sweep (detail.ag_gemm_variant_crossover), not stitched from
    # different rounds.
    {"block_m": 128, "block_n": 256, "block_k": 4096},
    {"variant": "pipelined", "block_m": 512, "block_n": 256,
     "block_k": 1024},
)

# gemm_rs gets the same treatment (round-1 winner first): its detail
# number rode a single hardcoded config and drifted with noise.
GEMM_RS_CONFIGS = (
    {"block_m": 1024, "block_n": 128, "block_k": 4096},
    {"block_m": 512, "block_n": 128, "block_k": 4096},
    # NOT 1024x256x4096: 20 MB scoped VMEM > the 16 MB limit — it can
    # OOM asynchronously mid-sweep where the skip-on-compile-failure
    # policy cannot catch it.
    {"block_m": 512, "block_n": 128, "block_k": 2048},
)


def _make_chain(step, iters):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(a, b):
        def body(_, a):
            out = step(a, b)
            # Visible scalar bump: forces true sequential execution
            # (an invisible-in-bf16 bump lets XLA hoist the op).
            bump = (out.reshape(-1)[0].astype(jnp.float32) * 1e-3
                    ).astype(a.dtype)
            return jnp.clip(a + bump, -4.0, 4.0)
        s = jax.lax.fori_loop(0, iters, body, a)
        return jnp.sum(s.astype(jnp.float32))
    return chain


def _timed_chain(step, a, b, repeats=REPEATS):
    """step: (a, b) -> out; returns seconds/iter via two-point slope."""
    times = {}
    for iters in (ITERS_LO, ITERS_HI):
        chain = _make_chain(step, iters)
        v = np.asarray(chain(a, b))  # warmup/compile
        assert np.isfinite(v), "benchmark chain produced non-finite value"
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            np.asarray(chain(a, b))
            best = min(best, time.perf_counter() - t0)
        times[iters] = best
    return (times[ITERS_HI] - times[ITERS_LO]) / (ITERS_HI - ITERS_LO)


def _timed_chain_group(entries, repeats=REPEATS, lo=ITERS_LO,
                       hi=ITERS_HI_FINAL):
    """Interleaved slope timing for a group of steps.

    entries: {name: (step, a, b)} -> {name: seconds/iter}. Every repeat
    samples EVERY chain back-to-back, so slow phases of the host or
    the chip hit numerator and denominator alike — the round-1 failure
    mode was sequential timing letting drift between two measurements
    swing the efficiency ratio +-15%.
    """
    chains = {}
    for name, (step, a, b) in entries.items():
        per = {}
        for iters in (lo, hi):
            c = _make_chain(step, iters)
            v = np.asarray(c(a, b))  # warmup/compile
            assert np.isfinite(v), f"chain {name!r} produced non-finite"
            per[iters] = c
        chains[name] = per
    best = {name: {lo: float("inf"), hi: float("inf")}
            for name in entries}
    for _ in range(repeats):
        for name, (step, a, b) in entries.items():
            for iters in (lo, hi):
                t0 = time.perf_counter()
                np.asarray(chains[name][iters](a, b))
                dt = time.perf_counter() - t0
                best[name][iters] = min(best[name][iters], dt)
    return {name: (best[name][hi] - best[name][lo]) / (hi - lo)
            for name in entries}


def _interpret_megakernel_times() -> dict:
    """Interpret-mode megakernel decode-step timing, static vs dynamic
    schedule side by side (CPU-only hosts previously emitted
    ``value: null`` here — the interpreter executes the REAL scoreboard
    protocol, so the ratio tracks schedule+dispatch overhead, not
    silicon). Also reports each schedule's idle (NOOP) slot count —
    the scoreboard-step metric the dynamic claim scheduler shrinks."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    toks = jnp.asarray([1, 2], jnp.int32)
    out = {"megakernel_decode_step_ms": {}, "megakernel_idle_slots": {},
           "megakernel_sim": {}}
    for mode in ("static", "dynamic"):
        eng = MegaKernelEngine(cfg, mesh, batch=2, max_len=32,
                               tile_w=16, t_tile=16, num_cores=2,
                               strategy="cost_lpt", schedule=mode)
        np.asarray(eng.decode_step(toks, 0))     # compile + warmup
        best = float("inf")
        for i in range(2):
            t0 = time.perf_counter()
            np.asarray(eng.decode_step(toks, 1 + i))
            best = min(best, time.perf_counter() - t0)
        out["megakernel_decode_step_ms"][mode] = round(best * 1e3, 3)
        out["megakernel_idle_slots"][mode] = eng.builder.noop_slots()
        out["megakernel_sim"][mode] = {
            "idle_units": eng.builder.idle_units,
            "makespan": eng.builder.makespan}
    return out


def _interpret_mega_parity() -> dict:
    """Megakernel serving parity on the interpret mesh: the paged
    persistent lane's decode-step wall time per kv_dtype (fused
    quantize-on-write / dequantize-on-read vs the fp32 pools) and the
    Q-block speculative tokens/s vs the non-spec lane on the same
    repetitive trace — the serving-speed keys the layer path has had
    since PR 8, now with megakernel values (interpret overhead, not
    silicon; presence + relative shape are the signal)."""
    import jax
    import jax.numpy as jnp  # noqa: F401 — backend warmup
    from jax.sharding import Mesh

    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models.config import ModelConfig
    from triton_dist_tpu.serving import ServingEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kw = dict(batch=2, max_len=32, tile_w=16, t_tile=16, paged=True,
              page=16, num_pages=5)

    out = {"megakernel_decode_quant_ms": {},
           "megakernel_tokens_per_s_spec": {}}
    for kvd in ("bf16", "int8", "fp8"):
        mk = MegaKernelEngine(cfg, mesh, kv_dtype=kvd, **kw)
        s = ServingEngine(mk, kv_dtype=kvd)
        s.generate([[1, 2, 3]], max_new_tokens=2)    # compile warmup
        s.submit([4, 5, 6], max_new_tokens=6)
        s.submit([7, 8], max_new_tokens=6)
        n0 = s.stats()["decode_dispatches"]
        t0 = time.perf_counter()
        s.run()
        dt = time.perf_counter() - t0
        n = s.stats()["decode_dispatches"] - n0
        out["megakernel_decode_quant_ms"][kvd] = round(
            dt * 1e3 / max(n, 1), 3)

    # Q-block speculation on/off over the repetitive greedy trace (the
    # workload the n-gram draft wins on): tokens/s including the
    # prefill-lane ticks, plus the accept rate and the one-entry
    # verification jit gate.
    spec_trace = [[1, 2, 3, 1, 2, 3, 1, 2], [7, 8, 7, 8, 7, 8]]
    out["megakernel_spec_accept_rate"] = None
    for name, k in (("nospec", 0), ("spec", 2)):
        mk = MegaKernelEngine(cfg, mesh, spec_k=k,
                              schedule="dynamic" if k else "static",
                              **kw)
        s = ServingEngine(mk, spec_k=k)
        s.generate(spec_trace, max_new_tokens=8)     # compile warmup
        for c in s.stats_counters:
            s.stats_counters[c] = type(s.stats_counters[c])(0)
        t0 = time.perf_counter()
        s.generate(spec_trace, max_new_tokens=16)
        dt = time.perf_counter() - t0
        st = s.stats()
        out["megakernel_tokens_per_s_spec"][name] = round(
            st["tokens_generated"] / max(dt, 1e-9), 2)
        if k:
            out["megakernel_spec_accept_rate"] = (
                None if st["spec"]["accept_rate"] is None
                else round(st["spec"]["accept_rate"], 4))
            assert st["spec"]["tokens_per_dispatch"] > 1.0, (
                "megakernel speculation never amortized a dispatch")
    return out


def _interpret_mega_chunked() -> dict:
    """Megakernel chunked prefill on the interpret mesh: per-chunk
    dispatch wall time plus prefill-heavy tokens/s for the bucketed
    WRITE_KV_CHUNK/ATTN_CHUNK lane vs the one-token-per-tick prefill
    lane on the SAME engine shape and workload (long prompts, two
    generated tokens). Interpret overhead, not silicon — the
    chunked / onetok RATIO is the signal and the mkchunk_smoke gate
    checks it ≥ 2x (one chunk dispatch retires a bucket of prompt
    tokens; one prefill tick retires exactly one)."""
    import jax
    import jax.numpy as jnp  # noqa: F401 — backend warmup
    from jax.sharding import Mesh

    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models.config import ModelConfig
    from triton_dist_tpu.ops.chunked_prefill import plan_chunks
    from triton_dist_tpu.serving import ServingEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    buckets = (16,)
    kw = dict(batch=2, max_len=48, tile_w=16, t_tile=16, paged=True,
              page=16, num_pages=7)
    # Prefill-heavy: ~30 prompt tokens per request, 2 generated.
    prompts = [[(7 * i + j) % 60 + 1 for j in range(30)]
               for i in range(2)]
    n_chunks = sum(len(plan_chunks(len(p), buckets)) for p in prompts)

    out = {"megakernel_prefill_chunk_ms": None,
           "megakernel_tokens_per_s_prefill_heavy": {}}
    for name, bk in (("onetok", None), ("chunked", buckets)):
        mk = MegaKernelEngine(cfg, mesh, prefill_buckets=bk, **kw)
        s = ServingEngine(mk, prefill_buckets=bk)
        s.generate([p[:18] for p in prompts],
                   max_new_tokens=2)               # compile warmup
        t0 = time.perf_counter()
        toks = s.generate(prompts, max_new_tokens=2)
        dt = time.perf_counter() - t0
        n_tok = sum(len(p) for p in prompts) + sum(len(t) for t in toks)
        out["megakernel_tokens_per_s_prefill_heavy"][name] = round(
            n_tok / max(dt, 1e-9), 2)
        if bk:
            # Whole-run wall over the chunk count: prefill dominates
            # this workload, so this upper-bounds the per-chunk cost.
            out["megakernel_prefill_chunk_ms"] = round(
                dt * 1e3 / max(n_chunks, 1), 3)
            assert s.prefill_cache_size() <= len(bk), (
                "chunk jit cache outgrew the bucket count")
    h = out["megakernel_tokens_per_s_prefill_heavy"]
    out["megakernel_prefill_chunk_speedup"] = round(
        h["chunked"] / max(h["onetok"], 1e-9), 2)
    return out


def _interpret_serving_times() -> dict:
    """Serving throughput on the CPU mesh: the continuous-batching
    ServingEngine vs gang ("static") batching over the SAME engine and
    workload — a skewed gen-length mix, so static burns decode slots on
    finished requests while continuous recycles them. Absolute numbers
    track the XLA-on-CPU decode step, not silicon; the continuous /
    static RATIO is the scheduling win and is shape-stable."""
    import jax
    import jax.numpy as jnp  # noqa: F401 — backend warmup
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.serving import ServingEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9], [10, 11], [12]]
    gens = [2, 10, 2, 10, 2, 10]          # skewed: static wastes slots

    out = {"serving_tokens_per_s": {}, "serving_decode_dispatches": {},
           "serving_decode_cache_entries": {}}
    for policy in ("continuous", "static"):
        srv = ServingEngine(eng, num_slots=2, page=8, policy=policy)
        srv.generate([[1, 2]], max_new_tokens=2)     # compile warmup
        for k in srv.stats_counters:
            srv.stats_counters[k] = type(srv.stats_counters[k])(0)
        for p, g in zip(prompts, gens):
            srv.submit(p, max_new_tokens=g)
        srv.run()
        st = srv.stats()
        out["serving_tokens_per_s"][policy] = round(
            st.get("tokens_per_s", 0.0), 2)
        out["serving_decode_dispatches"][policy] = st[
            "decode_dispatches"]
        out["serving_decode_cache_entries"][policy] = (
            srv.decode_cache_size())

    # Chunked vs monolithic prefill on a PREFILL-HEAVY mixed-length
    # trace (every prompt a distinct length — the serving reality
    # ROADMAP Open item 1 names): monolithic prefill compiles once per
    # length, chunked once per bucket, so the wall-clock ratio here is
    # dominated by exactly the compile tax the bucketing removes.
    # Wall time INCLUDES prefill (unlike tokens_per_s above) — that is
    # the number disaggregation/chunking moves. Fresh engine per
    # variant: the jit caches must not be shared.
    rng = np.random.RandomState(0)
    trace = [[int(t) for t in rng.randint(0, 64, n)]
             for n in (3, 5, 7, 9, 11, 14, 17, 21)]

    def run_trace(buckets):
        e = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)
        s = ServingEngine(e, num_slots=2, page=8,
                          prefill_buckets=buckets)
        t0 = time.perf_counter()
        s.generate(trace, max_new_tokens=4)
        dt = time.perf_counter() - t0
        return dt, s.stats()["tokens_generated"], s.prefill_cache_size()

    dt_m, toks_m, pre_m = run_trace(None)
    dt_c, toks_c, pre_c = run_trace((8,))
    out["prefill_chunked_vs_monolithic_ms"] = {
        "monolithic": round(dt_m * 1e3, 1),
        "chunked": round(dt_c * 1e3, 1)}
    out["serving_tokens_per_s_prefill_heavy"] = {
        "monolithic": round(toks_m / max(dt_m, 1e-9), 2),
        "chunked": round(toks_c / max(dt_c, 1e-9), 2)}
    out["serving_prefill_cache_entries"] = {
        "monolithic": pre_m, "chunked": pre_c}

    # Speculative decode on/off over the SAME repetitive decode-heavy
    # trace (the workload speculation exists for: greedy decode of
    # looping/templated continuations, where the n-gram self-draft
    # predicts several tokens per dispatch). Ratio = dispatches
    # amortized; absolute numbers track the CPU dispatch overhead.
    spec_trace = [[1, 2, 3, 1, 2, 3, 1, 2], [7, 8, 7, 8, 7, 8],
                  [5, 5, 5, 5], [9, 10, 11, 9, 10, 11]]
    out["serving_tokens_per_s_spec"] = {}
    out["serving_spec_accept_rate"] = None
    for name, k in (("nospec", 0), ("spec", 4)):
        e = Engine(cfg, mesh, mode="xla", max_len=96, seed=0)
        s = ServingEngine(e, num_slots=1, page=8, spec_k=k)
        # Warm with the SAME trace: prefill compiles once per distinct
        # prompt length — the timed pass measures the steady-state
        # decode loop (the surface speculation moves), not the
        # per-length compile tax the chunked-prefill key already owns.
        s.generate(spec_trace, max_new_tokens=32)
        for c in s.stats_counters:
            s.stats_counters[c] = type(s.stats_counters[c])(0)
        t0 = time.perf_counter()
        s.generate(spec_trace, max_new_tokens=32)
        dt = time.perf_counter() - t0
        st = s.stats()
        out["serving_tokens_per_s_spec"][name] = round(
            st["tokens_generated"] / max(dt, 1e-9), 2)
        if k:
            out["serving_spec_accept_rate"] = (
                None if st["spec"]["accept_rate"] is None
                else round(st["spec"]["accept_rate"], 4))
            assert s.decode_cache_size() == 1, (
                "spec verify dispatch re-specialized")

    # Telemetry: TTFT / inter-token-latency percentiles from the
    # counters-mode histograms over the same skewed trace, plus the
    # telemetry overhead — counters-mode wall clock vs telemetry="off"
    # on identical traffic (best-of-3 each; the acceptance bar is
    # < 5%, and the honest expectation is ~0: counters mode costs two
    # clock reads and a bisect per instrumented region while every
    # dispatch is an XLA call).
    def telemetry_run(mode):
        srv = ServingEngine(eng, num_slots=2, page=8, telemetry=mode)
        srv.generate([[1, 2]], max_new_tokens=2)     # compile warmup
        best = float("inf")
        for _ in range(3):
            for k in srv.stats_counters:
                srv.stats_counters[k] = type(srv.stats_counters[k])(0)
            for p, g in zip(prompts, gens):
                srv.submit(p, max_new_tokens=g)
            t0 = time.perf_counter()
            srv.run()
            best = min(best, time.perf_counter() - t0)
        return best, srv.stats()

    t_off, _ = telemetry_run("off")
    t_cnt, st_cnt = telemetry_run("counters")
    lat = st_cnt.get("latency") or {}

    def _pcts(series):
        s = lat.get(series) or {}
        return {"p50": s.get("p50"), "p99": s.get("p99")}

    out["serving_ttft_ms"] = _pcts("ttft_ms")
    out["serving_itl_ms"] = _pcts("itl_ms")
    out["telemetry_overhead_pct"] = round(
        (t_cnt / max(t_off, 1e-9) - 1.0) * 100.0, 2)

    # Quantized paged KV: HBM cost per token at each kv_dtype (from
    # the model plan) and the paged decode step's wall time bf16 vs
    # int8/fp8 through the SAME ServingEngine decode dispatch (ref
    # attention on this CPU host — dequant-on-gather; the TPU kernel
    # fuses the dequant into the page prefetch).
    out["kv_bytes_per_token"] = {}
    out["paged_decode_quant_ms"] = {}
    for kvd in ("bf16", "int8", "fp8"):
        e = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)
        s = ServingEngine(e, num_slots=2, page=8, kv_dtype=kvd)
        out["kv_bytes_per_token"][kvd] = round(
            s.plan["bytes_per_token"], 2)
        s.generate([[1, 2, 3]], max_new_tokens=2)   # compile warmup
        s.submit([4, 5, 6], max_new_tokens=8)
        s.submit([7, 8], max_new_tokens=8)
        n0 = s.stats()["decode_dispatches"]
        t0 = time.perf_counter()
        s.run()
        dt = time.perf_counter() - t0
        n = s.stats()["decode_dispatches"] - n0
        out["paged_decode_quant_ms"][kvd] = round(
            dt * 1e3 / max(n, 1), 3)
    return out


def _interpret_ep_times() -> dict:
    """Decode-batch EP dispatch round-trip, ragged vs low-latency, on
    the interpret mesh — the ``detail.ep_dispatch_ms`` surface a
    CPU-only host must still fill (non-null gate in scripts/
    ep_smoke.sh). ``ragged`` times the exact-splits
    ep_dispatch/ep_combine pair; ``ll`` times the count-free
    wire-quantized ll_a2a there-and-back at the same (B·K, d) payload
    (force_kernel: the single-chip mesh must still run the full slot-
    parity kernel, not the short-circuit). Interpreter-step overhead,
    not silicon — meaningful as presence + relative shape only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_dist_tpu.ops.ep_a2a import (create_ep_context,
                                            ep_dispatch, ep_combine)
    from triton_dist_tpu.ops.low_latency import ll_a2a
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.utils.testing import spmd

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mctx = MeshContext.from_mesh(mesh)
    b, k, d, e = 4, 2, 32, 8
    ctx = create_ep_context(mctx, num_experts=e, topk=k, axis="tp")
    x = jax.random.normal(jax.random.PRNGKey(0), (b, d), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, k), 0, e)
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (b, k)),
                       axis=-1)

    def ragged(tok, ids_, w_):
        recv, _, st = ep_dispatch(tok, ids_, ctx)
        return ep_combine(recv, st, w_, ctx)

    def ll(tok, ids_, w_):
        del ids_, w_
        payload = jnp.repeat(tok, k, axis=0)[None]      # (1, BK, d)
        out = ll_a2a(payload, ctx=mctx, axis="tp", step=0,
                     force_kernel=True)
        back = ll_a2a(out, ctx=mctx, axis="tp", step=1,
                      force_kernel=True)
        return back[0]

    specs = (P(None, None), P(None, None), P(None, None))
    steps = {
        "ragged": spmd(mesh, ragged, specs, P(None, None)),
        "ll": spmd(mesh, ll, specs, P(None, None)),
    }
    out = {}
    for name, step in steps.items():
        np.asarray(step(x, ids, w))                     # warmup
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(step(x, ids, w))
            best = min(best, time.perf_counter() - t0)
        out[name] = round(best * 1e3, 3)
    return {"ep_dispatch_ms": out,
            "ep_dispatch_shape": {"batch": b, "topk": k, "hidden": d,
                                  "experts": e}}


def _interpret_ep2d() -> dict:
    """Hierarchical 2-hop EP decode dispatch, ``ar`` vs ``ll2d``, on
    the interpret mesh — the ``detail.ep_dispatch_2d_ms`` surface a
    CPU-only host must still fill (non-null gate in
    scripts/ep2d_smoke.sh). One device plays a degenerate 1×1
    (dcn, ici) hierarchy: both hops still trace, so the trace-time put
    ledger records the real hop schedule, and the ``ep2d_dcn_puts``
    block reports the canonical 2×4 arithmetic the schedule implies —
    1 DCN slab put per dispatch where the flat ``ll`` pays 4.
    Interpreter-step overhead, not silicon — presence + relative shape
    only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_dist_tpu.layers import ep_moe
    from triton_dist_tpu.models.config import ModelConfig
    from triton_dist_tpu.ops.ep_a2a import create_ep2d_context
    from triton_dist_tpu.ops.ll_a2a_2d import record_dispatch_puts
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.utils.testing import spmd

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("dcn", "ici"))
    mctx = MeshContext.from_mesh(mesh)
    b, k, d, e = 4, 2, 32, 8
    cfg = ModelConfig.tiny_moe(hidden_size=d, moe_intermediate_size=16,
                               num_experts=e, num_experts_per_tok=k)
    ctx = create_ep2d_context(mctx, num_experts=e, topk=k,
                              outer_axis="dcn", inner_axis="ici")
    params = ep_moe.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, d), jnp.float32)
    axis = ("dcn", "ici")
    pspecs = {name: ep_moe.param_specs(axis)[name] for name in params}

    def step_for(tr):
        return spmd(mesh,
                    lambda p, v, _tr=tr: ep_moe.fwd_decode(
                        p, v, topk=k, axis=axis, transport=_tr,
                        ep_ctx=ctx),
                    (pspecs, P(None, None)), P(None, None))

    out = {}
    for tr in ("ar", "ll2d"):
        step = step_for(tr)
        np.asarray(step(params, x))                     # warmup
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(step(params, x))
            best = min(best, time.perf_counter() - t0)
        out[tr] = round(best * 1e3, 3)

    # The put schedule, read off an actual dispatch trace (hop order
    # and per-hop put arithmetic are shape-static, so the degenerate
    # mesh records the same 2-hop schedule a real hierarchy issues).
    with record_dispatch_puts() as led:
        jax.eval_shape(step_for("ll2d"), params, x)
    puts = {"hops_traced": [ev["hop"] for ev in led],
            # canonical 2 nodes x 4 chips: (n_out-1) vs (n_out-1)*n_in
            "hierarchy": "2x4", "ll2d": 1, "flat_ll": 4}
    return {"ep_dispatch_2d_ms": out,
            "ep2d_dcn_puts": puts,
            "ep_dispatch_2d_shape": {"batch": b, "topk": k, "hidden": d,
                                     "experts": e}}


def _interpret_qblock_times() -> dict:
    """Paged Q-block attention, flash kernel vs gather ref, on the
    interpret mesh — the ``chunk_attend_ms`` / ``verify_attend_ms``
    surface a CPU-only host must still fill (non-null gate in
    scripts/qblock_smoke.sh). Shapes mirror the serving reality the
    kernel exists for: a pool sized for the CAPACITY (p_max·page) with
    slots resident far below it — the gather ref materializes every
    slot's full dense row per call, the kernel walks only the resident
    pages, so flash <= ref even at interpreter-step overhead. The
    verify shape is the K-candidate decode batch, the chunk shape one
    slot's bucketed chunk."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.ops.paged_flash_qblock import (
        paged_flash_qblock, paged_flash_qblock_ref)

    kvh, rep, hd, page, p_max = 4, 2, 32, 32, 16
    h = kvh * rep
    resident = 40                   # tokens actually resident per slot

    def one(b, cq):
        rng = np.random.RandomState(0)
        num_pages = b * p_max + 1
        kp = jnp.asarray(rng.randn(num_pages, kvh, page, hd)
                         .astype(np.float32))
        vp = jnp.asarray(rng.randn(num_pages, kvh, page, hd)
                         .astype(np.float32))
        tbl = jnp.asarray((1 + np.arange(b * p_max))
                          .reshape(b, p_max).astype(np.int32))
        q = jnp.asarray(rng.randn(b, cq, h, hd).astype(np.float32))
        pos = jnp.asarray((resident + np.arange(cq))[None]
                          .repeat(b, 0).astype(np.int32))
        out = {}
        for name, fn in (("flash", paged_flash_qblock),
                         ("ref", paged_flash_qblock_ref)):
            step = jax.jit(lambda *a, _f=fn: _f(*a))
            np.asarray(step(q, kp, vp, tbl, pos))      # warmup
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(step(q, kp, vp, tbl, pos))
                best = min(best, time.perf_counter() - t0)
            out[name] = round(best * 1e3, 3)
        return out

    return {
        "chunk_attend_ms": one(1, 32),      # one slot, bucket of 32
        "verify_attend_ms": one(4, 4),      # 4 slots, K=4 candidates
        "qblock_shape": {"kv_heads": kvh, "gqa": rep, "head_dim": hd,
                         "page": page, "p_max": p_max,
                         "resident_tokens": resident},
    }


def _interpret_chaos() -> dict:
    """A short seeded chaos soak through the fault-tolerant serving
    stack on the CPU mesh — the ``detail.chaos_survived_faults``
    surface (non-null gate in scripts/chaos_smoke.sh): seeded mixed
    traffic + injected dropped/wedged migrations, chunk faults, decode
    faults and a worker kill, with the invariant checker after every
    tick and token-exactness vs the fault-free oracle. A completed
    soak IS the result — any violation raises and nulls the keys."""
    import jax
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.resilience import chaos
    from triton_dist_tpu.resilience.policy import RetryPolicy
    from triton_dist_tpu.serving import DisaggServingEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))

    def factory():
        eng = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)
        return DisaggServingEngine(
            eng, num_slots=2, page=8, prefill_buckets=(4, 8),
            prefix_reuse=True, retry=RetryPolicy(max_attempts=2),
            worker_fail_threshold=2)

    rep = chaos.run_soak(factory, seed=11, ticks=40, n_faults=5,
                         restore_at=18)
    return {
        "chaos_survived_faults": rep.survived_faults,
        "chaos_ticks": rep.ticks,
        "chaos_requests": rep.requests,
        "chaos_retries": rep.counters["retries"],
        "chaos_failovers": rep.counters["failovers"],
        "chaos_restored_requests": rep.counters["restored_requests"],
        "chaos_invariant_checks": rep.invariant_checks,
    }


def _interpret_supervised() -> dict:
    """Process-level fault domain on the CPU mesh — the
    ``crash_recovery_ms`` / ``supervised_survived_faults`` /
    ``integrity_checks`` surface (non-null gate in
    scripts/supervise_smoke.sh): a short seeded supervised soak (a
    REAL child process SIGKILLed and stalled mid-serve, streams
    resumed token-exact from the checkpoint ring) plus the in-process
    integrity drill (seeded payload corruption at the tier /
    migration / handoff boundaries, each detected and recovered).  A
    completed run IS the result — divergence or a missed detection
    raises and nulls the keys."""
    import tempfile

    from triton_dist_tpu.resilience import chaos

    rep = chaos.run_supervised_soak(
        checkpoint_dir=tempfile.mkdtemp(prefix="tdt-sup-bench-"),
        seed=11, n_requests=3, n_faults=2,
        kinds=(("kill_child", None, None),
               ("stall_child", None, None)),
        gen_choices=(4, 6), deadline_s=300.0)
    drill = chaos.run_integrity_drill()
    rec = rep.supervisor.get("last_recovery_ms")
    return {
        "crash_recovery_ms": round(rec, 1) if rec else None,
        "supervised_survived_faults": rep.survived_faults,
        "supervised_restarts": rep.supervisor["restarts"],
        "supervised_dedup_dropped": rep.supervisor["dedup_dropped"],
        "integrity_checks": (drill["tier_checks"]
                            + drill["migration_integrity_failures"]
                            + drill["handoff_integrity_failures"]),
        "integrity_quarantined": drill["tier_quarantined"],
    }


def _interpret_tiers() -> dict:
    """Tiered KV memory hierarchy on the CPU mesh — the
    ``kv_hot_hit_rate`` / ``session_resume_ms`` / ``offloaded_pages``
    surface (non-null gate in scripts/tier_smoke.sh): a seeded
    heavy-tailed multi-turn trace over a 100k-session id space served
    through an HBM pool sized WELL below the working set, so cold
    prefixes demote into the host tier and hot reuse prefetches them
    back; plus a park/resume drill whose resume latency (requeue →
    token-exact reactivation, prefetch overlapped against decode)
    lands in the per-op histogram. Absolute times track the CPU
    dispatch, not silicon; the hit rate and the non-null presence are
    the gates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.serving import ServingEngine, heavy_tail_trace
    from triton_dist_tpu.serving.tiers import extend_session

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)
    srv = ServingEngine(eng, num_slots=2, page=4, num_pages=12,
                        prefix_reuse=True, prefill_buckets=(4, 8),
                        kv_tiers={"host_pages": 512})
    events = heavy_tail_trace(28, n_sessions=100_000, vocab=64, seed=7,
                              max_total=20)
    history = {}
    t0 = time.perf_counter()
    for ev in events:
        prompt = extend_session(history, ev, max_prompt=12)
        h = srv.submit(prompt, max_new_tokens=ev["gen"])
        srv.run()
        extend_session(history, ev, reply=h.tokens)
    trace_dt = time.perf_counter() - t0
    # Park/resume drill: 3 sessions parked mid-decode and resumed —
    # the resume span (requeue -> reactivation) feeds the histogram.
    for i in range(3):
        h = srv.submit([1 + i, 2, 3], max_new_tokens=5)
        while h.status != "running":
            srv.step()
        srv.step()
        srv.park(h)
        srv.resume(h)
        srv.run()
        assert h.status == "done"
    st = srv.stats()
    resume = (st["latency"]["ops"].get("resume") or {})
    assert srv.decode_cache_size() == 1, "tiering re-specialized decode"
    return {
        "kv_hot_hit_rate": st["kv_hot_hit_rate"],
        "session_resume_ms": resume.get("mean"),
        "offloaded_pages": st["offloaded_pages"],
        "tier_detail": {
            "trace_events": len(events),
            "trace_session_space": 100_000,
            "distinct_sessions": len({e["session"] for e in events}),
            "trace_wall_ms": round(trace_dt * 1e3, 1),
            "tier_hits": st["tier_hits"],
            "tier_misses": st["tier_misses"],
            "prefetched_pages": st["prefetched_pages"],
            "demotions": st["pool"]["demotions"],
            "parks": st["parks"], "resumes": st["resumes"],
            "session_resume_p99_ms": resume.get("p99"),
            "hbm_pool_pages": 12,
        },
    }


def _interpret_fleet() -> dict:
    """Fleet-scale serving on the CPU mesh — the
    ``fleet_p99_ttft_ms`` / ``fleet_failover_resumed`` /
    ``fleet_shed_requests`` / ``router_affinity_hit_rate`` surface
    (non-null gate in scripts/fleet_smoke.sh): a seeded heavy-tailed
    multi-turn trace routed with prefix affinity across R=2 fleets, a
    mid-run reachable fleet kill whose running session fails over
    cross-fleet through the parked-tier path (token-exactness
    asserted inline), and a saturation drill that sheds one
    batch-class request. Absolute times track the CPU dispatch; the
    counters and non-null presence are the gates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.resilience import chaos
    from triton_dist_tpu.serving import (
        FleetRouter, ServingEngine, heavy_tail_trace,
    )
    from triton_dist_tpu.serving.tiers import extend_session

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)

    def factory(**kw):
        args = dict(num_slots=2, page=4, num_pages=16,
                    prefix_reuse=True, kv_tiers={"host_pages": 128})
        args.update(kw)
        return ServingEngine(eng, **args)

    router = FleetRouter(lambda: factory(), fleets=2)
    events = heavy_tail_trace(24, n_sessions=40, vocab=64, seed=5,
                              zipf_a=1.2, turn_tokens=(4, 8),
                              max_total=16)
    history = {}
    t0 = time.perf_counter()
    for ev in events:
        prompt = extend_session(history, ev, max_prompt=16)
        h = router.submit(prompt, max_new_tokens=ev["gen"])
        router.run()
        extend_session(history, ev, reply=h.tokens)
    trace_dt = time.perf_counter() - t0
    # Mid-run fleet-kill drill: a running session fails over through
    # the parked-tier hop and must resume token-exact.
    prompt = [5, 5, 5, 5, 5, 5, 5, 5]
    ids = np.tile(np.asarray([prompt], np.int32), (1, 1))
    want = np.asarray(eng.serve(jnp.asarray(ids),
                                gen_len=8))[0].tolist()
    h = router.submit(prompt, max_new_tokens=8)
    for _ in range(200):
        if h.status == "running" and h.tokens:
            break
        router.step()
    victim = router._fleet_of(h)
    router.kill_fleet(victim.id, reachable=True)
    chaos.check_fleet_invariants(router, [h])
    router.run()
    assert h.status == "done" and h.tokens == want, (
        "cross-fleet failover diverged from the single-engine oracle")
    st = router.stats()
    assert all(n == 1 for n in router.decode_cache_sizes()), (
        "fleet routing re-specialized a decode dispatch")
    # Saturation shed drill (tiny queues, batch class): deterministic
    # graceful degradation so the shed counter is a real measurement.
    shed_router = FleetRouter(
        lambda: factory(num_slots=1, max_queue=1, kv_tiers=None),
        fleets=2, max_queue=0, affinity=False)
    backlog = [shed_router.submit([i + 1, 2], max_new_tokens=2)
               for i in range(2)]
    dropped = shed_router.submit([9, 9], max_new_tokens=2)
    assert dropped.status == "shed"
    shed_router.run()
    assert all(b.status == "done" for b in backlog)
    ttft = st["fleet_ttft_ms"] or {}
    return {
        "fleet_p99_ttft_ms": ttft.get("p99"),
        "fleet_failover_resumed": st["failover_resumed"],
        "fleet_shed_requests":
            shed_router.stats()["shed_requests"],
        "router_affinity_hit_rate": st["router_affinity_hit_rate"],
        "fleet_detail": {
            "fleets": 2,
            "trace_events": len(events),
            "trace_wall_ms": round(trace_dt * 1e3, 1),
            "routed": st["routed"],
            "spillovers": st["spillovers"],
            "fleet_failovers": st["fleet_failovers"],
            "failover_reprefilled": st["failover_reprefilled"],
            "kv_hot_hit_rate": st["kv_hot_hit_rate"],
            "fleet_p50_ttft_ms": ttft.get("p50"),
            "live_fleets": st["live_fleets"],
        },
    }


def _interpret_slo() -> dict:
    """Multi-tenant SLO scheduling on the CPU mesh — the
    ``slo_attainment`` / ``tenant_interactive_p99_ttft_ms`` /
    ``slo_preemptions`` surface (non-null gate in
    scripts/slo_smoke.sh): the SAME seeded mixed-tenant trace (a bulk
    batch flood plus periodic interactive arrivals with deadlines)
    served twice on a fake tick clock — once FIFO, once through the
    SLO layer with preemption armed. The measurement is the isolation
    ratio: interactive p99 TTFT must improve >= 2x under SLO while the
    bulk tenant's tokens/s degrades <= 20% (ISSUE 20's acceptance
    bar), with every stream bit-identical to ``Engine.serve`` and the
    decode jit cache at one entry. Absolute tick counts track the CPU
    dispatch; the ratio and the non-null presence are the gates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.serving import ServingEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)

    def run_trace(slo):
        clock = [0.0]
        srv = ServingEngine(eng, num_slots=2, page=4,
                            clock=lambda: clock[0], slo=slo)
        bulk = [srv.submit([i + 1, 2, 3], max_new_tokens=12,
                           tenant="bulk") for i in range(4)]
        chat, tick, t0 = [], 0, time.perf_counter()
        while not srv._drained() or len(chat) < 4:
            if tick % 2 == 0 and len(chat) < 4:
                # Deadline 12 ticks out: comfortably past the ~6-tick
                # service time, close enough that a chat stuck >= 2
                # ticks behind the flood enters the preemption margin.
                # The FIFO baseline gets the tenant label only — a
                # scheduler that ignores deadlines would otherwise
                # EXPIRE these requests, not serve them late.
                kw = ({"deadline": clock[0] + 12.0}
                      if slo is not None else {})
                chat.append(srv.submit([40 + len(chat), 7],
                                       max_new_tokens=4,
                                       tenant="chat", **kw))
            srv.step()
            clock[0] += 1.0
            tick += 1
            assert tick < 500, "slo bench trace failed to drain"
        wall = time.perf_counter() - t0
        for h in bulk + chat:
            n = h.request.max_new_tokens
            ids = jnp.asarray(np.tile(np.asarray(
                [list(h.request.prompt)], np.int32), (1, 1)))
            want = np.asarray(eng.serve(ids, gen_len=n))[0].tolist()
            assert h.tokens == want, (
                f"slo={slo is not None}: stream diverged from the "
                f"serve oracle for {h.request.request_id}")
        assert srv.decode_cache_size() == 1, (
            "SLO scheduling re-specialized the decode dispatch")
        st = srv.stats()
        lat = st["latency"]["per_tenant"]["chat"]["ttft_ms"]
        # Batch throughput over the full serving window — last-finish
        # would penalize the REORDERING itself (batch inherently
        # finishes later when interactive runs first), not lost work.
        return {
            "p99_ttft": lat["p99"], "ticks": tick, "wall": wall,
            "bulk_tokens_per_tick": 4 * 12 / tick, "stats": st,
        }

    fifo = run_trace(None)
    slo = run_trace({"specs": [{"name": "chat", "weight": 2.0}],
                     "preempt_margin_s": 10.0})
    isolation = fifo["p99_ttft"] / max(slo["p99_ttft"], 1e-9)
    bulk_ratio = (slo["bulk_tokens_per_tick"]
                  / max(fifo["bulk_tokens_per_tick"], 1e-9))
    st = slo["stats"]
    assert isolation >= 2.0, (
        f"interactive isolation only {isolation:.2f}x (need >= 2x)")
    assert bulk_ratio >= 0.8, (
        f"bulk throughput degraded to {bulk_ratio:.2f} (floor 0.8)")
    assert st["slo_preemptions"] >= 1
    return {
        "slo_attainment": st["slo_attainment"],
        "tenant_interactive_p99_ttft_ms": st[
            "latency"]["per_tenant"]["chat"]["ttft_ms"]["p99"],
        "slo_preemptions": st["slo_preemptions"],
        "slo_detail": {
            "interactive_isolation_x": round(isolation, 2),
            "fifo_interactive_p99_ttft_ms": fifo["p99_ttft"],
            "bulk_throughput_ratio": round(bulk_ratio, 3),
            "fifo_ticks": fifo["ticks"], "slo_ticks": slo["ticks"],
            "slo_wall_ms": round(slo["wall"] * 1e3, 1),
            "tenants": {t: {k: v[k] for k in
                            ("admitted", "released", "preempted",
                             "met", "missed")}
                        for t, v in st["slo"]["tenants"].items()},
        },
    }


def _variant_best_ms(sweep, variant, block_m=None):
    """Best swept time (ms) for one ag_gemm variant, optionally pinned
    to one block_m; None — not omitted — when nothing lowered."""
    ts = [t for t, c, _ in sweep
          if c.get("variant", "panel") == variant
          and (block_m is None or c.get("block_m") == block_m)]
    return round(min(ts) * 1e3, 3) if ts else None


def _interpret_ag_variants() -> dict:
    """Panel-vs-pipelined crossover on the interpret mesh: both
    variants at block_m {128, 256, 512} on the same sim ring and
    shape. Interpreter ratios track schedule/body-count overhead, not
    silicon overlap — but the pipelined variant runs its REAL
    scoped-VMEM streamed kernel here (no fallback exists), so the
    comparison is meaningful for gating: the streamed grid has no kk
    dimension, and a regression that re-bloats its body count or
    staging shows up as pipelined >> panel.

    Shape: m_loc=512 after the sim-4 split so block_m=512 is a real
    single-row-tile grid; K=32 with block_k=16 gives each variant two
    k-steps (the panel as grid bodies, the stream as rotating
    buffers) while every staged buffer stays <= 64 KB — the interpret
    harness starves above that.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_dist_tpu.ops import ag_gemm, create_ag_gemm_context
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.utils.testing import spmd

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mctx = MeshContext.from_mesh(mesh)
    sim = 4
    a = jax.random.normal(jax.random.PRNGKey(4), (2048, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(5), (32, 64), jnp.float32)
    want = np.asarray(a, np.float32) @ np.asarray(b, np.float32)

    crossover = {}
    best = {"panel": None, "pipelined": None}
    for bm in (128, 256, 512):
        row = {}
        for variant in ("panel", "pipelined"):
            ctx = create_ag_gemm_context(mctx, block_m=bm, block_n=64,
                                         block_k=16, variant=variant)
            step = spmd(mesh,
                        lambda x, w, _c=ctx: ag_gemm(x, w, _c,
                                                     sim_ranks=sim),
                        (P(None, None), P(None, None)), P(None, None))
            got = np.asarray(step(a, b), np.float32)  # warmup + gate
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
            t = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                np.asarray(step(a, b))
                t = min(t, time.perf_counter() - t0)
            row[f"{variant}_ms"] = round(t * 1e3, 3)
            if best[variant] is None or t * 1e3 < best[variant]:
                best[variant] = round(t * 1e3, 3)
        crossover[str(bm)] = row
    return {"ag_gemm_panel_ms": best["panel"],
            "ag_gemm_pipelined_ms": best["pipelined"],
            "ag_gemm_variant_crossover": crossover}


def _interpret_bench() -> None:
    """``BENCH_BACKEND=cpu``: run the overlap-schedule family on the
    interpret mesh (what the CPU smoke scripts gate on).

    The interpreter executes the REAL kernel schedule — ring puts,
    arrival waits, panel staging, swizzled chunk order — so the ratio
    below tracks schedule correctness and interpreter-step overhead,
    NOT hardware overlap efficiency: the record says ``platform: cpu``
    and ``detail.interpret_mode``, and carries no device number.
    Small shapes: the interpreter is ~1000x silicon."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_dist_tpu.ops import (ag_gemm, create_ag_gemm_context,
                                     create_gemm_rs_context, gemm_rs)
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.utils.testing import spmd

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mctx = MeshContext.from_mesh(mesh)
    sim = 4
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)

    ag_ctx = create_ag_gemm_context(mctx, block_m=16, block_n=8)
    rs_ctx = create_gemm_rs_context(mctx, block_m=16, block_n=16)
    steps = {
        "ag_gemm": spmd(mesh, lambda x, w: ag_gemm(x, w, ag_ctx,
                                                   sim_ranks=sim),
                        (P(None, None), P(None, None)), P(None, None)),
        "gemm_rs": spmd(mesh, lambda x, w: gemm_rs(x, w, rs_ctx,
                                                   sim_ranks=sim),
                        (P(None, None), P(None, None)), P(None, None)),
        "compute": spmd(mesh,
                        lambda x, w: jnp.dot(
                            x, w, preferred_element_type=jnp.float32
                        ).astype(x.dtype),
                        (P(None, None), P(None, None)), P(None, None)),
    }
    want = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    times = {}
    for name, step in steps.items():
        got = np.asarray(step(a, b), np.float32)  # warmup + correctness
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(step(a, b))
            best = min(best, time.perf_counter() - t0)
        times[name] = best

    eff = times["compute"] / max(times["ag_gemm"], 1e-9)
    try:
        mk = _interpret_megakernel_times()
    except Exception as e:  # megakernel bench must not sink the record
        mk = {"megakernel_decode_step_ms": None,
              "megakernel_error": str(e)[:200]}
    try:
        sv = _interpret_serving_times()
    except Exception as e:  # serving bench must not sink the record
        sv = {"serving_tokens_per_s": None,
              "prefill_chunked_vs_monolithic_ms": None,
              "serving_tokens_per_s_prefill_heavy": None,
              "serving_tokens_per_s_spec": None,
              "serving_spec_accept_rate": None,
              "kv_bytes_per_token": None,
              "paged_decode_quant_ms": None,
              "serving_ttft_ms": None,
              "serving_itl_ms": None,
              "telemetry_overhead_pct": None,
              "serving_error": str(e)[:200]}
    try:
        ep = _interpret_ep_times()
    except Exception as e:  # ep bench must not sink the record
        ep = {"ep_dispatch_ms": None, "ep_error": str(e)[:200]}
    try:
        e2 = _interpret_ep2d()
    except Exception as e:  # ep2d bench must not sink the record
        # Nulled, NOT omitted: the ep2d_smoke gate greps these keys.
        e2 = {"ep_dispatch_2d_ms": None, "ep2d_dcn_puts": None,
              "ep2d_error": str(e)[:200]}
    try:
        qb = _interpret_qblock_times()
    except Exception as e:  # qblock bench must not sink the record
        # Nulled, NOT omitted: a consumer greps the keys either way.
        qb = {"chunk_attend_ms": None, "verify_attend_ms": None,
              "qblock_error": str(e)[:200]}
    try:
        ch = _interpret_chaos()
    except Exception as e:  # chaos soak must not sink the record
        ch = {"chaos_survived_faults": None,
              "chaos_error": str(e)[:300]}
    try:
        sp = _interpret_supervised()
    except Exception as e:  # supervised soak must not sink the record
        # Nulled, NOT omitted: the supervise_smoke gate greps these.
        sp = {"crash_recovery_ms": None,
              "supervised_survived_faults": None,
              "integrity_checks": None,
              "supervise_error": str(e)[:300]}
    try:
        ti = _interpret_tiers()
    except Exception as e:  # tier bench must not sink the record
        # Nulled, NOT omitted: the tier_smoke gate greps these keys.
        ti = {"kv_hot_hit_rate": None, "session_resume_ms": None,
              "offloaded_pages": None, "tiers_error": str(e)[:300]}
    try:
        fl = _interpret_fleet()
    except Exception as e:  # fleet bench must not sink the record
        # Nulled, NOT omitted: the fleet_smoke gate greps these keys.
        fl = {"fleet_p99_ttft_ms": None,
              "fleet_failover_resumed": None,
              "fleet_shed_requests": None,
              "router_affinity_hit_rate": None,
              "fleet_error": str(e)[:300]}
    try:
        so = _interpret_slo()
    except Exception as e:  # slo bench must not sink the record
        # Nulled, NOT omitted: the slo_smoke gate greps these keys.
        so = {"slo_attainment": None,
              "tenant_interactive_p99_ttft_ms": None,
              "slo_preemptions": None,
              "slo_error": str(e)[:300]}
    try:
        mp = _interpret_mega_parity()
    except Exception as e:  # mk parity bench must not sink the record
        # Nulled, NOT omitted: the mega_parity_smoke gate greps these.
        mp = {"megakernel_decode_quant_ms": None,
              "megakernel_tokens_per_s_spec": None,
              "megakernel_spec_accept_rate": None,
              "mega_error": str(e)[:300]}
    try:
        mc = _interpret_mega_chunked()
    except Exception as e:  # mk chunked bench must not sink the record
        # Nulled, NOT omitted: the mkchunk_smoke gate greps these.
        mc = {"megakernel_prefill_chunk_ms": None,
              "megakernel_tokens_per_s_prefill_heavy": None,
              "megakernel_prefill_chunk_speedup": None,
              "mega_error": str(e)[:300]}
    try:
        av = _interpret_ag_variants()
    except Exception as e:  # variant sweep must not sink the record
        # Nulled, NOT omitted: the aggemm_smoke gate greps these keys.
        av = {"ag_gemm_panel_ms": None, "ag_gemm_pipelined_ms": None,
              "ag_gemm_variant_crossover": None,
              "ag_variant_error": str(e)[:300]}
    out = {
        "metric": "ag_gemm_overlap_efficiency_interpret",
        "value": round(float(eff), 4),
        "unit": "ratio_vs_compute_only_gemm_interpret",
        "vs_baseline": None,   # interpreter ratios are not comparable
        "platform": jax.devices()[0].platform,
        "detail": {
            "interpret_mode": True,
            "measured_at_unix": int(time.time()),
            "sim_ranks": sim,
            "ag_gemm_ms": round(times["ag_gemm"] * 1e3, 3),
            "gemm_rs_ms": round(times["gemm_rs"] * 1e3, 3),
            "gemm_rs_efficiency": round(
                float(times["compute"] / max(times["gemm_rs"], 1e-9)), 4),
            "compute_only_ms": round(times["compute"] * 1e3, 3),
            "shape_m_k_n": [256, 32, 64],
            **mk,
            **sv,
            **ep,
            **e2,
            **qb,
            **ch,
            **sp,
            **ti,
            **fl,
            **so,
            **mp,
            **mc,
            **av,
        },
    }
    print(json.dumps(out))


def _require_tpu():
    """The device measurements have no CPU form: a missing chip is an
    error naming what JAX found, not a fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench.py: no TPU — JAX found platform="
                 f"{devices[0].platform!r} (BENCH_BACKEND=cpu runs the "
                 "interpreter pass instead)")
    return devices


def main():
    if os.environ.get("BENCH_BACKEND") == "cpu":
        _interpret_bench()
        return

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from triton_dist_tpu.ops import ag_gemm, create_ag_gemm_context
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.utils.distributed import enable_compile_cache

    devices = _require_tpu()
    enable_compile_cache()
    n = len(devices)
    m_full, k_dim, n_dim = 2048, 4096, 4096
    dtype = jnp.bfloat16

    mesh = Mesh(np.array(devices), ("tp",))
    mctx = MeshContext.from_mesh(mesh)

    a = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (m_full, k_dim), dtype),
        NamedSharding(mesh, P("tp", None)))
    b = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (k_dim, n_dim), dtype),
        NamedSharding(mesh, P(None, "tp")))

    # Single chip: self-simulated ring (full multi-chip schedule with
    # self-targeted puts). Multi chip: the real overlapped collective.
    sim = SIM_RANKS if n == 1 else 0

    def make_fused_step(cfg, sim_ranks=sim):
        ctx = create_ag_gemm_context(mctx, **cfg)

        def fused_step(x, w):
            return jax.shard_map(
                lambda xs, ws: ag_gemm(
                    xs, ws, ctx, sim_ranks=sim_ranks,
                    force_kernel=(n == 1 and not sim_ranks)),
                mesh=mesh, in_specs=(P("tp", None), P(None, "tp")),
                out_specs=P(None, "tp"), check_vma=False)(x, w)
        return fused_step

    # Compute-only oracle: GEMM on already-gathered A (what overlap is
    # measured against in the reference charts, README.md:193).
    a_full = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (m_full, k_dim), dtype),
        NamedSharding(mesh, P(None, None)))

    def compute_step(x, w):
        return jax.shard_map(
            lambda xs, ws: jnp.dot(xs, ws, preferred_element_type=jnp.float32
                                   ).astype(dtype),
            mesh=mesh, in_specs=(P(None, None), P(None, "tp")),
            out_specs=P(None, "tp"), check_vma=False)(x, w)

    # Sweep block configs (tune-cache winner first), then re-time the
    # winner at full repeats. A single hardcoded config made round 1's
    # number a coin flip against noise.
    from triton_dist_tpu import tune

    tune_key = tune.make_key("ag_gemm_bench", m=m_full, k=k_dim, n=n_dim,
                             dtype=str(dtype.dtype), world=n)
    cached = tune.load_autotune_data(tune_key)
    configs = list(AG_GEMM_CONFIGS)
    if cached is not None and cached not in configs:
        configs.append(cached)  # extra candidate from a previous run

    def _sweep(name, cfgs, make_step, *args):
        """Time each config briefly; return sorted [(t, cfg, step)].
        Configs that fail to lower (e.g. VMEM overflow) are skipped —
        the autotuner's policy."""
        results, errs = [], []
        for cfg in cfgs:
            step = make_step(cfg)
            try:
                t = max(_timed_chain(step, *args, repeats=SWEEP_REPEATS),
                        1e-9)
            except Exception as e:
                errs.append(f"{cfg}: {type(e).__name__}: {str(e)[:600]}")
                continue
            results.append((t, cfg, step))
        assert results, f"no {name} config compiled:\n" + "\n".join(errs)
        results.sort(key=lambda e: e[0])
        return results

    # A ring that compiles under no config is an error carrying the
    # compiler's messages (_sweep's assertion) — never a demotion to the
    # rankless kernel, which skips the ring this benchmark is about.
    sweep = _sweep("ag_gemm", configs, make_fused_step, a, b)
    _, best_cfg, fused_step = sweep[0]

    # Correctness gate before persisting or timing: a fast wrong kernel
    # is worthless (and must not poison the tune cache).
    # jit the gate: the eager path compiles separately (and near VMEM
    # limits can fail where the measured jitted path does not).
    got = np.asarray(jax.jit(fused_step)(a, b), np.float32)
    want = np.asarray(compute_step(a_full, b), np.float32)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-1)
    tune.store_autotune_data(tune_key, best_cfg, seconds=sweep[0][0])

    # Secondary: GEMM+RS efficiency on the transposed problem — swept
    # over configs like ag_gemm above.
    from triton_dist_tpu.ops import gemm_rs, create_gemm_rs_context
    a_rs = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (m_full, k_dim), dtype),
        NamedSharding(mesh, P(None, "tp")))
    b_rs = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(3), (k_dim, n_dim), dtype),
        NamedSharding(mesh, P("tp", None)))

    def make_rs_step(cfg):
        ctx = create_gemm_rs_context(mctx, **cfg)

        def rs_step(x, w):
            return jax.shard_map(
                lambda xs, ws: gemm_rs(xs, ws, ctx, sim_ranks=sim),
                mesh=mesh, in_specs=(P(None, "tp"), P("tp", None)),
                out_specs=P("tp", None), check_vma=False)(x, w)
        return rs_step

    rs_key = tune.make_key("gemm_rs_bench", m=m_full, k=k_dim, n=n_dim,
                           dtype=str(dtype.dtype), world=n)
    rs_cached = tune.load_autotune_data(rs_key)
    rs_configs = list(GEMM_RS_CONFIGS)
    if rs_cached is not None and rs_cached not in rs_configs:
        rs_configs.append(rs_cached)
    rs_sweep = _sweep("gemm_rs", rs_configs, make_rs_step, a_rs, b_rs)
    rs_best_cfg, rs_fused = rs_sweep[0][1], rs_sweep[0][2]
    got_rs = np.asarray(jax.jit(rs_fused)(a_rs, b_rs), np.float32)
    want_rs = (np.asarray(a_rs, np.float32)
               @ np.asarray(b_rs, np.float32))
    np.testing.assert_allclose(got_rs, want_rs, rtol=3e-2, atol=3e-1)
    tune.store_autotune_data(rs_key, rs_best_cfg,
                             seconds=rs_sweep[0][0])

    # Tertiary: SP ring-attention kernel efficiency vs XLA's own dense
    # attention (the measurement the round-1 verdict flagged as missing
    # for the SP/CP family). Single-chip only: at n > 1 the fused op
    # solves a sequence-sharded n*S problem the dense chain doesn't —
    # the ratio would compare different problems (a proper multi-chip
    # attention benchmark needs sharded inputs + a global oracle).
    group = {
        "compute": (compute_step, a_full, b),
        "fused": (fused_step, a, b),
        "rs": (rs_fused, a_rs, b_rs),
    }
    if sim:
        # Continuity with rounds 1-3: the rankless pipeline number the
        # old headline reported (no ring; upper bound on the sim one).
        group["fused_rankless"] = (make_fused_step(best_cfg, 0), a, b)
    sp_attn_error = None
    if n == 1:
        from triton_dist_tpu.ops import sp_ag_attention_fused
        from triton_dist_tpu.ops.sp_ag_attention import _masked_attn

        s_len, h_n, kvh_n, hd_n = 2048, 16, 8, 128
        s_last = s_len // SIM_RANKS
        qa = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(4), (s_len, h_n, hd_n),
                              dtype) * 0.3,
            NamedSharding(mesh, P(None, None, None)))
        kv_a = tuple(
            jax.device_put(
                jax.random.normal(jax.random.PRNGKey(5 + i),
                                  (s_len, kvh_n, hd_n), dtype) * 0.3,
                NamedSharding(mesh, P(None, None, None)))
            for i in range(2))

        # Self-sim ring: play the last of SIM_RANKS ranks, all chunk
        # arrivals riding real self-put DMAs. Oracle computes the SAME
        # slice (last-rank queries over the full KV), so the ratio
        # compares identical work, overlap machinery included.
        def attn_fused(q_, kv_):
            return jax.shard_map(
                lambda qq, kk, vv: sp_ag_attention_fused(
                    qq, kk, vv, ctx=mctx, axis="tp",
                    sim_ranks=SIM_RANKS),
                mesh=mesh, in_specs=(P(None, None, None),) * 3,
                out_specs=P(None, None, None),
                check_vma=False)(q_, *kv_)

        def attn_xla(q_, kv_):
            return _masked_attn(q_[-s_last:], kv_[0], kv_[1],
                                s_len - s_last).astype(q_.dtype)

        # Correctness gate before timing (same policy as ag_gemm above:
        # a fast wrong kernel is worthless). A lowering failure of this
        # tertiary metric is recorded with the compiler's message and
        # the attn numbers are null, not fatal to the headline.
        try:
            np.testing.assert_allclose(
                np.asarray(attn_fused(qa, kv_a), np.float32),
                np.asarray(attn_xla(qa, kv_a), np.float32),
                rtol=3e-2, atol=3e-2)
            group["attn_fused"] = (attn_fused, qa, kv_a)
            group["attn_xla"] = (attn_xla, qa, kv_a)
        except AssertionError:
            raise    # numerics wrong: must surface, not skip
        except Exception as e:
            sp_attn_error = f"{type(e).__name__}: {str(e)[:600]}"

    # Final numbers: every chain interleaved in ONE measurement group —
    # numerator and denominator see the same host/chip conditions.
    times = _timed_chain_group(group)
    t_compute = max(times["compute"], 1e-9)
    t_fused = max(times["fused"], 1e-9)
    t_rs = max(times["rs"], 1e-9)
    t_attn_fused = max(times.get("attn_fused", 0.0), 1e-9)
    t_attn_xla = times.get("attn_xla")

    eff = t_compute / t_fused
    flops = 2 * m_full * k_dim * n_dim / max(n, 1)
    t_rankless = times.get("fused_rankless")
    result = {
        "metric": ("ag_gemm_overlap_efficiency" if n > 1 else
                   "ag_gemm_overlap_efficiency_selfsim_ring"),
        "value": round(float(eff), 4),
        "unit": "ratio_vs_compute_only_gemm",
        "vs_baseline": round(float(eff) / 0.90, 4),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": n,
        "detail": {
            "measured_at_unix": int(time.time()),
            "devices": n,
            "sim_ranks": (SIM_RANKS if sim else None),
            "sp_attn_error": sp_attn_error,
            "rankless_kernel_efficiency": (
                round(float(t_compute / t_rankless), 4)
                if t_rankless else None),
            "t_fused_ms": round(t_fused * 1e3, 3),
            "t_compute_only_ms": round(t_compute * 1e3, 3),
            "fused_tflops_per_chip": round(flops / t_fused / 1e12, 2),
            "gemm_rs_ms": round(t_rs * 1e3, 3),
            "gemm_rs_efficiency": round(float(t_compute / t_rs), 4),
            "gemm_rs_best_config": rs_best_cfg,
            "sp_attn_fused_ms": (round(t_attn_fused * 1e3, 3)
                                 if t_attn_xla else None),
            "sp_attn_xla_ms": (round(t_attn_xla * 1e3, 3)
                               if t_attn_xla else None),
            "sp_attn_kernel_efficiency": (
                round(float(t_attn_xla / t_attn_fused), 4)
                if t_attn_xla else None),
            "shape_m_k_n": [m_full, k_dim, n_dim],
            "best_config": best_cfg,
            # Per-variant bests + the block_m crossover table (nulled,
            # NOT omitted, when a variant's configs all failed to
            # lower: the aggemm_smoke gate greps these keys either
            # way).
            "ag_gemm_panel_ms": _variant_best_ms(sweep, "panel"),
            "ag_gemm_pipelined_ms": _variant_best_ms(sweep, "pipelined"),
            "ag_gemm_variant_crossover": {
                str(bm): {
                    "panel_ms": _variant_best_ms(sweep, "panel", bm),
                    "pipelined_ms": _variant_best_ms(sweep, "pipelined",
                                                     bm)}
                for bm in (128, 256, 512)},
            "swept_ms": {
                (f"{c.get('variant', 'panel')}:"
                 f"{c['block_m']}x{c['block_n']}x{c['block_k']}"):
                round(t * 1e3, 3) for t, c, _ in sweep},
        },
    }

    # Fold the hardware-battery pass rate into the headline record. The
    # battery runs IN THIS PROCESS: a chip belongs to one process, so a
    # child started from here could never reach it. The budget bounds
    # the gaps between entries. Set BENCH_BATTERY_BUDGET_S=0 to skip.
    budget = float(os.environ.get("BENCH_BATTERY_BUDGET_S", "1500"))
    if budget > 0:
        summary = _summarize_battery(battery(
            quiet=True, deadline=time.perf_counter() + budget))
        dp = summary.pop("decode_perf", None)
        result["detail"]["battery"] = summary
        if dp:
            result["detail"]["decode_perf"] = dp
    print(json.dumps(result))


def _summarize_battery(results) -> dict:
    ran, dropped, failed, decode_perf = 0, 0, [], None
    for rec in results:
        if rec.get("skipped"):
            dropped += 1
            continue
        ran += 1
        if not rec.get("ok"):
            failed.append(rec["op"])
        if rec["op"] == "engine_decode_throughput" and rec.get("ok"):
            decode_perf = {k: v for k, v in rec.items()
                           if k not in ("op", "ok", "wall_s")}
    out = {"pass_rate": round((ran - len(failed)) / max(ran, 1), 4),
           "passed": ran - len(failed), "ran": ran,
           "skipped": dropped, "failed_ops": failed}
    if decode_perf:
        out["decode_perf"] = decode_perf
    return out


def battery(quiet=False, deadline=None):
    """``bench.py --all``: execute EVERY fused op family once on the
    real chip at production-ish shapes (round-1 gap: only
    ag_gemm/gemm_rs had ever lowered on hardware — Mosaic-only failures
    in the others were invisible). Single chip, so collectives run
    rankless via force_kernel: the full Mosaic lowering (VMEM budgets,
    semaphore tables, HBM-workspace rules) executes; only the ICI wire
    is absent. Prints one JSON line per entry + a summary line."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_dist_tpu.parallel.mesh import MeshContext
    import triton_dist_tpu.ops as ops

    devices = _require_tpu()
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    mctx = MeshContext.from_mesh(mesh)
    dt = jnp.bfloat16

    def sm(fn, in_specs, out_specs=P(None, None)):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=False))

    k0 = jax.random.PRNGKey(0)
    b4k = jax.random.normal(jax.random.PRNGKey(1), (4096, 4096), dt)
    m1k = jax.random.normal(jax.random.PRNGKey(2), (1024, 4096), dt)

    def run_gemm_ar():
        """Correctness of both exchange schemes + the decode-shape perf
        comparison: fused gemm_ar vs the XLA dot
        (the n=1 psum oracle) at M=128 (reference
        low_latency_gemm_allreduce_op's regime, gemm_allreduce.py:669).
        Timed with the SELF-SIMULATED exchange (sim_ranks=8): the full
        push + per-slot reduce schedule runs, peers = self."""
        small = jax.random.normal(k0, (128, 4096), dt)
        want = np.asarray(small, np.float32) @ np.asarray(b4k, np.float32)
        steps = {}
        for variant in ("ll", "one_shot"):
            ctx = ops.create_gemm_ar_context(
                mctx, block_n=512, block_k=1024, variant=variant)
            f = sm(lambda x, w, c=ctx: ops.gemm_ar(x, w, c,
                                                   sim_ranks=8),
                   (P(None, None), P(None, None)))
            out = np.asarray(f(small, b4k), np.float32)
            np.testing.assert_allclose(out, want, rtol=3e-2, atol=3.0)
            steps[variant] = f

        def xla_step(x, w):
            return jnp.dot(x, w, preferred_element_type=jnp.float32
                           ).astype(dt)

        times = _timed_chain_group(
            {"ll": (steps["ll"], small, b4k),
             "one_shot": (steps["one_shot"], small, b4k),
             "xla_dot": (jax.jit(xla_step), small, b4k)},
            repeats=3, hi=72)
        return {"gemm_ar_ll_ms": round(times["ll"] * 1e3, 4),
                "gemm_ar_one_shot_ms": round(times["one_shot"] * 1e3, 4),
                "xla_dot_ms": round(times["xla_dot"] * 1e3, 4),
                "ll_vs_oracle": round(times["xla_dot"]
                                      / max(times["ll"], 1e-9), 4)}

    def run_allreduce(method):
        def go():
            f = sm(lambda x: ops.all_reduce(x, ctx=mctx, axis="tp",
                                            method=method,
                                            force_kernel=True),
                   (P(None, None),))
            out = np.asarray(f(m1k), np.float32)
            np.testing.assert_allclose(out, np.asarray(m1k, np.float32),
                                       rtol=1e-2, atol=1e-2)
        return go

    def run_allgather(mode):
        def go():
            f = sm(lambda x: ops.all_gather(x, ctx=mctx, axis="tp",
                                            mode=mode,
                                            force_kernel=True),
                   (P(None, None),))
            out = np.asarray(f(m1k), np.float32)
            np.testing.assert_allclose(out, np.asarray(m1k, np.float32))
        return go

    def run_a2a():
        x = jax.random.normal(k0, (1, 1024, 4096), dt)
        f = sm(lambda v: ops.all_to_all(v, ctx=mctx, axis="tp",
                                        force_kernel=True),
               (P(None, None, None),), P(None, None, None))
        out = np.asarray(f(x), np.float32)
        np.testing.assert_allclose(out, np.asarray(x, np.float32))

    def run_fast_allgather():
        # push_2d exercises the factored-grid _push_nd_kernel (push_1d
        # delegates to the full-mesh AG already covered above).
        x = jax.random.normal(k0, (128, 4096), dt)  # decode-shape msg
        f = sm(lambda v: ops.fast_allgather(v, ctx=mctx, axis="tp",
                                            mode="push_2d",
                                            force_kernel=True),
               (P(None, None),))
        out = np.asarray(f(x), np.float32)
        np.testing.assert_allclose(out, np.asarray(x, np.float32))

    def run_ll_a2a():
        # Decode-shape message (the op's contract: whole chunks stage
        # in VMEM; big payloads belong on all_to_all).
        x = jax.random.normal(k0, (1, 128, 4096), dt)
        f = sm(lambda v: ops.ll_a2a(v, ctx=mctx, axis="tp",
                                    force_kernel=True),
               (P(None, None, None),), P(None, None, None))
        out = np.asarray(f(x), np.float32)
        np.testing.assert_allclose(out, np.asarray(x, np.float32),
                                   rtol=0.05, atol=0.05)

    def run_ll_a2a_steps():
        """Decode-loop amortization: S=8 a2a steps fused into ONE
        kernel invocation (one entry barrier + launch, slot-parity
        wire buffers, credit flow control) vs 8 chained single-step
        calls in one jit. The per-step delta is the per-invocation
        overhead the persistent form eliminates."""
        from triton_dist_tpu.ops import ll_a2a, ll_a2a_steps

        S, c, d = 8, 128, 4096
        xs = jax.random.normal(k0, (S, 1, c, d), dt)

        multi = sm(lambda v: ll_a2a_steps(v, ctx=mctx, axis="tp",
                                          force_kernel=True),
                   (P(None, None, None, None),),
                   P(None, None, None, None))

        def chained(v):
            outs = []
            for s in range(S):
                outs.append(ll_a2a(v[s], ctx=mctx, axis="tp", step=s,
                                   force_kernel=True))
            return jnp.stack(outs)

        single = sm(chained, (P(None, None, None, None),),
                    P(None, None, None, None))
        got = np.asarray(multi(xs), np.float32)
        want = np.asarray(single(xs), np.float32)
        np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)

        times = _timed_chain_group(
            {"fused_steps": (lambda a, b_: multi(a), xs, xs),
             "chained": (lambda a, b_: single(a), xs, xs)},
            repeats=3, hi=24, lo=4)
        return {"steps_fused_ms_per_step": round(
                    times["fused_steps"] * 1e3 / S, 4),
                "steps_chained_ms_per_step": round(
                    times["chained"] * 1e3 / S, 4),
                "per_step_overhead_saved_ms": round(
                    (times["chained"] - times["fused_steps"]) * 1e3 / S,
                    4)}

    def run_moe_rs():
        y = jax.random.normal(k0, (2048, 8, 2048), dt)
        w = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(3), (2048, 8)), -1)
        f = sm(lambda yy, ww: ops.moe_reduce_rs(yy, ww, ctx=mctx,
                                                axis="tp", block_m=256,
                                                force_kernel=True),
               (P(None, None, None), P(None, None)))
        out = np.asarray(f(y, w), np.float32)
        want = np.einsum("tkd,tk->td", np.asarray(y, np.float32),
                         np.asarray(w, np.float32))
        np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-1)

    def run_ep_fused():
        ctx = ops.create_ep_fused_context(
            mctx, num_experts=4, topk=2, capacity_per_expert=512,
            axis="tp", block_f=512, block_d=512)
        tok = jax.random.normal(k0, (256, 1024), dt)
        ids = jax.random.randint(jax.random.PRNGKey(4), (256, 2), 0, 4)
        w = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(5), (256, 2)), -1
        ).astype(dt)
        kg, ku, kd = jax.random.split(jax.random.PRNGKey(6), 3)
        wg = jax.random.normal(kg, (4, 1024, 1024), dt) * 0.03
        wu = jax.random.normal(ku, (4, 1024, 1024), dt) * 0.03
        wd = jax.random.normal(kd, (4, 1024, 1024), dt) * 0.03
        f = sm(lambda *args: ops.ep_moe_fused(*args, ctx)[0],
               (P(None, None),) * 3 + (P(None, None, None),) * 3)
        out = f(tok, ids, w, wg, wu, wd)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def run_grouped(op):
        """Shared harness for the grouped-GEMM family: sorted-layout
        prep, the op under test, and the tile-einsum oracle."""
        def go():
            e, d, ff, t, kk, tm = 8, 2048, 2048, 1024, 2, 256
            x = jax.random.normal(k0, (t, d), dt)
            ids = jax.random.randint(jax.random.PRNGKey(13), (t, kk),
                                     0, e)
            w = jax.random.normal(jax.random.PRNGKey(14), (e, d, ff),
                                  dt) * 0.02
            x_s, te, _ = jax.jit(
                lambda a, b: ops.prepare_grouped_tokens(a, b, e, tm)
            )(x, ids)
            if op == "ag":
                ctx = ops.create_ag_moe_context(
                    mctx, num_experts=e, block_m=tm, block_n=512,
                    block_k=1024)
                f = sm(lambda a, ww, t_: ops.ag_group_gemm(
                    a, ww, t_, ctx, force_kernel=True),
                       (P(None, None), P(None, None, None), P(None)))
            else:
                f = jax.jit(lambda a, ww, t_: ops.grouped_gemm_tiles(
                    a, ww, t_, block_n=512, block_k=1024))
            out = np.asarray(f(x_s, w, te), np.float32)
            tiles = np.asarray(x_s, np.float32).reshape(-1, tm, d)
            want = np.einsum("ima,iaf->imf", tiles,
                             np.asarray(w, np.float32)[np.asarray(te)])
            np.testing.assert_allclose(out, want.reshape(out.shape),
                                       rtol=3e-2, atol=3.0)
        return go

    def run_moe_ar():
        y = jax.random.normal(k0, (128, 8, 2048), dt)
        w = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(17), (128, 8)), -1)
        f = sm(lambda yy, ww: ops.moe_reduce_ar(yy, ww, ctx=mctx,
                                                axis="tp", block_n=512,
                                                force_kernel=True),
               (P(None, None, None), P(None, None)))
        out = np.asarray(f(y, w), np.float32)
        want = np.einsum("tkd,tk->td", np.asarray(y, np.float32),
                         np.asarray(w, np.float32))
        np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-1)

    def run_a2a_gemm_fused():
        x = jax.random.normal(k0, (1, 1024, 4096), dt)
        f = sm(lambda v, w: ops.a2a_gemm_fused(
            v, w, ops.create_a2a_gemm_context(mctx, "tp", block_m=512,
                                              block_n=512, block_k=1024),
            force_kernel=True),
               (P(None, None, None), P(None, None)))
        out = np.asarray(f(x, b4k), np.float32)
        want = (np.asarray(x, np.float32).reshape(1024, 4096)
                @ np.asarray(b4k, np.float32))
        np.testing.assert_allclose(out, want, rtol=3e-2, atol=3.0)

    def run_sp_ag_attention_fused():
        from triton_dist_tpu.ops import sp_ag_attention_fused
        s, h, kvh, hd = 2048, 16, 8, 128
        q = jax.random.normal(k0, (s, h, hd), dt) * 0.3
        kk = jax.random.normal(jax.random.PRNGKey(11), (s, kvh, hd),
                               dt) * 0.3
        vv = jax.random.normal(jax.random.PRNGKey(12), (s, kvh, hd),
                               dt) * 0.3
        f = sm(lambda a, b, c: sp_ag_attention_fused(
            a, b, c, ctx=mctx, axis="tp", force_kernel=True),
               (P(None, None, None),) * 3, P(None, None, None))
        out = np.asarray(f(q, kk, vv), np.float32)
        assert np.isfinite(out).all()

    def run_ulysses():
        ctx = ops.create_ulysses_fused_context(mctx, axis="tp",
                                               block_m=256, block_n=512)
        wq = ops.group_qkv_columns(
            jax.random.normal(k0, (2048, 32 * 128), dt) * 0.02,
            n=1, num_heads=16, num_kv_heads=8, head_dim=128)
        f = sm(lambda x, w: ops.qkv_gemm_a2a(x, w, ctx),
               (P(None, None), P(None, None, None)),
               P(None, None, None))
        out = f(m1k[:1024, :2048].reshape(1024, 2048), wq)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def run_paged_decode():
        kp = jax.random.normal(k0, (64, 8, 128, 128), dt) * 0.3
        vp = jax.random.normal(jax.random.PRNGKey(7),
                               (64, 8, 128, 128), dt) * 0.3
        tbl = jnp.arange(64, dtype=jnp.int32).reshape(8, 8)
        kv_len = jnp.full((8,), 777, jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(8), (8, 32, 128), dt)
        out = jax.jit(lambda q_: ops.paged_flash_decode(
            q_, kp, vp, tbl, kv_len))(q)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def run_fused_decode():
        """Fused split-KV decode (in-kernel RDMA partial exchange,
        sim_ranks=8 self-exchange at full schedule/traffic) vs the
        pmax+2psum XLA composition — the sim-ranks number
        for the one-kernel-per-step path (reference flash_decode.py
        1→32-GPU scaling)."""
        from triton_dist_tpu.ops import sp_flash_decode_fused
        from triton_dist_tpu.ops.flash_decode import sp_flash_decode

        b, h, kvh, hd, t = 8, 32, 8, 128, 2048
        q = jax.random.normal(k0, (b, h, hd), dt) * 0.3
        k_hm = jax.random.normal(jax.random.PRNGKey(21),
                                 (b, kvh, t, hd), dt) * 0.3
        v_hm = jax.random.normal(jax.random.PRNGKey(22),
                                 (b, kvh, t, hd), dt) * 0.3
        kv_len = jnp.full((b,), t, jnp.int32)

        fused = sm(lambda qq, l: sp_flash_decode_fused(
            qq, k_hm, v_hm, l, ctx=mctx, axis="tp", page=256,
            sim_ranks=8),
            (P(None, None, None), P(None)), P(None, None, None))
        k_tm = jnp.transpose(k_hm, (0, 2, 1, 3))
        v_tm = jnp.transpose(v_hm, (0, 2, 1, 3))
        xla = sm(lambda qq, l: sp_flash_decode(qq, k_tm, v_tm, l,
                                               axis="tp"),
                 (P(None, None, None), P(None)), P(None, None, None))
        got = np.asarray(fused(q, kv_len), np.float32)
        want = np.asarray(xla(q, kv_len), np.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)

        times = _timed_chain_group(
            {"fused": (lambda a, b_: fused(a, kv_len), q, q),
             "xla": (lambda a, b_: xla(a, kv_len), q, q)},
            repeats=3, hi=72)
        cache_gb = 2 * b * kvh * t * hd * 2 / 1e9
        return {"fused_decode_ms": round(times["fused"] * 1e3, 4),
                "xla_decode_ms": round(times["xla"] * 1e3, 4),
                "fused_vs_xla": round(times["xla"]
                                      / max(times["fused"], 1e-9), 4),
                "fused_decode_gbps": round(
                    cache_gb / max(times["fused"], 1e-9), 1)}

    def run_decode_perf():
        """Decode throughput, layer engine vs megakernel, measured as
        the slope between two on-device greedy-decode loop lengths (the
        dispatch overhead cancels) — the reference's ``bench_qwen3.py``
        comparison."""
        from triton_dist_tpu.models import ModelConfig, dense
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine

        cfg = ModelConfig.tiny(
            vocab_size=8192, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, head_dim=128)
        B, PRE, LEN = 8, 128, 512
        specs = dense.param_specs(cfg, "tp")
        params = jax.tree.map(
            lambda x, s: jax.device_put(
                x, jax.sharding.NamedSharding(mesh, s)),
            dense.init_params(jax.random.PRNGKey(0), cfg), specs)
        ids = jax.random.randint(jax.random.PRNGKey(1), (B, PRE), 0,
                                 cfg.vocab_size)
        kv_spec = dense.cache_specs("tp")

        prefill = jax.jit(jax.shard_map(
            lambda p, i: dense.prefill(p, i, cfg, max_len=LEN),
            mesh=mesh, in_specs=(specs, P(None, None)),
            out_specs=(P(None, None), kv_spec), check_vma=False))
        logits0, cache0 = prefill(params, ids)
        tok0 = jnp.argmax(logits0, -1).astype(jnp.int32)

        def make_layer_loop(iters):
            def inner(p, tok, cache):
                def body(_, carry):
                    tok, cache = carry
                    lg, cache = dense.decode_step(p, tok, cache, cfg)
                    return (jnp.argmax(lg, -1).astype(jnp.int32), cache)
                tok, cache = jax.lax.fori_loop(0, iters, body,
                                               (tok, cache))
                return tok
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=(specs, P(None), kv_spec),
                out_specs=P(None), check_vma=False))

        def slope(make, lo=8, hi=32, reps=3):
            best = {}
            for it in (lo, hi):
                f = make(it)
                f()  # compile + warm
                b = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    f()
                    b = min(b, time.perf_counter() - t0)
                best[it] = b
            return (best[hi] - best[lo]) / (hi - lo)

        t_layer = slope(lambda it: (
            lambda f=make_layer_loop(it): np.asarray(
                f(params, tok0, cache0))))

        # Megakernel: same loop over the persistent-kernel step.
        mk = MegaKernelEngine(cfg, mesh, batch=B, max_len=LEN,
                              prefill_seq=PRE)
        mk.prefill(ids)
        step = mk.builder.step_fn()
        kvspec_mk = P(None, None, None, "tp", None)

        def make_mk_loop(iters):
            def inner(arena, k, v, tok, tbl):
                def body(i, carry):
                    tok, arena, k, v = carry
                    lg, arena, k, v = step(arena, k, v, tok, PRE + i,
                                           tbl)
                    return (jnp.argmax(lg, -1).astype(jnp.int32),
                            arena, k, v)
                out = jax.lax.fori_loop(
                    0, iters, body, (tok, arena, k, v))
                return out[0]
            return jax.jit(jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P("tp", None), kvspec_mk, kvspec_mk, P(None),
                          P(None)),
                out_specs=P(None), check_vma=False))

        t_mk = slope(lambda it: (
            lambda f=make_mk_loop(it): np.asarray(
                f(mk._arena, mk.k_cache, mk.v_cache, tok0,
                  mk.block_table))))
        return {"layer_tok_s": round(B / max(t_layer, 1e-9), 1),
                "megakernel_tok_s": round(B / max(t_mk, 1e-9), 1),
                "batch": B, "prefix": PRE,
                # On TPU, jit already compiles the whole layer decode
                # into ONE executable, so the megakernel's
                # launch-elimination win (the reference's GPU story)
                # does not transfer; its persistent task loop pays
                # interpreter overhead instead. Kept as an honest
                # capability measurement.
                "note": "layer decode is one XLA executable under jit"}

    def run_hybrid_gdn():
        from triton_dist_tpu.models import Engine, ModelConfig, qwen_next

        cfg = ModelConfig.tiny_next(
            hidden_size=256, intermediate_size=512,
            num_attention_heads=8, num_key_value_heads=4, head_dim=32,
            gdn_num_heads=8, gdn_head_dim_k=32, gdn_head_dim_v=32)
        eng = Engine(cfg, mesh, mode="xla", max_len=128, seed=7,
                     model=qwen_next)
        ids = jax.random.randint(jax.random.PRNGKey(18), (2, 64), 0,
                                 cfg.vocab_size)
        toks = np.asarray(eng.serve(ids, gen_len=8))
        assert toks.shape == (2, 8) and np.isfinite(toks).all()

    def run_hybrid_hf_cell():
        """HF-checkpoint-faithful Qwen3-Next cell (conv GDN + gated
        attention + shared-expert MoE) through the Engine — the shape
        real checkpoints serve with."""
        from triton_dist_tpu.models import Engine, ModelConfig, qwen_next

        n = len(mesh.devices.reshape(-1))
        cfg = ModelConfig.tiny_next(
            vocab_size=256, hidden_size=256, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=max(8, n),
            num_key_value_heads=max(8, n), head_dim=32,
            gdn_num_heads=2 * max(8, n), gdn_head_dim_k=32,
            gdn_head_dim_v=32, full_attn_interval=2,
            gdn_num_key_heads=max(8, n), gdn_conv_kernel=4,
            attn_gate=True, partial_rotary_factor=0.25,
            num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=128,
            shared_expert_intermediate_size=128)
        eng = Engine(cfg, mesh, mode="xla", max_len=128, seed=9,
                     model=qwen_next)
        ids = jax.random.randint(jax.random.PRNGKey(19), (2, 64), 0,
                                 cfg.vocab_size)
        toks = np.asarray(eng.serve(ids, gen_len=8))
        assert toks.shape == (2, 8) and np.isfinite(toks).all()

    def run_megakernel(paged):
        def go():
            from triton_dist_tpu.megakernel.engine import MegaKernelEngine
            from triton_dist_tpu.models.config import ModelConfig

            cfg = ModelConfig.tiny(vocab_size=4096, hidden_size=1024,
                                   intermediate_size=2048,
                                   num_hidden_layers=2,
                                   num_attention_heads=8,
                                   num_key_value_heads=4, head_dim=128)
            eng = MegaKernelEngine(cfg, mesh, batch=4, max_len=256,
                                   prefill_seq=16, paged=paged)
            prompts = jnp.ones((4, 16), jnp.int32)
            logits = eng.prefill(prompts)
            assert np.isfinite(np.asarray(logits, np.float32)).all()
            l2 = eng.decode_step(
                jnp.argmax(logits, -1).astype(jnp.int32), 16)
            assert np.isfinite(np.asarray(l2, np.float32)).all()
        return go

    def _run_megakernel_family(make_cfg):
        """Shared silicon gate for the non-dense megakernel families:
        engine + prefill_chain + greedy steps, with the FINAL LOGITS
        checked for finiteness (greedy int tokens are always finite —
        they cannot catch a NaN lowering)."""
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine
        from triton_dist_tpu.models.config import ModelConfig

        eng = MegaKernelEngine(make_cfg(ModelConfig), mesh, batch=4,
                               max_len=128)
        seed = eng.prefill_chain(jnp.ones((4, 8), jnp.int32))
        tok = seed
        for i in range(4):
            logits = eng.decode_step(tok, 7 + i)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        lg = np.asarray(logits, np.float32)
        assert lg.shape[0] == 4 and np.isfinite(lg).all()

    def run_megakernel_moe():
        """MOE_WEIGHTS/WEIGHTED_ADD task bodies on real Mosaic (they
        have interpret-mode coverage; this is their silicon gate)."""
        _run_megakernel_family(lambda MC: MC.tiny_moe(
            vocab_size=4096, hidden_size=1024, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=4, head_dim=128,
            num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=512))

    def run_megakernel_hybrid():
        """GDN_DECODE task body on real Mosaic (recurrent state buffer
        threading + per-head delta-rule update)."""
        _run_megakernel_family(lambda MC: MC.tiny_next(
            vocab_size=4096, hidden_size=1024, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=4, head_dim=128,
            gdn_num_heads=8, gdn_head_dim_k=128, gdn_head_dim_v=128,
            full_attn_interval=2))

    def run_real_checkpoint_decode():
        """decode_tok_s for a REAL public checkpoint through BOTH
        engines (ROADMAP item 3's missing number). Harness flag:
        ``BENCH_HF_DIR=<local checkpoint dir>`` — when absent (this
        CPU-only container) the entry records the skip reason instead
        of a number, so the next on-chip run captures it by exporting
        one variable. Decode rate is the slope between two generation
        lengths (prefill + dispatch overhead cancel)."""
        hf_dir = os.environ.get("BENCH_HF_DIR")
        if not hf_dir:
            return {"skipped": "set BENCH_HF_DIR=<hf checkpoint dir> "
                               "to record real-checkpoint decode_tok_s"}
        from triton_dist_tpu.models import Engine, qwen_moe
        from triton_dist_tpu.models.hf_loader import load_hf_checkpoint
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine

        cfg, params = load_hf_checkpoint(hf_dir, dtype=jnp.bfloat16)
        b, pre, lo, hi = 4, 32, 8, 64
        ids = jax.random.randint(jax.random.PRNGKey(0), (b, pre), 0,
                                 cfg.vocab_size)
        model_kw = {"model": qwen_moe} if cfg.is_moe else {}
        eng = Engine(cfg, mesh, mode="xla", max_len=pre + hi + 8,
                     params=params, **model_kw)

        def timed_serve(gen):
            np.asarray(eng.serve(ids, gen_len=gen))   # compile + warm
            t0 = time.perf_counter()
            np.asarray(eng.serve(ids, gen_len=gen))
            return time.perf_counter() - t0

        t_layer = (timed_serve(hi) - timed_serve(lo)) / (hi - lo)
        out = {"checkpoint": os.path.basename(os.path.normpath(hf_dir)),
               "decode_tok_s": {"layer": round(b / max(t_layer, 1e-9),
                                               1)}}
        try:
            mk = MegaKernelEngine(cfg, mesh, batch=b,
                                  max_len=pre + hi + 8, params=params,
                                  prefill_seq=pre)
            tok = jnp.argmax(mk.prefill(ids), -1).astype(jnp.int32)
            np.asarray(mk.decode_step(tok, pre))      # compile + warm

            def timed_mk(gen):
                t = tok
                t0 = time.perf_counter()
                for i in range(gen):
                    lg = mk.decode_step(t, pre + 1 + i)
                    t = jnp.argmax(lg, -1).astype(jnp.int32)
                np.asarray(t)
                return time.perf_counter() - t0

            t_mk = (timed_mk(hi) - timed_mk(lo)) / (hi - lo)
            out["decode_tok_s"]["megakernel"] = round(
                b / max(t_mk, 1e-9), 1)
        except Exception as e:  # record the layer number regardless
            out["decode_tok_s"]["megakernel"] = None
            out["megakernel_error"] = (f"{type(e).__name__}: "
                                       f"{str(e)[:160]}")
        return out

    entries = [
        ("gemm_ar", run_gemm_ar),
        ("allreduce_one_shot", run_allreduce("one_shot")),
        ("allreduce_two_shot", run_allreduce("two_shot")),
        ("allreduce_rhd", run_allreduce("recursive")),
        ("allgather_ring", run_allgather("ring")),
        ("allgather_full_mesh", run_allgather("full_mesh")),
        ("all_to_all", run_a2a),
        ("fast_allgather_push", run_fast_allgather),
        ("ll_a2a_int8", run_ll_a2a),
        ("moe_reduce_rs", run_moe_rs),
        ("moe_reduce_ar", run_moe_ar),
        ("ag_group_gemm", run_grouped("ag")),
        ("grouped_gemm_tiles", run_grouped("local")),
        ("a2a_gemm_fused", run_a2a_gemm_fused),
        ("sp_ag_attention_fused", run_sp_ag_attention_fused),
        ("ep_moe_fused", run_ep_fused),
        ("ulysses_qkv_gemm_a2a", run_ulysses),
        ("paged_flash_decode", run_paged_decode),
        ("fused_sp_decode", run_fused_decode),
        ("ll_a2a_steps", run_ll_a2a_steps),
        ("hybrid_gdn_engine", run_hybrid_gdn),
        ("hybrid_hf_cell_engine", run_hybrid_hf_cell),
        ("engine_decode_throughput", run_decode_perf),
        ("megakernel_prefill_decode", run_megakernel(False)),
        ("megakernel_paged", run_megakernel(True)),
        ("megakernel_moe", run_megakernel_moe),
        ("megakernel_hybrid_gdn", run_megakernel_hybrid),
        ("real_checkpoint_decode", run_real_checkpoint_decode),
    ]
    results = []
    for name, fn in entries:
        if deadline is not None and time.perf_counter() > deadline:
            rec = {"op": name, "ok": False, "skipped": True,
                   "error": "battery time budget exhausted"}
            results.append(rec)
            if not quiet:
                print(json.dumps(rec), flush=True)
            continue
        t0 = time.perf_counter()
        extra = None
        try:
            extra = fn()   # optional dict of measured numbers
            ok, err = True, None
        except Exception as e:  # record, keep going
            ok, err = False, f"{type(e).__name__}: {str(e)[:160]}"
        dt_s = time.perf_counter() - t0
        rec = {"op": name, "ok": ok, "wall_s": round(dt_s, 2)}
        if isinstance(extra, dict):
            rec.update(extra)
        if err:
            rec["error"] = err
        results.append(rec)
        if not quiet:
            print(json.dumps(rec), flush=True)
    n_ok = sum(r["ok"] for r in results)
    if not quiet:
        print(json.dumps({"metric": "hardware_battery_pass_rate",
                          "value": round(n_ok / len(results), 4),
                          "unit": "fraction", "vs_baseline": None,
                          "passed": n_ok, "total": len(results)}))
    return results


if __name__ == "__main__":
    if "--all" in sys.argv:
        battery()
    else:
        main()
