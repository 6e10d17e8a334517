# Top-level targets (reference: .github/workflows/amd-ci.yml battery).

PY ?= python

.PHONY: csrc test quick race verify-faults bench-smoke bench-megakernel \
	serve-smoke ep-smoke ep2d-smoke aggemm-smoke disagg-smoke \
	spec-smoke chaos-smoke \
	qblock-smoke obs-smoke tier-smoke fleet-smoke slo-smoke \
	mega-parity-smoke mkchunk-smoke supervise-smoke apicheck ci \
	bench-all

csrc:
	$(MAKE) -C csrc

# Tier-1 as the driver runs it (`commands` in /root/TESTS_LAST_RUN.json):
# six workers, the `slow` tier left out. Its wall time is what a PR is
# judged on (docs/testing.md). PYTEST_ARGS lets CI deselect files covered
# by dedicated jobs (e.g. --ignore=tests/test_multihost.py).
test: csrc
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
	    -p xdist -n 6 --dist load $(PYTEST_ARGS)

# Sub-2-minute smoke tier for iteration (primitives, collectives,
# low-latency family, tools; tier-1, `make test`, stays the merge gate).
quick: csrc
	$(PY) -m pytest tests/test_shmem.py tests/test_tools.py \
	    tests/test_low_latency.py tests/test_collectives.py -x -q

# The whole battery under the vector-clock race detector — the
# deliberate signal-protocol checker (SURVEY.md section 5).
race: csrc
	TRITON_DIST_TPU_DETECT_RACES=1 $(PY) -m pytest \
	    tests/test_shmem.py tests/test_collectives.py -x -q

# Fault battery: tier-1 plus tests/test_resilience.py under the race
# detector on the CPU mesh (docs/resilience.md).
verify-faults: csrc
	bash scripts/verify_faults.sh

# Overlap-schedule smoke: swizzle/prefetch parity sweep + interpret-mode
# bench on the CPU mesh — verify-faults' perf sibling (docs/perf.md).
bench-smoke: csrc
	bash scripts/bench_smoke.sh

# Megakernel scheduler battery: dynamic-vs-static token-exactness on the
# CPU mesh + interpret-mode bench with non-null megakernel values
# (docs/megakernel.md, dynamic scoreboard scheduler).
bench-megakernel: csrc
	bash scripts/bench_megakernel.sh

# Serving battery: continuous batching + streaming chat server on the
# CPU mesh, gated on per-request token-exactness vs Engine.serve and
# the fixed-decode-shape jit-cache check (docs/serving.md).
serve-smoke: csrc
	bash scripts/serve_smoke.sh

# EP serving battery: skewed-routing token-exactness across decode
# transports on the CPU mesh + a non-null bench.py ep_dispatch_ms gate
# (docs/serving.md EP-decode section).
ep-smoke: csrc
	bash scripts/ep_smoke.sh

# Hierarchical EP decode battery: 2-hop ll2d token-exactness + the
# asserted DCN put-coalescing gate on the CPU mesh, a forced-2D-mesh
# chat e2e gating the transport=ll2d exit line, and the non-null
# bench.py ep_dispatch_2d_ms / ep2d_dcn_puts gate (docs/serving.md
# EP-decode hierarchy section).
ep2d-smoke: csrc
	bash scripts/ep2d_smoke.sh

# ag_gemm variant battery: panel/pipelined parity (both real kernels,
# no interpret fallback) across swizzle x depth x sim-ring, wide-K
# host-side schedule math, the variant-autotune round-trip, and the
# non-null bench.py panel/pipelined crossover gate (pipelined must
# stay within 1.1x of panel at block_m <= 512; docs/perf.md).
aggemm-smoke: csrc
	bash scripts/aggemm_smoke.sh

# Disaggregated-serving battery: chunked-prefill bucket gates + page
# migration on the CPU mesh, a split-role chat e2e, and the non-null
# chunked-vs-monolithic bench gate (docs/serving.md disaggregation
# section).
disagg-smoke: csrc
	bash scripts/disagg_smoke.sh

# Quantized-KV + speculative-decode battery: bounded-divergence and
# capacity gates, spec determinism/rollback, a quantized+speculative
# chat e2e, and the non-null spec/quant bench-key gate
# (docs/serving.md quantization + speculation sections).
spec-smoke: csrc
	bash scripts/spec_smoke.sh

# Fault-tolerance battery: retry/backoff + failover + checkpoint/
# restore units, the seeded 200-tick chaos acceptance soak (invariant
# checker every tick, survivors token-exact vs the fault-free oracle),
# a chat-server kill/resume e2e, and the non-null
# chaos_survived_faults bench gate (docs/resilience.md).
chaos-smoke: csrc
	bash scripts/chaos_smoke.sh

# Paged flash Q-block battery: kernel-vs-gather-oracle parity across
# pool dtypes, flash-path chunk/verify token-exactness + no-recompile
# gates, a flash chat e2e, and the non-null flash<=ref bench gate on
# chunk_attend_ms/verify_attend_ms (docs/serving.md, "Attention
# implementations").
qblock-smoke: csrc
	bash scripts/qblock_smoke.sh

# Observability battery: span-timeline determinism under a fake clock,
# histogram/percentile units, telemetry bit-exactness + no-growth
# gates, and a traced chat e2e gating the merged Perfetto file and the
# one-line `obs:` latency summary (docs/observability.md).
obs-smoke: csrc
	bash scripts/obs_smoke.sh

# Tiered-KV battery: tier-store/scored-eviction units, park/resume
# token-exactness, tier coherence under chaos, the heavy-tailed
# multi-turn trace, a parked-and-resumed chat e2e gating the `tiers:`
# exit-summary line, and the non-null kv_hot_hit_rate /
# session_resume_ms / offloaded_pages bench gate (docs/serving.md,
# "KV memory hierarchy").
tier-smoke: csrc
	bash scripts/tier_smoke.sh

# Fleet-serving battery: affinity routing vs round-robin, cross-fleet
# failover token-exactness (parked-tier handoff + re-prefill),
# drain/restore autoscale, shed-by-deadline-class, the fleet chaos
# soak, an R=2 chat e2e with a mid-serve fleet kill gating
# bit-identical token streams, and the non-null fleet_p99_ttft_ms /
# fleet_failover_resumed / fleet_shed_requests /
# router_affinity_hit_rate bench gate (docs/serving.md, "Fleet
# serving").
fleet-smoke: csrc
	bash scripts/fleet_smoke.sh

# Multi-tenant SLO battery: EDF/DRR/aging units on a fake clock,
# per-tenant backpressure + decode quotas, preemption token-exactness
# through both eviction paths, the noisy-neighbor isolation gate, the
# router's class/over-quota shed order, the multi-tenant chaos soak,
# a bit-identical-streams chat e2e with --slo --tenants 2, and the
# non-null slo_attainment / tenant_interactive_p99_ttft_ms /
# slo_preemptions bench gate (>= 2x interactive isolation at >= 0.8x
# bulk throughput; docs/serving.md, "Multi-tenant SLO scheduling").
slo-smoke: csrc
	bash scripts/slo_smoke.sh

# Megakernel serving-parity battery: quantized-KV token agreement +
# capacity gates, Q-block speculation token-exact vs the non-spec mk
# run, schema checkpoint/restore resuming mid-stream, a
# bit-identical-streams chat e2e with --megakernel --kv-quant int8
# --spec, and the non-null megakernel_decode_quant_ms /
# megakernel_tokens_per_s_spec bench gate (docs/megakernel.md,
# "Arena schema").
mega-parity-smoke: csrc
	bash scripts/mega_parity_smoke.sh

# Megakernel chunked-prefill battery: bucket-edge token-exactness vs
# the one-token lane and the layer ChunkedPrefill, quantized chunk
# writes, prefix-hit skip of resident pages, the chunk-step no-growth
# gates, a bit-identical-streams chat e2e with --megakernel
# --mk-chunked, and the non-null megakernel_prefill_chunk_ms /
# megakernel_tokens_per_s_prefill_heavy (>= 2x one-token lane) bench
# gate (docs/megakernel.md, "Chunked prefill").
mkchunk-smoke: csrc
	bash scripts/mkchunk_smoke.sh

# Supervised-serving battery: checkpoint-envelope + keep-last-K ring
# corruption fallback, parent-side ack dedupe/divergence/gap units,
# real-child crash + stall recovery token-exact, the three-boundary
# payload-integrity drill (tier put / migration send / fleet
# handoff), the >= 6-fault supervised soak, a SIGKILL-mid-stream
# crash/resume e2e, and the non-null crash_recovery_ms /
# supervised_survived_faults / integrity_checks bench gate
# (docs/resilience.md, "Process supervision").
supervise-smoke: csrc
	bash scripts/supervise_smoke.sh

# docs/api.md is generated; fail CI when it drifts from the source.
apicheck:
	$(PY) -m triton_dist_tpu.tools.gen_api --check

ci: test race apicheck

# Hardware battery: every fused op once on the real chip (needs a TPU).
bench-all:
	$(PY) bench.py --all
