#!/usr/bin/env bash
# Multi-tenant SLO scheduling smoke battery on the CPU mesh:
#
#  1. tests/test_slo.py — EDF / DRR / aging units on a fake clock,
#     per-tenant backpressure + rate limits, decode-quota gating,
#     priority preemption token-exact through BOTH eviction paths
#     (deterministic re-prefill and kv_tiers park), the noisy-neighbor
#     isolation gate, class-aware timeout victims, the router's
#     (class, over-quota tenant) shed order, checkpoint/restore with
#     tenant queues, the multi-tenant chaos mini-soak, and the
#     tenant-fairness invariant checker's corruption units;
#  2. a chat e2e through examples/chat_server.py --slo --tenants 2:
#     token streams must be BIT-IDENTICAL to the slo-off run (the SLO
#     layer reorders, never rewrites), with the one-line `slo:` exit
#     summary reporting per-tenant releases;
#  3. a bench.py gate: slo_attainment, tenant_interactive_p99_ttft_ms,
#     and slo_preemptions non-null, interactive isolation >= 2x FIFO
#     with bulk throughput >= 0.8x (asserted inside the interpreter).
#
# Sibling of scripts/fleet_smoke.sh, wired as `make slo-smoke`.
# A preemption byte drift, a starved tenant, or a quota bucket that
# leaks tokens fails here in minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== multi-tenant SLO battery (CPU mesh) =="
$PY -m pytest tests/test_slo.py -q -m 'not slow'

echo "== chat e2e: --slo --tenants 2 vs slo-off =="
prompts='1 2 3 4 5\n7 8 9\n@vip 5 5 5 5\n1 2 3 4 5\n'
plain=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 | grep '^->')
slo_out=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --slo --tenants 2 --tenant-quota vip=50)
echo "$slo_out"
slo=$(echo "$slo_out" | grep '^->')
[ "$plain" = "$slo" ] || {
  echo "the SLO layer changed the token streams:";
  echo "slo-off: $plain"; echo "slo-on:  $slo"; exit 1; }
summary=$(echo "$slo_out" | grep 'slo: attainment=') || {
  echo "missing 'slo:' exit-summary line"; exit 1; }
echo "$summary" | grep -q 'vip(released=1' || {
  echo "expected vip(released=1 ...) in: $summary"; exit 1; }
echo "$summary" | grep -q 'tenants=3' || {
  echo "expected tenants=3 (t0, t1, vip) in: $summary"; exit 1; }

echo "== bench gate: slo keys non-null =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/slo_bench.json 2>/tmp/slo_bench.err \
  || { cat /tmp/slo_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/slo_bench.json"))["detail"]
att = d.get("slo_attainment")
p99 = d.get("tenant_interactive_p99_ttft_ms")
pre = d.get("slo_preemptions")
err = d.get("slo_error")
assert att is not None and att >= 0.99, (
    f"slo_attainment null/low: {att!r} (slo_error={err!r})")
assert p99 is not None and p99 > 0, (
    f"tenant_interactive_p99_ttft_ms null/zero (slo_error={err!r})")
assert pre is not None and pre >= 1, f"slo_preemptions: {pre!r}"
sd = d.get("slo_detail") or {}
iso = sd.get("interactive_isolation_x")
rat = sd.get("bulk_throughput_ratio")
assert iso is not None and iso >= 2.0, f"isolation {iso!r} < 2x"
assert rat is not None and rat >= 0.8, f"bulk ratio {rat!r} < 0.8"
print(f"slo-smoke: ok (attainment {att}, interactive p99 ttft {p99} "
      f"ms at {iso}x isolation, bulk ratio {rat}, "
      f"{pre} preemption(s))")
EOF
