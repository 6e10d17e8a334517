#!/usr/bin/env bash
# Fleet-scale serving smoke battery on the CPU mesh:
#
#  1. tests/test_fleet.py — prefix-affinity routing beats the
#     round-robin baseline on the seeded multi-turn trace, fleet-kill
#     failover token-exact through BOTH cross-fleet paths
#     (parked-tier handoff and deterministic re-prefill), drain/
#     restore autoscale round-trip with in-flight sessions,
#     deterministic saturation spillover, shed-by-deadline-class
#     ordering, the fleet chaos soak mini-run, and the fleet
#     invariant checker's corruption units;
#  2. a chat e2e through examples/chat_server.py --fleet 2
#     --kill-fleet-after 4: one fleet dies MID-SERVE and the token
#     streams must be BIT-IDENTICAL to the --fleet 1 run, with the
#     one-line `fleet:` exit summary reporting the failover;
#  3. a bench.py gate: fleet_p99_ttft_ms, fleet_failover_resumed,
#     fleet_shed_requests, and router_affinity_hit_rate non-null on
#     this CPU-only host.
#
# Sibling of scripts/tier_smoke.sh, wired as `make fleet-smoke`.
# A failover byte drift, a lost request after a fleet kill, or a
# router that re-specializes a fleet's decode dispatch fails here in
# minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== fleet serving battery (CPU mesh) =="
$PY -m pytest tests/test_fleet.py -q -m 'not slow'

echo "== chat e2e: --fleet 2 --kill-fleet-after 4 vs --fleet 1 =="
prompts='1 2 3 4 5\n7 8 9\n5 5 5 5\n1 2 3 4 5\n'
single=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --fleet 1 --kv-tiers | grep '^->')
fleet_out=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --fleet 2 --kv-tiers --kill-fleet-after 4)
echo "$fleet_out"
fleet=$(echo "$fleet_out" | grep '^->')
[ "$single" = "$fleet" ] || {
  echo "a mid-serve fleet kill changed the token streams:";
  echo "R=1:        $single"; echo "R=2+kill:   $fleet"; exit 1; }
summary=$(echo "$fleet_out" | grep 'fleet: routed=') || {
  echo "missing 'fleet:' exit-summary line"; exit 1; }
echo "$summary" | grep -q 'failovers=1' || {
  echo "expected failovers=1 in: $summary"; exit 1; }
echo "$summary" | grep -q 'resumed=1' || {
  echo "expected resumed=1 (parked-tier handoff) in: $summary"; exit 1; }

echo "== bench gate: fleet keys non-null =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/fleet_bench.json 2>/tmp/fleet_bench.err \
  || { cat /tmp/fleet_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/fleet_bench.json"))["detail"]
p99 = d.get("fleet_p99_ttft_ms")
res = d.get("fleet_failover_resumed")
shd = d.get("fleet_shed_requests")
aff = d.get("router_affinity_hit_rate")
err = d.get("fleet_error")
assert p99 is not None and p99 > 0, (
    f"fleet_p99_ttft_ms null/zero (fleet_error={err!r})")
assert res is not None and res >= 1, f"fleet_failover_resumed: {res!r}"
assert shd is not None and shd >= 1, f"fleet_shed_requests: {shd!r}"
assert aff is not None and aff > 0, f"router_affinity_hit_rate: {aff!r}"
fd = d.get("fleet_detail") or {}
print(f"fleet-smoke: ok (p99 ttft {p99} ms, affinity hit rate {aff}, "
      f"{res} failover-resumed, {shd} shed over "
      f"{fd.get('trace_events')} trace events, "
      f"{fd.get('fleet_failovers')} fleet failover(s))")
EOF
