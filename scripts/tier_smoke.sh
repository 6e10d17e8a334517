#!/usr/bin/env bash
# Tiered KV memory hierarchy smoke battery on the CPU mesh:
#
#  1. tests/test_kv_tiers.py — tier-store round-trip/spill/two-phase
#     units, scored (frequency/recency) eviction with demote-not-drop,
#     park/resume token-exactness vs Engine.serve (bf16 bit-exact,
#     int8 bit-exact, park_quant approximate), prefix pages demoted
#     under a live sharer never corrupted, tier coherence under the
#     chaos soak (dropped/wedged tier transfers + seeded park drill),
#     checkpoint/restore with offloaded pages, and the seeded
#     100k-session heavy-tailed multi-turn trace running to drain on
#     an undersized HBM pool;
#  2. a parked-and-resumed chat e2e through examples/chat_server.py
#     --kv-tiers --park-after-idle: token streams must be
#     BIT-IDENTICAL to the plain run, and the one-line `tiers:` exit
#     summary must report the offload/resume counts;
#  3. a bench.py gate: kv_hot_hit_rate, session_resume_ms, and
#     offloaded_pages non-null on this CPU-only host.
#
# Sibling of scripts/spec_smoke.sh, wired as `make tier-smoke`.
# A park/resume byte drift, a demotion that corrupts a live sharer,
# or a tier-scatter that re-specializes the decode dispatch fails
# here in minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== tiered KV battery (CPU mesh) =="
$PY -m pytest tests/test_kv_tiers.py -q

echo "== chat e2e: --kv-tiers --park-after-idle (park/resume drill) =="
prompts='1 2 3 4 5\n7 8 9\n5 5 5 5\n'
plain=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 | grep '^->')
tiered_out=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --kv-tiers --park-after-idle 2)
echo "$tiered_out"
tiered=$(echo "$tiered_out" | grep '^->')
[ "$plain" = "$tiered" ] || {
  echo "park/resume changed the token streams:";
  echo "plain:  $plain"; echo "tiered: $tiered"; exit 1; }
summary=$(echo "$tiered_out" | grep 'tiers: offloaded=') || {
  echo "missing 'tiers:' exit-summary line"; exit 1; }
echo "$summary" | grep -q 'resumed=3' || {
  echo "expected 3 resumed sessions in: $summary"; exit 1; }

echo "== bench gate: tier keys non-null =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/tier_bench.json 2>/tmp/tier_bench.err \
  || { cat /tmp/tier_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/tier_bench.json"))["detail"]
hr = d.get("kv_hot_hit_rate")
rm = d.get("session_resume_ms")
op = d.get("offloaded_pages")
assert hr is not None, (
    f"kv_hot_hit_rate null (tiers_error={d.get('tiers_error')!r})")
assert rm is not None and rm > 0, f"session_resume_ms null/zero: {rm!r}"
assert op is not None and op > 0, f"offloaded_pages null/zero: {op!r}"
td = d.get("tier_detail") or {}
print(f"tier-smoke: ok (hot hit rate {hr}, resume {rm} ms, "
      f"{op} offloaded pages, {td.get('parks')} parks over "
      f"{td.get('trace_events')} heavy-tail events)")
EOF
