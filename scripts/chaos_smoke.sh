#!/usr/bin/env bash
# Fault-tolerance / chaos smoke battery on the CPU mesh (no TPU):
#
#  1. tests/test_fault_tolerance.py + tests/test_chaos.py (fast
#     subset) — RetryPolicy units, migration/chunk retry-with-backoff,
#     prefill-worker failover (threshold + operator kill + N>1
#     standby), checkpoint/restore edges (prefix-shared refcounts,
#     int8/fp8 scales bit-exact, mid-spec, mid-run kill/restore), the
#     invariant-checker units, and seeded mini-soaks;
#  2. the long acceptance soak (tests/test_chaos.py -m slow): 200+
#     ticks, >= 10 injected faults over split roles with a mid-run
#     checkpoint/restore — every request terminal, zero leaked pages,
#     survivors token-exact vs the fault-free oracle;
#  3. a checkpoint/restore e2e through examples/chat_server.py
#     --checkpoint-dir: kill mid-stream (the deterministic
#     --checkpoint-after drill through the SIGTERM code path), restart,
#     and diff the restored request's FULL token list against a clean
#     uninterrupted run;
#  4. a bench.py gate: detail.chaos_survived_faults non-null (the
#     seeded soak inside the bench record completed with invariants
#     intact).
#
# Sibling of scripts/disagg_smoke.sh, wired as `make chaos-smoke`.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== fault-tolerance battery (CPU mesh) =="
$PY -m pytest tests/test_fault_tolerance.py tests/test_chaos.py \
    -q -m 'not slow'

echo "== acceptance soak: 200 ticks, 12 faults, mid-run restore =="
$PY -m pytest tests/test_chaos.py -q -m slow

echo "== checkpoint/restore e2e (chat server kill + resume) =="
CKDIR=$(mktemp -d)
trap 'rm -rf "$CKDIR"' EXIT
clean=$(printf '1 2 3 4 5\n' | timeout 300 $PY examples/chat_server.py \
        --tp 1 --gen-len 10 | grep '^->' | sed 's/^-> //')
printf '1 2 3 4 5\n' | timeout 300 $PY examples/chat_server.py --tp 1 \
    --gen-len 10 --checkpoint-dir "$CKDIR" --checkpoint-after 4 \
    | grep -q 'checkpointed 1 in-flight' \
    || { echo "checkpoint drill did not snapshot"; exit 1; }
[ -f "$CKDIR/serving.ckpt" ] || { echo "no snapshot written"; exit 1; }
out=$(printf '' | timeout 300 $PY examples/chat_server.py --tp 1 \
      --gen-len 10 --checkpoint-dir "$CKDIR")
echo "$out" | grep -q 'restored 1 in-flight' \
  || { echo "restart did not restore"; exit 1; }
echo "$out" | grep -q 'ft: .*restored=1' \
  || { echo "missing restored counter in exit summary"; exit 1; }
resumed=$(echo "$out" | grep '^\[restored ' | sed 's/^\[restored [^]]*\] //')
[ "$resumed" = "$clean" ] \
  || { echo "restored tokens diverged: '$resumed' != '$clean'"; exit 1; }
echo "restored run token-exact: $resumed"

echo "== bench gate: chaos_survived_faults non-null =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/chaos_bench.json 2>/tmp/chaos_bench.err \
  || { cat /tmp/chaos_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/chaos_bench.json"))["detail"]
sf = d.get("chaos_survived_faults")
assert sf is not None and sf >= 1, (
    f"chaos_survived_faults null: {sf!r} "
    f"(chaos_error={d.get('chaos_error')!r})")
print(f"chaos-smoke: ok (survived {sf} faults over "
      f"{d.get('chaos_ticks')} ticks, requests {d.get('chaos_requests')}, "
      f"retries={d.get('chaos_retries')} "
      f"failovers={d.get('chaos_failovers')} "
      f"restored={d.get('chaos_restored_requests')})")
EOF
