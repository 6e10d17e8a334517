#!/usr/bin/env bash
# Disaggregated-serving smoke battery on the CPU mesh (no TPU):
#
#  1. tests/test_disagg_serving.py — fixed-shape chunked prefill
#     (bucket-edge token-exactness, jit-cache-bounded-by-buckets gate,
#     prefix-reuse chunk skipping, deterministic preempt-resume),
#     page-migration bit-exactness over the p2p bridge, and the
#     dropped/wedged-migration one-request containment;
#  2. a mixed prefill-heavy/decode-heavy e2e through
#     examples/chat_server.py --disagg (split-role meshes, streamed
#     replies, migration summary line);
#  3. a bench.py gate: prefill_chunked_vs_monolithic_ms and
#     serving_tokens_per_s_prefill_heavy non-null on this CPU-only
#     host, with chunked >= monolithic throughput on the mixed trace.
#
# Sibling of scripts/serve_smoke.sh, wired as `make disagg-smoke`.
# A prefill shape leak (recompile per prompt length), a migration that
# corrupts pages, or a handoff that can kill the server fails here in
# minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== chunked-prefill + disaggregated serving battery (CPU mesh) =="
$PY -m pytest tests/test_disagg_serving.py -q

echo "== mixed prefill-heavy/decode-heavy e2e (--disagg, split roles) =="
# Long prompts (prefill-heavy) interleaved with short ones
# (decode-heavy) through the two-role server.
out=$(printf '1 2 3\n9 8 7 6 5 4 3 2 1 9 8 7 6 5 4 3 2 1 9 8 7\n5 5\n1 2 3 4 5 6 7 8 9 10 11 12 13\n' \
      | timeout 300 $PY examples/chat_server.py --tp 2 --gen-len 6 --disagg)
echo "$out"
lines=$(echo "$out" | grep -c '^-> [0-9 ]*$' || true)
[ "$lines" -eq 4 ] || { echo "expected 4 streamed replies, got $lines"; exit 1; }
echo "$out" | grep -q 'roles=prefill|decode/disjoint' \
  || { echo "missing split-role summary"; exit 1; }
echo "$out" | grep -Eq 'migrated_pages=[1-9]' \
  || { echo "no pages migrated"; exit 1; }
echo "$out" | grep -Eq 'prefill_chunks=[1-9]' \
  || { echo "no chunked prefill ran"; exit 1; }

echo "== bench gate: chunked-vs-monolithic prefill non-null, chunked >= monolithic =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/disagg_bench.json 2>/tmp/disagg_bench.err \
  || { cat /tmp/disagg_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/disagg_bench.json"))["detail"]
ms = d.get("prefill_chunked_vs_monolithic_ms")
tps = d.get("serving_tokens_per_s_prefill_heavy")
assert ms and ms.get("chunked") and ms.get("monolithic"), (
    f"prefill_chunked_vs_monolithic_ms null: {ms!r} "
    f"(serving_error={d.get('serving_error')!r})")
assert tps and tps.get("chunked") and tps.get("monolithic"), (
    f"serving_tokens_per_s_prefill_heavy null: {tps!r}")
assert tps["chunked"] >= tps["monolithic"], (
    f"chunked prefill lost the mixed trace: {tps}")
print(f"disagg-smoke: ok (prefill ms {ms}, prefill-heavy tok/s {tps}, "
      f"prefill cache entries {d.get('serving_prefill_cache_entries')})")
EOF
