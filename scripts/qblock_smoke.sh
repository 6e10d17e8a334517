#!/usr/bin/env bash
# Paged flash Q-block attention smoke battery on the CPU mesh:
#
#  1. tests/test_paged_qblock.py — kernel == gather oracle across
#     bf16/int8/fp8 pools and the edge shapes (ragged final pages,
#     prefix-shared pages, parked slots), chunk-boundary b-1/b/b+1
#     token-exactness vs Engine.serve through the flash chunk path,
#     spec rollback after a flash-path verify, and the no-recompile
#     gates with attn_impl="flash" active;
#  2. an e2e through examples/chat_server.py --attn-impl flash --spec
#     (chunked prefill + K-token verification both riding the Q-block
#     kernel, gated on the attn= exit-summary line);
#  3. a bench.py gate: chunk_attend_ms and verify_attend_ms non-null
#     on this CPU-only host, with flash <= ref on both (the kernel
#     walks resident pages; the ref materializes full dense rows).
#
# Sibling of scripts/spec_smoke.sh, wired as `make qblock-smoke`.
# A kernel/oracle divergence, a chunk dispatch that re-specializes on
# positions, or a flash path slower than the gather it replaces fails
# here in minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== paged flash Q-block battery (CPU mesh) =="
$PY -m pytest tests/test_paged_qblock.py -q

echo "== chat e2e: --attn-impl flash --spec (flash chunk + verify) =="
out=$(printf '1 2 3 1 2 3 1 2\n7 8 7 8 7 8\n5 5\n' \
      | timeout 300 $PY examples/chat_server.py --tp 2 --gen-len 8 \
          --attn-impl flash --spec --spec-k 4)
echo "$out"
lines=$(echo "$out" | grep -c '^-> [0-9 ]*$' || true)
[ "$lines" -eq 3 ] || { echo "expected 3 streamed replies, got $lines"; exit 1; }
echo "$out" | grep -q 'attn=flash (chunk/verify flash)' \
  || { echo "exit summary missing attn=flash line"; exit 1; }

echo "== bench gate: qblock keys non-null, flash <= ref =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/qblock_bench.json 2>/tmp/qblock_bench.err \
  || { cat /tmp/qblock_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/qblock_bench.json"))["detail"]
for key in ("chunk_attend_ms", "verify_attend_ms"):
    v = d.get(key)
    assert v and v.get("flash") and v.get("ref"), (
        f"{key} null: {v!r} (qblock_error={d.get('qblock_error')!r})")
    assert v["flash"] <= v["ref"], (
        f"{key}: flash {v['flash']} ms > ref {v['ref']} ms — the "
        "kernel lost to the dense-row gather it exists to replace")
print(f"qblock-smoke: ok (chunk {d['chunk_attend_ms']}, "
      f"verify {d['verify_attend_ms']})")
EOF
