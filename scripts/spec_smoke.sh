#!/usr/bin/env bash
# Quantized-KV + speculative-decoding smoke battery on the CPU mesh:
#
#  1. tests/test_spec_decode.py — acceptance/rollback determinism vs
#     the non-spec greedy run, preemption mid-draft, the fixed-shape
#     no-recompile gate, dropped-verification one-request containment;
#  2. tests/test_kv_quant.py — the bounded-divergence gates (logit
#     max-abs-err + greedy agreement), the >=1.9x int8 capacity gate,
#     fresh-scale page reuse, scale migration bit-exactness, and the
#     scaleless-reader loud failure;
#  3. an e2e through examples/chat_server.py --kv-quant int8 --spec
#     (streamed replies over a quantized pool with speculation on);
#  4. a bench.py gate: serving_tokens_per_s_spec, kv_bytes_per_token,
#     and paged_decode_quant_ms non-null on this CPU-only host, with
#     int8 bytes/token strictly below native.
#
# Sibling of scripts/disagg_smoke.sh, wired as `make spec-smoke`.
# A verify-dispatch shape leak (recompile per acceptance pattern), a
# scale that survives page reuse, or a draft that changes tokens
# fails here in minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== speculative decode + quantized KV battery (CPU mesh) =="
$PY -m pytest tests/test_spec_decode.py tests/test_kv_quant.py -q

echo "== chat e2e: --kv-quant int8 --spec (streamed, quantized, speculative) =="
out=$(printf '1 2 3 1 2 3 1 2\n7 8 7 8 7 8\n5 5\n' \
      | timeout 300 $PY examples/chat_server.py --tp 2 --gen-len 8 \
          --kv-quant int8 --spec --spec-k 4)
echo "$out"
lines=$(echo "$out" | grep -c '^-> [0-9 ]*$' || true)
[ "$lines" -eq 3 ] || { echo "expected 3 streamed replies, got $lines"; exit 1; }

echo "== bench gate: spec + quant keys non-null =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/spec_bench.json 2>/tmp/spec_bench.err \
  || { cat /tmp/spec_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/spec_bench.json"))["detail"]
sp = d.get("serving_tokens_per_s_spec")
bt = d.get("kv_bytes_per_token")
qm = d.get("paged_decode_quant_ms")
assert sp and sp.get("spec") and sp.get("nospec"), (
    f"serving_tokens_per_s_spec null: {sp!r} "
    f"(serving_error={d.get('serving_error')!r})")
assert bt and all(bt.get(k) for k in ("bf16", "int8", "fp8")), (
    f"kv_bytes_per_token null: {bt!r}")
assert qm and all(qm.get(k) for k in ("bf16", "int8", "fp8")), (
    f"paged_decode_quant_ms null: {qm!r}")
assert bt["int8"] < bt["bf16"], f"int8 not smaller: {bt}"
print(f"spec-smoke: ok (spec tok/s {sp}, accept "
      f"{d.get('serving_spec_accept_rate')}, bytes/token {bt}, "
      f"quant decode ms {qm})")
EOF
