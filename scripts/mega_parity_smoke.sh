#!/usr/bin/env bash
# Megakernel serving-parity smoke battery on the CPU mesh:
#
#  1. the converted mk parity tests — quantized-KV token agreement +
#     the >=1.9x capacity gate (tests/test_kv_quant.py), Q-block
#     speculation token-exact vs the non-spec megakernel run under
#     schedule="dynamic" (tests/test_spec_decode.py), and
#     checkpoint->restore resuming mid-stream decode token-exact at
#     bf16 AND int8 (tests/test_fault_tolerance.py) plus the arena
#     schema units (tests/test_megakernel.py -k schema);
#  2. chat e2e A: --megakernel --spec streams BIT-IDENTICAL tokens to
#     the plain --megakernel run (speculation changes throughput,
#     never tokens — the per-row verification bodies are op-for-op
#     the decode bodies');
#  3. chat e2e B: --megakernel --kv-quant int8 --spec --spec-k 2
#     serves, and the exit summary's lane-capability line
#     (mk: kv_dtype=int8 spec=2 checkpointable=yes) is present —
#     the stats()-surface gate that replaced grepping tracebacks for
#     the old layer-path-only rejects;
#  4. a bench.py gate: megakernel_decode_quant_ms (per kv_dtype) and
#     megakernel_tokens_per_s_spec non-null on this CPU-only host
#     (nulled-not-omitted with a mega_error detail on failure).
#
# Sibling of scripts/spec_smoke.sh, wired as `make mega-parity-smoke`.
# A scale that corrupts a page, a verification row that diverges from
# the sequential decode, or an arena snapshot that drops a region
# fails here in minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== megakernel parity battery (CPU mesh) =="
$PY -m pytest tests/test_kv_quant.py -k megakernel \
    tests/test_spec_decode.py -q
$PY -m pytest tests/test_fault_tolerance.py -k megakernel -q
$PY -m pytest tests/test_megakernel.py -k "schema or qblock" -q
$PY -m pytest tests/test_chaos.py -k "megakernel or arena" -q

echo "== chat e2e A: mk --spec streams bit-identical to plain mk =="
prompts='1 2 3 1 2 3 1 2\n7 8 7 8 7 8\n'
plain=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --megakernel | grep '^->')
spec=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
       --tp 2 --gen-len 8 --megakernel --spec --spec-k 2 | grep '^->')
[ "$plain" = "$spec" ] || {
  echo "mk spec streams diverged from the plain mk run:"
  echo "plain: $plain"; echo "spec:  $spec"; exit 1; }
echo "spec streams bit-identical: ok"

echo "== chat e2e B: mk --kv-quant int8 --spec --spec-k 2 =="
out=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
      --tp 2 --gen-len 8 --megakernel --kv-quant int8 --spec --spec-k 2)
echo "$out"
lines=$(echo "$out" | grep -c '^-> [0-9 ]*$' || true)
[ "$lines" -eq 2 ] || { echo "expected 2 streamed replies, got $lines"; exit 1; }
echo "$out" | grep -q 'mk: kv_dtype=int8 spec=2 checkpointable=yes' \
  || { echo "lane-capability line missing from the exit summary"; exit 1; }

echo "== bench gate: megakernel parity keys non-null =="
BENCH_BACKEND=cpu timeout 900 $PY bench.py > /tmp/mega_bench.json 2>/tmp/mega_bench.err \
  || { cat /tmp/mega_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/mega_bench.json"))["detail"]
qm = d.get("megakernel_decode_quant_ms")
sp = d.get("megakernel_tokens_per_s_spec")
assert qm and all(qm.get(k) for k in ("bf16", "int8", "fp8")), (
    f"megakernel_decode_quant_ms null: {qm!r} "
    f"(mega_error={d.get('mega_error')!r})")
assert sp and sp.get("spec") and sp.get("nospec"), (
    f"megakernel_tokens_per_s_spec null: {sp!r} "
    f"(mega_error={d.get('mega_error')!r})")
print(f"mega-parity-smoke: ok (quant decode ms {qm}, spec tok/s {sp}, "
      f"accept {d.get('megakernel_spec_accept_rate')})")
EOF
