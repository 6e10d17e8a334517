#!/usr/bin/env bash
# Megakernel chunked-prefill smoke battery on the CPU mesh:
#
#  1. tests/test_mk_chunked_prefill.py — bucket-edge (b-1/b/b+1)
#     token-exactness vs the one-token mk lane AND vs the layer
#     ChunkedPrefill path, quantized (int8/fp8) chunk writes token-
#     agreeing, prefix-shared pages never re-blitted, spec_k composing
#     on chunked admission, the chunk/decode jit no-growth gates, and
#     the knob-validation / arena-tier NotImplementedError contracts;
#  2. chat e2e: --megakernel --mk-chunked streams BIT-IDENTICAL tokens
#     to the plain --megakernel run on page-crossing prompts (chunked
#     admission changes prefill wall time, never tokens), and the exit
#     summary's lane-capability line carries chunked=[...];
#  3. a bench.py gate: megakernel_prefill_chunk_ms and
#     megakernel_tokens_per_s_prefill_heavy non-null on this CPU-only
#     host (nulled-not-omitted with a mega_error detail on failure),
#     with the chunked lane >= 2x the one-token lane.
#
# Sibling of scripts/mega_parity_smoke.sh, wired as
# `make mkchunk-smoke`. A chunk body that diverges from the one-token
# decode, a chunk dispatch that re-specializes on positions, or a
# chunked lane slower than the tick loop it replaces fails here in
# minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== megakernel chunked-prefill battery (CPU mesh) =="
$PY -m pytest tests/test_mk_chunked_prefill.py -q
$PY -m pytest tests/test_kv_tiers.py -k megakernel -q

echo "== chat e2e: mk --mk-chunked streams bit-identical to plain mk =="
prompts='1 2 3 4 5 6 7 8 9 10 11 12 13 14 15 16 17 18\n7 8 7 8 7 8 7 8 7 8 7 8\n'
plain=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --megakernel | grep '^->')
chunk=$(printf "$prompts" | timeout 300 $PY examples/chat_server.py \
        --tp 2 --gen-len 8 --megakernel --mk-chunked)
echo "$chunk"
chunked=$(echo "$chunk" | grep '^->')
[ "$plain" = "$chunked" ] || {
  echo "mk chunked streams diverged from the one-token-lane run:"
  echo "onetok:  $plain"; echo "chunked: $chunked"; exit 1; }
echo "chunked streams bit-identical: ok"
echo "$chunk" | grep -q 'chunked=\[8, 32\]' \
  || { echo "lane-capability line missing chunked=[8, 32]"; exit 1; }

echo "== bench gate: mk chunked-prefill keys non-null, >= 2x =="
BENCH_BACKEND=cpu timeout 900 $PY bench.py > /tmp/mkchunk_bench.json 2>/tmp/mkchunk_bench.err \
  || { cat /tmp/mkchunk_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/mkchunk_bench.json"))["detail"]
cm = d.get("megakernel_prefill_chunk_ms")
th = d.get("megakernel_tokens_per_s_prefill_heavy")
assert cm, (f"megakernel_prefill_chunk_ms null: {cm!r} "
            f"(mega_error={d.get('mega_error')!r})")
assert th and th.get("chunked") and th.get("onetok"), (
    f"megakernel_tokens_per_s_prefill_heavy null: {th!r} "
    f"(mega_error={d.get('mega_error')!r})")
assert th["chunked"] >= 2.0 * th["onetok"], (
    f"chunked prefill {th['chunked']} tok/s < 2x the one-token lane "
    f"{th['onetok']} tok/s — the chunk tasks lost to the tick loop "
    "they replace")
print(f"mkchunk-smoke: ok (chunk {cm} ms, prefill-heavy tok/s {th}, "
      f"speedup {d.get('megakernel_prefill_chunk_speedup')}x)")
EOF
