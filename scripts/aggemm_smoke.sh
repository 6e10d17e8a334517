#!/usr/bin/env bash
# ag_gemm variant smoke battery on the CPU interpret mesh (no TPU):
#
#  1. tests/test_overlap.py -k ag_gemm — the full variant x swizzle x
#     depth parity sweep (panel AND pipelined, both REAL kernels —
#     the interpret fallback that silently swapped pipelined for
#     panel is gone), the panel-vs-pipelined BIT-parity checks, the
#     self-sim ring sweep at ring {2,4,8}, and the offline variant
#     autotune round-trip (sweep -> persist -> cache hit);
#  2. tests/test_fused_gemm.py -k ag_gemm (2D-mesh cases excluded:
#     multi-axis meshes are an open interpreter gap) — the
#     kernel-level battery including the spy test that PROVES
#     sim_ranks dispatches the real pipelined kernel;
#  3. tests/test_schedule_math.py — the wide-K (K=4096) host-side
#     staging arithmetic the interpret harness cannot reach with
#     device buffers;
#  4. a bench.py (interpret) pass gating NON-NULL
#     detail.ag_gemm_pipelined_ms / ag_gemm_panel_ms plus the
#     block_m {128,256,512} crossover table, and asserting the
#     streamed variant stays within 1.1x of panel — a regression
#     that re-bloats the streamed schedule's body count fails here
#     in minutes, off-silicon.
#
# Wired as `make aggemm-smoke`.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== ag_gemm variant/parity battery (CPU mesh) =="
$PY -m pytest tests/test_overlap.py -q -k "ag_gemm or choose_depth or stream_plan"

echo "== ag_gemm kernel battery (2D-mesh interpreter gap excluded) =="
$PY -m pytest tests/test_fused_gemm.py -q -k "ag_gemm and not 2d"

echo "== wide-K schedule math (host-side, no device buffers) =="
$PY -m pytest tests/test_schedule_math.py -q

echo "== bench.py ag_gemm variant gate (interpret) =="
bench_out=$(mktemp)
BENCH_BACKEND=cpu timeout 900 $PY bench.py 2>/dev/null > "$bench_out"
$PY - "$bench_out" <<'EOF'
import json, sys
with open(sys.argv[1]) as f:
    rec = json.loads(f.read().strip().splitlines()[-1])
d = rec["detail"]
panel = d.get("ag_gemm_panel_ms")
pipe = d.get("ag_gemm_pipelined_ms")
assert isinstance(panel, (int, float)) and panel > 0, \
    f"ag_gemm_panel_ms missing: {d.get('ag_variant_error')}"
assert isinstance(pipe, (int, float)) and pipe > 0, \
    f"ag_gemm_pipelined_ms missing: {d.get('ag_variant_error')}"
cx = d.get("ag_gemm_variant_crossover")
assert isinstance(cx, dict) and set(cx) == {"128", "256", "512"}, cx
for bm, row in cx.items():
    for k in ("panel_ms", "pipelined_ms"):
        assert isinstance(row.get(k), (int, float)) and row[k] > 0, \
            (bm, row)
# The streamed schedule must stay competitive with panel at the
# block_m <= 512 granularities (best-of over the sweep): anything
# past 1.1x means the fine-granularity path regressed.
assert pipe <= 1.1 * panel, \
    f"pipelined {pipe}ms > 1.1x panel {panel}ms"
print("ag_gemm_panel_ms:", panel)
print("ag_gemm_pipelined_ms:", pipe)
print("crossover:", json.dumps(cx))
EOF
rm -f "$bench_out"
