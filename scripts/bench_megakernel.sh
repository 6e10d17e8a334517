#!/usr/bin/env bash
# Megakernel scheduler smoke battery on the CPU interpret mesh (no TPU):
#
#  1. tests/test_megakernel.py — the full megakernel acceptance battery,
#     including the dynamic scoreboard scheduler's token-exactness vs
#     static on the dense / MoE / hybrid-GDN families, the scheduler
#     fairness sweep, and the skewed-cost idle-step comparison;
#  2. an interpret-mode bench.py pass, asserting the record carries
#     NON-NULL megakernel_decode_step_ms values for BOTH schedule modes
#     (a CPU-only host once emitted value: null).
#
# Sibling of scripts/bench_smoke.sh, wired as `make bench-megakernel`.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== megakernel battery: static + dynamic scheduler (CPU interpret mesh) =="
$PY -m pytest tests/test_megakernel.py -q

echo "== interpret-mode bench (megakernel values must be non-null) =="
out=$(BENCH_BACKEND=cpu BENCH_BATTERY_BUDGET_S=0 timeout 600 $PY bench.py)
echo "$out" | tail -1
$PY - "$out" <<'EOF'
import json, sys
rec = json.loads(sys.argv[1].strip().splitlines()[-1])
mk = rec["detail"].get("megakernel_decode_step_ms")
assert isinstance(mk, dict), rec["detail"].get("megakernel_error", rec)
for mode in ("static", "dynamic"):
    assert mk.get(mode) is not None, (mode, mk)
idle = rec["detail"]["megakernel_idle_slots"]
assert idle["dynamic"] < idle["static"], idle
print("bench-megakernel: ok "
      f"(decode_step_ms static={mk['static']} dynamic={mk['dynamic']}, "
      f"idle_slots static={idle['static']} dynamic={idle['dynamic']})")
EOF
