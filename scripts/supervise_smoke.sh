#!/usr/bin/env bash
# Supervised-serving / payload-integrity smoke battery on the CPU
# mesh (no TPU):
#
#  1. tests/test_supervisor.py (fast subset) — checkpoint envelope
#     corruption/truncation detection, keep-last-K ring ordering +
#     corrupt-newest fallback, parent-side ack dedupe/divergence/gap
#     protocol units, real-child crash + stall recovery, payload
#     digest units, the three-boundary integrity drill, and the
#     single-injectable-clock fleet check;
#  2. the long acceptance soak (tests/test_supervisor.py -m slow):
#     a REAL child process survives >= 6 seeded SIGKILLs/forced
#     crashes/stalls mid-decode — every stream finishes token-exact
#     vs the in-process fault-free oracle;
#  3. a crash/resume e2e: supervise a real child, SIGKILL it after
#     >= 3 streamed tokens, and diff the resumed stream (dedupe
#     absorbs the replayed prefix) against a clean in-process run —
#     bit-identical or fail;
#  4. a bench.py gate: detail.crash_recovery_ms,
#     detail.supervised_survived_faults and detail.integrity_checks
#     non-null (the seeded supervised soak + integrity drill inside
#     the bench record completed with their oracles intact).
#
# Sibling of scripts/chaos_smoke.sh, wired as `make supervise-smoke`.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PY=${PY:-python}

echo "== supervisor + integrity battery (CPU mesh) =="
$PY -m pytest tests/test_supervisor.py -q -m 'not slow'

echo "== acceptance soak: 6 seeded kills/stalls, token-exact =="
$PY -m pytest tests/test_supervisor.py -q -m slow

echo "== crash/resume e2e (SIGKILL mid-stream + dedup replay) =="
CKDIR=$(mktemp -d)
trap 'rm -rf "$CKDIR"' EXIT
timeout 300 $PY - "$CKDIR" <<'EOF'
import sys
import time

from triton_dist_tpu.resilience.chaos import (_oracle_tokens,
                                              supervised_tiny_factory)
from triton_dist_tpu.resilience.supervisor import ServingSupervisor

PROMPT = [3, 1, 4, 1, 5]
GEN = 8

# Fault-free oracle: same factory, same seed, in this process.
oracle = _oracle_tokens(supervised_tiny_factory().engine, PROMPT, GEN, {})

streamed = []
sup = ServingSupervisor(
    "triton_dist_tpu.resilience.chaos:supervised_tiny_factory",
    checkpoint_dir=sys.argv[1], checkpoint_every=2,
    heartbeat_timeout_s=120.0, tick_throttle_s=0.05)
with sup:
    h = sup.submit(PROMPT, max_new_tokens=GEN,
                   stream_cb=streamed.append)
    # Let the stream get going, then kill the child mid-decode.
    deadline = time.monotonic() + 240
    while sup.counters["acked_tokens"] < 3:
        sup.pump()
        time.sleep(0.01)
        assert time.monotonic() < deadline, "no tokens before kill"
    sup.kill_child()
    sup.run_until_done(deadline_s=240)

assert sup.counters["crashes"] >= 1, sup.counters
assert h.status == "done", (h.status, h.error)
assert h.tokens == oracle, (h.tokens, oracle)
assert streamed == oracle, "stream_cb saw a duplicate or gap"
print(f"crash/resume e2e token-exact: {oracle} "
      f"(recovery_ms={sup.last_recovery_ms:.0f} "
      f"dedup_dropped={sup.counters['dedup_dropped']})")
EOF

echo "== bench gate: crash_recovery_ms + integrity_checks non-null =="
BENCH_BACKEND=cpu timeout 600 $PY bench.py > /tmp/supervise_bench.json \
    2>/tmp/supervise_bench.err \
  || { cat /tmp/supervise_bench.err; exit 1; }
$PY - <<'EOF'
import json

d = json.load(open("/tmp/supervise_bench.json"))["detail"]
rec = d.get("crash_recovery_ms")
sf = d.get("supervised_survived_faults")
ic = d.get("integrity_checks")
err = d.get("supervise_error")
assert rec is not None, f"crash_recovery_ms null (supervise_error={err!r})"
assert sf is not None and sf >= 1, (
    f"supervised_survived_faults null/zero: {sf!r} "
    f"(supervise_error={err!r})")
assert ic is not None and ic >= 1, (
    f"integrity_checks null/zero: {ic!r} (supervise_error={err!r})")
print(f"supervise-smoke: ok (recovered in {rec}ms, survived {sf} "
      f"faults, restarts={d.get('supervised_restarts')} "
      f"dedup_dropped={d.get('supervised_dedup_dropped')}, "
      f"integrity checks={ic} "
      f"quarantined={d.get('integrity_quarantined')})")
EOF
