"""Resilience battery: fault plans against the comm path, watchdog
deadlines, and the graceful-degradation policy.

Acceptance contract (ISSUE 1): every injected fault plan TERMINATES —
either bit-correct output (tolerated fault) or a structured
``CommTimeoutError`` carrying rank + op + progress (detected fault) —
never a hang. Deadlock-prone plans run through the subprocess harness
(``resilience.harness``), whose deadline is the no-hang guarantee.
"""

import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.ops.ag_gemm import (
    ag_gemm, ag_gemm_ref, create_ag_gemm_context)
from triton_dist_tpu.resilience import (
    CommTimeoutError, InjectedFault, Watchdog, faults, harness, policy,
)
from triton_dist_tpu.utils.testing import assert_allclose, spmd

# Bound for subprocess cases: covers jax import + trace in the child
# with margin; the deadline only has to FIRE for genuinely wedged
# schedules (blocking interpreter backends).
SUBPROC_DEADLINE_S = 240.0


@pytest.fixture(scope="module")
def ag_gemm_deadline_s():
    """Deadline for the ag_gemm child, reckoned from a healthy one.

    A wedged child is waited for until the deadline, and nothing is
    learned after its last progress marker: the wait is as long as the
    child needs when nothing is wrong (a tolerated plan runs the same
    program to its end, under the load of the moment), twice over, and
    never under 20 s."""
    t0 = time.monotonic()
    verdict, _ = harness.run_plan("skewed_barrier", "ag_gemm", rank=1,
                                  iters=1, deadline_s=SUBPROC_DEADLINE_S)
    assert verdict == "ok"
    return max(20.0, 2.0 * (time.monotonic() - t0))


def _run_ag_gemm(mesh, ctx8, plan=None):
    """Trace a FRESH ag_gemm closure (inside the inject scope when a
    plan is given — faults bake in at trace time) and return its
    output; never reuses a jit cache across plans."""
    n, m_loc, kdim, nloc = 8, 16, 128, 128
    a = (jnp.arange(n * m_loc * kdim, dtype=jnp.float32)
         .reshape(n * m_loc, kdim) % 13) / 13.0
    b = (jnp.arange(kdim * nloc, dtype=jnp.float32)
         .reshape(kdim, nloc) % 7) / 7.0
    ctx = create_ag_gemm_context(ctx8, "tp", block_m=m_loc,
                                 block_n=nloc, block_k=kdim)

    def call():
        f = spmd(mesh, lambda a_, b_: ag_gemm(a_, b_, ctx),
                 (P("tp", None), P(None, None)), P(None, None))
        return f(a, b)

    if plan is None:
        out = call()
    else:
        with faults.inject(plan):
            out = call()
    want = spmd(mesh, lambda a_, b_: ag_gemm_ref(a_, b_, axis="tp"),
                (P("tp", None), P(None, None)), P(None, None))(a, b)
    return out, want


# ---------------------------------------------------------------------------
# Tolerated faults: adversarial timing the protocols must absorb.
# ---------------------------------------------------------------------------

def test_delayed_dma_ag_gemm_bit_correct(tp8_mesh, tp8_ctx):
    """Maximally-late DMA completion + a spin before rank 2's ring
    kick-off put: the arrival waits must still certify every chunk."""
    plan = faults.get_plan("delayed_dma", op="ag_gemm", rank=2, k=0,
                           iters=5000)
    out, want = _run_ag_gemm(tp8_mesh, tp8_ctx, plan)
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_skewed_barrier_ag_gemm_bit_correct(tp8_mesh, tp8_ctx):
    """One rank arrives late at the entry barrier (straggler spin):
    the reference's straggler_option scenario, as a named plan."""
    plan = faults.get_plan("skewed_barrier", op="ag_gemm", rank=5,
                           iters=5000)
    out, want = _run_ag_gemm(tp8_mesh, tp8_ctx, plan)
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_no_plan_is_free_and_correct(tp8_mesh, tp8_ctx):
    """The hooks are inert without an active plan."""
    assert faults.active_plan() is None
    out, want = _run_ag_gemm(tp8_mesh, tp8_ctx, None)
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Detected faults: protocol-breaking plans must terminate in a bounded,
# attributable way. Subprocess-isolated: a genuinely wedged interpreter
# thread cannot be cancelled in-process.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", ["dropped_signal", "dup_signal"])
def test_signal_faults_ag_gemm_terminate(plan, ag_gemm_deadline_s):
    try:
        verdict, _ = harness.run_plan(plan, "ag_gemm", rank=1, k=0,
                                      deadline_s=ag_gemm_deadline_s)
    except CommTimeoutError as e:
        # Detected: the structured error must attribute the hang.
        assert e.op == "ag_gemm"
        assert e.timeout_s == ag_gemm_deadline_s
        assert e.progress is not None, "no progress marker recorded"
        return
    assert verdict == "ok"   # tolerated: bit-correct output


@pytest.mark.slow
def test_dropped_edge_megakernel_terminates():
    """A suppressed scoreboard completion signal either leaves the
    merged queue's output intact (non-blocking backend) or wedges the
    schedule — which must surface as CommTimeoutError naming the
    last-completed queue slot, not as a hang."""
    try:
        verdict, _ = harness.run_plan(
            "dropped_edge", "megakernel", k=0,
            deadline_s=SUBPROC_DEADLINE_S,
            extra_env={"TRITON_DIST_TPU_TRACE_PROGRESS": "1"})
    except CommTimeoutError as e:
        assert e.op == "megakernel"
        assert e.progress is not None
        return
    assert verdict == "ok"


def test_fail_kth_call_raises_structured():
    plan = faults.get_plan("fail_kth_call", op="ag_gemm", k=1)
    with faults.inject(plan):
        with faults.on_op_call("ag_gemm"):
            pass                      # call 0 passes
        with pytest.raises(InjectedFault) as ei:
            with faults.on_op_call("ag_gemm"):
                pass                  # call 1 raises
    assert ei.value.op == "ag_gemm"
    assert ei.value.call_index == 1
    # Other ops are untouched.
    with faults.inject(plan):
        with faults.on_op_call("gemm_rs"):
            pass


# ---------------------------------------------------------------------------
# Watchdog semantics.
# ---------------------------------------------------------------------------

def test_watchdog_timeout_structured():
    wd = Watchdog(0.2, op="unit.slow",
                  progress_fn=lambda: {"step": 7})
    with pytest.raises(CommTimeoutError) as ei:
        wd.run(time.sleep, 5.0)
    e = ei.value
    assert e.op == "unit.slow"
    assert e.timeout_s == 0.2
    assert e.progress == {"step": 7}
    assert e.rank == jax.process_index()
    for field in ("unit.slow", "progress"):
        assert field in str(e)


def test_watchdog_passthrough_and_errors():
    wd = Watchdog(5.0, op="unit.fast")
    assert wd.run(lambda: 42) == 42

    with pytest.raises(ZeroDivisionError):
        wd.run(lambda: 1 // 0)


def test_shmem_barrier_cached_and_bounded(tp8_mesh):
    from triton_dist_tpu.shmem import workspace

    workspace._BARRIER_CACHE.clear()
    workspace.barrier_all(tp8_mesh, timeout_s=60.0)
    assert len(workspace._BARRIER_CACHE) == 1
    compiled = workspace._BARRIER_CACHE[(tp8_mesh, "tp")]
    workspace.barrier_all(tp8_mesh)           # satellite: no re-jit
    assert workspace._BARRIER_CACHE[(tp8_mesh, "tp")] is compiled
    assert len(workspace._BARRIER_CACHE) == 1


# ---------------------------------------------------------------------------
# Bring-up / teardown robustness (satellites).
# ---------------------------------------------------------------------------

def test_initialize_retries_with_backoff(monkeypatch):
    from triton_dist_tpu.utils import distributed

    calls = []
    sleeps = []

    def flaky(**kw):
        calls.append(kw)
        if len(calls) < 3:
            raise RuntimeError("coordinator not ready")

    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    monkeypatch.setattr(distributed.time, "sleep", sleeps.append)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        distributed.initialize_distributed(
            coordinator_address="localhost:1234", num_processes=2,
            process_id=0, max_attempts=4, backoff_s=0.25)
    assert len(calls) == 3                      # 2 failures + 1 success
    assert sleeps == [0.25, 0.5]                # exponential backoff


def test_initialize_exhausts_attempts(monkeypatch):
    from triton_dist_tpu.utils import distributed

    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: (_ for _ in ()).throw(RuntimeError("nope")))
    monkeypatch.setattr(distributed.time, "sleep", lambda s: None)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(RuntimeError, match="after 2 attempts"):
            distributed.initialize_distributed(
                coordinator_address="localhost:1234", num_processes=2,
                process_id=0, max_attempts=2)


def test_finalize_warns_on_teardown_failure(monkeypatch):
    from triton_dist_tpu.utils import distributed

    monkeypatch.setattr(
        jax.distributed, "shutdown",
        lambda: (_ for _ in ()).throw(RuntimeError("dead coordinator")))
    with pytest.warns(RuntimeWarning, match="dead coordinator"):
        distributed.finalize_distributed()


# ---------------------------------------------------------------------------
# Graceful degradation: Engine fallback="xla".
# ---------------------------------------------------------------------------

# Head counts divisible by the 8-way tp mesh the engine tests run on.
CFG = ModelConfig.tiny(vocab_size=64, hidden_size=64,
                       intermediate_size=64, num_hidden_layers=2,
                       num_attention_heads=8, num_key_value_heads=8,
                       head_dim=8)


def test_engine_fallback_serves_when_fused_fails(tp8_mesh):
    """Force every fused op call to raise: Engine(fallback="xla") must
    log once, rebuild on the XLA path, and serve the same tokens the
    plain-XLA engine serves."""
    from triton_dist_tpu.models.engine import Engine

    policy.reset()
    ids = np.arange(2 * 4, dtype=np.int32).reshape(2, 4) % 7

    want = Engine(CFG, tp8_mesh, mode="xla", max_len=32,
                  seed=3).serve(ids, gen_len=4)

    plan = faults.get_plan("fail_kth_call", op="*", k=0)
    with faults.inject(plan):
        eng = Engine(CFG, tp8_mesh, mode="fused", max_len=32, seed=3,
                     fallback="xla")
        got = eng.serve(ids, gen_len=4)
    assert eng.mode == "xla"          # degraded, not dead
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    policy.reset()


def test_engine_no_fallback_raises(tp8_mesh):
    from triton_dist_tpu.models.engine import Engine

    policy.reset()
    plan = faults.get_plan("fail_kth_call", op="*", k=0)
    ids = np.zeros((2, 4), np.int32)
    with faults.inject(plan):
        eng = Engine(CFG, tp8_mesh, mode="fused", max_len=32)
        with pytest.raises(Exception):
            eng.serve(ids, gen_len=2)
    policy.reset()


def test_decode_counter_not_advanced_on_failure(tp8_mesh):
    """Satellite: a raised decode step must leave the overflow guard
    exactly where it was."""
    from triton_dist_tpu.models.engine import Engine

    eng = Engine(CFG, tp8_mesh, mode="xla", max_len=32)
    logits, cache = eng.prefill(np.zeros((2, 4), np.int32))
    assert eng._host_len == 4

    def boom(*a, **k):
        raise RuntimeError("injected decode failure")

    real = eng._decode
    eng._decode = boom
    with pytest.raises(RuntimeError, match="injected decode failure"):
        eng.decode(np.zeros((2,), np.int32), cache)
    assert eng._host_len == 4         # unchanged after the raise
    eng._decode = real
    logits, cache = eng.decode(np.zeros((2,), np.int32), cache)
    assert eng._host_len == 5


def test_policy_force_env(monkeypatch):
    policy.reset()
    monkeypatch.setenv("TRITON_DIST_TPU_FORCE_XLA", "gemm_rs")
    assert policy.should_fallback("gemm_rs")
    monkeypatch.setenv("TRITON_DIST_TPU_FORCE_XLA", "*")
    assert policy.should_fallback("anything")
    monkeypatch.delenv("TRITON_DIST_TPU_FORCE_XLA")
    policy.reset()


def test_policy_note_failure_sticky():
    policy.reset()
    assert not policy.should_fallback("unit_op")
    policy.note_failure("unit_op", RuntimeError("boom"))
    assert policy.should_fallback("unit_op")
    policy.reset()
    assert not policy.should_fallback("unit_op")


def test_force_xla_reroutes_op_dispatch(tp8_mesh, tp8_ctx, monkeypatch):
    """TRITON_DIST_TPU_FORCE_XLA must actually change the dispatch:
    with the fused impl patched to raise, the op only survives if the
    wrapper re-routed through the XLA oracle — and the output must
    still be correct."""
    import importlib

    # ops/__init__ re-exports the functions under the module names, so
    # attribute-style imports resolve to the functions; go via
    # sys.modules for the module objects.
    ag_mod = importlib.import_module("triton_dist_tpu.ops.ag_gemm")
    a2a_mod = importlib.import_module("triton_dist_tpu.ops.all_to_all")
    rs_mod = importlib.import_module("triton_dist_tpu.ops.gemm_rs")

    policy.reset()
    monkeypatch.setenv("TRITON_DIST_TPU_FORCE_XLA",
                       "ag_gemm,gemm_rs,all_to_all")

    def forbidden(*a, **k):
        raise AssertionError("fused impl dispatched despite FORCE_XLA")

    monkeypatch.setattr(ag_mod, "_ag_gemm_impl", forbidden)
    monkeypatch.setattr(rs_mod, "_gemm_rs_impl", forbidden)
    monkeypatch.setattr(a2a_mod, "_all_to_all_impl", forbidden)

    out, want = _run_ag_gemm(tp8_mesh, tp8_ctx)
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)

    a = (jnp.arange(8 * 16 * 128, dtype=jnp.float32)
         .reshape(8 * 16, 128) % 11) / 11.0
    b = (jnp.arange(128 * 128, dtype=jnp.float32)
         .reshape(128, 128) % 5) / 5.0
    ctx = rs_mod.create_gemm_rs_context(tp8_ctx, "tp")
    got = spmd(tp8_mesh, lambda a_, b_: rs_mod.gemm_rs(a_, b_, ctx),
               (P(None, "tp"), P("tp", None)), P("tp", None))(a, b)
    ref = spmd(tp8_mesh,
               lambda a_, b_: rs_mod.gemm_rs_ref(a_, b_, axis="tp"),
               (P(None, "tp"), P("tp", None)), P("tp", None))(a, b)
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)

    x = jnp.arange(8 * 8 * 4, dtype=jnp.float32).reshape(8 * 8, 4)
    got = spmd(tp8_mesh,
               lambda x_: a2a_mod.all_to_all(x_, ctx=tp8_ctx, axis="tp"),
               P("tp", None), P("tp", None))(x)
    ref = spmd(tp8_mesh,
               lambda x_: a2a_mod.all_to_all_ref(x_, axis="tp"),
               P("tp", None), P("tp", None))(x)
    assert_allclose(got, ref, rtol=0, atol=0)
    policy.reset()


def test_health_probe_reports_healthy(tp8_mesh):
    """On any working interpret backend the tiny fused canary matches
    its oracle — the probe must say healthy (and must never hang:
    it is watchdog-bounded by construction)."""
    assert policy.health_probe(tp8_mesh, "tp") is True


def test_scheduler_describe_slot():
    from triton_dist_tpu.megakernel.scheduler import (
        describe_slot, schedule_mc)

    s = schedule_mc(5, [0, 0, 1, 2, 3], [1, 2, 3, 3, 4], num_cores=2)
    seen = set()
    for q in range(s["queue"].shape[0]):
        for c in range(2):
            d = describe_slot(s, q, c)
            assert d["merged_index"] == q * 2 + c
            if d["task"] >= 0:
                seen.add(d["task"])
                assert isinstance(d["waits_on_edges"], list)
                assert isinstance(d["signals_edges"], list)
    assert seen == {0, 1, 2, 3, 4}
    with pytest.raises(IndexError):
        describe_slot(s, 10 ** 6, 0)


def test_fault_plan_registry_complete():
    names = faults.battery()
    for required in ("delayed_dma", "dropped_signal", "dup_signal",
                     "skewed_barrier", "dropped_edge", "fail_kth_call"):
        assert required in names
    with pytest.raises(KeyError):
        faults.get_plan("no_such_plan")
