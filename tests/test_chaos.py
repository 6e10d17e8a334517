"""Chaos soak battery: seeded fault schedules over live serving
traffic, gated on the invariant checker.

The fast deterministic subset runs in tier-1 (seconds); the full
acceptance soak — 200+ ticks, >= 10 injected faults across every
fault family incl. a worker kill and a mid-run checkpoint/restore —
is marked ``slow`` (it is the `make chaos-smoke` / release gate).
A failing soak replays bit-for-bit from its seed.
"""

import dataclasses

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig, dense
from triton_dist_tpu.resilience import chaos
from triton_dist_tpu.resilience.policy import RetryPolicy
from triton_dist_tpu.serving import DisaggServingEngine, ServingEngine

TINY = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                        intermediate_size=32, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=4,
                        head_dim=8)
CFG = ModelConfig.tiny()


@pytest.fixture(scope="module")
def tiny_factory():
    """Colocated two-role serving over the tiny model on one device —
    the cheap soak target (chunked prefill + local migration + retry +
    failover all reachable)."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))

    def factory():
        eng = Engine(TINY, mesh, mode="xla", max_len=32, seed=0)
        return DisaggServingEngine(
            eng, num_slots=2, page=8, prefill_buckets=(4, 8),
            prefix_reuse=True, retry=RetryPolicy(max_attempts=2),
            worker_fail_threshold=2)

    return factory


# ---------------------------------------------------------------------------
# Invariant checker units: a checker that cannot fail gates nothing.
# ---------------------------------------------------------------------------

def _live_engine():
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(TINY, mesh, mode="xla", max_len=32, seed=0)
    srv = ServingEngine(eng, num_slots=2, page=8, prefix_reuse=True)
    srv.submit([1, 2, 3], max_new_tokens=6)
    srv.submit([4, 5], max_new_tokens=6)
    for _ in range(2):
        srv.step()
    return srv


def test_checker_passes_on_healthy_engine():
    srv = _live_engine()
    chaos.check_invariants(srv)
    srv.run()
    chaos.check_invariants(srv)


def test_checker_catches_leaked_page():
    srv = _live_engine()
    # simulate a leak: a page vanishes from the free list with no ref
    srv.manager._free.pop()
    with pytest.raises(chaos.InvariantViolation, match="LEAKED"):
        chaos.check_invariants(srv)


def test_checker_catches_refcount_drift():
    srv = _live_engine()
    slot = next(iter(srv.manager._slot_pages))
    pid = srv.manager._slot_pages[slot][0]
    srv.manager._refs[pid] += 1
    with pytest.raises(chaos.InvariantViolation, match="refcount"):
        chaos.check_invariants(srv)


def test_checker_catches_mirror_drift():
    srv = _live_engine()
    slot = next(iter(srv.sched.slots))
    srv._lens[slot] += 3
    with pytest.raises(chaos.InvariantViolation, match="mirror"):
        chaos.check_invariants(srv)


def test_checker_catches_staged_published_overlap():
    srv = _live_engine()
    mgr = srv.manager
    slot = next(iter(mgr._slot_pages))
    pid = mgr._slot_pages[slot][0]
    key = next(iter(mgr._prefix)) if mgr._prefix else ("k",)
    mgr._pending_prefix[slot] = [(key, pid)]
    mgr._prefix[key] = pid
    mgr._refs[pid] += 1
    with pytest.raises(chaos.InvariantViolation):
        chaos.check_invariants(srv)


# ---------------------------------------------------------------------------
# Seeded soaks (fast tier-1 subset)
# ---------------------------------------------------------------------------

def test_soak_replays_bit_for_bit(tiny_factory):
    a = chaos.run_soak(tiny_factory, seed=3, ticks=25, n_faults=3)
    b = chaos.run_soak(tiny_factory, seed=3, ticks=25, n_faults=3)

    def sched(rep):
        # Everything but the `at` clock stamp must replay bit-for-bit;
        # `at` rides the engine clock (wall time here — deterministic
        # only under an injected fake clock, see tests/test_obs.py).
        return [dataclasses.astuple(e)[:-1] for e in rep.events]

    assert sched(a) == sched(b)
    assert all(e.at is not None for e in a.events if e.fired)
    assert a.requests == b.requests
    assert a.counters == b.counters


def test_soak_fast_mixed_faults(tiny_factory):
    rep = chaos.run_soak(tiny_factory, seed=7, ticks=60, n_faults=6)
    assert rep.faults_injected == 6
    assert rep.survived_faults == 6
    assert rep.requests["submitted"] > 0
    total = sum(rep.requests[k] for k in ("done", "failed", "timeout"))
    assert total == rep.requests["submitted"], "all terminal"
    assert rep.token_exact_requests == rep.requests["done"]
    assert rep.invariant_checks >= rep.ticks


def test_soak_with_midrun_restore(tiny_factory):
    rep = chaos.run_soak(tiny_factory, seed=7, ticks=60, n_faults=6,
                         restore_at=25)
    assert rep.restored_at == 25
    assert rep.survived_faults == 6
    assert rep.token_exact_requests == rep.requests["done"]


def test_soak_worker_kill_only(tiny_factory):
    """Pin the schedule to the dead-prefill-worker event — failover
    must fire and the run still resolves token-exact."""
    kinds = [("kill_prefill_worker", None, None)]
    rep = chaos.run_soak(tiny_factory, seed=5, ticks=40, n_faults=2,
                         kinds=kinds)
    assert rep.counters["failovers"] >= 1
    assert rep.token_exact_requests == rep.requests["done"]


# One engine per build config for the module: each factory() call
# wraps the SAME engine in a fresh ServingEngine — safe because the
# soak's factory re-invocations are strictly sequential (the restore
# drill overwrites pools/scales wholesale; the oracle runs only after
# the soak srv drained) and engine builds dominate wall clock.
_MK_ENGINES: dict = {}


def _mk_factory(**kw):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    key = tuple(sorted(kw.items()))
    if key not in _MK_ENGINES:
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        _MK_ENGINES[key] = MegaKernelEngine(
            TINY, mesh, batch=2, max_len=32, tile_w=16, t_tile=16,
            paged=True, page=16, num_pages=5, **kw)

    def factory():
        return ServingEngine(_MK_ENGINES[key], **(
            {"kv_dtype": kw["kv_dtype"]} if "kv_dtype" in kw else {}))

    return factory


def test_soak_megakernel_with_restore():
    """The converted mk-reject: the chaos soak drives the PERSISTENT
    lane too — seeded decode drops/wedges under MK_FAULT_KINDS, the
    mid-run kill/checkpoint/restore drill through the schema snapshot,
    the extended arena-coherence sweep (region disjointness, scale
    sanity, monotonic counters) after EVERY tick, and survivors
    token-exact vs a fault-free serving oracle."""
    # The fewest ticks at which this seed's schedule still fires all of
    # it: a dropped decode (tick 4), a wedged verify (5) and a wedged
    # decode (6) after a restore (3) that revives a request in flight,
    # one request failed, one timed out and one done and token-exact.
    # An interpreted step is most of a second, and the oracle pays one
    # for every prompt token of every finished request.
    ticks = 7
    rep = chaos.run_soak(_mk_factory(), seed=3, ticks=ticks, n_faults=3,
                         kinds=chaos.MK_FAULT_KINDS, restore_at=3,
                         gen_choices=(2, 3), arrival_p=0.25)
    assert rep.faults_injected == 3
    assert rep.restored_at == 3
    assert rep.counters["restored_requests"] >= 1
    assert rep.requests["done"] >= 1
    assert rep.token_exact_requests == rep.requests["done"]
    assert rep.invariant_checks >= ticks


def test_soak_megakernel_quantized():
    """Quantized mk soak: the scale-sanity half of the arena sweep
    runs against live int8 pools under decode faults."""
    # Six ticks: a dropped verify (tick 1), a dropped decode that fails
    # a live request (5), two requests done on the int8 pools.
    rep = chaos.run_soak(_mk_factory(kv_dtype="int8"), seed=5,
                         ticks=6, n_faults=2,
                         kinds=chaos.MK_FAULT_KINDS,
                         gen_choices=(2, 3), arrival_p=0.4)
    assert rep.faults_injected == 2
    assert rep.requests["done"] >= 1
    assert rep.token_exact_requests == rep.requests["done"]


def test_arena_checker_catches_corruption():
    """A checker that cannot fail gates nothing: a clobbered scale
    plane and a backwards counter must raise InvariantViolation."""
    import jax.numpy as jnp

    srv = _mk_factory(kv_dtype="int8")()
    srv.generate([[1, 2, 3]], max_new_tokens=2)
    chaos.check_invariants(srv)               # healthy passes
    good = srv.engine.k_scale
    srv.engine.k_scale = jnp.asarray(good).at[0, 1, 0, 0].set(-1.0)
    with pytest.raises(chaos.InvariantViolation, match="scale"):
        chaos.check_invariants(srv)
    srv.engine.k_scale = good
    chaos.check_invariants(srv)

    moe = ModelConfig.tiny_moe(vocab_size=64, hidden_size=32,
                               num_hidden_layers=2,
                               num_attention_heads=4,
                               num_key_value_heads=2, head_dim=8,
                               num_experts=4, num_experts_per_tok=2,
                               moe_intermediate_size=32)
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    msrv = ServingEngine(MegaKernelEngine(moe, mesh, batch=2,
                                          max_len=32, tile_w=16,
                                          t_tile=16, paged=True,
                                          page=16, num_pages=5))
    msrv.generate([[1, 2]], max_new_tokens=2)
    chaos.check_invariants(msrv)              # seeds the counter sweep
    msrv._mk_counts_sweep = msrv._mk_counts_sweep + 10
    with pytest.raises(chaos.InvariantViolation, match="BACKWARDS"):
        chaos.check_invariants(msrv)


# ---------------------------------------------------------------------------
# The acceptance soak (slow tier): 200+ ticks, >= 10 faults, split
# roles, mid-run kill/restore.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_acceptance_200_ticks_disjoint_roles():
    params = dense.init_params(jax.random.PRNGKey(3), CFG)
    devs = jax.devices()

    def factory():
        pf = Engine(CFG, Mesh(np.array(devs[:2]), ("tp",)),
                    mode="xla", max_len=64, params=params)
        dec = Engine(CFG, Mesh(np.array(devs[2:4]), ("tp",)),
                     mode="xla", max_len=64, params=params)
        return DisaggServingEngine(
            dec, prefill_engine=pf, num_slots=2, page=8,
            prefill_buckets=(4, 16), prefix_reuse=True,
            retry=RetryPolicy(max_attempts=2),
            worker_fail_threshold=2)

    rep = chaos.run_soak(factory, seed=17, ticks=200, n_faults=12,
                         restore_at=90)
    assert rep.faults_injected >= 10
    assert rep.survived_faults >= 10
    assert rep.restored_at == 90
    total = sum(rep.requests[k] for k in ("done", "failed", "timeout"))
    assert total == rep.requests["submitted"]
    assert rep.token_exact_requests == rep.requests["done"]
    assert rep.invariant_checks >= 200
