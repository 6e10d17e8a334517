"""Hierarchical 2-hop EP decode dispatch battery (ISSUE 18 / ROADMAP
open item 1: kill the ``ll``→``ar`` fallback on multi-node meshes).

Covers the ``ll2d`` transport end to end: ``ll_a2a_2d`` hop semantics
vs the flat wire reference (int8 + fp8, kernel + xla hop impls),
``fwd_decode`` parity with the ``"ar"`` oracle under uniform and
adversarially skewed routing, serving-level greedy-token exactness
with the ``dispatch_transport`` observability line, the DCN
put-coalescing claim ASSERTED from the trace-time put ledger (puts per
dispatch == peer-NODE count, not peer-chip count), per-hop fault
containment, the 2D-keyed tune round-trip, and the jit no-growth gate
on the serving decode dispatch.

Mesh shape: the 8 CPU devices as a 2 (node/DCN) x 4 (chip/ICI)
hierarchy — ``dp`` plays the DCN axis, ``tp`` the ICI axis, matching
the canonical outermost-DCN convention (docs/build.md).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.models import Engine, ModelConfig, qwen_moe
from triton_dist_tpu.ops.ep_a2a import (EP2DContext, create_ep_context,
                                        create_ep2d_context)
from triton_dist_tpu.ops.ll_a2a_2d import (hop_put_counts, ll_a2a_2d,
                                           record_dispatch_puts)
from triton_dist_tpu.ops.low_latency import wire_roundtrip
from triton_dist_tpu.parallel.mesh import MeshContext
from triton_dist_tpu.resilience import faults
from triton_dist_tpu.serving import ServingEngine

N_OUT, N_IN = 2, 4
N = N_OUT * N_IN
CFG = ModelConfig.tiny_moe(vocab_size=64, hidden_size=32,
                           num_hidden_layers=2, num_attention_heads=4,
                           num_key_value_heads=4, head_dim=8,
                           num_experts=8, num_experts_per_tok=2,
                           moe_intermediate_size=16)
PAGE = 8
PROMPTS = [[3, 5, 7], [11, 2]]
GEN = 4


@pytest.fixture(scope="module")
def hier_mesh():
    """The 2 (DCN) x 4 (ICI) hierarchy over all 8 devices."""
    return Mesh(np.array(jax.devices()).reshape(N_OUT, N_IN),
                ("dp", "tp"))


@pytest.fixture(scope="module")
def hier_ctx(hier_mesh):
    return MeshContext.from_mesh(hier_mesh)


def _skewed(params):
    """Every routed assignment onto expert 0/1/2 — all owned by node
    0's chips at 8 experts over 8 ranks (the ±pair router trick from
    tests/test_ep_serving.py): maximal cross-node imbalance."""
    p = jax.tree.map(lambda x: x, params)
    rng = np.random.RandomState(0)
    for lp in p["layers"]:
        d, e = lp["moe"]["router"].shape
        g = rng.randn(d).astype(np.float32)
        r = np.zeros((d, e), np.float32)
        r[:, 0] = g
        r[:, 1] = -g
        lp["moe"]["router"] = jnp.asarray(r)
    return p


# ---------------------------------------------------------------------------
# ll_a2a_2d: hop semantics vs the flat wire reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", ["int8", "float8_e4m3fn"])
def test_ll_a2a_2d_matches_flat_wire_reference(hier_mesh, hier_ctx,
                                               wire):
    """The 2-hop composition delivers EXACTLY the flat ll_a2a contract
    (out[g'] on rank m = x_{g'}[m], outer-major ranks) up to the
    second wire quantization — compared against a per-chunk
    double-``wire_roundtrip`` oracle, which IS the 2-hop numerics."""
    wire_dtype = jnp.dtype(wire)
    c, d = 6, 16
    rng = np.random.RandomState(1)
    x_all = rng.randn(N, N, c, d).astype(np.float32)  # [src][dst]

    got = jax.jit(jax.shard_map(
        lambda xs: ll_a2a_2d(xs, ctx=hier_ctx, outer_axis="dp",
                             inner_axis="tp", wire_dtype=wire_dtype),
        mesh=hier_mesh, in_specs=P(("dp", "tp"), None, None),
        out_specs=P(("dp", "tp"), None, None), check_vma=False))(
            jnp.asarray(x_all.reshape(N * N, c, d)))
    got = np.asarray(got).reshape(N, N, c, d)

    def wire2(v):
        v1 = wire_roundtrip(jnp.asarray(v), wire_dtype)
        return np.asarray(wire_roundtrip(v1, wire_dtype))

    want = np.stack([np.stack([wire2(x_all[g][m]) for g in range(N)])
                     for m in range(N)])
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("shape", [(1, 8), (8, 1)])
def test_ll_a2a_2d_kernel_hop_degenerate_hierarchy(shape):
    """Degenerate 1×n / n×1 hierarchies keep ONE non-trivial axis, so
    the Pallas kernel hop runs under interpret (the genuine-2D CPU
    case degrades to the identical-numerics xla hop — _resolve_impl).
    The non-trivial hop must match flat ll_a2a wire numerics with the
    trivial hop's extra wire_roundtrip applied."""
    from triton_dist_tpu.ops.low_latency import ll_a2a

    n_out, n_in = shape
    mesh = Mesh(np.array(jax.devices()).reshape(n_out, n_in),
                ("dp", "tp"))
    mctx = MeshContext.from_mesh(mesh)
    c, d = 4, 16
    rng = np.random.RandomState(2)
    x_all = rng.randn(N, N, c, d).astype(np.float32)
    xs = jnp.asarray(x_all.reshape(N * N, c, d))
    spec = P(("dp", "tp"), None, None)

    got = jax.jit(jax.shard_map(
        lambda v: ll_a2a_2d(v, ctx=mctx, outer_axis="dp",
                            inner_axis="tp"),
        mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False))(xs)
    flat_axis = "tp" if n_in > 1 else "dp"
    want = jax.jit(jax.shard_map(
        lambda v: wire_roundtrip(
            ll_a2a(v, ctx=mctx, axis=flat_axis), jnp.int8),
        mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False))(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# fwd_decode: ll2d vs the "ar" oracle (uniform + skew, int8 + fp8)
# ---------------------------------------------------------------------------

def _decode_out(hier_mesh, ctx2d, params, x, transport):
    axis = ("dp", "tp")
    specs = ep_moe.param_specs(axis)
    f = jax.jit(jax.shard_map(
        lambda p, v: ep_moe.fwd_decode(
            p, v, topk=CFG.num_experts_per_tok, axis=axis,
            transport=transport, ep_ctx=ctx2d),
        mesh=hier_mesh, in_specs=(specs, P(None, None)),
        out_specs=P(None, None), check_vma=False))
    return np.asarray(f(params, x))


@pytest.mark.parametrize("routing", ["uniform", "skew"])
@pytest.mark.parametrize("wire", ["int8", "float8_e4m3fn"])
def test_fwd_decode_ll2d_matches_ar(hier_mesh, hier_ctx, routing,
                                    wire):
    """The 2-hop dispatch reproduces the zero-communication "ar"
    oracle within the double-wire quantization budget, under uniform
    and all-to-one-node skewed routing."""
    ctx2d = create_ep2d_context(hier_ctx,
                                num_experts=CFG.num_experts,
                                topk=CFG.num_experts_per_tok,
                                outer_axis="dp", inner_axis="tp",
                                wire_dtype=jnp.dtype(wire))
    params = ep_moe.init(jax.random.PRNGKey(3), CFG)
    if routing == "skew":
        d, e = np.asarray(params["router"]).shape
        g = np.random.RandomState(4).randn(d).astype(np.float32)
        r = np.zeros((d, e), np.float32)
        r[:, 0] = g
        r[:, 1] = -g
        params = dict(params, router=jnp.asarray(r))
    x = jax.random.normal(jax.random.PRNGKey(5), (4, CFG.hidden_size),
                          jnp.float32)
    ar = _decode_out(hier_mesh, ctx2d, params, x, "ar")
    ll2d = _decode_out(hier_mesh, ctx2d, params, x, "ll2d")
    # fp8 e4m3 has 3 mantissa bits and the token crosses the wire
    # twice — same budget as test_ep_moe's double-quantization gate.
    tol = 1e-1 if wire == "float8_e4m3fn" else 2e-2
    np.testing.assert_allclose(ll2d, ar, rtol=tol, atol=tol)


def test_fwd_decode_ll2d_needs_2d_context(hier_mesh, hier_ctx):
    params = ep_moe.init(jax.random.PRNGKey(6), CFG)
    x = jnp.zeros((2, CFG.hidden_size), jnp.float32)
    with pytest.raises(ValueError, match="EP2DContext"):
        ep_moe.fwd_decode(params, x, topk=2, transport="ll2d",
                          ep_ctx=None)
    ctx2d = create_ep2d_context(hier_ctx, num_experts=8, topk=2,
                                outer_axis="dp", inner_axis="tp")
    with pytest.raises(ValueError, match="replica"):
        ep_moe.fwd_decode(params, x, topk=2, transport="ll2d",
                          ep_ctx=ctx2d,
                          replicas={"slot_expert": jnp.zeros((1,))})


# ---------------------------------------------------------------------------
# DCN put coalescing: ASSERTED from the trace-time ledger
# ---------------------------------------------------------------------------

def test_dcn_puts_counted_per_peer_node(hier_mesh, hier_ctx):
    """One dispatch issues n_out-1 DCN payload puts (peer NODES), not
    (n_out-1)·n_in (peer chips): the coalescing the tentpole claims,
    read off the put ledger of an actual dispatch trace."""
    ctx2d = create_ep2d_context(hier_ctx,
                                num_experts=CFG.num_experts,
                                topk=CFG.num_experts_per_tok,
                                outer_axis="dp", inner_axis="tp")
    params = ep_moe.init(jax.random.PRNGKey(7), CFG)
    x = jnp.zeros((4, CFG.hidden_size), jnp.float32)
    axis = ("dp", "tp")
    specs = ep_moe.param_specs(axis)
    with record_dispatch_puts() as led:
        jax.eval_shape(
            lambda p, v: jax.shard_map(
                lambda pp, vv: ep_moe.fwd_decode(
                    pp, vv, topk=CFG.num_experts_per_tok, axis=axis,
                    transport="ll2d", ep_ctx=ctx2d),
                mesh=hier_mesh, in_specs=(specs, P(None, None)),
                out_specs=P(None, None), check_vma=False)(p, v),
            params, x)
    # fwd_decode = dispatch + return hop: two ll_a2a_2d calls, each
    # one ICI + one DCN hop.
    dcn = [e for e in led if e["hop"] == "dcn"]
    ici = [e for e in led if e["hop"] == "ici"]
    assert len(dcn) == 2 and len(ici) == 2, led
    analytic = hop_put_counts(hier_ctx, outer_axis="dp",
                              inner_axis="tp")
    for e in dcn:
        assert e["payload_puts"] == N_OUT - 1 == analytic["dcn"]
        # The flat-ll DCN cost this replaces: one put per peer CHIP.
        assert analytic["flat_dcn"] == (N_OUT - 1) * N_IN
        assert e["payload_puts"] * N_IN == analytic["flat_dcn"]
    for e in ici:
        assert e["payload_puts"] == N_IN - 1 == analytic["ici"]


# ---------------------------------------------------------------------------
# per-hop fault containment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["ll2d_ici", "ll2d_dcn"])
def test_fault_containment_per_hop(hier_mesh, hier_ctx, op):
    """Dropping either hop fails THAT dispatch with the hop's own op
    name (scoped faults.on_op_call), and the next dispatch outside the
    plan succeeds — one lost dispatch, not a dead server."""
    ctx2d = create_ep2d_context(hier_ctx,
                                num_experts=CFG.num_experts,
                                topk=CFG.num_experts_per_tok,
                                outer_axis="dp", inner_axis="tp")
    params = ep_moe.init(jax.random.PRNGKey(8), CFG)
    x = jnp.ones((2, CFG.hidden_size), jnp.float32)

    def trace_once():
        return _decode_out(hier_mesh, ctx2d, params, x, "ll2d")

    with faults.inject(faults.get_plan("fail_kth_call", op=op, k=0)):
        with pytest.raises(faults.InjectedFault) as ei:
            trace_once()
        assert op in str(ei.value)   # the fault names the hop
    out = trace_once()               # the server survives the fault
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# serving: token exactness + observability + jit no-growth
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hier_engines(hier_mesh):
    base = qwen_moe.init_params(jax.random.PRNGKey(0), CFG)
    params = {"uniform": base, "skew": _skewed(base)}
    cache = {}

    def get(routing: str) -> Engine:
        if routing not in cache:
            cache[routing] = Engine(CFG, hier_mesh, mode="xla",
                                    max_len=32, model=qwen_moe,
                                    moe_impl="ep",
                                    ep_axis=("dp", "tp"),
                                    params=params[routing])
        return cache[routing]

    return get


@pytest.mark.parametrize("routing", ["uniform", "skew"])
def test_serving_ll2d_token_exact_and_observable(hier_engines,
                                                 routing):
    """Greedy decode through the 2-hop dispatch is TOKEN-EXACT vs the
    "ar" serve on the same hierarchical engine; the resolved transport
    is observable in stats; the decode dispatch never re-specializes;
    and the unset-knob default resolves to ll2d — the fallback is
    dead, not hidden."""
    eng = hier_engines(routing)
    want = ServingEngine(eng, num_slots=2, page=PAGE,
                         transport="ar").generate(
        PROMPTS, max_new_tokens=GEN)

    srv = ServingEngine(eng, num_slots=2, page=PAGE, transport="ll2d")
    got = srv.generate(PROMPTS, max_new_tokens=GEN)
    assert got == want
    assert srv.stats()["dispatch_transport"] == "ll2d"
    assert srv.decode_cache_size() <= 2   # PR-4 fixed-shape gate

    # transport unset -> "auto" -> untuned hierarchical mesh -> ll2d.
    auto = ServingEngine(eng, num_slots=2, page=PAGE)
    assert auto.generate(PROMPTS, max_new_tokens=GEN) == want
    assert auto.stats()["dispatch_transport"] == "ll2d"


def test_serving_ll2d_rejects_replicas(hier_engines):
    with pytest.raises(ValueError, match="replica"):
        ServingEngine(hier_engines("uniform"), num_slots=2, page=PAGE,
                      transport="ll2d", replica_slots=1)


# ---------------------------------------------------------------------------
# 2D-keyed tune round-trip
# ---------------------------------------------------------------------------

def test_tune_transport_2d_roundtrip(hier_mesh, hier_ctx, tmp_path,
                                     monkeypatch):
    """On a hierarchical mesh ``tune_transport`` sweeps ar vs ll2d,
    persists the winner under the hierarchy-shaped key, ``"auto"``
    resolution loads it back — and the 2D key can never collide with
    a flat-mesh key of the same total size."""
    from triton_dist_tpu import tune

    monkeypatch.setenv("TRITON_DIST_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(tune, "_CACHE", None)
    monkeypatch.setattr(tune, "_CACHE_PATH", None)

    ctx2d = create_ep2d_context(hier_ctx,
                                num_experts=CFG.num_experts,
                                topk=CFG.num_experts_per_tok,
                                outer_axis="dp", inner_axis="tp")
    params = ep_moe.init(jax.random.PRNGKey(9), CFG)
    kw = dict(ctx=ctx2d, batch=2, hidden=CFG.hidden_size,
              dtype=jnp.float32, topk=CFG.num_experts_per_tok)
    # Untuned hierarchical mesh: ll2d, NOT the old "ar" fallback.
    assert ep_moe.resolve_transport("auto", **kw) == "ll2d"
    winner = ep_moe.tune_transport(hier_mesh, params, ctx2d, batch=2,
                                   topk=CFG.num_experts_per_tok,
                                   reps=1)
    assert winner in ("ar", "ll2d")
    assert ep_moe.resolve_transport("auto", **kw) == winner
    # cache hit (no re-timing)
    assert ep_moe.tune_transport(
        hier_mesh, params, ctx2d, batch=2,
        topk=CFG.num_experts_per_tok) == winner
    # forced store wins over timing noise
    forced = "ar" if winner == "ll2d" else "ll2d"
    tune.store_autotune_data(
        ep_moe._transport_key(ctx2d, batch=2, hidden=CFG.hidden_size,
                              dtype=np.dtype("float32"),
                              topk=CFG.num_experts_per_tok),
        {"transport": forced})
    assert ep_moe.resolve_transport("auto", **kw) == forced
    # Hierarchy shape is IN the key: flat and 2D contexts over the
    # same 8 devices key differently.
    flat = create_ep_context(hier_ctx, num_experts=CFG.num_experts,
                             topk=CFG.num_experts_per_tok, axis="tp")
    k2d = ep_moe._transport_key(ctx2d, batch=2,
                                hidden=CFG.hidden_size,
                                dtype=jnp.float32,
                                topk=CFG.num_experts_per_tok)
    kflat = ep_moe._transport_key(flat, batch=2,
                                  hidden=CFG.hidden_size,
                                  dtype=jnp.float32,
                                  topk=CFG.num_experts_per_tok)
    assert k2d != kflat


# ---------------------------------------------------------------------------
# megakernel expert counts with chunked prefill (PR 6 known limit)
# ---------------------------------------------------------------------------

def test_mk_expert_counts_with_chunked_prefill():
    """The ``moe_counts`` arena region is now engine-wide (same
    offset AND rows in every builder sharing the arena), so
    ``expert_counts()`` stays correct — monotonic, consistent with
    the decode telemetry — with chunked prefill active. Under the old
    layout the chunk builder's activation tail aliased the decode
    builder's counters."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    cfg = ModelConfig.tiny_moe(vocab_size=64, hidden_size=32,
                               num_hidden_layers=2,
                               num_attention_heads=4,
                               num_key_value_heads=4, head_dim=8,
                               num_experts=4, num_experts_per_tok=2,
                               moe_intermediate_size=32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    buckets = (4, 8)
    mk = MegaKernelEngine(cfg, mesh, batch=2, max_len=64, tile_w=16,
                          t_tile=16, paged=True, page=16, num_pages=9,
                          prefill_buckets=buckets)
    # Every builder claims the SAME counter span.
    dec_reg = mk.builder.schema.region("moe_counts")
    for cb in mk.chunk_builders.values():
        reg = cb.schema.region("moe_counts")
        assert (reg.offset, reg.rows) == (dec_reg.offset, dec_reg.rows)
    assert dec_reg.rows >= max(buckets)

    srv = ServingEngine(mk, prefill_buckets=buckets)
    prompts = [[int(t) for t in
                np.random.RandomState(s).randint(1, 64, 7)]
               for s in (0, 1)]
    c0 = mk.expert_counts()
    srv.generate(prompts, max_new_tokens=2)
    c1 = mk.expert_counts()
    # Counters accumulated routed assignments (prefill chunks AND
    # decode steps) and stayed monotonic + bounded by the routed-row
    # budget: rows * topk * n_layers per launch.
    assert (c1 >= c0).all() and c1.sum() > c0.sum()
    assert c1.sum() % (cfg.num_experts_per_tok
                       * cfg.num_hidden_layers) == 0
    srv.generate(prompts[:1], max_new_tokens=1)     # a second launch set
    c2 = mk.expert_counts()
    assert (c2 >= c1).all() and c2.sum() > c1.sum()
