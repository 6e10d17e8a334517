"""EP serving battery: low-latency decode dispatch + hot-expert
rebalancing (ISSUE 6 / ROADMAP open item 2).

Covers the decode ``transport`` knob (ragged exact-splits vs the
count-free wire-quantized ``ll`` path vs the tune-resolved ``auto``)
under uniform AND adversarially skewed routing, on both serving
backends; hot-expert replication staying token-exact; the on-device
expert-load telemetry; and the dynamic scoreboard's expert-load claim
priority.

Adversarial skew construction: the router has no bias, so "all tokens
to one expert" is forged with a ±pair — column 0 = +g, column 1 = -g,
the rest exactly zero. Every token's top-1 lands on expert 0 or 1 and
the tied-at-zero second pick deterministically on expert 2 (top_k
breaks ties by index) — ALL routed assignments hit ep rank 0's expert
shard (experts 0-3 at TP=2), the hot-rank regime the rebalancer must
react to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.models import Engine, ModelConfig, qwen_moe
from triton_dist_tpu.serving import ServingEngine

TP = 2
CFG = ModelConfig.tiny_moe(num_experts=8)
MAX_LEN = 32
PAGE = 8
VOCAB = CFG.vocab_size
PROMPTS = [[3, 5, 7], [11, 2]]
GEN = 3


def _skewed(params):
    """Force every routed assignment onto ep rank 0's experts (the
    ±pair trick, module docstring): top-1 on expert 0 or 1, the tied
    second pick on expert 2."""
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


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:TP]), ("tp",))


@pytest.fixture(scope="module")
def engines(mesh):
    """Lazily-built (routing, transport) -> Engine cache: engine
    construction compiles the fused ll kernels, so tests share them."""
    base = qwen_moe.init_params(jax.random.PRNGKey(0), CFG)
    params = {"uniform": base, "skew": _skewed(base)}
    cache = {}

    def get(routing: str, transport: str) -> Engine:
        key = (routing, transport)
        if key not in cache:
            cache[key] = Engine(CFG, mesh, mode="xla", max_len=MAX_LEN,
                                model=qwen_moe, moe_impl="ep",
                                ep_transport=transport,
                                params=params[routing])
        return cache[key]

    return get


def _solo(eng, prompt, gen):
    ids = jnp.asarray(np.tile(np.asarray([prompt], np.int32), (TP, 1)))
    return np.asarray(eng.serve(ids, gen_len=gen))[0].tolist()


# ---------------------------------------------------------------------------
# layer engine: transport × routing token-exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("routing", ["uniform", "skew"])
@pytest.mark.parametrize("transport", ["ragged", "ll", "auto"])
def test_layer_transport_token_exact(engines, routing, transport):
    """Continuous-batching decode through each transport matches the
    solo Engine.serve baseline per request, uniform and skewed.
    ``auto`` (untuned cache) resolves to ``ll`` and shares its engine —
    the resolution itself is what's under test."""
    eng = engines(routing, "ll" if transport == "auto" else transport)
    want = [_solo(eng, p, GEN) for p in PROMPTS]
    srv = ServingEngine(eng, num_slots=2, page=PAGE,
                        transport=transport)
    got = srv.generate(PROMPTS, max_new_tokens=GEN)
    assert got == want
    st = srv.stats()
    assert st["dispatch_transport"] == (
        "ll" if transport == "auto" else transport)
    # On-device telemetry: every decode dispatch routed
    # num_slots * topk * n_layers assignments.
    per_step = 2 * CFG.num_experts_per_tok * CFG.num_hidden_layers
    assert sum(st["expert_totals"]) == (
        st["decode_dispatches"] * per_step)
    assert srv.decode_cache_size() <= 2  # PR-4 fixed-shape gate


def test_skew_concentrates_expert_load(engines):
    """The ±pair router sends every top-1 to experts {0, 1}: the load
    EWMA's argmax must sit there, and trace() must record per-step
    histograms whose hot mass dominates."""
    eng = engines("skew", "ll")
    srv = ServingEngine(eng, num_slots=2, page=PAGE)
    with srv.trace("ep-load"):
        srv.generate(PROMPTS, max_new_tokens=GEN)
    st = srv.stats()
    load = np.asarray(st["expert_load"])
    assert int(np.argmax(load)) in (0, 1, 2)
    # EVERY routed assignment hits rank 0's expert shard (0-3).
    tot = np.asarray(st["expert_totals"], np.float64)
    assert tot[:4].sum() == tot.sum() and tot.sum() > 0
    assert len(srv.expert_hist) == st["decode_dispatches"]
    assert all(h.sum() > 0 for h in srv.expert_hist)


def test_ll_replication_token_exact(engines):
    """Hot-expert replication under skew: the rebalancer installs a
    replica on the other rank, routing splits to it (data, no
    recompile), and greedy tokens stay EXACTLY those of the
    replica-free run."""
    eng = engines("skew", "ll")
    plain = ServingEngine(eng, num_slots=2, page=PAGE)
    want = plain.generate(PROMPTS, max_new_tokens=GEN)

    srv = ServingEngine(eng, num_slots=2, page=PAGE, replica_slots=1,
                        rebalance_every=2, hot_expert_factor=1.2)
    srv.generate([[9, 1], [4]], max_new_tokens=3)   # warm the EWMA
    warm = srv.decode_cache_size()
    got = srv.generate(PROMPTS, max_new_tokens=GEN)
    st = srv.stats()
    assert st["replicated_experts"], "skewed load never replicated"
    e, rank = next(iter(st["replicated_experts"].items()))
    assert e in (0, 1, 2) and rank == 1  # hot expert copied off rank 0
    assert got == want
    assert srv.decode_cache_size() == warm, (
        "replica refresh re-specialized the decode dispatch")


def test_replication_requires_ll(engines):
    with pytest.raises(ValueError, match="transport='ll'"):
        ServingEngine(engines("uniform", "ragged"), num_slots=2,
                      page=PAGE, replica_slots=1)


def test_transport_validation(engines):
    with pytest.raises(ValueError, match="not in"):
        ServingEngine(engines("uniform", "ll"), num_slots=2, page=PAGE,
                      transport="bogus")


# ---------------------------------------------------------------------------
# transport autotune store
# ---------------------------------------------------------------------------

def test_auto_transport_tune_roundtrip(mesh, tmp_path, monkeypatch):
    """tune_transport sweeps ragged vs ll, persists a winner, and
    ``transport="auto"`` resolution loads it back."""
    from triton_dist_tpu import tune
    from triton_dist_tpu.ops.ep_a2a import create_ep_context
    from triton_dist_tpu.parallel.mesh import MeshContext

    monkeypatch.setenv("TRITON_DIST_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(tune, "_CACHE", None)
    monkeypatch.setattr(tune, "_CACHE_PATH", None)

    mctx = MeshContext.from_mesh(mesh)
    ctx = create_ep_context(mctx, num_experts=CFG.num_experts,
                            topk=CFG.num_experts_per_tok, axis="tp")
    params = ep_moe.init(jax.random.PRNGKey(1), CFG)
    kw = dict(ctx=ctx, batch=2, hidden=CFG.hidden_size,
              dtype=jnp.float32, topk=CFG.num_experts_per_tok)
    assert ep_moe.resolve_transport("auto", **kw) == "ll"  # untuned
    winner = ep_moe.tune_transport(mesh, params, ctx, batch=2,
                                   topk=CFG.num_experts_per_tok,
                                   reps=1)
    assert winner in ("ragged", "ll")
    assert ep_moe.resolve_transport("auto", **kw) == winner
    # second call is a cache hit (no re-timing)
    assert ep_moe.tune_transport(mesh, params, ctx, batch=2,
                                 topk=CFG.num_experts_per_tok) == winner
    # resolution honors whatever the store says, independent of this
    # host's timing noise (jnp.float32 and np.dtype must key alike).
    forced = "ragged" if winner == "ll" else "ll"
    tune.store_autotune_data(
        ep_moe._transport_key(ctx, batch=2, hidden=CFG.hidden_size,
                              dtype=np.dtype("float32"),
                              topk=CFG.num_experts_per_tok),
        {"transport": forced})
    assert ep_moe.resolve_transport("auto", **kw) == forced


# ---------------------------------------------------------------------------
# megakernel engine: skewed routing + expert-load claim priority
# ---------------------------------------------------------------------------

MK_GEN = 2     # the prefill lane's token and one decode step; a step is ~1 s


def _mk_engine(cfg, params=None, **kw):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))
    return MegaKernelEngine(cfg, mesh1, batch=2, max_len=16, tile_w=16,
                            t_tile=16, params=params, **kw)


@pytest.fixture(scope="module")
def mk_cfg_params():
    # Four experts hold the skew (top-1 on expert 0 or 1, the tie on 2);
    # an interpreted step costs in proportion to its queue, and every
    # expert and every head adds its tasks to it.
    cfg = ModelConfig.tiny_moe(vocab_size=128, num_experts=4,
                               num_attention_heads=4,
                               num_key_value_heads=2)
    params = _skewed(qwen_moe.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


@pytest.fixture(scope="module")
def mk_solo_tokens(mk_cfg_params):
    """Each prompt alone on the static-schedule engine: the oracle of the
    serving tests below. One engine serves both prompts: a dense cache's
    stale rows are masked beyond the length."""
    cfg, params = mk_cfg_params
    e = _mk_engine(cfg, params=params)

    def solo(prompt):
        tiled = jnp.asarray(np.tile(np.asarray([prompt], np.int32),
                                    (2, 1)))
        seed = e.prefill_chain(tiled)
        return np.asarray(e.generate(
            seed, steps=MK_GEN, start_pos=len(prompt) - 1))[0].tolist()

    return [solo(p) for p in PROMPTS]


@pytest.mark.parametrize("transport", ["ragged", "ll"])
def test_megakernel_skew_serving_token_exact(mk_cfg_params, mk_solo_tokens,
                                             transport):
    """Megakernel serving under adversarial skew: the transport knob is
    accepted (experts are served in-kernel, TP regime — stats say so),
    tokens match solo runs, and the in-kernel router counters surface
    the hot experts."""
    cfg, params = mk_cfg_params
    mk = _mk_engine(cfg, params=params)
    srv = ServingEngine(mk, transport=transport)
    h = [srv.submit(p, max_new_tokens=MK_GEN) for p in PROMPTS]
    srv.run()
    assert [x.tokens for x in h] == mk_solo_tokens
    st = srv.stats()
    assert st["dispatch_transport"] == "in-kernel-tp"
    tot = np.asarray(st["expert_totals"], np.float64)
    assert tot.sum() > 0 and tot[:3].sum() == tot.sum()


def test_megakernel_dynamic_rebalance_token_exact(mk_cfg_params,
                                                  mk_solo_tokens):
    """schedule="dynamic" + rebalance: the serving loop feeds the load
    EWMA into the scoreboard (claim tables rebuilt mid-serve) and the
    tokens still match the static-schedule solo baseline."""
    cfg, params = mk_cfg_params
    mk = _mk_engine(cfg, params=params, schedule="dynamic")
    srv = ServingEngine(mk, rebalance_every=2, hot_expert_factor=0.0)
    h = [srv.submit(p, max_new_tokens=MK_GEN) for p in PROMPTS]
    srv.run()
    assert [x.tokens for x in h] == mk_solo_tokens
    assert srv._mk_load_sig is not None, "rebalance never applied"
    assert mk.builder.expert_load is not None


def test_claim_order_shifts_under_skew():
    """graph.comm_priority expert_load: a hot expert's FFN chain is
    claimed measurably earlier than under uniform load, and the
    schedule stays a permutation of the task set (fairness)."""
    from triton_dist_tpu.megakernel.builder import ModelBuilder

    cfg = ModelConfig.tiny_moe(vocab_size=128, num_experts=8)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kw = dict(batch=2, max_len=16, tile_w=16, t_tile=16,
              schedule="dynamic")
    hot = 7
    load = [1.0] * cfg.num_experts
    load[hot] = 50.0
    b_uni = ModelBuilder(cfg, mesh1, **kw)
    b_hot = ModelBuilder(cfg, mesh1, expert_load=load, **kw)

    def check(b):
        claims = b.claims.reshape(-1)
        real = claims[claims >= 0]
        assert sorted(real.tolist()) == list(range(len(b.graph.tasks)))
        pos = {int(t): i for i, t in enumerate(claims)}
        return np.mean([pos[t.task_id] for t in b.graph.tasks
                        if t.expert == hot])

    mean_uni, mean_hot = check(b_uni), check(b_hot)
    assert mean_hot < mean_uni, (
        f"hot-expert chain not promoted: {mean_hot} !< {mean_uni}")
    # reprioritize back to uniform restores the original order
    b_hot.reprioritize(None)
    assert np.array_equal(b_hot.claims, b_uni.claims)


def test_mk_expert_counts_exact(mk_cfg_params):
    """The in-kernel router counters count exactly
    batch * topk * n_layers selections per decode step."""
    cfg, params = mk_cfg_params
    mk = _mk_engine(cfg, params=params)
    mk.decode_step(jnp.asarray([1, 2], jnp.int32), 0)
    c1 = mk.expert_counts()
    mk.decode_step(jnp.asarray([3, 4], jnp.int32), 1)
    c2 = mk.expert_counts()
    per_step = 2 * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert c1.sum() == per_step
    assert (c2 - c1).sum() == per_step
    assert (c2 >= c1).all()
