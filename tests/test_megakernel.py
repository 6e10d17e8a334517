"""Megakernel: one persistent kernel per device must reproduce the
layer-by-layer decode step (reference acceptance: megakernel output vs
triton_dist layer path, ``mega_triton_kernel/test/models/``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
import jax.numpy as jnp

from triton_dist_tpu.layers import tp_attn, tp_mlp
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.megakernel import ModelBuilder, schedule
from triton_dist_tpu.megakernel.graph import Graph
from triton_dist_tpu.megakernel.task import TaskType
from triton_dist_tpu.models import dense
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.utils.testing import spmd, assert_allclose

CFG = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                       intermediate_size=32, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=8)
B, MAXLEN, NTP = 2, 32, 2


def test_scheduler_native():
    """C++ scheduler: topological order + cycle detection."""
    s = schedule(4, [0, 1, 2], [1, 2, 3], num_cores=1)
    assert list(s["order"]) == [0, 1, 2, 3]
    with pytest.raises(ValueError, match="cycle"):
        schedule(2, [0, 1], [1, 0], num_cores=1)
    # Multi-core packing keeps deps cross-core.
    s = schedule(4, [0, 1], [2, 3], num_cores=2)
    assert sorted(s["order"]) == [0, 1, 2, 3]


def test_scheduler_mc_merged_order_safety():
    """tdt_schedule_mc: every task's merged index exceeds all its
    predecessors' (the no-deadlock-under-sequential guarantee), and
    cross-core edges carry wait/signal entries."""
    from triton_dist_tpu.megakernel.scheduler import schedule_mc

    # Diamond + chain: 0→1, 0→2, 1→3, 2→3, 3→4.
    s = schedule_mc(5, [0, 0, 1, 2, 3], [1, 2, 3, 3, 4], num_cores=2)
    q = s["queue"]
    merged = {}
    for qi in range(q.shape[0]):
        for c in range(2):
            t = q[qi, c]
            if t >= 0:
                merged[int(t)] = qi * 2 + c
    assert sorted(merged) == [0, 1, 2, 3, 4]
    for a, b2 in [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4)]:
        assert merged[b2] > merged[a]
    # waits == signals overall, and every cross-core edge has both.
    assert s["n_edges"] == len(s["wait_edges"]) == len(s["sig_edges"])
    with pytest.raises(ValueError, match="cycle"):
        schedule_mc(2, [0, 1], [1, 0], num_cores=2)


def test_scheduler_mc_pinning_and_cost():
    from triton_dist_tpu.megakernel.scheduler import schedule_mc

    # Independent tasks; pin task 2 to core 0; heavy task 3.
    s = schedule_mc(4, [], [], num_cores=2, strategy="cost_lpt",
                    task_cost=[1, 1, 1, 100], pin_core=[-1, -1, 0, -1])
    q = s["queue"]
    core = {}
    for qi in range(q.shape[0]):
        for c in range(2):
            if q[qi, c] >= 0:
                core[int(q[qi, c])] = c
    assert core[2] == 0
    # LPT actually balances: after the heavy task lands on a core, the
    # remaining 1-cost tasks all go to the other core.
    heavy_core = core[3]
    light = [core[t_] for t_ in (0, 1) ] + [core[2]]
    assert sum(1 for c in light if c != heavy_core) >= 2


def test_graph_dataflow_deps():
    g = Graph()
    t0 = g.add(TaskType.RMSNORM, (0, 0, 10, 1), reads=[(0, 2)],
               writes=[(10, 2)])
    t1 = g.add(TaskType.LINEAR, (10, 0, 20, 1, 1, 0), reads=[(10, 2)],
               writes=[(20, 2)])
    t2 = g.add(TaskType.ADD, (0, 20, 10, 1), reads=[(0, 2), (20, 2)],
               writes=[(10, 2)])  # WAR on t1's read of 10
    assert t1.deps == [t0.task_id]
    assert t0.task_id in t2.deps or t1.task_id in t2.deps


@pytest.fixture(scope="module")
def tp2_mesh():
    return Mesh(np.array(jax.devices()[:NTP]), ("tp",))


@pytest.mark.parametrize("cores,strategy,schedule", [
    (1, "round_robin", "static"),
    (2, "round_robin", "static"),
    (2, "cost_lpt", "static"),
    (1, "round_robin", "dynamic"),
    (2, "cost_lpt", "dynamic"),
])
def test_megakernel_decode_vs_layers(tp2_mesh, cores, strategy,
                                     schedule):
    mesh = tp2_mesh
    mb = ModelBuilder(CFG, mesh, batch=B, max_len=MAXLEN, tile_w=16,
                      t_tile=16, num_cores=cores, strategy=strategy,
                      schedule=schedule)
    if schedule == "dynamic":
        # The claim list covers every task exactly once, and with
        # multiple cores the cross-core claim edges really exist.
        claimed = sorted(int(t) for t in mb.claims.reshape(-1)
                         if t >= 0)
        assert claimed == list(range(len(mb.graph.tasks)))
        if cores > 1:
            assert mb.n_edges > 0
    if schedule == "static" and cores > 1:
        # The padded schedule really uses both queues and emits a
        # scoreboard.
        assert (mb.task_types != int(TaskType.NOOP)).any(axis=1).all()
        assert mb.n_edges > 0
        assert (np.asarray(mb.task_types)[:, 1]
                != int(TaskType.NOOP)).any()
    params = dense.init_params(jax.random.PRNGKey(0), CFG)
    specs = dense.param_specs(CFG)

    kv_loc = CFG.num_key_value_heads // NTP
    cache_shape = (CFG.num_hidden_layers, B, MAXLEN,
                   CFG.num_key_value_heads, CFG.head_dim)
    k_cache = jax.random.normal(jax.random.PRNGKey(1), cache_shape) * 0.3
    v_cache = jax.random.normal(jax.random.PRNGKey(2), cache_shape) * 0.3
    tokens = jnp.asarray([3, 17], jnp.int32)
    pos = jnp.asarray(5, jnp.int32)
    kvspec = P(None, None, None, "tp", None)

    # --- megakernel path (embedding + stack + LM head in-kernel) ---
    pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
    arena = pack(params)
    step = spmd(mesh, mb.step_fn(),
                (P("tp", None), kvspec, kvspec, P(None), P()),
                (P(None, "tp"), P("tp", None), kvspec, kvspec))
    logits, arena2, kc2, vc2 = step(arena, k_cache, v_cache, tokens, pos)

    # --- layer-by-layer oracle (xla mode, proven against dense) ---
    def oracle(p, tok, kc, vc):
        h = p["embed"][tok]
        new_k, new_v = kc, vc
        for li, lp in enumerate(p["layers"]):
            t = rms_norm(h, lp["ln_attn"], CFG.rms_norm_eps)
            ao, (lk, lv) = tp_attn.fwd_decode(
                lp["attn"], t, CFG, new_k[li], new_v[li], pos, mode="xla")
            new_k = new_k.at[li].set(lk)
            new_v = new_v.at[li].set(lv)
            h = h + ao
            t = rms_norm(h, lp["ln_mlp"], CFG.rms_norm_eps)
            h = h + tp_mlp.fwd(lp["mlp"], t, mode="xla_ar")
        h = rms_norm(h, p["ln_f"], CFG.rms_norm_eps)
        logits_loc = h @ p["lm_head"].T
        return (jax.lax.all_gather(logits_loc, "tp", axis=1, tiled=True),
                new_k, new_v)

    of = spmd(mesh, oracle, (specs, P(None), kvspec, kvspec),
              (P(None, None), kvspec, kvspec))
    want_logits, want_k, want_v = of(params, tokens, k_cache, v_cache)

    assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-3)
    # Cache slot 5 must hold the new roped+normed K and the raw V.
    assert_allclose(np.asarray(kc2)[:, :, 5], np.asarray(want_k)[:, :, 5],
                    rtol=2e-3, atol=2e-3)
    assert_allclose(np.asarray(vc2)[:, :, 5], np.asarray(want_v)[:, :, 5],
                    rtol=2e-3, atol=2e-3)
    # Untouched slots unchanged.
    assert_allclose(np.asarray(kc2)[:, :, :5], np.asarray(k_cache)[:, :, :5])



def _layer_engine_greedy(engine, cfg, seed_tok, steps):
    """Greedy decode chain through the layer Engine from an empty cache
    (the megakernel tests' shared oracle)."""
    from triton_dist_tpu.models.kv_cache import KVCache

    cache = KVCache.empty(cfg.num_hidden_layers, seed_tok.shape[0],
                          MAXLEN, cfg.num_key_value_heads, cfg.head_dim)
    tok = seed_tok
    ref = []
    for _ in range(steps):
        logits, cache = engine._decode(engine.params, tok, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        ref.append(np.asarray(tok))
    return np.stack(ref, axis=1)


def test_megakernel_engine_generate(tp2_mesh):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    eng = MegaKernelEngine(CFG, tp2_mesh, batch=B, max_len=MAXLEN,
                           tile_w=16, t_tile=16, seed=4,
                           keep_params=True)
    toks = np.asarray(eng.generate(jnp.zeros((B,), jnp.int32), steps=4))
    assert toks.shape == (B, 4)
    assert np.isfinite(toks).all()

    # Oracle: same params through the layer-path Engine decode chain
    # (a decode at position 0 on an empty cache == the seed prefill).
    from triton_dist_tpu.models import Engine
    params = jax.tree.map(np.asarray, eng.params)
    e2 = Engine(CFG, tp2_mesh, mode="xla", max_len=MAXLEN, params=params)
    ref = _layer_engine_greedy(e2, CFG, jnp.zeros((B,), jnp.int32), 4)
    np.testing.assert_array_equal(toks, ref)


def test_megakernel_batched_prefill(tp2_mesh):
    """One batched-prefill launch == the token-by-token decode chain
    (logits at the last position AND the whole written cache)."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    S = 4
    eng = MegaKernelEngine(CFG, tp2_mesh, batch=B, max_len=MAXLEN,
                           tile_w=16, t_tile=16, seed=7,
                           keep_params=True, prefill_seq=S)
    prompts = jnp.asarray([[3, 9, 1, 12], [5, 0, 7, 2]], jnp.int32)
    logits = np.asarray(eng.prefill(prompts))
    kc_pref = np.asarray(eng.k_cache)
    vc_pref = np.asarray(eng.v_cache)

    # Oracle: a second engine feeding the same prompt token-by-token.
    eng2 = MegaKernelEngine(CFG, tp2_mesh, batch=B, max_len=MAXLEN,
                            tile_w=16, t_tile=16, seed=7,
                            keep_params=True)
    for pos in range(S - 1):
        eng2.decode_step(prompts[:, pos], pos)
    want = np.asarray(eng2.decode_step(prompts[:, -1], S - 1))

    np.testing.assert_allclose(logits, want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(kc_pref[:, :, :S],
                               np.asarray(eng2.k_cache)[:, :, :S],
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(vc_pref[:, :, :S],
                               np.asarray(eng2.v_cache)[:, :, :S],
                               rtol=2e-3, atol=2e-3)

    # Decode continues from the batched prefill seamlessly.
    nxt = jnp.argmax(jnp.asarray(logits), -1).astype(jnp.int32)
    l2 = np.asarray(eng.decode_step(nxt, S))
    nxt2 = jnp.argmax(jnp.asarray(want), -1).astype(jnp.int32)
    w2 = np.asarray(eng2.decode_step(nxt2, S))
    np.testing.assert_allclose(l2, w2, rtol=2e-3, atol=2e-3)


def test_megakernel_paged_vs_dense(tp2_mesh):
    """Paged KV (pool + block table) must reproduce the dense-cache
    engine exactly: batched prefill, then decode steps, including a
    NON-identity block table (pages physically shuffled in the pool)."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    S = 4
    kw = dict(batch=B, max_len=MAXLEN, tile_w=16, t_tile=8, seed=9,
              keep_params=True, prefill_seq=S)
    dense_eng = MegaKernelEngine(CFG, tp2_mesh, **kw)
    paged_eng = MegaKernelEngine(CFG, tp2_mesh, paged=True, page=8,
                                 **kw)
    p_max = paged_eng.builder.p_max
    assert p_max == MAXLEN // 8

    # Scramble the pool: reverse the identity table (still a bijection).
    n_slots = B * p_max
    paged_eng.block_table = jnp.asarray(
        np.arange(n_slots)[::-1].copy(), jnp.int32)

    prompts = jnp.asarray([[3, 9, 1, 12], [5, 0, 7, 2]], jnp.int32)
    lp = np.asarray(paged_eng.prefill(prompts))
    ld = np.asarray(dense_eng.prefill(prompts))
    np.testing.assert_allclose(lp, ld, rtol=2e-3, atol=2e-3)

    tok = jnp.argmax(jnp.asarray(ld), -1).astype(jnp.int32)
    for i in range(6):  # positions 4..9: writes cross into page 1 at 8
        l2p = np.asarray(paged_eng.decode_step(tok, S + i))
        l2d = np.asarray(dense_eng.decode_step(tok, S + i))
        np.testing.assert_allclose(l2p, l2d, rtol=2e-3, atol=2e-3)
        tok = jnp.argmax(jnp.asarray(l2d), -1).astype(jnp.int32)


def test_megakernel_moe_decode_vs_layers(tp2_mesh):
    """MoE megakernel: in-kernel router + all-expert swiglu + weighted
    combine must match the layer oracle (tp_moe.fwd_ar — the same
    all-expert small-batch math)."""
    from triton_dist_tpu.layers import tp_moe
    from triton_dist_tpu.models import qwen_moe

    mcfg = ModelConfig.tiny_moe(vocab_size=64, hidden_size=32,
                                num_hidden_layers=2,
                                num_attention_heads=4,
                                num_key_value_heads=2, head_dim=8,
                                num_experts=4, num_experts_per_tok=2,
                                moe_intermediate_size=32)
    mesh = tp2_mesh
    mb = ModelBuilder(mcfg, mesh, batch=B, max_len=MAXLEN, tile_w=16,
                      t_tile=16)
    assert mb.moe and (mb.task_types == int(TaskType.MOE_WEIGHTS)).sum()
    params = qwen_moe.init_params(jax.random.PRNGKey(3), mcfg)
    specs = qwen_moe.param_specs(mcfg, moe_impl="tp")

    cache_shape = (mcfg.num_hidden_layers, B, MAXLEN,
                   mcfg.num_key_value_heads, mcfg.head_dim)
    k_cache = jax.random.normal(jax.random.PRNGKey(4), cache_shape) * 0.3
    v_cache = jax.random.normal(jax.random.PRNGKey(5), cache_shape) * 0.3
    tokens = jnp.asarray([9, 41], jnp.int32)
    pos = jnp.asarray(5, jnp.int32)
    kvspec = P(None, None, None, "tp", None)

    pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
    arena = pack(params)
    step = spmd(mesh, mb.step_fn(),
                (P("tp", None), kvspec, kvspec, P(None), P()),
                (P(None, "tp"), P("tp", None), kvspec, kvspec))
    logits, _, _, _ = step(arena, k_cache, v_cache, tokens, pos)

    def oracle(p, tok, kc, vc):
        h = p["embed"][tok]
        new_k, new_v = kc, vc
        for li, lp in enumerate(p["layers"]):
            t = rms_norm(h, lp["ln_attn"], mcfg.rms_norm_eps)
            ao, (lk, lv) = tp_attn.fwd_decode(
                lp["attn"], t, mcfg, new_k[li], new_v[li], pos,
                mode="xla")
            new_k = new_k.at[li].set(lk)
            new_v = new_v.at[li].set(lv)
            h = h + ao
            t = rms_norm(h, lp["ln_mlp"], mcfg.rms_norm_eps)
            h = h + tp_moe.fwd_ar(lp["moe"], t,
                                  topk=mcfg.num_experts_per_tok,
                                  num_experts=mcfg.num_experts,
                                  norm_topk_prob=mcfg.norm_topk_prob)
        h = rms_norm(h, p["ln_f"], mcfg.rms_norm_eps)
        logits_loc = h @ p["lm_head"].T
        return jax.lax.all_gather(logits_loc, "tp", axis=1, tiled=True)

    of = spmd(mesh, oracle, (specs, P(None), kvspec, kvspec),
              P(None, None))
    want = of(params, tokens, k_cache, v_cache)
    assert_allclose(logits, want, rtol=2e-3, atol=2e-3)


def test_megakernel_profile_slots(tp2_mesh):
    """profile=True: the step emits one (task_type, arg0) row per queue
    slot; core_activity computes the per-core busy fraction (the
    reference's SM-activity metric) and the rows export to Perfetto."""
    mesh = tp2_mesh
    mb = ModelBuilder(CFG, mesh, batch=B, max_len=MAXLEN, tile_w=16,
                      t_tile=16, num_cores=2, strategy="cost_lpt",
                      profile=True)
    params = dense.init_params(jax.random.PRNGKey(0), CFG)
    specs = dense.param_specs(CFG)
    cache_shape = (CFG.num_hidden_layers, B, MAXLEN,
                   CFG.num_key_value_heads, CFG.head_dim)
    k_cache = jnp.zeros(cache_shape)
    v_cache = jnp.zeros(cache_shape)
    kvspec = P(None, None, None, "tp", None)

    pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
    arena = pack(params)
    step = spmd(mesh, mb.step_fn(),
                (P("tp", None), kvspec, kvspec, P(None), P()),
                (P(None, "tp"), P("tp", None), kvspec, kvspec,
                 P(None, None)))
    logits, _, _, _, prof = step(arena, k_cache, v_cache,
                                 jnp.asarray([1, 2], jnp.int32),
                                 jnp.asarray(0, jnp.int32))
    prof = np.asarray(prof)
    assert prof.shape == (mb.qlen * 2, 2)
    # Every real task type in the schedule appears in the log
    # (tags are task_type + 1 — the exporter's (0,0) unused-slot
    # sentinel must never collide with RMSNORM=0 rows).
    logged = set(prof[:, 0].tolist())
    for tt in (TaskType.LINEAR, TaskType.RMSNORM, TaskType.ALLREDUCE):
        assert int(tt) + 1 in logged
    act = mb.core_activity(prof)
    assert act.shape == (2,) and (act > 0).all() and (act <= 1).all()

    # The slot log is Perfetto-exportable via the standard viewer.
    import tempfile, os, json
    from triton_dist_tpu.profiler import export_to_perfetto_trace
    with tempfile.TemporaryDirectory() as td:
        path = export_to_perfetto_trace(
            prof, os.path.join(td, "mk.json"),
            tag_names={int(t) + 1: t.name for t in TaskType})
        names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert "LINEAR" in names


def test_megakernel_moe_paged_compose(tp2_mesh):
    """MoE task graph composes with the paged-KV cache: the paged
    engine's prefill+decode logits must MATCH the dense-cache MoE
    engine on identical params (the paged_vs_dense oracle pattern)."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models import qwen_moe

    mcfg = ModelConfig.tiny_moe(vocab_size=64, hidden_size=32,
                                num_hidden_layers=2,
                                num_attention_heads=4,
                                num_key_value_heads=2, head_dim=8,
                                num_experts=4, num_experts_per_tok=2,
                                moe_intermediate_size=32)
    params = qwen_moe.init_params(jax.random.PRNGKey(11), mcfg)
    kw = dict(batch=2, max_len=32, tile_w=16, t_tile=16,
              prefill_seq=16, params=params)
    paged = MegaKernelEngine(mcfg, tp2_mesh, paged=True, **kw)
    dense_e = MegaKernelEngine(mcfg, tp2_mesh, paged=False, **kw)

    prompts = jnp.asarray(
        np.random.RandomState(3).randint(0, mcfg.vocab_size, (2, 16)),
        jnp.int32)
    lp = paged.prefill(prompts)
    ld = dense_e.prefill(prompts)
    assert_allclose(np.asarray(lp, np.float32),
                    np.asarray(ld, np.float32), rtol=2e-3, atol=2e-3)
    tok = jnp.argmax(ld, -1).astype(jnp.int32)
    lp2 = paged.decode_step(tok, 16)
    ld2 = dense_e.decode_step(tok, 16)
    assert_allclose(np.asarray(lp2, np.float32),
                    np.asarray(ld2, np.float32), rtol=2e-3, atol=2e-3)


def test_megakernel_hybrid_gdn_decode_vs_layers(tp2_mesh):
    """Hybrid (qwen_next) decode in the megakernel: GDN layers advance
    their recurrent state via the GDN_DECODE task, softmax layers use
    the KV cache — logits and new states must match the qwen_next
    layer decode_step."""
    from triton_dist_tpu.models import qwen_next
    from triton_dist_tpu.models.kv_cache import KVCache

    hcfg = ModelConfig.tiny_next(vocab_size=64, hidden_size=32,
                                 num_hidden_layers=4,
                                 num_attention_heads=4,
                                 num_key_value_heads=2, head_dim=8,
                                 gdn_num_heads=8, gdn_head_dim_k=8,
                                 gdn_head_dim_v=8, full_attn_interval=2)
    mesh = tp2_mesh
    mb = ModelBuilder(hcfg, mesh, batch=B, max_len=MAXLEN, tile_w=16,
                      t_tile=16)
    assert mb.hybrid and (mb.task_types == int(TaskType.GDN_DECODE)
                          ).sum() == 2  # layers 0, 2
    params = qwen_next.init_params(jax.random.PRNGKey(7), hcfg)
    specs = qwen_next.param_specs(hcfg)

    n_attn, n_gdn = 2, 2
    cache_shape = (n_attn, B, MAXLEN, hcfg.num_key_value_heads,
                   hcfg.head_dim)
    k_cache = jax.random.normal(jax.random.PRNGKey(8), cache_shape) * 0.3
    v_cache = jax.random.normal(jax.random.PRNGKey(9), cache_shape) * 0.3
    states0 = jax.random.normal(
        jax.random.PRNGKey(10),
        (n_gdn, B, hcfg.gdn_num_heads, hcfg.gdn_head_dim_k,
         hcfg.gdn_head_dim_v)) * 0.2
    tokens = jnp.asarray([5, 23], jnp.int32)
    pos = jnp.asarray(5, jnp.int32)
    kvspec = P(None, None, None, "tp", None)
    stspec = P(None, None, "tp", None, None)

    pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
    arena = pack(params)
    step = spmd(mesh, mb.step_fn(),
                (P("tp", None), kvspec, kvspec, P(None), P(), P(None),
                 stspec),
                (P(None, "tp"), P("tp", None), kvspec, kvspec, stspec))
    logits, _, _, _, states2 = step(
        arena, k_cache, v_cache, tokens, pos, jnp.zeros((1,), jnp.int32),
        states0)

    def oracle(p, tok, kc, vc, st):
        cache = qwen_next.HybridCache(
            kv=KVCache(k=kc, v=vc, length=pos), states=st,
            conv=jnp.zeros((st.shape[0], st.shape[1], 0, 0),
                           jnp.float32))
        lg, cache2 = qwen_next.decode_step(p, tok, cache, hcfg)
        return lg, cache2.states

    of = spmd(mesh, oracle,
              (specs, P(None), kvspec, kvspec, stspec),
              (P(None, None), stspec))
    want_logits, want_states = of(params, tokens, k_cache, v_cache,
                                  states0)
    assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-3)
    assert_allclose(np.asarray(states2), np.asarray(want_states),
                    rtol=2e-3, atol=2e-3)


def test_megakernel_hybrid_engine_matches_layer_engine(tp2_mesh):
    """MegaKernelEngine with a hybrid config (prefill_chain + generate)
    produces the same greedy tokens as the layer-path Engine serving
    qwen_next on identical params."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models import Engine, qwen_next

    # One GDN and one attention layer (two of each, one step:
    # ..._hybrid_gdn_decode_vs_layers above), a 4-token prompt and three
    # generated tokens: the state and the cache carry the prefix across
    # the prefill-to-decode handoff and two decode steps after it.
    hcfg = ModelConfig.tiny_next(vocab_size=64, hidden_size=32,
                                 num_hidden_layers=2,
                                 num_attention_heads=4,
                                 num_key_value_heads=2, head_dim=8,
                                 gdn_num_heads=8, gdn_head_dim_k=8,
                                 gdn_head_dim_v=8, full_attn_interval=2)
    params = qwen_next.init_params(jax.random.PRNGKey(12), hcfg)
    mk = MegaKernelEngine(hcfg, tp2_mesh, batch=2, max_len=32,
                          tile_w=16, t_tile=16, params=params)
    prompts = jnp.asarray(
        np.random.RandomState(5).randint(0, hcfg.vocab_size, (2, 4)),
        jnp.int32)
    seed_tok = mk.prefill_chain(prompts)
    mk_toks = np.asarray(mk.generate(seed_tok, steps=3, start_pos=3))

    eng = Engine(hcfg, tp2_mesh, mode="xla", max_len=32,
                 model=qwen_next, params=params)
    eng_toks = np.asarray(eng.serve(prompts, gen_len=3))
    np.testing.assert_array_equal(mk_toks, eng_toks)


def test_megakernel_hybrid_reset_states(tp2_mesh):
    """Reusing a hybrid engine for a second independent prompt must
    reproduce the fresh-engine tokens after reset_states() (stale
    recurrent state has no position mask, unlike KV rows)."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models import qwen_next

    hcfg = ModelConfig.tiny_next(vocab_size=64, hidden_size=32,
                                 num_hidden_layers=2,
                                 num_attention_heads=4,
                                 num_key_value_heads=2, head_dim=8,
                                 gdn_num_heads=4, gdn_head_dim_k=8,
                                 gdn_head_dim_v=8, full_attn_interval=2)
    params = qwen_next.init_params(jax.random.PRNGKey(30), hcfg)
    eng = MegaKernelEngine(hcfg, tp2_mesh, batch=2, max_len=32,
                           tile_w=16, t_tile=16, params=params)
    p1 = jnp.asarray([[3, 9, 27], [5, 25, 61]], jnp.int32)
    p2 = jnp.asarray([[8, 16, 32], [7, 49, 23]], jnp.int32)
    # The first prompt only has to leave its state behind.
    eng.generate(eng.prefill_chain(p1), steps=1, start_pos=2)

    eng.reset_states()
    t2_reused = np.asarray(
        eng.generate(eng.prefill_chain(p2), steps=2, start_pos=2))

    fresh = MegaKernelEngine(hcfg, tp2_mesh, batch=2, max_len=32,
                             tile_w=16, t_tile=16, params=params)
    t2_fresh = np.asarray(
        fresh.generate(fresh.prefill_chain(p2), steps=2, start_pos=2))
    np.testing.assert_array_equal(t2_reused, t2_fresh)


def test_profile_feedback_rescheduling_improves_activity(tp2_mesh):
    """Profile-feedback loop (reference enable_runtime_scheduler,
    answered at schedule time): a cost_lpt build whose cost table is
    miscalibrated (all types weighted to ~nothing, collapsing LPT to
    slot-filling) is re-scheduled with calibrated weights — the second
    build must strictly beat the first on mean core activity and stay
    numerically identical."""
    from triton_dist_tpu.megakernel.builder import calibrate_cost_table

    mesh = tp2_mesh

    def build(table):
        return ModelBuilder(CFG, mesh, batch=B, max_len=MAXLEN,
                            tile_w=16, t_tile=16, num_cores=2,
                            strategy="cost_lpt", profile=True,
                            cost_table=table)

    # "First run": a badly calibrated table (every unit ~free).
    bad = {int(tt): 1e-6 for tt in TaskType}
    mb_bad = build(bad)

    # "Measured feedback": synthetic wall times at 1 time-unit per work
    # unit (what silicon timing would show if the static estimates were
    # perfect), over a FULL-RANK observation mix — the base build plus
    # one build-variant per type with that type's count scaled up.
    mb_probe = ModelBuilder(CFG, mesh, batch=B, max_len=MAXLEN,
                            tile_w=16, t_tile=16, num_cores=2,
                            strategy="cost_lpt")
    c1 = mb_probe.task_unit_counts()
    unit_ns = 3.7e-9
    obs = [(c1, sum(c1.values()) * unit_ns)]
    for k in c1:
        c = dict(c1)
        c[k] = c1[k] * 3
        obs.append((c, sum(c.values()) * unit_ns))
    table = calibrate_cost_table(obs)
    # Perfect static estimates -> ~uniform per-unit weights.
    assert all(abs(w - 1.0) < 1e-6 for w in table.values()), table
    assert all(w >= 0 for w in table.values())
    mb_good = build(table)

    # Calibrated schedule is at least as balanced, and strictly better
    # than the degenerate one.
    params = dense.init_params(jax.random.PRNGKey(0), CFG)
    specs = dense.param_specs(CFG)
    cache_shape = (CFG.num_hidden_layers, B, MAXLEN,
                   CFG.num_key_value_heads, CFG.head_dim)
    k_cache = jnp.zeros(cache_shape)
    v_cache = jnp.zeros(cache_shape)
    kvspec = P(None, None, None, "tp", None)
    toks = jnp.asarray([1, 2], jnp.int32)

    acts, logits_out = [], []
    for mb in (mb_bad, mb_good):
        pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
        arena = pack(params)
        step = spmd(mesh, mb.step_fn(),
                    (P("tp", None), kvspec, kvspec, P(None), P()),
                    (P(None, "tp"), P("tp", None), kvspec, kvspec,
                     P(None, None)))
        logits, _, _, _, prof = step(arena, k_cache, v_cache, toks,
                                     jnp.asarray(0, jnp.int32))
        acts.append(float(np.mean(mb.core_activity(prof))))
        logits_out.append(np.asarray(logits))
    np.testing.assert_allclose(logits_out[0], logits_out[1],
                               rtol=1e-5, atol=1e-5)
    assert acts[1] > acts[0], (acts, mb_bad.qlen, mb_good.qlen)


def test_calibrate_cost_table_recovers_weights():
    """lstsq recovery: synthetic observations from known per-unit
    times must reproduce their ratios."""
    from triton_dist_tpu.megakernel.builder import calibrate_cost_table

    truth = {0: 1.0, 3: 4.0, 7: 2.5}
    rng = np.random.default_rng(0)
    obs = []
    for _ in range(6):
        counts = {k: int(rng.integers(5, 50)) for k in truth}
        wall = sum(truth[k] * v for k, v in counts.items()) * 1e-7
        obs.append((counts, wall))
    table = calibrate_cost_table(obs)
    assert abs(table[3] / table[0] - 4.0) < 1e-6
    assert abs(table[7] / table[0] - 2.5) < 1e-6


def test_perfetto_export_labels_timing_model(tp2_mesh):
    """Timing honesty (VERDICT r4 weak #5): the default export labels
    every event 'reconstructed' (program order, no duration claim); an
    export fed by the calibrated cost model emits spans labeled
    'calibrated' with durations from the model."""
    import json
    import os
    import tempfile

    from triton_dist_tpu.megakernel.builder import calibrate_cost_table
    from triton_dist_tpu.profiler import export_to_perfetto_trace

    mesh = tp2_mesh
    mb = ModelBuilder(CFG, mesh, batch=B, max_len=MAXLEN, tile_w=16,
                      t_tile=16, num_cores=2, strategy="cost_lpt",
                      profile=True)
    # Synthetic measured observations (full-rank mix) -> calibrated
    # per-type weights. Rank-deficient mixes must raise, not fit.
    c1 = mb.task_unit_counts()
    with pytest.raises(ValueError, match="rank"):
        calibrate_cost_table(
            [(c1, 1.0), ({k: v * 2 for k, v in c1.items()}, 2.0)])
    obs = [(c1, sum(c1.values()) * 2e-9)]
    for k in c1:
        c = dict(c1)
        c[k] = c1[k] * 3
        obs.append((c, sum(c.values()) * 2e-9))
    table = calibrate_cost_table(obs)
    durs = mb.slot_durations(table, unit_s=2e-9)
    assert durs.shape == (2, mb.qlen)

    # A REAL step's profile output through the prof_tracks adapter.
    params = dense.init_params(jax.random.PRNGKey(0), CFG)
    specs = dense.param_specs(CFG)
    cache_shape = (CFG.num_hidden_layers, B, MAXLEN,
                   CFG.num_key_value_heads, CFG.head_dim)
    kvspec = P(None, None, None, "tp", None)
    pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
    arena = pack(params)
    step = spmd(mesh, mb.step_fn(),
                (P("tp", None), kvspec, kvspec, P(None), P()),
                (P(None, "tp"), P("tp", None), kvspec, kvspec,
                 P(None, None)))
    _, _, _, _, prof = step(arena, jnp.zeros(cache_shape),
                            jnp.zeros(cache_shape),
                            jnp.asarray([1, 2], jnp.int32),
                            jnp.asarray(0, jnp.int32))
    tracks = mb.prof_tracks(prof)
    assert tracks.shape == (2, mb.qlen, 2)
    with tempfile.TemporaryDirectory() as td:
        p1 = export_to_perfetto_trace(
            tracks, os.path.join(td, "recon.json"),
            tag_names={int(t) + 1: t.name for t in TaskType})
        ev1 = json.load(open(p1))["traceEvents"]
        p2 = export_to_perfetto_trace(
            tracks, os.path.join(td, "calib.json"),
            tag_names={int(t) + 1: t.name for t in TaskType},
            slot_durations=durs)
        ev2 = json.load(open(p2))["traceEvents"]
    assert all(e["args"]["timing"] == "reconstructed"
               for e in ev1 if "value" in e.get("args", {}))
    spans = [e for e in ev2 if e["ph"] == "X"]
    assert spans and all(e["args"]["timing"] == "calibrated"
                         for e in spans)
    assert any(e["dur"] > 0 for e in spans)


def _graph_cases():
    """Synthetic dependency graphs for the scheduler sweeps."""
    chain = ([0, 1, 2], [1, 2, 3], 4)
    diamond = ([0, 0, 1, 2, 3], [1, 2, 3, 3, 4], 5)
    # Skewed: a heavy chain plus a crowd of light independents.
    sk_src = [0, 1, 2]
    sk_dst = [1, 2, 3]
    skewed = (sk_src, sk_dst, 12)
    wide = ([0] * 6, list(range(1, 7)), 8)
    return {"chain": chain, "diamond": diamond, "skewed": skewed,
            "wide": wide}


@pytest.mark.parametrize("gname", sorted(_graph_cases()))
@pytest.mark.parametrize("cores", [1, 2, 3, 4])
def test_scheduler_fairness_every_task_claimed_once(gname, cores):
    """Starvation sweep: across every (graph, core count, priority
    bucket) combination — including adversarial priorities that starve
    a bucket if the claim loop ever could — each task is claimed
    exactly once, holes only arise from pinning, and the claim order
    is topologically valid."""
    from triton_dist_tpu.megakernel.scheduler import schedule_dyn

    src, dst, n = _graph_cases()[gname]
    rng = np.random.RandomState(hash(gname) % 2 ** 16)
    for trial in range(3):
        prio = rng.randint(0, 1 << 20, size=n)
        bkt = rng.randint(0, 3, size=n)
        pin = np.where(rng.rand(n) < 0.3,
                       rng.randint(0, cores, size=n), -1)
        d = schedule_dyn(n, src, dst, num_cores=cores, priority=prio,
                         bucket=bkt, task_cost=rng.randint(1, 50, n),
                         pin_core=pin)
        order = d["claim_order"]
        claimed = sorted(int(t) for t in order if t >= 0)
        assert claimed == list(range(n)), (gname, cores, trial)
        # claim_of inverts claim_order.
        for i, t in enumerate(order):
            if t >= 0:
                assert d["claim_of"][t] == i
        # Topological validity + pinning honored.
        pos = {int(t): i for i, t in enumerate(order) if t >= 0}
        for a, b2 in zip(src, dst):
            assert pos[b2] > pos[a]
        for t in range(n):
            if pin[t] >= 0:
                assert pos[t] % cores == pin[t] % cores
        # Holes can only come from pinning.
        if (pin < 0).all():
            assert (order >= 0).all()
        # Every cross-core wait has a matching signal.
        assert d["n_edges"] == len(d["wait_edges"]) == len(
            d["sig_edges"])


def test_dynamic_beats_cost_lpt_on_skewed_graph():
    """The acceptance comparison: on a skewed-cost graph the dynamic
    claim schedule must show strictly fewer idle scoreboard steps (NOOP
    slots) AND a strictly better timed model than cost_lpt — the
    static packer balances total load blind to readiness, so the heavy
    chain serializes behind padding."""
    from triton_dist_tpu.megakernel.graph import comm_priority
    from triton_dist_tpu.megakernel.scheduler import (
        prune_deps, schedule_dyn, schedule_mc, simulate_static)
    from triton_dist_tpu.megakernel.task import Task

    # Heavy chain 0->1->2->3 (cost 40 each) + 8 light independents.
    src = [0, 1, 2]
    dst = [1, 2, 3]
    n = 12
    cost = [40, 40, 40, 40] + [10] * 8
    tasks = [Task(task_id=i, task_type=TaskType.LINEAR, args=(),
                  deps=([i - 1] if 1 <= i <= 3 else []))
             for i in range(n)]
    prio, bkt, _ = comm_priority(tasks, n_ranks=1, task_cost=cost)
    # Critical-path priority must rank the chain head first.
    assert prio[0] == max(prio)

    s = schedule_mc(n, src, dst, num_cores=2, strategy="cost_lpt",
                    task_cost=cost)
    ps, pd = prune_deps(n, src, dst)
    stat = simulate_static(n, ps, pd, s["queue"], task_cost=cost)
    d = schedule_dyn(n, src, dst, num_cores=2, priority=prio,
                     bucket=bkt, task_cost=cost)

    static_noops = int((s["queue"] < 0).sum())
    dyn_slots = -(-d["n_claims"] // 2) * 2
    dyn_noops = int((d["claim_order"] < 0).sum()) + dyn_slots - d[
        "n_claims"]
    assert dyn_noops < static_noops, (dyn_noops, static_noops)
    assert d["idle_units"] < stat["idle_units"], (d, stat)
    assert d["makespan"] <= stat["makespan"], (d, stat)


def test_dynamic_fewer_idle_steps_interpret_counter(tp2_mesh):
    """Model-level skewed-cost comparison scored on the INTERPRET-MODE
    step counter: a profiled step executes strictly fewer NOOP slots
    under the dynamic scheduler than under cost_lpt when the cost
    table is skewed (LINEAR weighted heavy)."""
    mesh = tp2_mesh
    skew = {int(tt): 1.0 for tt in TaskType}
    skew[int(TaskType.LINEAR)] = 8.0
    noops = {}
    for schedule in ("static", "dynamic"):
        mb = ModelBuilder(CFG, mesh, batch=B, max_len=MAXLEN, tile_w=16,
                          t_tile=16, num_cores=2, strategy="cost_lpt",
                          schedule=schedule, profile=True,
                          cost_table=skew)
        params = dense.init_params(jax.random.PRNGKey(0), CFG)
        specs = dense.param_specs(CFG)
        cache_shape = (CFG.num_hidden_layers, B, MAXLEN,
                       CFG.num_key_value_heads, CFG.head_dim)
        kvspec = P(None, None, None, "tp", None)
        pack = spmd(mesh, mb.pack_arena, (specs,), P("tp", None))
        arena = pack(params)
        step = spmd(mesh, mb.step_fn(),
                    (P("tp", None), kvspec, kvspec, P(None), P()),
                    (P(None, "tp"), P("tp", None), kvspec, kvspec,
                     P(None, None)))
        _, _, _, _, prof = step(arena, jnp.zeros(cache_shape),
                                jnp.zeros(cache_shape),
                                jnp.asarray([1, 2], jnp.int32),
                                jnp.asarray(0, jnp.int32))
        prof = np.asarray(prof)
        executed_noops = int(
            (prof[:, 0] == int(TaskType.NOOP) + 1).sum())
        assert executed_noops == mb.noop_slots()
        noops[schedule] = executed_noops
        # The profile-feedback fold sees exactly the executed units.
        assert mb.profile_unit_counts(prof) == mb.task_unit_counts()
    assert noops["dynamic"] < noops["static"], noops


@pytest.mark.parametrize("family", ["dense", "moe", "hybrid"])
def test_megakernel_dynamic_token_exact_all_families(tp2_mesh, family):
    """Acceptance: schedule="dynamic" produces token-exact greedy
    output vs static on the dense, MoE, and hybrid-GDN families."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models import qwen_moe, qwen_next

    if family == "dense":
        cfg, model = CFG, dense
    elif family == "moe":
        cfg, model = ModelConfig.tiny_moe(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32), qwen_moe
    else:
        # One GDN and one attention layer: every task type of the family
        # is in the queue (two of each: ..._hybrid_gdn_decode_vs_layers).
        cfg, model = ModelConfig.tiny_next(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            gdn_num_heads=8, gdn_head_dim_k=8, gdn_head_dim_v=8,
            full_attn_interval=2), qwen_next
    params = model.init_params(jax.random.PRNGKey(21), cfg)
    toks = {}
    for schedule in ("static", "dynamic"):
        eng = MegaKernelEngine(cfg, tp2_mesh, batch=B, max_len=32,
                               tile_w=16, t_tile=16, params=params,
                               num_cores=2, strategy="cost_lpt",
                               schedule=schedule)
        # Three steps: from the empty cache, and twice on what the step
        # before wrote.
        toks[schedule] = np.asarray(
            eng.generate(jnp.asarray([3, 7], jnp.int32), steps=3))
    np.testing.assert_array_equal(toks["static"], toks["dynamic"])


def test_dynamic_dropped_edge_terminates_or_raises(tp2_mesh):
    """Fault-injection gate: a dropped scoreboard edge under the
    dynamic scheduler must terminate or raise — never livelock. The Watchdog
    deadline converts a livelock into a hard failure."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.resilience import CommTimeoutError, faults
    from triton_dist_tpu.resilience.watchdog import Watchdog

    plan = faults.get_plan("dropped_edge", op="megakernel", k=0)
    with faults.inject(plan):
        eng = MegaKernelEngine(CFG, tp2_mesh, batch=B, max_len=32,
                               tile_w=16, t_tile=16, seed=4,
                               num_cores=2, schedule="dynamic")
        assert eng.builder.n_edges > 0  # the plan has an edge to drop
        try:
            toks = Watchdog(120.0, op="megakernel.dynamic").run(
                lambda: np.asarray(eng.generate(
                    jnp.zeros((B,), jnp.int32), steps=2)))
        except CommTimeoutError as e:
            # A blocking backend wedges on the missing signal — the
            # structured timeout IS the accepted outcome there.
            assert e.op == "megakernel.dynamic"
            return
    # Non-blocking backend: the run must have terminated with sane
    # output and the claim-counter progress must be intact.
    assert toks.shape == (B, 2)
    prog = eng.progress()
    assert prog["progress_counter"] == "claim"
    assert prog["steps_done"] == 2


def test_describe_slot_dynamic_and_claim():
    """describe_slot on a dynamic schedule attributes (q, c) as a
    claim-counter value: claimed task id, priority bucket, and edge
    semaphores — not a static queue position."""
    from triton_dist_tpu.megakernel.scheduler import (
        describe_claim, schedule_dyn)

    src, dst = [0, 0, 1, 2], [1, 2, 3, 3]
    d = schedule_dyn(4, src, dst, num_cores=2,
                     priority=[3, 2, 1, 0], bucket=[0, 0, 1, 1])
    seen = set()
    for claim in range(d["n_claims"]):
        desc = describe_claim(d, claim)
        assert desc["schedule"] == "dynamic"
        assert desc["claim"] == claim
        assert desc["core"] == claim % 2
        if desc["task"] >= 0:
            seen.add(desc["task"])
            assert "bucket" in desc
    assert seen == {0, 1, 2, 3}
    from triton_dist_tpu.megakernel.scheduler import describe_slot
    assert describe_slot(d, 0, 1) == describe_claim(d, 1)
    # Tail padding past n_claims is named, not an error.
    tail = describe_claim(d, d["n_claims"] + 1)
    assert tail["task"] == -1 and tail["tail_padding"]


def test_tune_schedule_persists_and_auto_resolves(tp2_mesh, tmp_path,
                                                 monkeypatch):
    """The schedule autotune entry: tune_schedule times both modes,
    persists the winner under the (model, mesh, batch, cores) key, and
    MegaKernelEngine(schedule="auto") resolves to it from the cache."""
    import triton_dist_tpu.tune as tune
    from triton_dist_tpu.megakernel.engine import (
        MegaKernelEngine, lookup_schedule, tune_schedule)

    monkeypatch.setenv("TRITON_DIST_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(tune, "_CACHE", None)
    monkeypatch.setattr(tune, "_CACHE_PATH", None)

    assert lookup_schedule(CFG, tp2_mesh, batch=B) == "static"  # untuned
    winner = tune_schedule(CFG, tp2_mesh, batch=B, max_len=32,
                           tile_w=16, t_tile=16, reps=1)
    assert winner in ("static", "dynamic")
    assert lookup_schedule(CFG, tp2_mesh, batch=B) == winner
    # Cached: a second call must not re-time (hits the cache).
    assert tune_schedule(CFG, tp2_mesh, batch=B, max_len=32,
                         tile_w=16, t_tile=16, reps=1) == winner
    eng = MegaKernelEngine(CFG, tp2_mesh, batch=B, max_len=32,
                           tile_w=16, t_tile=16, schedule="auto")
    assert eng.schedule == winner


@pytest.mark.parametrize("fixture", ["qwen3_tiny", "qwen3_moe_tiny"])
def test_megakernel_serves_real_checkpoints(tp2_mesh, fixture):
    """The dense and MoE megakernel families serve the committed
    REAL-format HF fixtures token-exactly against the layer Engine —
    checkpoint weights, not synthetic init (the reference megakernel's
    acceptance is real-model serving)."""
    import os

    from triton_dist_tpu.megakernel.engine import MegaKernelEngine
    from triton_dist_tpu.models import Engine, qwen_moe
    from triton_dist_tpu.models.hf_loader import load_hf_checkpoint

    here = os.path.dirname(os.path.abspath(__file__))
    cfg, params = load_hf_checkpoint(
        os.path.join(here, "fixtures", fixture), dtype=jnp.float32)
    mk = MegaKernelEngine(cfg, tp2_mesh, batch=B, max_len=MAXLEN,
                          tile_w=16, t_tile=16, params=params,
                          keep_params=True)
    toks = np.asarray(
        mk.generate(jnp.asarray([3, 7], jnp.int32), steps=4))

    ekw = {"model": qwen_moe} if cfg.is_moe else {}
    e2 = Engine(cfg, tp2_mesh, mode="xla", max_len=MAXLEN,
                params=params, **ekw)
    ref = _layer_engine_greedy(e2, cfg,
                               jnp.asarray([3, 7], jnp.int32), 4)
    np.testing.assert_array_equal(toks, ref)


# ---------------------------------------------------------------------------
# Arena schema: the described memory layout (PR: megakernel serving
# parity) — every region named, disjoint, and addressable.
# ---------------------------------------------------------------------------

def test_arena_schema_regions_disjoint_and_named(tp2_mesh):
    """Every _alloc lands in the schema with a name + kind; the
    in-arena regions tile [0, arena_rows) exactly (no overlap, no
    gap) and the legacy offset table agrees with the schema."""
    mb = ModelBuilder(CFG, tp2_mesh, batch=B, max_len=MAXLEN,
                      tile_w=16, t_tile=16)
    mb.schema.check_disjoint()
    assert mb.schema.rows == mb.arena_rows
    for name, off in mb._offsets.items():
        assert mb.schema.region(name).offset == off
    kinds = {r.kind for r in mb.schema}
    assert {"weight", "activation", "workspace", "io"} <= kinds
    # Weight rows match the pack manifest the arena assembler uses.
    wrows = sum(r.rows for r in mb.schema.regions(kind="weight"))
    assert wrows == sum(r for _, r in mb._weight_entries)


def test_arena_schema_counter_and_buffers():
    """MoE builds name their router-counter region; engines register
    the KV pools (+ scale tables on quantized builds) as schema
    buffers, and snapshot_regions() is exactly the checkpoint set."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mcfg = ModelConfig.tiny_moe(vocab_size=64, hidden_size=32,
                                num_hidden_layers=2,
                                num_attention_heads=4,
                                num_key_value_heads=2, head_dim=8,
                                num_experts=4, num_experts_per_tok=2,
                                moe_intermediate_size=32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = MegaKernelEngine(mcfg, mesh, batch=2, max_len=32, tile_w=16,
                           t_tile=16, paged=True, page=16, num_pages=5,
                           kv_dtype="int8")
    sch = eng.builder.schema
    assert "moe_counts" in sch
    assert sch.region("moe_counts").kind == "counter"
    assert sch.region("moe_counts").offset == eng.builder.moe_counts_off
    names = {r.name for r in sch.snapshot_regions()}
    assert names == {"moe_counts", "k_cache", "v_cache", "k_scale",
                     "v_scale"}
    # describe() is plain data (the docs/diagnostics surface).
    d = sch.describe()
    assert any(e["name"] == "k_scale" and e["kind"] == "scale"
               for e in d)
    # Double allocation fails loudly.
    with pytest.raises(ValueError, match="already allocated"):
        sch.alloc("moe_counts", 1, "counter")


def test_qblock_builder_schedules_verification_tasks():
    """qblock=True swaps the KV pair for WRITE_KV_QBLOCK/ATTN_QBLOCK
    (per-row-position verification tasks), requires paged, and keeps
    the dynamic claim list covering every task exactly once."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mb = ModelBuilder(CFG, mesh, batch=2 * 2, max_len=32, tile_w=16,
                      t_tile=16, seq=2, qblock=True, paged=True,
                      page=16, schedule="dynamic")
    tt = set(int(t.task_type) for t in mb.graph.tasks)
    assert int(TaskType.WRITE_KV_QBLOCK) in tt
    assert int(TaskType.ATTN_QBLOCK) in tt
    assert int(TaskType.WRITE_KV) not in tt
    assert int(TaskType.ATTN_PREFILL) not in tt
    claimed = sorted(int(t) for t in mb.claims.reshape(-1) if t >= 0)
    assert claimed == list(range(len(mb.graph.tasks)))
    with pytest.raises(ValueError, match="paged"):
        ModelBuilder(CFG, mesh, batch=4, max_len=32, tile_w=16,
                     t_tile=16, seq=2, qblock=True)
