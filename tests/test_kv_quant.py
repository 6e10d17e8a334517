"""Quantized paged KV serving battery (kv_dtype="int8"|"fp8").

Gates, in order of importance:

1. the NON-quantized path stays bit-identical to ``Engine.serve``
   (the pre-existing token-exactness contract must not regress just
   because the quantized machinery exists);
2. the quantized path's divergence is BOUNDED — a direct logit
   max-abs-err gate on one decode dispatch against the bf16 pool, and
   a greedy-token agreement gate over whole served requests (surfaced
   via ``stats()["greedy_agreement"]``);
3. the capacity win is real and reported: int8 ≥ 1.9x pages at fixed
   pool bytes per ``BlockManager`` stats;
4. quantization composes with the rest of the serving stack (chunked
   prefill, prefix reuse, disaggregated migration, speculation).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import triton_dist_tpu as tdt
from triton_dist_tpu.models import Engine, ModelConfig, dense
from triton_dist_tpu.serving import PagedKVCache, ServingEngine

TP = 4
CFG = ModelConfig.tiny()
MAX_LEN = 64
PAGE = 8


@pytest.fixture(scope="module")
def engine():
    mesh = Mesh(np.array(jax.devices()[:TP]), ("tp",))
    return Engine(CFG, mesh, mode="xla", max_len=MAX_LEN, seed=3)


def _baseline(engine, prompt, gen_len):
    ids = jnp.asarray(np.tile(np.asarray([prompt], np.int32), (TP, 1)))
    return np.asarray(engine.serve(ids, gen_len=gen_len))[0].tolist()


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [3, 1, 4, 1, 5]]


def test_unquantized_path_still_token_exact(engine):
    """kv_dtype='bf16' (and the default) run the ORIGINAL pool code —
    outputs bit-identical to Engine.serve, scales absent."""
    want = [_baseline(engine, p, 8) for p in PROMPTS]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        kv_dtype="bf16")
    assert srv.cache.k_scale is None
    got = srv.generate(PROMPTS, max_new_tokens=8)
    assert got == want


def test_quantized_logit_divergence_bounded(engine):
    """One decode dispatch over identically-prefilled bf16 vs int8/fp8
    pools: logit max-abs-err under a fixed threshold (the CPU
    battery's bounded-divergence gate for the fused-dequant path) —
    the SAME token fed over the same prompt, only the pool storage
    differs."""
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]

    def first_decode_logits(kvd):
        srv = ServingEngine(engine, num_slots=2, page=PAGE,
                            kv_dtype=kvd)
        h = srv.submit(prompt, max_new_tokens=2)
        stalled = []
        for hh in srv.sched.admit():
            srv._admit(hh, stalled)     # prefill + blit; exact token 1
        srv._toks[0] = h.tokens[-1]
        srv.manager.append(0, int(srv._lens[0]))
        tbl = np.zeros((srv.num_slots, srv.p_max), np.int32)
        tbl[0] = srv.manager.table_row(0)
        from triton_dist_tpu.serving.server import _Flight

        _, _, logits, _ = srv._enqueue_decode(_Flight(0), [h], tbl, False)
        return np.asarray(logits[0])

    base = first_decode_logits("bf16")
    # Thresholds: the CPU battery's empirical bound with ~5x margin
    # (measured: int8 ~3e-3, fp8 ~1e-2 on this tiny config).
    for kvd, thresh in (("int8", 0.05), ("fp8", 0.15)):
        err = np.abs(first_decode_logits(kvd) - base).max()
        assert err < thresh, f"{kvd} logit divergence {err}"


@pytest.mark.parametrize("kvd,min_agree", [("int8", 0.7), ("fp8", 0.5)])
def test_quantized_greedy_agreement_surfaced(engine, kvd, min_agree):
    """Whole-request greedy agreement vs the exact run, folded into
    stats() via compare_greedy — the serving-level accuracy surface."""
    want = [_baseline(engine, p, 8) for p in PROMPTS]
    srv = ServingEngine(engine, num_slots=2, page=PAGE, kv_dtype=kvd)
    got = srv.generate(PROMPTS, max_new_tokens=8)
    agree = srv.compare_greedy(zip(got, want))
    st = srv.stats()
    assert st["greedy_agreement"] == agree
    assert agree >= min_agree, (kvd, agree, got, want)
    assert st["kv_dtype"] == kvd


def test_int8_capacity_ratio_gate(engine):
    """int8 KV buys >= 1.9x pages at fixed pool bytes — reported by
    the BlockManager stats and the model plan."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        kv_dtype="int8")
    pool = srv.stats()["pool"]
    assert pool["capacity_ratio_vs_native"] >= 1.9, pool
    assert pool["bytes_per_token"] < srv.plan[
        "native_page_bytes_per_rank"] / PAGE
    assert srv.plan["capacity_ratio_vs_native"] >= 1.9
    # pages_at_native_bytes: what the SAME HBM would hold quantized.
    assert pool["pages_at_native_bytes"] >= int(
        1.9 * (pool["num_pages"] - 1))


def test_quantized_chunked_prefill_and_prefix_reuse(engine):
    """Quantization composes with the bucketed chunk stream and
    refcounted prefix sharing: shared pages keep the first sharer's
    bytes AND scales; chunk boundaries do not shift the numerics
    regime (greedy agreement holds)."""
    shared = list(range(1, PAGE + 1))
    prompts = [shared + [20, 21], shared + [30]]
    want = [_baseline(engine, p, 6) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=PAGE,
                        kv_dtype="int8", prefix_reuse=True,
                        prefill_buckets=(4,))
    # Sequential submits: prefix pages publish at commit (end of the
    # first chunk stream), so the second request must arrive after.
    got = [srv.generate([prompts[0]], max_new_tokens=6)[0],
           srv.generate([prompts[1]], max_new_tokens=6)[0]]
    assert srv.stats()["pool"]["prefix_hits"] >= 1
    agree = srv.compare_greedy(zip(got, want))
    assert agree >= 0.6, (agree, got, want)
    assert srv.prefill_cache_size() <= 1


def test_quantized_disagg_migration_bit_exact():
    """Pages migrate as their STORED bytes + scales: the decode-side
    pool holds bit-identical int8 content after the handoff (scatter
    without scales is rejected)."""
    import os

    from triton_dist_tpu.serving import DisaggServingEngine

    cfg = ModelConfig.tiny()
    devs = jax.devices()
    params = dense.init_params(jax.random.PRNGKey(0), cfg)
    pf = Engine(cfg, tdt.make_mesh(tp=1, devices=devs[:1]), mode="xla",
                max_len=MAX_LEN, params=params)
    dec = Engine(cfg, tdt.make_mesh(tp=1, devices=devs[1:2]),
                 mode="xla", max_len=MAX_LEN, params=params)
    srv = DisaggServingEngine(dec, prefill_engine=pf, num_slots=2,
                              page=PAGE, prefill_buckets=(4,),
                              kv_dtype="int8")
    h = srv.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=2)
    # Drive chunks until the migration is issued, then capture the
    # staging pages BEFORE the scatter consumes them.
    for _ in range(20):
        if srv._pending:
            break
        srv.step()
    assert srv._pending, "migration never issued"
    _, _, payload, dst_ids, _, _, _ = srv._pending[0]
    k_pay = np.asarray(payload[0])
    ks_pay = np.asarray(payload[2])
    # Collect the migration and compare BEFORE any decode append can
    # requantize the slot's (partially-filled) final page.
    srv._complete_migrations()
    assert not srv._pending
    # Only the real destination rows carry the payload — scratch-
    # padded rows (dropped prefix/padding) are garbage by contract.
    sel = np.asarray(dst_ids) != 0
    got = np.asarray(srv.cache.k_pages[:, dst_ids])[:, sel]
    got_s = np.asarray(srv.cache.k_scale[:, dst_ids])[:, sel]
    np.testing.assert_array_equal(
        got.view(np.uint8), k_pay[:, sel].view(np.uint8))
    np.testing.assert_array_equal(got_s, ks_pay[:, sel])
    srv.run()
    assert h.status == "done"


def test_scatter_scale_mismatch_raises():
    c_q = PagedKVCache.empty(1, 4, PAGE, 2, 8, num_slots=1, p_max=2,
                             kv_dtype="int8")
    c_n = PagedKVCache.empty(1, 4, PAGE, 2, 8, num_slots=1, p_max=2)
    ids = jnp.asarray([1, 2], jnp.int32)
    pay = c_q.gather_pages(ids)
    with pytest.raises(ValueError, match="needs the payload's"):
        c_q.scatter_pages(pay[0], pay[1], ids)
    with pytest.raises(ValueError, match="unquantized"):
        c_n.scatter_pages(np.zeros((1, 2, 2, PAGE, 8), np.float32),
                          np.zeros((1, 2, 2, PAGE, 8), np.float32),
                          ids, pay[2], pay[3])


def test_quantized_spec_composes(engine):
    """Speculation over a quantized pool: self-consistent (spec on/off
    produce the SAME quantized-path tokens) — the rollback path's
    scratch routing keeps rejected candidates out of real pages."""
    srv_q = ServingEngine(engine, num_slots=2, page=PAGE,
                          kv_dtype="int8")
    want = srv_q.generate(PROMPTS, max_new_tokens=8)
    srv_sq = ServingEngine(engine, num_slots=2, page=PAGE,
                           kv_dtype="int8", spec_k=4)
    got = srv_sq.generate(PROMPTS, max_new_tokens=8)
    assert got == want


def _mk_cfg():
    # The megakernel tests' micro config: an interpreted step costs in
    # proportion to the tasks in its queue, and the heads and the FFN's
    # width add theirs (77 tasks a step at ``tiny(vocab_size=128)``, 49
    # here).
    return ModelConfig.tiny(vocab_size=64, hidden_size=32,
                            intermediate_size=32, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            head_dim=8)


# One megakernel engine per kv_dtype for the whole module: engine
# builds dominate the battery's wall clock, and reuse is exactly the
# serving layer's slot-recycling contract (positions rewrite, lengths
# mask — stale pool bytes are never read).
_MK_CACHE: dict = {}


def _mk_engine(**kw):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    key = tuple(sorted(kw.items()))
    if key not in _MK_CACHE:
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        base = dict(batch=2, max_len=32, tile_w=16, t_tile=16,
                    paged=True, page=16, num_pages=5)
        base.update(kw)
        _MK_CACHE[key] = MegaKernelEngine(_mk_cfg(), mesh, **base)
    return _MK_CACHE[key]


MK_PROMPTS = [[5, 6, 7], [3, 4], [9, 10, 11, 12], [1]]


@pytest.fixture(scope="module")
def mk_want():
    """The unquantized lane's tokens, once a module (a fresh
    ServingEngine; the engine's pool is rewritten by whoever comes
    next)."""
    return ServingEngine(_mk_engine()).generate(MK_PROMPTS,
                                                max_new_tokens=6)


def test_megakernel_bf16_still_bit_identical(mk_want):
    """The quantization machinery existing must not perturb the
    unquantized persistent lane: kv_dtype='bf16' serving tokens equal
    solo runs on a fresh engine (the pre-existing mk contract), and
    the jitted step count stays flat after warmup."""
    srv = ServingEngine(_mk_engine(), kv_dtype="bf16")
    assert srv.engine.k_scale is None     # bf16 = no scale tables
    got = srv.generate(MK_PROMPTS, max_new_tokens=6)
    assert got == mk_want
    n = srv.decode_cache_size()
    srv.generate([[2, 4]], max_new_tokens=3)
    assert srv.decode_cache_size() == n, "mk decode step re-specialized"


@pytest.mark.parametrize("kvd,min_agree", [("int8", 0.7), ("fp8", 0.5)])
def test_megakernel_quant_decode_token_agreement(kvd, min_agree, mk_want):
    """The converted mk-reject: int8/fp8 pools on the persistent lane
    decode token-AGREEING with the layer-path quantized contract's
    bar (fused quantize-on-write / dequantize-on-read vs the fp32
    pools), surfaced via compare_greedy, with the jit cache flat."""
    srv = ServingEngine(_mk_engine(kv_dtype=kvd), kv_dtype=kvd)
    got = srv.generate(MK_PROMPTS, max_new_tokens=6)
    agree = srv.compare_greedy(zip(got, mk_want))
    st = srv.stats()
    assert st["greedy_agreement"] == agree
    assert agree >= min_agree, (kvd, agree, got, mk_want)
    assert st["kv_dtype"] == kvd
    assert st["mk_kv_dtype"] == kvd
    n = srv.decode_cache_size()
    srv.generate([[2, 4]], max_new_tokens=3)
    assert srv.decode_cache_size() == n, "mk decode step re-specialized"


def test_megakernel_int8_capacity_ratio_gate():
    """The capacity win is planned and reported on the mk lane too:
    int8 >= 1.9x pages at fixed pool bytes vs the fp32-native pools
    (BlockManager stats + the model plan, like the layer path)."""
    srv = ServingEngine(_mk_engine(kv_dtype="int8"), kv_dtype="int8")
    pool = srv.stats()["pool"]
    assert pool["capacity_ratio_vs_native"] >= 1.9, pool
    assert srv.plan["capacity_ratio_vs_native"] >= 1.9
    assert srv.stats()["kv_bytes_per_token"] < srv.plan[
        "native_page_bytes_per_rank"] / 16


def test_megakernel_quant_knob_validation():
    """kv_dtype is an ENGINE knob on the mk lane: a serving/engine
    mismatch, a dense (non-paged) build, and a hybrid build all fail
    loudly with actionable messages."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="kv_dtype mismatch"):
        ServingEngine(_mk_engine(), kv_dtype="int8")
    with pytest.raises(ValueError, match="paged"):
        MegaKernelEngine(_mk_cfg(), mesh, batch=2, max_len=32,
                         tile_w=16, t_tile=16, kv_dtype="int8")
    hcfg = ModelConfig.tiny_next(vocab_size=128, num_key_value_heads=4,
                                 full_attn_interval=2)
    with pytest.raises(NotImplementedError, match="hybrid"):
        MegaKernelEngine(hcfg, mesh, batch=2, max_len=32, tile_w=16,
                         t_tile=16, paged=True, page=16,
                         kv_dtype="int8")


def test_bad_kv_dtype_rejected(engine):
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(engine, num_slots=2, page=PAGE, kv_dtype="int4")
