"""Megakernel chunked-prefill battery.

The WRITE_KV_CHUNK/ATTN_CHUNK task pair replaces the one-token-per-tick
megakernel prefill lane with bucketed fixed-shape chunk launches — the
mk lane's half of ROADMAP Open item 1's chunked-prefill contract.
Everything here is token-exact three ways: chunked mk serving vs the
one-token mk lane, vs the layer ``Engine.serve`` oracle on shared
params, and (quantized) across kv_dtypes between the two mk lanes. The
jit-cache gates mirror tests/test_disagg_serving.py's layer-path ones:
chunk steps bounded by the bucket count, decode never re-specializing
across chunked admissions.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.serving import ServingEngine

# The bench micro config: interpret-mode dispatch cost scales with
# layers x heads, and this battery builds ~8 engine variants — the
# full tiny config would eat the tier-1 wall-clock budget by itself.
CFG = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                       intermediate_size=32, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=8)
VOCAB = CFG.vocab_size
BUCKETS = (4, 8)

# One megakernel engine per build config for the whole module — engine
# builds dominate wall clock, and reuse is the serving layer's
# slot-recycling contract (positions rewrite, lengths mask).
_MK_CACHE: dict = {}


def _mk_engine(**kw):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    key = tuple(sorted(kw.items()))
    if key not in _MK_CACHE:
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        base = dict(batch=4, max_len=32, tile_w=16, t_tile=8,
                    paged=True, page=8, num_pages=17,
                    keep_params=True)
        base.update(kw)
        _MK_CACHE[key] = MegaKernelEngine(CFG, mesh, **base)
    return _MK_CACHE[key]


def _onetok_tokens(prompts, gen, **kw):
    """Oracle A: the SAME engine shape served through the one-token
    prefill lane (no prefill_buckets)."""
    return ServingEngine(_mk_engine(**kw),
                         **{k: v for k, v in kw.items()
                            if k in ("kv_dtype", "spec_k")}).generate(
        prompts, max_new_tokens=gen)


# ---------------------------------------------------------------------------
# token exactness at the bucket edges
# ---------------------------------------------------------------------------

def _layer_tokens(prompts, gen):
    """Oracle B: the layer path, ``Engine.serve`` end to end on the mk
    engine's own weights."""
    mk = _mk_engine()
    params = jax.tree.map(np.asarray, mk.params)
    e2 = Engine(CFG, mk.mesh, mode="xla", max_len=32, params=params)
    return [np.asarray(e2.serve(np.asarray([p], np.int32),
                                gen_len=gen))[0].tolist()
            for p in prompts]


@pytest.mark.parametrize("bucket", BUCKETS)
def test_mk_chunked_token_exact_bucket_edges_vs_lane_and_layer(bucket):
    """Prompt lengths straddling a bucket edge (b-1 / b / b+1):
    chunked mk serving streams the SAME tokens as the one-token mk
    lane AND as the layer ``Engine.serve`` oracle on the mk engine's
    own params — chunk boundaries, padding rows, and the sign-encoded
    position codes are all invisible in the tokens."""
    prompts = [[int(t) for t in
                np.random.RandomState(n).randint(1, VOCAB, n)]
               for n in (bucket - 1, bucket, bucket + 1)]
    gen = 2          # the prefill's token, and one decode step after it
    want = _onetok_tokens(prompts, gen)

    mk = _mk_engine(prefill_buckets=BUCKETS)
    srv = ServingEngine(mk, prefill_buckets=BUCKETS)
    got = srv.generate(prompts, max_new_tokens=gen)
    assert got == want, "chunked lane diverged from the one-token lane"
    assert want == _layer_tokens(prompts, gen), (
        "mk lanes diverged from Engine.serve")

    st = srv.stats()
    assert st["prefill_chunks"] > 0
    assert st["mk_chunked_prefill"] == list(BUCKETS)
    assert st["prefill_buckets"] == list(BUCKETS)


@pytest.mark.slow  # ~100s interpret-mode; mkchunk-smoke runs it unfiltered
def test_mk_chunked_quantized_writes_token_agree():
    """int8 / fp8 fused quantize-on-write through WRITE_KV_CHUNK: the
    chunked lane agrees token-for-token with the one-token lane at the
    SAME kv_dtype (both lanes quantize through the same page-start
    scale reset), at bucket-edge lengths covering ragged chunk
    tails."""
    prompts = [[int(t) for t in
                np.random.RandomState(7).randint(1, VOCAB, 17)],
               [int(t) for t in
                np.random.RandomState(8).randint(1, VOCAB, 15)]]
    for kvd in ("int8", "fp8"):
        want = _onetok_tokens(prompts, 4, kv_dtype=kvd)
        srv = ServingEngine(
            _mk_engine(prefill_buckets=BUCKETS, kv_dtype=kvd),
            kv_dtype=kvd, prefill_buckets=BUCKETS)
        assert srv.generate(prompts, max_new_tokens=4) == want, (
            f"{kvd} chunked lane diverged from the one-token lane")


# ---------------------------------------------------------------------------
# prefix reuse: resident pages attend-only, never re-blitted
# ---------------------------------------------------------------------------

def test_mk_chunked_prefix_reuse_never_reblits_resident_pages():
    """Chunked mk × prefix-reuse: the second sharer's chunk stream
    starts past the resident prefix (fewer chunks), the shared pages'
    POOL BYTES are untouched by its prefill (attend-only codes — the
    kernel's write is masked), and tokens stay exact."""
    shared = [int(t) for t in
              np.random.RandomState(3).randint(1, VOCAB, 16)]
    p1, p2 = shared + [30, 31], shared + [40]
    want = _layer_tokens([p1, p2], 3)

    mk = _mk_engine(prefill_buckets=BUCKETS)
    srv = ServingEngine(mk, prefill_buckets=BUCKETS, prefix_reuse=True)
    h1 = srv.submit(p1, max_new_tokens=3)
    for _ in range(4):
        srv.step()                   # p1 fully prefilled (8+8+4)
    h2 = srv.submit(p2, max_new_tokens=3)    # while h1 still decodes
    pool_before = np.asarray(mk.k_cache)
    srv.step()
    assert srv.manager.prefix_hits(h2.slot) == 2, (
        "second sharer must hit both full prefix pages")
    # The shared pages' bytes are bit-identical across h2's admission
    # chunk: resident positions ride attend-only (enc <= -2) codes, so
    # WRITE_KV_CHUNK never stores to them.
    table = np.asarray(mk.block_table).reshape(srv.num_slots, -1)
    for pid in table[h2.slot][:2]:
        np.testing.assert_array_equal(
            np.asarray(mk.k_cache)[:, int(pid)],
            pool_before[:, int(pid)],
            err_msg="resident prefix page re-blitted by a chunk write")
    srv.run()
    assert [h1.tokens, h2.tokens] == want
    # h2 computed only its non-shared tail: one bucket-4 chunk at the
    # first non-resident position, vs h1's full 8+8+4 stream.
    assert h1.chunks == [(0, 8, 8), (8, 8, 8), (16, 4, 2)]
    assert h2.chunks == [(16, 4, 1)]


# ---------------------------------------------------------------------------
# speculation composes on chunked admission
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~30s interpret-mode; mkchunk-smoke runs it unfiltered
def test_mk_chunked_spec_composes_token_exact():
    """spec_k on top of chunked admission: prompts enter through the
    chunk task pair, then decode through Q-block verification — tokens
    exactly the plain one-token-lane run's, with > 1 tokens per
    dispatch on the repetitive trace and the sampled-fallback counter
    surfacing in stats()."""
    rep = [[1, 2, 3, 1, 2, 3, 1, 2] * 2, [7, 8, 7, 8, 7, 8] * 2]
    want = _onetok_tokens(rep, 12)
    srv = ServingEngine(
        _mk_engine(prefill_buckets=BUCKETS, spec_k=2,
                   schedule="dynamic"),
        spec_k=2, prefill_buckets=BUCKETS)
    assert srv.generate(rep, max_new_tokens=12) == want
    st = srv.stats()
    assert st["spec"]["tokens_per_dispatch"] > 1.0, st["spec"]
    assert st["prefill_chunks"] > 0
    assert st["spec"]["sampled_fallbacks"] == 0
    assert st["spec_sampled_fallbacks"] == 0

    # A sampled request rides the degenerate repeat-draft (one commit
    # per dispatch) and the fallback counter records each one.
    srv.generate([[5, 6, 7]], max_new_tokens=3, temperature=0.9,
                 seed=11)
    st = srv.stats()
    assert st["spec_sampled_fallbacks"] > 0
    assert st["spec"]["sampled_fallbacks"] == (
        st["spec_sampled_fallbacks"])


# ---------------------------------------------------------------------------
# jit-cache bounds: buckets bound prefill; decode never re-specializes
# ---------------------------------------------------------------------------

def test_mk_chunked_jit_caches_bounded():
    """After warmup over the buckets, UNSEEN prompt lengths cause zero
    new chunk-step or decode compilations: the chunk jit caches stay
    bounded by the bucket count (the engine gates this inline after
    every dispatch) and the decode dispatch is untouched by chunked
    admission."""
    srv = ServingEngine(_mk_engine(prefill_buckets=BUCKETS),
                        prefill_buckets=BUCKETS)
    rng = np.random.RandomState(11)
    srv.generate([[1, 2, 3], list(range(1, 13))], max_new_tokens=2)
    pre, dec = srv.prefill_cache_size(), srv.decode_cache_size()
    assert 0 < pre <= len(BUCKETS)
    for n in (2, 9, 19):                # unseen lengths + a resume mix
        srv.submit([int(t) for t in rng.randint(1, VOCAB, n)],
                   max_new_tokens=2)
        srv.step()
    srv.run()
    assert srv.prefill_cache_size() == pre, "chunk step re-specialized"
    assert srv.decode_cache_size() == dec, "decode re-specialized"
    st = srv.stats()
    assert st["prefill_cache_size"] == pre


# ---------------------------------------------------------------------------
# knob validation + the arena-tier rejects
# ---------------------------------------------------------------------------

def test_mk_chunked_knob_validation():
    """prefill_buckets is an ENGINE knob on the mk lane (the chunk
    task pair is compiled at engine construction): serving/engine
    mismatch in EITHER direction, non-paged builds, and unpadded
    chunk lengths all fail loudly."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="prefill_buckets mismatch"):
        ServingEngine(_mk_engine(), prefill_buckets=BUCKETS)
    with pytest.raises(ValueError, match="prefill_buckets mismatch"):
        ServingEngine(_mk_engine(prefill_buckets=BUCKETS))
    with pytest.raises(ValueError, match="paged"):
        MegaKernelEngine(CFG, mesh, batch=2, max_len=32, tile_w=16,
                         t_tile=16, prefill_buckets=(4,))
    eng = _mk_engine(prefill_buckets=BUCKETS)
    with pytest.raises(ValueError, match="no chunk step for bucket"):
        eng.prefill_chunk(np.zeros(5, np.int32),
                          np.full(5, -1, np.int32),
                          np.zeros(eng.builder.p_max, np.int32))


def test_mk_chunked_lane_rejects_tiers_and_park():
    """The arena-tier limitation rejects stay proper
    NotImplementedErrors naming the limitation and the ROADMAP item
    tracking it, with chunked admission active."""
    srv = ServingEngine(_mk_engine(prefill_buckets=BUCKETS),
                        prefill_buckets=BUCKETS)
    h = srv.submit([1, 2, 3], max_new_tokens=8)
    srv.step()
    with pytest.raises(NotImplementedError, match="arena-tier"):
        srv.park(h)
    with pytest.raises(NotImplementedError, match="Open item 3"):
        srv.park(h)
