"""``models.looped`` on the CPU at a tiny size: layers applied several
times over the same weights, a cache a pass, the final norm between the
passes and the pick among them, and the server around them, against the
benchmark family's plain reference (``benchmark/families/looped.py``,
which imports nothing of the program) on seeded weights.

The preset is the real block small (``tests/benchmark/data/configs/
tiny-ouro.json``): d 64, 2 four-norm layers applied 3 times, 2 heads of
32, FFN 128; the exit threshold a parameter.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import triton_dist_tpu as tdt
from benchmark.harness import loader, reference, weights as W
from triton_dist_tpu.models import Engine, ModelConfig, looped
from triton_dist_tpu.serving.blocks import PagedKVCache
from triton_dist_tpu.serving.server import ServingEngine

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
F = loader.load_family("looped", [loader.DATA_ROOT])
SYS = loader.sibling(F.__file__, "looped_system")
SEED = 13
THRESHOLDS = (1.0, 0.6)
PROMPTS = (21, 37, 9, 30)


def _config(threshold=1.0):
    with open(os.path.join(DATA, "configs", "tiny-ouro.json")) as f:
        return dict(json.load(f), early_exit_threshold=threshold)


@pytest.fixture(scope="module")
def tiny():
    """(mesh, seeded params): the thresholds share the weights."""
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    return mesh, SYS.make_params(_config(), mesh, SEED)


@pytest.fixture(scope="module")
def served(tiny):
    """threshold -> (server, prompts, handles, the logits row of every
    served token as ``ServingEngine._pick`` was handed it): prompts of
    1 to 3 chunks over 2 slots, so chunks run alone, with the decode
    batch aboard and parked, and ``jit__decode`` between them."""
    mesh, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in PROMPTS]
    out, sound = {}, ServingEngine._pick
    for threshold in THRESHOLDS:
        cfg = SYS.model_config(_config(threshold))
        eng = Engine(cfg, mesh, model=looped, mode="xla",
                     dtype=jnp.float32, max_len=64, params=params)
        srv = eng.serving(num_slots=2, page=8, prefill_buckets=(8, 16),
                          telemetry="spans")
        rows = {}

        def tap(self, row, req, step, rows=rows):
            rows.setdefault(req.request_id, []).append(
                np.asarray(row).copy())
            return sound(self, row, req, step)

        ServingEngine._pick = tap
        try:
            handles = [srv.submit(p, max_new_tokens=8, request_id=f"r{i}")
                       for i, p in enumerate(prompts)]
            srv.run()
        finally:
            ServingEngine._pick = sound
        out[threshold] = (srv, prompts, handles, rows)
    return out


# -- the configuration ------------------------------------------------------

def test_config_reads_the_published_config():
    with open(os.path.join(loader.DATA_ROOT, "configs",
                           "ouro-2.6b-1chip.json")) as f:
        published = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert {k: published[k] for k in row["config"]} == row["config"]
        published = row["config"]
    cfg = ModelConfig.from_hf_config(published)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers,
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.vocab_size, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.tie_word_embeddings) == (2048, 5632, 48, 16, 16, 128,
                                         49152, 1000000, 1e-6, False)
    assert (cfg.num_passes, cfg.exit_threshold, cfg.post_norm,
            cfg.qk_norm, cfg.attention_bias, cfg.model_name) == (
                4, 1.0, True, False, False, "ouro")
    # A cache a pass: the page the plan, the block manager and stats()
    # count is 192 layers', 1.5 MiB a token.
    assert cfg.num_paged_layers == 192
    assert looped.paged_pool(cfg) == (PagedKVCache, (16, 128),
                                      {"layers": 192})
    plan = cfg.kv_cache_plan(max_len=768, page=128, num_slots=6,
                             dtype_bytes=2)
    assert plan["bytes_per_token"] == 3 * 2**19
    assert plan["page_bytes_per_rank"] == 128 * 3 * 2**19 == 201_326_592
    assert plan["num_pages"] == 37
    assert plan["pool_bytes_per_rank"] == 37 * 201_326_592   # 7.45 GB
    # Every other model's pool is what it was: one layer, one cache.
    assert ModelConfig.tiny().num_paged_layers == 2


@pytest.mark.parametrize("change, what", [
    ({"use_sliding_window": True}, "sliding window"),
    ({"sliding_window": 4096}, "sliding window"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope"),
    ({"total_ut_steps": None}, "total_ut_steps")])
def test_what_is_not_computed_is_refused_by_the_reader(change, what):
    with pytest.raises((NotImplementedError, KeyError), match=what):
        ModelConfig.from_hf_config(dict(_config(), **change))


@pytest.mark.parametrize("cfg, call, what", [
    (ModelConfig.tiny(), lambda c: looped.paged_pool(c), "post_norm"),
    (ModelConfig.tiny_looped(),
     lambda c: looped.paged_cache_specs(quantized=True), "unquantized"),
    (ModelConfig.tiny_looped(), lambda c: looped.prefill(None, None, c),
     "paged pool only"),
    (ModelConfig.tiny_looped(),
     lambda c: looped.decode_step_paged(None, None, None, c, mode="fused"),
     "mode='xla'")])
def test_what_the_module_does_not_serve_is_refused(cfg, call, what):
    with pytest.raises((ValueError, NotImplementedError), match=what):
        call(cfg)


# -- the programs -----------------------------------------------------------

def _chunk_program(cfg, mesh):
    kv_spec = looped.paged_cache_specs("tp")
    return jax.jit(jax.shard_map(
        lambda p, t, c, row, start, valid: looped.prefill_chunk_paged(
            p, t, c, row, cfg, start=start, wfrom=0, valid=valid),
        mesh=mesh,
        in_specs=(looped.param_specs(cfg), P(None), kv_spec, P(None), P(),
                  P()),
        out_specs=(P(None), kv_spec, P(None)), check_vma=False))


def test_each_pass_of_each_layer_writes_its_own_pool_layer(tiny):
    """After one chunk, pool layer ``t L + l`` holds the keys pass ``t``
    (0-based) of layer ``l`` computes in the reference, and no two pool
    layers hold the same."""
    mesh, params = tiny
    config = _config()
    dims, cfg = F.dims(config), SYS.model_config(config)
    _, per_token, keeps = looped.paged_pool(cfg)
    assert keeps == {"layers": dims.passes * dims.layers} == {"layers": 6}
    cache = PagedKVCache.empty(keeps["layers"], 4, 8, *per_token,
                               num_slots=1, p_max=2, dtype=jnp.float32)
    seq = np.random.default_rng(3).integers(0, 256, size=16)
    row = jnp.asarray([2, 3], jnp.int32)
    _, cache, exits = _chunk_program(cfg, mesh)(
        params, jnp.asarray(seq, jnp.int32), cache, row, np.int32(0),
        np.int32(16))
    assert exits.tolist() == [dims.passes]
    # pages 2 and 3 of every pool layer, position-major: (6, 16, kv, hd)
    got = np.asarray(cache.k_pages)[:, 2:4].transpose(0, 1, 3, 2, 4)
    got = got.reshape(6, 16, dims.kv_heads, dims.head_dim)
    root, dot = W.root_key(SEED), reference._dot
    g_f = W.make_final_norm(root, dims, jnp.float32)
    h = W.make_table(root, "embed", dims, jnp.float32)[seq]
    for t in range(dims.passes):
        u = h
        for li in range(dims.layers):
            w = W.make_layer(root, li, F.layer_leaves(dims, "block"),
                             F.LEAF_IDS, jnp.float32)
            k = F._rope(dot(reference.rms(u, w["ln_attn_in"], dims.eps),
                            w["wk"]).reshape(16, dims.kv_heads,
                                             dims.head_dim),
                        dims.rope_theta)
            np.testing.assert_allclose(got[t * dims.layers + li], k,
                                       atol=2e-5)
            u = F.layer(u, w, "block", dims, dot)
        h = reference.rms(u, g_f, dims.eps)
    for a in range(6):
        for b in range(a):
            assert np.abs(got[a] - got[b]).max() > 0.1
    # Nothing else of the pool was touched but the scratch page.
    rest = np.asarray(cache.k_pages)[:, 1]
    assert not rest.any()


def test_the_step_programs_do_not_grow_with_the_layers(tiny):
    """One traced layer body under a scan: a stack of five layers and
    four passes traces to the equations of two and three."""
    mesh, _ = tiny

    def traced(layers, passes):
        cfg = dataclasses.replace(SYS.model_config(_config()),
                                  num_hidden_layers=layers,
                                  num_passes=passes)
        params = jax.eval_shape(lambda: looped.init_params(
            jax.random.PRNGKey(0), cfg))
        _, per_token, keeps = looped.paged_pool(cfg)
        cache = jax.eval_shape(lambda: PagedKVCache.empty(
            keeps["layers"], 4, 8, *per_token, num_slots=1, p_max=2))
        ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        text = str(jax.make_jaxpr(_chunk_program(cfg, mesh))(
            params, ints(16), cache, ints(2), ints(), ints()))
        return len(text.splitlines())

    assert traced(2, 3) == traced(5, 4)


# -- the server -------------------------------------------------------------

@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_served_logits_are_the_references(served, threshold):
    """Chunked prefill, then decode through the pages, against the
    family's float32 forward of the whole sequence. Tolerance 1e-4 of
    the logits' spread: two float32 forms of the same sums read 2e-6 to
    1e-5 here, one product in bfloat16 1e-2; a pass read from another
    pass's cache, or a row's logits from another pass than it left by,
    reads over 1e-1."""
    srv, prompts, handles, rows = served[threshold]
    dims = F.dims(_config(threshold))
    worst = 0.0
    for i, (prompt, h) in enumerate(zip(prompts, handles)):
        assert h.status == "done" and len(h.tokens) == 8
        seq, at = reference.served_positions(prompt, h.tokens)
        want = reference.logits_at(SEED, F, dims, jnp.float32, [seq],
                                   [at], pad_to=64)[0]
        got = np.stack(rows[f"r{i}"])
        assert got.shape == want.shape == (8, 256)
        worst = max(worst, float(np.abs(got - want).max() / want.std()))
        assert h.tokens == want.argmax(axis=1).tolist()
    assert worst < 1e-4
    by_pass = srv.stats()["picked_by_pass"]
    assert sum(by_pass) == 32
    if threshold == 1.0:
        assert by_pass == [0, 0, 32]
    else:
        assert all(n >= 4 for n in by_pass)     # rows leave at every pass


def test_the_server_counts_what_a_looped_model_keeps(served):
    srv = served[1.0][0]
    st = srv.stats()
    assert (st["passes"], st["paged_layers"],
            st["layer_applications_a_step"]) == (3, 6, 6)
    assert st["kv_bytes_per_token"] == 6 * 2 * 2 * 32 * 4
    assert st["plan"]["page_bytes_per_rank"] == 8 * st["kv_bytes_per_token"]
    assert srv.manager.page_bytes == st["plan"]["page_bytes_per_rank"]
    assert st["pool"]["bytes_per_token"] == st["kv_bytes_per_token"]
    assert srv.cache.k_pages.shape == (6, 17, 2, 8, 32)
    assert st["tokens_picked_on_device"] == st["tokens_generated"] == 32
    assert 0 < st["decode_dispatches_fused"] < st["decode_dispatches"]
    assert srv.decode_cache_size() == 1 and srv.prefill_cache_size() <= 2
    spans = srv.obs.log.spans()
    decodes = [s for s in spans if s.kind == "decode"]
    assert decodes and all(s.attrs["passes"] == 3
                           and s.attrs["exit_pass"] == 3.0 for s in decodes)
    assert {s.attrs["fused"] for s in decodes} == {0, 1}
    assert all(s.attrs["passes"] == 3 for s in spans
               if s.kind == "prefill_chunk")
    firsts = [s for s in spans if s.kind == "prefill_fetch"]
    assert len(firsts) == 4 and all(s.attrs["exit_pass"] == 3
                                    for s in firsts)
    # Below the threshold's 1.0 the mean of a step's rows is no whole
    # number everywhere.
    low = [s.attrs["exit_pass"] for s in served[0.6][0].obs.log.spans()
           if s.kind == "decode"]
    assert 1.0 <= min(low) < max(low) <= 3.0


def test_the_pass_a_row_took_reaches_a_profiler_capture(served, tmp_path):
    """``exit_pass`` is known once a step's tokens are on the host: set
    while the ``decode`` span is open, it is a stat of ``tdt.decode``
    in the capture all the same (``exit_pass_mean.ouro`` reads it)."""
    from jax.profiler import ProfileData

    srv, prompts = served[0.6][:2]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        srv.generate(prompts[:2], max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    stats = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tdt."):
                    stats.setdefault(ev.name[4:], []).append(dict(ev.stats))
    assert stats["decode"] and all(
        st["passes"] == 3 and 1.0 <= st["exit_pass"] <= 3.0
        and "batch" in st and "fused" in st for st in stats["decode"])
    assert all(st["passes"] == 3 for st in stats["prefill_chunk"])
    assert all(st["exit_pass"] in (1, 2, 3) for st in stats["prefill_fetch"])


def test_two_ranks_serve_the_tokens_one_serves(served):
    """Heads and FFN columns over two ranks, the dense family's specs a
    stacked axis in: the psums run inside the scans, and the tokens are
    the one-rank server's."""
    _, prompts, handles, _ = served[0.6]
    mesh = tdt.make_mesh(tp=2, devices=jax.devices()[:2])
    config = _config(0.6)
    eng = Engine(SYS.model_config(config), mesh, model=looped, mode="xla",
                 dtype=jnp.float32, max_len=64,
                 params=SYS.make_params(config, mesh, SEED))
    assert eng.params["layers"]["mlp"]["w_gate"].sharding.spec == P(
        None, None, "tp")
    srv = eng.serving(num_slots=2, page=8, prefill_buckets=(8, 16))
    assert srv.cache.k_pages.sharding.spec[2] == "tp"
    assert srv.generate(prompts[:2], max_new_tokens=8) == [
        h.tokens for h in handles[:2]]
