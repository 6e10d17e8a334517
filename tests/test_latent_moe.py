"""``models.latent_moe`` on the CPU at a tiny size: the latent paged pool,
the two attention paths, the held-experts layer, and the server around
them, against the benchmark family's plain reference
(``benchmark/families/mla_moe.py``, which imports nothing of the
program) on seeded weights.

The preset is the real block small: d 64, 4 heads of 8 + 8 and 16, r_q
32, r_kv 16, 16 experts of which 4 a token and 4 held, 2 layers, YaRN
with an original length of 16 so that a 40-token sequence crosses it
twice (the blend of frequencies and the query's scale by position are
both away from the identity).
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import triton_dist_tpu as tdt
from benchmark.harness import loader, reference, weights as W
from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.layers.rope import rope_freqs, yarn_freqs
from triton_dist_tpu.models import Engine, ModelConfig, latent_moe
from triton_dist_tpu.serving.blocks import LatentPagedCache

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
F = loader.load_family("mla_moe", [loader.DATA_ROOT])
SYS = loader.sibling(F.__file__, "mla_moe_system")
SEED = 11


@pytest.fixture(scope="module")
def tiny():
    """(config file, dims, ModelConfig, mesh, seeded params)."""
    with open(os.path.join(DATA, "configs", "tiny-mla.json")) as f:
        config = json.load(f)
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    return (config, F.dims(config), SYS.model_config(config), mesh,
            SYS.make_params(config, mesh, SEED))


def _on_mesh(mesh, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _empty(cfg, *, pages=9, page=8, slots=2, p_max=8):
    return LatentPagedCache.empty(
        cfg.num_hidden_layers, pages, page, latent_moe.cache_width(cfg),
        num_slots=slots, p_max=p_max, dtype=jnp.float32)


def test_config_reads_the_published_keys(tiny):
    config, dims, cfg, _, _ = tiny
    assert cfg.is_latent and (cfg.q_lora_rank, cfg.kv_lora_rank) == (32, 16)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (8, 8, 16)
    assert cfg.num_experts == 16 and cfg.held_experts == 4
    assert cfg.shared_expert_intermediate_size == 32
    assert (cfg.rope_factor, cfg.rope_original_max_position,
            cfg.rope_query_scale_beta) == (8, 16, 0.1)
    assert cfg.rope_theta == 10000
    # The program's frequencies and scale are the reference's own.
    np.testing.assert_allclose(
        yarn_freqs(8, 10000.0, factor=8.0, original=16, beta_fast=4.0,
                   beta_slow=1.0), F.yarn_inv_freq(dims), rtol=1e-6)
    assert latent_moe.softmax_scale(cfg) == pytest.approx(
        F.softmax_scale(dims))
    np.testing.assert_array_equal(
        yarn_freqs(8, 10000.0, factor=1.0, original=16),
        rope_freqs(8, 10000.0))


@pytest.mark.parametrize("scaling", ["linear", "llama3", "longrope"])
def test_a_rope_scaling_that_is_not_computed_is_refused(tiny, scaling):
    config = tiny[0]
    rope = dict(config["rope_parameters"], rope_type=scaling, type=scaling)
    with pytest.raises(NotImplementedError, match=scaling):
        ModelConfig.from_hf_config(dict(config, rope_parameters=rope))
    plain = {k: v for k, v in config.items()
             if k not in ("kv_lora_rank", "rope_parameters")}
    with pytest.raises(NotImplementedError, match=scaling):
        ModelConfig.from_hf_config(dict(plain, rope_scaling=rope))
    assert ModelConfig.from_hf_config(
        dict(plain, rope_scaling=None)).rope_factor == 1.0


def test_the_pool_plan_is_one_latent_a_token(tiny):
    cfg = tiny[2]
    plan = cfg.kv_cache_plan(max_len=64, page=8, num_slots=3,
                             dtype_bytes=2)
    assert plan["bytes_per_token"] == 2 * (16 + 8) * 2     # layers x width
    assert plan["pool_bytes_per_rank"] == plan["page_bytes_per_rank"] * 25
    with pytest.raises(ValueError, match="not quantized"):
        cfg.kv_cache_plan(max_len=64, page=8, num_slots=3, kv_dtype="int8")
    pool, per_token = latent_moe.paged_pool(cfg)
    assert pool is LatentPagedCache and per_token == (24,)


def test_the_pool_writers_put_a_token_in_its_column(tiny):
    cfg = tiny[2]
    rng = np.random.default_rng(0)
    cache = dataclasses.replace(
        _empty(cfg), block_table=jnp.asarray([[3, 5, 0, 0, 0, 0, 0, 0],
                                              [0] * 8], jnp.int32),
        lens=jnp.asarray([11, 0], jnp.int32),
        live=jnp.asarray([1, 0], jnp.int32))
    rows = rng.normal(size=(2, 24)).astype(np.float32)
    got = np.asarray(cache.append_decode(1, jnp.asarray(rows)).pages)
    want = np.zeros_like(got)
    want[1, 5, :, 3] = rows[0]           # position 11: page 1, offset 3
    want[1, 0, :, 0] = rows[1]           # parked: the scratch page
    np.testing.assert_array_equal(got, want)
    # A chunk of 16 rows from position 6, 10 of them valid, positions
    # below 8 resident: pages 1 and 2 of the row take positions 8..15.
    chunk = rng.normal(size=(16, 24)).astype(np.float32)
    row = jnp.asarray([4, 7, 2, 0, 0, 0, 0, 0], jnp.int32)
    got = np.asarray(cache.write_chunk(
        0, jnp.asarray(chunk), row, 6 + jnp.arange(16), 10, 8).pages)
    want = np.zeros_like(got)
    want[0, 7, :, :] = chunk[2:10].T
    np.testing.assert_array_equal(got, want)
    assert cache.advance().lens.tolist() == [12, 0]


def test_shared_expert_is_gated_by_what_the_parameters_hold():
    rng = np.random.default_rng(1)
    d, f = 16, 8
    p = {k: jnp.asarray(rng.normal(size=s), jnp.float32) for k, s in (
        ("w_shared_gate", (d, f)), ("w_shared_up", (d, f)),
        ("w_shared_down", (f, d)))}
    x = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    plain = ep_moe.shared_expert_out(p, x)
    want = (jax.nn.silu(x @ p["w_shared_gate"])
            * (x @ p["w_shared_up"])) @ p["w_shared_down"]
    np.testing.assert_allclose(plain, want, rtol=1e-5, atol=1e-5)
    gate = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    gated = ep_moe.shared_expert_out(dict(p, shared_gate=gate), x)
    np.testing.assert_allclose(
        gated, want * jax.nn.sigmoid(x @ gate)[:, None], rtol=1e-5,
        atol=1e-5)
    assert ep_moe.shared_expert_out({}, x) is None


def test_the_shares_add_up(tiny, monkeypatch):
    """The routed parts the four chips of the deployment compute, plus
    the shared expert counted once, are the uncut layer: the program's
    held-experts layer share by share against the reference with all 16
    experts (and the reference's own shares against itself)."""
    _, dims, cfg, _, _ = tiny
    whole = dataclasses.replace(dims, held=16, first_held=0)
    w = {k: v.astype(jnp.float32) for k, v in W.make_layer(
        W.root_key(SEED), 0, F.layer_leaves(whole), F.LEAF_IDS,
        jnp.float32).items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (48, dims.d), jnp.float32)
    want = F.experts(x, w, whole, reference._dot) - x
    y = reference.rms(x, w["ln_mlp"], dims.eps)
    shared = {"w_shared_" + k: w["shared_" + k]
              for k in ("gate", "up", "down")}
    once = ep_moe.shared_expert_out(shared, y)
    total, ref_total, pairs = once, once, 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        moe = dict(shared, router=w["router"],
                   w_gate=w["experts_gate"][held],
                   w_up=w["experts_up"][held],
                   w_down=w["experts_down"][held])
        out, stats = ep_moe.fwd_held(moe, y, topk=cfg.num_experts_per_tok,
                                     first=first)
        total = total + (out - once)
        pairs += int(stats[0])
        assert 0 < int(stats[1]) <= int(stats[0])
        share = dataclasses.replace(dims, held=4, first_held=first)
        ws = dict(w, **{k: w[k][held] for k in (
            "experts_gate", "experts_up", "experts_down")})
        ref_total = ref_total + (
            F.experts(x, ws, share, reference._dot) - x - once)
    assert pairs == 48 * 4               # every pair fell to one share
    # The reference gathers an expert's rows a fixed number a pass: with
    # room for the even share only (12 rows), the fuller experts take
    # further passes and the result is the same.
    monkeypatch.setattr(F, "CAPACITY_FACTOR", 1)
    assert F.expert_capacity(whole, 48) == 12
    np.testing.assert_allclose(
        F.experts(x, w, whole, reference._dot) - x, want, rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_total, want, rtol=1e-4, atol=1e-5)


def _all_pairs(params, x, *, topk, first=0):
    """``fwd_held`` as it was before it took passes (PR 37-40): every
    token-expert pair is repeated, sorted and handed to the grouped
    SwiGLU, the pairs of absent experts last, and the held ones are
    selected at the very end. Returns ``(out, held, fullest)``."""
    from triton_dist_tpu.ops.group_gemm import (grouped_swiglu,
                                                sort_by_expert)

    t, d = x.shape
    n_held = params["w_gate"].shape[0]
    topk_ids, topk_w = ep_moe.route(params["router"], x, topk)
    local = topk_ids - first
    held = (local >= 0) & (local < n_held)
    sorted_tok, group_sizes, inv = sort_by_expert(
        jnp.repeat(x, topk, axis=0), jnp.where(held, local, -1).reshape(-1),
        n_held)
    out = grouped_swiglu(sorted_tok, params["w_gate"], params["w_up"],
                         params["w_down"], group_sizes)[inv]
    out = jnp.sum(jnp.where(
        held[..., None],
        out.reshape(t, topk, d).astype(jnp.float32) * topk_w[..., None],
        0.0), axis=1)
    return (out + ep_moe.shared_expert_out(params, x),
            int(jnp.sum(group_sizes)), int(jnp.max(group_sizes)))


# 512 tokens, 2 of 64 experts a token, experts 4..7 held: 1,024 pairs and
# an even share of 64, so the shape rule gives a pass of 128 rows and the
# routings below need 0 to 8 of them.
_ROUTINGS = {        # name: (held pairs, token indices -> (T, 2) experts)
    "even": (64, lambda t: np.stack([t % 64, (t + 5) % 64], 1)),
    "every-pair-held": (1024, lambda t: np.stack(
        [4 + t % 4, 4 + (t + 1) % 4], 1)),
    "no-pair-held": (0, lambda t: np.stack([t % 4, 8 + t % 56], 1)),
    "one-expert-given-all": (512, lambda t: np.stack(
        [6 + 0 * t, t % 4], 1)),
    "held-exactly-a-pass": (128, lambda t: np.stack(
        [np.where(t < 128, 4 + t % 4, t % 4), 8 + t % 56], 1)),
    "held-a-pass-and-one": (129, lambda t: np.stack(
        [np.where(t < 129, 4 + t % 4, t % 4), 8 + t % 56], 1)),
}


@pytest.mark.parametrize("impl, f, noise", [("xla", 16, 16),
                                            ("kernel", 128, 64)])
@pytest.mark.parametrize("routing", list(_ROUTINGS))
def test_the_held_experts_take_their_pairs_in_passes(routing, impl, f,
                                                     noise):
    """``fwd_held`` equals the all-pairs form at every routing, with no
    pair dropped when a pass cannot hold them all, and counts what it
    did: ``(held pairs, the fullest expert's rows, passes)``. The pass
    is small because few of many experts are held (the shape rule), not
    because anything was set. In both forms of a pass
    (``ep_moe.experts_impl``, by sizes): the ragged products over the
    sorted rows at ``d`` 80 and ``f`` 16, the Pallas kernel over the
    expert-major layout (interpreted) at whole lanes, 128 and 128: a
    pass of one row tile laid out in 1 + 4."""
    t, e, n_held, first, topk = 512, 64, 4, 4, 2
    rows = ep_moe.held_pass_rows(t, topk, n_held, e)
    assert rows == 128 < t * topk
    assert ep_moe.experts_impl(rows, n_held, e + noise, f,
                               jnp.float32) == impl
    held_pairs, experts_of = _ROUTINGS[routing]
    ids = experts_of(np.arange(t))
    rng = np.random.default_rng(5)
    # The router reads a token's scores off its first 64 values: the
    # two chosen experts stand out, the rest is noise like the others.
    x = rng.normal(size=(t, e + noise)).astype(np.float32)
    x[np.arange(t), ids[:, 0]] = 9.0
    x[np.arange(t), ids[:, 1]] = 8.0
    d = x.shape[1]
    params = {k: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
              for k, shape, scale in (
                  ("w_gate", (n_held, d, f), d ** -0.5),
                  ("w_up", (n_held, d, f), d ** -0.5),
                  ("w_down", (n_held, f, d), f ** -0.5),
                  ("w_shared_gate", (d, f), d ** -0.5),
                  ("w_shared_up", (d, f), d ** -0.5),
                  ("w_shared_down", (f, d), f ** -0.5))}
    params["router"] = jnp.eye(d, e, dtype=jnp.float32)
    x = jnp.asarray(x)
    np.testing.assert_array_equal(
        ep_moe.route(params["router"], x, topk)[0], ids)
    want, held, fullest = _all_pairs(params, x, topk=topk, first=first)
    assert held == held_pairs
    out, stats = jax.jit(lambda p, v: ep_moe.fwd_held(
        p, v, topk=topk, first=first))(params, x)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    assert stats.tolist() == [held, fullest, -(-held // rows)]
    if routing == "no-pair-held":
        np.testing.assert_allclose(
            out, ep_moe.shared_expert_out(params, x), rtol=1e-6)
    if routing == "one-expert-given-all":
        assert fullest == held


def test_absorbed_and_expanded_attention_agree_on_the_same_cache(tiny):
    _, _, cfg, _, params = tiny
    attn = params["layers"][1]["attn"]
    rng = np.random.default_rng(2)
    n, page = 40, 8
    cache = _empty(cfg, slots=1)
    row = jnp.asarray([2, 6, 1, 8, 3, 0, 0, 0], jnp.int32)
    cache = dataclasses.replace(
        cache.write_chunk(1, jnp.asarray(rng.normal(size=(n, 24)),
                                         jnp.float32),
                          row, jnp.arange(n), n, 0),
        block_table=row[None], lens=jnp.asarray([n], jnp.int32),
        live=jnp.asarray([1], jnp.int32))
    q = jnp.asarray(rng.normal(size=(n, 4, 16)), jnp.float32)
    qpos = jnp.arange(n, dtype=jnp.int32)
    expanded = latent_moe._attend_expanded(attn, q, cache, 1, row, qpos, cfg)
    absorbed = latent_moe._attend_absorbed(attn, q[None], cache, 1,
                                           qpos[None], cfg)
    assert expanded.shape == absorbed.shape == (n, 4 * 16)
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-4, atol=1e-5)
    # A table row that is no whole number of blocks walks to its end.
    table, ppb = latent_moe._blocked_table(jnp.zeros((3, 129), jnp.int32),
                                           128)
    assert (table.shape, ppb) == ((3, 130), 10)
    assert latent_moe._blocked_table(row, page)[1] == 8


def test_chunks_then_decode_through_the_pool_equal_the_reference(tiny):
    """A 40-token sequence: 24 tokens prefilled as chunks of 16 and 8 (in
    a bucket of 16, padded), then 16 decode steps fed the sequence's own
    tokens. The logits after the prompt and after each decoded token
    equal the reference's full forward at those positions. float32 on
    both sides; 1e-4 absolute on logits of std ~0.17 is what the orders
    of summation differ by (a running softmax over blocks of pages
    against one whole, grouped products against gathered ones), three
    orders under what a wrong rope, scale or expert moves."""
    config, dims, cfg, mesh, params = tiny
    specs = latent_moe.param_specs(cfg, "tp")
    kv = latent_moe.paged_cache_specs("tp")
    chunk = _on_mesh(
        mesh, lambda p, t, c, row, start, valid:
        latent_moe.prefill_chunk_paged(p, t, c, row, cfg, start=start,
                                       wfrom=0, valid=valid)[:2],
        (specs, P(None), kv, P(None), P(), P()), (P(None), kv))
    decode = _on_mesh(
        mesh, lambda p, t, c: latent_moe.decode_step_paged(p, t, c, cfg),
        (specs, P(None), kv), (P(None, None), kv, P(None)))
    seq = np.random.default_rng(5).integers(0, dims.vocab, size=40)
    row = jnp.asarray([4, 2, 7, 1, 5, 0, 0, 0], jnp.int32)
    cache = _empty(cfg)
    _, cache = chunk(params, jnp.asarray(seq[:16], jnp.int32), cache, row,
                     0, 16)
    toks = np.zeros(16, np.int32)
    toks[:8] = seq[16:24]
    logits, cache = chunk(params, jnp.asarray(toks), cache, row, 16, 8)
    got = [np.asarray(logits)]
    cache = dataclasses.replace(
        cache, block_table=jnp.stack([jnp.zeros_like(row), row]),
        lens=jnp.asarray([0, 24], jnp.int32),
        live=jnp.asarray([0, 1], jnp.int32))
    for t in seq[24:39]:
        logits, cache, stats = decode(
            params, jnp.asarray([0, t], jnp.int32), cache)
        got.append(np.asarray(logits)[1])
        assert 0 <= int(stats[1]) <= int(stats[0]) <= 2 * 4 * 2
    assert cache.lens.tolist() == [0, 39]
    want = reference.logits_at(SEED, F, dims, jnp.float32, [seq.tolist()],
                               [list(range(23, 39))])[0]
    assert want.std() > 0.1
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=1e-4)


def test_verification_sees_what_sequential_decode_sees(tiny):
    """Three candidates a slot through ``verify_step_paged`` give, row
    by row, the logits of three decode steps fed the same tokens: the
    absorbed walk with a position a query, against one query a slot."""
    _, dims, cfg, mesh, params = tiny
    specs = latent_moe.param_specs(cfg, "tp")
    kv = latent_moe.paged_cache_specs("tp")
    chunk = _on_mesh(
        mesh, lambda p, t, c, row: latent_moe.prefill_chunk_paged(
            p, t, c, row, cfg, start=0, wfrom=0, valid=16)[1],
        (specs, P(None), kv, P(None)), kv)
    decode = _on_mesh(
        mesh, lambda p, t, c: latent_moe.decode_step_paged(p, t, c, cfg)[:2],
        (specs, P(None), kv), (P(None, None), kv))
    verify = _on_mesh(
        mesh, lambda p, t, b, c: latent_moe.verify_step_paged(
            p, t, c, cfg, budget=b)[0],
        (specs, P(None, None), P(None), kv), P(None, None, None))
    rng = np.random.default_rng(6)
    row = jnp.asarray([3, 1, 6, 0, 0, 0, 0, 0], jnp.int32)
    cache = chunk(params, jnp.asarray(rng.integers(0, 256, 16), jnp.int32),
                  _empty(cfg), row)
    cache = dataclasses.replace(
        cache, block_table=jnp.stack([row, jnp.zeros_like(row)]),
        lens=jnp.asarray([16, 0], jnp.int32),
        live=jnp.asarray([1, 0], jnp.int32))
    cands = rng.integers(0, 256, size=(2, 3)).astype(np.int32)
    got = np.asarray(verify(params, jnp.asarray(cands),
                            jnp.asarray([3, 3], jnp.int32), cache))[0]
    want = []
    for j in range(3):
        logits, cache = decode(params, jnp.asarray(cands[:, j]), cache)
        want.append(np.asarray(logits)[0])
    np.testing.assert_allclose(got, np.stack(want), rtol=0, atol=1e-4)


def _serve(cfg, mesh, params, prompts, **kw):
    eng = Engine(cfg, mesh, model=latent_moe, mode="xla",
                 dtype=jnp.float32, max_len=64, params=params)
    srv = eng.serving(num_slots=3, page=8, prefill_buckets=(8, 16),
                      telemetry="spans", **kw)
    return srv, srv.generate(prompts, max_new_tokens=12)


def test_the_server_serves_it_with_the_pool_under_pressure(tiny):
    """Chunked prefill, the decode batch riding the chunk programs, the
    token picked on the chip, a slot preempted when the pool runs dry
    and its request resumed: the tokens are those of a roomy pool, and
    ``stats()`` carries the held experts' counters."""
    _, _, cfg, mesh, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, size=n).tolist()
               for n in (21, 37, 9, 30, 26)]
    roomy, want = _serve(cfg, mesh, params, prompts)
    tight, got = _serve(cfg, mesh, params, prompts, num_pages=12)
    assert got == want
    st, rs = tight.stats(), roomy.stats()
    assert st["preemptions"] > 0 and rs["preemptions"] == 0
    for s in (st, rs):
        assert s["decode_dispatches_fused"] > 0
        assert s["prefill_chunks"] > s["chunk_dispatches_kernel_walk"] == 0
        assert s["tokens_picked_on_device"] == s["tokens_generated"] == 60
        assert s["kv_bytes_per_token"] == 2 * 24 * 4
        assert s["expert_pairs_routed"] > s["expert_pairs_held"] > 0
        assert 0.1 < s["expert_held_share"] < 0.45      # 4 of 16 held
        assert s["expert_load_imbalance"] >= 1.0
        assert s["expert_rows_mean"] == pytest.approx(
            s["expert_pairs_held"] / (4 * 2 * s["expert_steps"]))
        # Programs this small take every pair in one pass (the shape
        # rule), so a layer runs one, or none where its 3 decode rows
        # sent no pair to a held expert: a layer or two a run.
        assert (s["expert_steps"] * 2 - 3 <= s["expert_passes"]
                <= s["expert_steps"] * 2)
        assert 0.9 < s["expert_passes_a_layer"] == pytest.approx(
            s["expert_passes"] / (2 * s["expert_steps"]))
        assert s["prefill_cache_size"] <= 2
    events = [e for e in tight.obs.log.spans()
              if e.kind == "expert_load"]
    assert events and all(
        e.attrs["held_pairs"] <= e.attrs["routed_pairs"]
        == e.attrs["rows"] * 4 * 2 for e in events)
    assert all((e.attrs["held_pairs"] > 0) <= e.attrs["passes"] <= 2
               for e in events)
    assert sum(e.attrs["passes"] for e in events) == st["expert_passes"]
    assert tight.decode_cache_size() == 1
    assert isinstance(tight.cache, LatentPagedCache)
    assert tight.cache.pages.shape == (2, 12, 24, 8)


@pytest.fixture(scope="module")
def legal(tiny):
    """The tiny preset with the heads, the latent and the pages of a
    size the chunk rows' kernel tiles (2 heads of 64 + 64 and 128 over a
    latent of 128, pages of 128 positions): (config, dims, ModelConfig,
    mesh, seeded params)."""
    config = dict(
        tiny[0], num_attention_heads=2, num_key_value_heads=2,
        head_dim=128, q_lora_rank=64, kv_lora_rank=128,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        max_position_embeddings=512)
    mesh = tiny[3]
    return (config, F.dims(config), SYS.model_config(config), mesh,
            SYS.make_params(config, mesh, SEED))


def _stated(model, cfg, rows, page=128, dtype=jnp.float32):
    """What ``model.step_kernels`` states for a program of ``rows`` rows
    in all."""
    return model.step_kernels(cfg, rows, decode_rows=0, page=page,
                              dtype=dtype)


def test_the_chunk_walk_is_chosen_by_sizes(tiny, legal):
    cfg, big = tiny[2], legal[2]
    assert "walk" not in _stated(latent_moe, cfg, 16, 8)
    assert "walk" not in _stated(latent_moe, cfg, 128, 128)     # heads
    assert "walk" in _stated(latent_moe, big, 128, 128)
    assert "walk" in _stated(latent_moe, big, 256, 128)
    assert "walk" not in _stated(latent_moe, big, 128, 8)       # pages
    assert "walk" not in _stated(latent_moe, big, 72, 128)      # rows
    with pytest.raises(ValueError, match="the model's own paths"):
        latent_moe.prefill_chunk_paged(
            None, jnp.zeros((128,), jnp.int32), None, None, big, start=0,
            wfrom=0, valid=1, attn_impl="flash")


def test_chunks_through_the_kernel_then_decode_equal_the_reference(legal):
    """:func:`test_chunks_then_decode_through_the_pool_equal_the_reference`
    where the chunk rows walk in the Pallas kernel (interpreted): 200
    tokens prefilled as chunks of 128 and 72 (in a bucket of 128,
    padded), the second with the decode batch aboard, then 8 decode
    steps; float32, the same 1e-4."""
    config, dims, cfg, mesh, params = legal
    specs = latent_moe.param_specs(cfg, "tp")
    kv = latent_moe.paged_cache_specs("tp")
    chunk = _on_mesh(
        mesh, lambda p, t, c, row, start, valid:
        latent_moe.prefill_chunk_paged(p, t, c, row, cfg, start=start,
                                       wfrom=0, valid=valid)[:2],
        (specs, P(None), kv, P(None), P(), P()), (P(None), kv))
    fused = _on_mesh(
        mesh, lambda p, t, d, c, row, start, valid:
        latent_moe.chunk_decode_paged(p, t, d, c, row, cfg, start=start,
                                      wfrom=0, valid=valid)[:3],
        (specs, P(None), P(None), kv, P(None), P(), P()),
        (P(None), P(None, None), kv))
    decode = _on_mesh(
        mesh, lambda p, t, c: latent_moe.decode_step_paged(p, t, c, cfg)[:2],
        (specs, P(None), kv), (P(None, None), kv))
    seq = np.random.default_rng(5).integers(0, dims.vocab, size=209)
    row = jnp.asarray([3, 1], jnp.int32)
    cache = _empty(cfg, pages=4, page=128, slots=2, p_max=2)
    _, cache = chunk(params, jnp.asarray(seq[:128], jnp.int32), cache,
                     row, 0, 128)
    toks = np.zeros(128, np.int32)
    toks[:72] = seq[128:200]
    logits, _, cache = fused(params, jnp.asarray(toks),
                             jnp.zeros((2,), jnp.int32), cache, row, 128,
                             72)
    got = [np.asarray(logits)]
    cache = dataclasses.replace(
        cache, block_table=jnp.stack([jnp.zeros_like(row), row]),
        lens=jnp.asarray([0, 200], jnp.int32),
        live=jnp.asarray([0, 1], jnp.int32))
    for t in seq[200:208]:
        logits, cache = decode(params, jnp.asarray([0, t], jnp.int32),
                               cache)
        got.append(np.asarray(logits)[1])
    want = reference.logits_at(SEED, F, dims, jnp.float32, [seq.tolist()],
                               [list(range(199, 208))])[0]
    assert want.std() > 0.1
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=1e-4)


def test_the_server_counts_its_chunk_dispatches_by_their_walk(legal):
    """Every chunk program of a configuration the kernel tiles walks in
    it: ``chunk_dispatches_kernel_walk`` is ``prefill_chunks``, and every
    ``prefill_chunk`` span says so."""
    _, _, cfg, mesh, params = legal
    eng = Engine(cfg, mesh, model=latent_moe, mode="xla",
                 dtype=jnp.float32, max_len=512, params=params)
    srv = eng.serving(num_slots=2, page=128, prefill_buckets=(128, 256),
                      telemetry="spans")
    rng = np.random.default_rng(4)
    out = srv.generate([rng.integers(0, 256, size=n).tolist()
                        for n in (150, 300)], max_new_tokens=3)
    assert [len(o) for o in out] == [3, 3]
    st = srv.stats()
    assert st["chunk_dispatches_kernel_walk"] == st["prefill_chunks"] == 4
    assert st["chunk_dispatches_kernel_scan"] == 0      # no such layer
    assert st["chunk_dispatches_kernel_experts"] == 0   # 64 and 32 wide
    assert st["attn_impl"] == st["chunk_attn"] == "ref"
    chunks = [e.attrs for e in srv.obs.log.spans()
              if e.kind == "prefill_chunk"]
    assert sorted((a["bucket"], a["walk_kernel"], a["experts_kernel"])
                  for a in chunks) == [
        (128, 1, 0), (128, 1, 0), (128, 1, 0), (256, 1, 0)]


def test_the_server_counts_its_chunk_dispatches_by_their_experts(tiny):
    """The tiny preset with a model width and experts of whole lanes
    (128 and 128): every chunk program's pass is whole row tiles, so its
    held experts run in the Pallas kernel (interpreted here),
    ``chunk_dispatches_kernel_experts`` is ``prefill_chunks`` and every
    ``prefill_chunk`` span says so; the tokens are those of the same
    weights under the XLA form."""
    config = dict(tiny[0], hidden_size=128, moe_intermediate_size=128,
                  max_position_embeddings=512)
    cfg, mesh = SYS.model_config(config), tiny[3]
    params = SYS.make_params(config, mesh, SEED)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (150, 300)]

    def serve():
        eng = Engine(cfg, mesh, model=latent_moe, mode="xla",
                     dtype=jnp.float32, max_len=512, params=params)
        srv = eng.serving(num_slots=2, page=8, prefill_buckets=(128, 256),
                          telemetry="spans")
        return srv, srv.generate(prompts, max_new_tokens=3)

    assert ["experts" in _stated(latent_moe, cfg, rows, 8)
            for rows in (130, 258, 2)] == [True, True, False]
    srv, out = serve()
    st = srv.stats()
    assert st["chunk_dispatches_kernel_experts"] == st["prefill_chunks"] == 4
    assert st["chunk_dispatches_kernel_walk"] == 0      # pages of 8
    assert st["expert_pairs_held"] > 0
    assert {(e.attrs["bucket"], e.attrs["experts_kernel"])
            for e in srv.obs.log.spans() if e.kind == "prefill_chunk"} == {
        (128, 1), (256, 1)}
    asked = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ep_moe, "experts_impl",
                   lambda *a: asked.append(a) or "xla")
        assert serve()[1] == out
    assert asked                      # a new engine traces its programs


def test_what_the_latent_pool_does_not_do_is_refused(tiny):
    _, _, cfg, mesh, params = tiny
    eng = Engine(cfg, mesh, model=latent_moe, mode="xla",
                 dtype=jnp.float32, max_len=64, params=params)
    with pytest.raises(NotImplementedError, match="chunked prefill"):
        eng.serving(num_slots=2, page=8)
    with pytest.raises(NotImplementedError, match="tiered"):
        eng.serving(num_slots=2, page=8, prefill_buckets=(8,),
                    kv_tiers=True)
    with pytest.raises(ValueError, match="not quantized"):
        eng.serving(num_slots=2, page=8, prefill_buckets=(8,),
                    kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="paged latent pool"):
        eng.serve(np.zeros((1, 4), np.int32), gen_len=2)


@pytest.fixture(scope="module")
def v5e():
    """libtpu's description of a v5e:2x2 (nothing attached, nothing
    run), or skip."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — libtpu says why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the chunk rows' kernel through Mosaic as on the chip, not
    through the interpreter this process's CPU backend would choose."""
    from triton_dist_tpu.utils import distributed
    monkeypatch.setattr(distributed, "platform", lambda: "tpu")


def _compile_step(v5e, cfg, program, *, pages, page, slots, p_max,
                  spec_k=4):
    """``program`` of ``models.latent_moe`` (``decode``, ``verify``,
    ``chunk-<rows>``, ``fused-<rows>``: a chunk with the decode batch
    aboard) lowered and compiled for one v5e chip as the serving engine
    jits it (pool donated, output shardings pinned). Returns ``(lowered,
    compiled, the pool's shape)``."""
    from jax.sharding import NamedSharding
    from triton_dist_tpu.serving.blocks import pool_shardings

    mesh = tdt.make_mesh(tp=1, devices=v5e.devices[:1])
    axis, dt = "tp", jnp.bfloat16

    def on_mesh(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=(
                    s if isinstance(s, NamedSharding)
                    else NamedSharding(mesh, s))),
            tree, specs, is_leaf=lambda s: isinstance(s, P))

    specs = latent_moe.param_specs(cfg, axis)
    params = on_mesh(jax.eval_shape(lambda: latent_moe.init_params(
        jax.random.PRNGKey(0), cfg, dt)), specs)
    kv_spec = latent_moe.paged_cache_specs(axis)
    kv_sh = pool_shardings(mesh, kv_spec)
    cache = on_mesh(jax.eval_shape(lambda: LatentPagedCache.empty(
        cfg.num_hidden_layers, pages, page, latent_moe.cache_width(cfg),
        num_slots=slots, p_max=p_max, dtype=dt)), kv_sh)
    ints = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=NamedSharding(mesh, P()))
    if program == "decode":
        step = lambda p, t, c: latent_moe.decode_step_paged(p, t, c, cfg)
        in_specs = (specs, P(None), kv_spec)
        out_specs = (P(None, None), kv_spec, P(None))
        args = (params, ints(slots), cache)
    elif program == "verify":
        step = lambda p, t, b, c: latent_moe.verify_step_paged(
            p, t, c, cfg, budget=b)
        in_specs = (specs, P(None, None), P(None), kv_spec)
        out_specs = (P(None, None, None), kv_spec)
        args = (params, ints(slots, spec_k), ints(slots), cache)
    elif program.startswith("chunk-"):
        step = lambda p, t, c, row, start, wfrom, valid: (
            latent_moe.prefill_chunk_paged(
                p, t, c, row, cfg, start=start, wfrom=wfrom, valid=valid))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P())
        out_specs = (P(None), kv_spec, P(None))
        args = (params, ints(int(program[6:])), cache, ints(p_max),
                ints(), ints(), ints())
    else:
        step = lambda p, t, c, row, start, wfrom, valid, d: (
            latent_moe.chunk_decode_paged(
                p, t, d, c, row, cfg, start=start, wfrom=wfrom,
                valid=valid))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P(),
                    P(None))
        out_specs = (P(None), P(None, None), kv_spec, P(None))
        args = (params, ints(int(program[6:])), cache, ints(p_max),
                ints(), ints(), ints(), ints(slots))
    lowered = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(args.index(cache),),
        out_shardings=tuple(kv_sh if s is kv_spec else NamedSharding(mesh, s)
                            for s in out_specs)).lower(*args)
    return lowered, lowered.compile(), cache.pages.shape


@pytest.mark.parametrize("program", ["decode", "verify", "fused-128",
                                     "fused-512"])
def test_compiled_step_keeps_the_latent_pool_in_place(v5e, mosaic,
                                                      program):
    """Each paged step of ``models.latent_moe``, lowered for one v5e chip
    as the serving engine jits it (pool donated, output shardings
    pinned): the entry computation neither relayouts the latent pool nor
    cuts a layer out of it (writes by ``lax.dynamic_update_slice``, one
    layout; the walks over pages carry it through and only read, the
    chunk rows' kernel fetches pages out of it whole), and the
    temporaries are smaller than one layer of it. The test of
    ``models.dense``'s pool (tests/test_paged_decode.py), for the pool
    this model states. Compile only."""
    from triton_dist_tpu.utils.testing import pool_copies

    cfg = ModelConfig.tiny_latent_moe(
        vocab_size=1024, hidden_size=512, num_attention_heads=8,
        q_lora_rank=256, kv_lora_rank=256, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=256,
        shared_expert_intermediate_size=256, rope_factor=128.0,
        rope_original_max_position=8192, rope_beta_fast=32.0)
    # ONE LAYER of the pool larger than the chip's 128 MiB of VMEM: the
    # walks read ``pages[layer]``, and a layer that fits XLA prefetches
    # there whole, which no serving pool gives it room for.
    _, compiled, pool_shape = _compile_step(
        v5e, cfg, program, pages=2049, page=128, slots=4, p_max=8)
    assert pool_shape == (2, 2049, 320, 128)
    assert pool_copies(compiled.as_text(), pool_shape) == []
    layer_bytes = 2 * int(np.prod(pool_shape[1:]))
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("program", ["chunk-512", "fused-512",
                                     "chunk-2048", "fused-2048"])
def test_compiled_chunk_walks_its_context_in_one_kernel(v5e, mosaic,
                                                        program):
    """The chunk programs at the attention sizes of
    ``mistral-small-4-1chip.longdocs`` (32 heads of 64 + 64 and 128 over
    a latent of 256, 16 slots of 129 pages, chunks of 512 and 2048 rows
    alone and with the decode batch aboard; the FFN and the vocabulary
    small), compiled for one v5e chip: the chunk rows' walk lowers
    through Mosaic, ONCE for the program's six layers (the layer is an
    operand: PERF.md, PR 30), which are one lowered function, no float32
    score array of heads x rows x
    keys is left in the program, the temporaries stay under one block of
    it (32 x 2048 x 1280 x 4 bytes), and the pool is neither copied nor
    relaid. Compile only."""
    from triton_dist_tpu.utils.testing import pool_copies

    rows, slots, p_max = int(program[6:]), 16, 129
    cfg = ModelConfig.tiny_latent_moe(
        vocab_size=1024, hidden_size=1024, num_hidden_layers=6,
        num_attention_heads=32, q_lora_rank=1024, kv_lora_rank=256,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        moe_intermediate_size=256, shared_expert_intermediate_size=256,
        rope_factor=128.0, rope_original_max_position=8192,
        rope_beta_fast=32.0)
    assert "walk" in _stated(latent_moe, cfg, rows)
    lowered, compiled, pool_shape = _compile_step(
        v5e, cfg, program, pages=slots * p_max + 1, page=128, slots=slots,
        p_max=p_max)
    text = lowered.as_text()
    assert text.count('kernel_name = "latent_flash_qblock"') == 1
    # ... and so do the held experts (PR 50: whole row tiles, a width
    # and an f of whole lanes), once for the six layers too.
    assert "experts" in latent_moe.step_kernels(
        cfg, rows, decode_rows=slots * (program[:5] == "fused"), page=128,
        dtype=jnp.bfloat16)
    assert text.count('kernel_name = "grouped_mlp_tiles"') == 1
    # ... inside ONE lowered function of a layer, called six times (a
    # program is traced and lowered at every start: PERF.md, PR 39).
    assert len(re.findall(r"func\.func private @layer\(", text)) == 1
    assert len(re.findall(r"call @layer\(", text)) == 6
    hlo = compiled.as_text()
    assert len(re.findall(r"%latent_flash_qblock(\.\d+)? = ", hlo)) == 6
    assert len(re.findall(r"%grouped_mlp_tiles(\.\d+)? = ", hlo)) == 6
    assert "ragged-dot" not in hlo
    assert f"f32[32,{rows},{latent_moe.BLOCK_KEYS}]" not in hlo
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 32 * 2048 * latent_moe.BLOCK_KEYS * 4)
    assert pool_copies(hlo, pool_shape) == []


@pytest.mark.parametrize("program", ["decode", "fused-512"])
def test_compiled_state_space_steps_keep_pool_and_state_in_place(
        v5e, mosaic, program):
    """``models.mamba_moe``'s paged steps (its pool is the dense
    family's with the attention layers alone, beside what a SEQUENCE
    keeps: PR 43), lowered for one v5e chip as the serving engine jits
    them, at the published Mamba-2 sizes over a narrow residual: neither the K/V pool nor the sequences'
    recurrent state is copied or relaid (both are donated and written
    by ``lax.dynamic_update_slice``), and the chunk rows' and decode
    rows' attention are the two paged kernels at 4 heads a group. Here
    and not in a file of its own: one worker holds libtpu. Compile
    only."""
    from jax.sharding import NamedSharding
    from triton_dist_tpu.models import mamba_moe
    from triton_dist_tpu.serving.blocks import PagedKVCache, pool_shardings
    from triton_dist_tpu.utils.testing import pool_copies

    cfg = ModelConfig.tiny_mamba_moe(
        vocab_size=1024, hidden_size=512, num_hidden_layers=7,
        layer_pattern="MMEM*MM", num_attention_heads=8,
        num_key_value_heads=2, head_dim=128, mamba_num_heads=128,
        mamba_head_dim=64,
        mamba_n_groups=8, ssm_state_size=128, mamba_chunk_size=128,
        moe_latent_size=256, moe_intermediate_size=256,
        shared_expert_intermediate_size=512)
    # The sequences' state as the cell has it, 168 MB: past the chip's
    # 128 MiB of VMEM, where a smaller one is fetched whole.
    slots, page, p_max, pages = 16, 128, 8, 2049
    mesh = tdt.make_mesh(tp=1, devices=v5e.devices[:1])
    dt = jnp.bfloat16

    def on_mesh(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=(
                    s if isinstance(s, NamedSharding)
                    else NamedSharding(mesh, s))),
            tree, specs, is_leaf=lambda s: isinstance(s, P))

    specs = mamba_moe.param_specs(cfg, "tp")
    params = on_mesh(jax.eval_shape(lambda: mamba_moe.init_params(
        jax.random.PRNGKey(0), cfg, dt)), specs)
    kv_spec = mamba_moe.paged_cache_specs("tp")
    kv_sh = pool_shardings(mesh, kv_spec)
    _, per_token, keeps = mamba_moe.paged_pool(cfg)
    cache = on_mesh(jax.eval_shape(lambda: PagedKVCache.empty(
        keeps["layers"], pages, page, *per_token, num_slots=slots,
        p_max=p_max, dtype=dt, seq_state=keeps["seq_state"])), kv_sh)
    ints = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=NamedSharding(mesh, P()))
    if program == "decode":
        step = lambda p, t, c: mamba_moe.decode_step_paged(
            p, t, c, cfg, attn_impl="flash")
        in_specs = (specs, P(None), kv_spec)
        out_specs = (P(None, None), kv_spec, P(None))
        args = (params, ints(slots), cache)
    else:
        step = lambda p, t, c, row, start, wfrom, valid, slot, d: (
            mamba_moe.chunk_decode_paged(
                p, t, d, c, row, cfg, start=start, wfrom=wfrom,
                valid=valid, slot=slot, attn_impl="flash",
                decode_attn_impl="flash"))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P(), P(),
                    P(None))
        out_specs = (P(None), P(None, None), kv_spec, P(None))
        args = (params, ints(512), cache, ints(p_max), ints(), ints(),
                ints(), ints(), ints(slots))
    lowered = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(args.index(cache),),
        out_shardings=tuple(kv_sh if s is kv_spec else NamedSharding(mesh, s)
                            for s in out_specs)).lower(*args)
    hlo = lowered.compile().as_text()
    assert cache.k_pages.shape == (1, 2049, 2, 128, 128)    # one layer's
    state = cache.seq[mamba_moe.STATE]
    assert (state.shape, state.dtype) == ((5, 16, 128, 64, 128), dt)
    assert pool_copies(hlo, cache.k_pages.shape) == []
    assert pool_copies(hlo, state.shape) == []
    assert "paged_flash_decode" in hlo
    assert ("paged_flash_qblock" in hlo) == (program != "decode")
    # The chunk rows' scan is ``ops.mamba2_chunk_scan`` (PR 46), lowered
    # through Mosaic ONCE for the five Mamba-2 layers, every call under
    # the block's scope ``tdt.ssm``; no float32 array of chunks x heads
    # x Q x Q is left in the program. The decode rows step in XLA.
    scans = re.findall(r"%mamba2_chunk_scan(?:\.\d+)? = .*", hlo)
    if program == "decode":
        assert scans == [] and "mamba2_chunk_scan" not in lowered.as_text()
        return
    assert "scan" in _stated(mamba_moe, cfg, 512)
    assert lowered.as_text().count(
        'kernel_name = "mamba2_chunk_scan"') == 1
    assert len(scans) == 5 and all("tdt.ssm/" in s for s in scans)
    assert "f32[4,128,128,128]" not in hlo


def _all_equations(jaxpr, into_kernels=True):
    """Every equation of ``jaxpr`` and of every jaxpr under it (loop and
    branch bodies; a Pallas kernel's body unless ``into_kernels`` is
    False)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_equations(sub, into_kernels)


def _equations(jaxpr) -> int:
    return sum(1 for _ in _all_equations(jaxpr))


def _pallas_bodies(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"]
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _pallas_bodies(sub)


@pytest.mark.parametrize("rows", [512, 2048])
def test_the_chunk_kernels_traced_body_stays_small(rows):
    """The set-up budget of ``ops.latent_flash_qblock`` (PERF.md, PR 39):
    a step program is traced and lowered at every start, no cache keeps
    either, so the kernel's body is what its loops hold ONCE. At the
    attention sizes of ``mistral-small-4-1chip.longdocs`` the traced
    body is 231 equations for both buckets; with the sub-tiles, the
    heads of a group and the pages of a step as Python loops (PR 38's
    form, refused on ``setup_s``) it was 1,345 at 2048 rows and 697 at
    512, 0.39 + 0.30 s to trace and lower against 0.06 + 0.07 s on this
    sandbox's CPU. 260 is held: a body that grows past it is paid by
    every start of the server. Trace only, nothing runs."""
    from triton_dist_tpu.ops import latent_flash_qblock as K

    s = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    assert K.block_sizes(rows, 32, 128, 128, 2) == (rows, 4, 256, 4)
    closed = jax.make_jaxpr(
        lambda *a: K._latent_qblock_call(*a, sigma=0.1))(
        s((rows, 32, 128), bf), s((6, 2065, 320, 128), bf),
        s((129,), jnp.int32), s((rows,), jnp.int32), s((256, 32, 192), bf),
        s((1,), jnp.int32))
    bodies = list(_pallas_bodies(closed.jaxpr))
    assert len(bodies) == 1
    assert _equations(bodies[0]) <= 260


@pytest.mark.parametrize("rows", [512, 2048])
def test_the_scan_kernels_traced_body_stays_small(rows):
    """The same budget for ``ops.mamba2_chunk_scan`` (PR 46) at the
    Mamba-2 sizes of ``nemotron-3-super-1chip.longdocs``: the slabs of
    a group are ONE traced body (a ``pl.loop``, unrolled by Mosaic and
    not by Python), 169 equations in both buckets; 200 is held. Trace
    only, nothing runs."""
    from triton_dist_tpu.ops import mamba2_chunk_scan as K

    s, f32 = jax.ShapeDtypeStruct, jnp.float32
    closed = jax.make_jaxpr(lambda *a: K.ssd_chunk_scan(*a, chunk=128))(
        s((rows, 128, 64), f32), s((rows, 128), f32), s((128,), f32),
        s((rows, 8, 128), f32), s((rows, 8, 128), f32), s((128,), f32),
        s((128, 64, 128), f32))
    bodies = list(_pallas_bodies(closed.jaxpr))
    assert len(bodies) == 1
    assert _equations(bodies[0]) <= 200


@pytest.mark.parametrize("t,n_held,passes_over_all,impl", [
    (2064, 32, False, "kernel"), (528, 32, False, "kernel"),
    (16, 32, True, "xla"), (2064, 128, True, "xla")])
def test_the_held_experts_lay_out_held_pairs_only(t, n_held,
                                                  passes_over_all, impl):
    """The expert block at the sizes of ``mistral-small-4-1chip`` (d
    4096, f 2048, 4 of 128 a token; trace only, nothing runs): ONE body
    a layer (a step program is traced and lowered at every start), and
    no array of ``T * topk`` rows by ``d`` or by ``f`` where a pass is
    smaller than every pair. The pass follows the shapes: a share of
    the pairs with a margin for the two chunk programs, every pair for
    the 16 decode rows and for a layer that holds every expert. What a
    pass runs follows them too (``ep_moe.experts_impl``): under
    ``"xla"`` three grouped products of the pass's rows and not six;
    under ``"kernel"`` ONE ``pallas_call`` and no grouped product, and
    outside it nothing wider than the pass's layout, ``rows / 128 +
    held`` tiles of 128 rows: fewer rows than the pairs in the 2048-row
    program, and in the 512-row one the 39 tiles its 32 experts may
    need."""
    d, f, topk, e = 4096, 2048, 4, 128
    pairs = t * topk
    rows = ep_moe.held_pass_rows(t, topk, n_held, e)
    assert (rows == pairs) == passes_over_all
    assert ep_moe.experts_impl(rows, n_held, d, f, jnp.bfloat16) == impl
    if not passes_over_all:
        # The share with its margin, in an odd number of 128-row tiles.
        assert rows % 256 == 128
        assert 0 <= rows - 1.25 * pairs * n_held / e < 256
    s, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    params = {"router": s((d, e), bf), "w_gate": s((n_held, d, f), bf),
              "w_up": s((n_held, d, f), bf), "w_down": s((n_held, f, d), bf),
              "w_shared_gate": s((d, f), bf), "w_shared_up": s((d, f), bf),
              "w_shared_down": s((f, d), bf)}
    closed = jax.make_jaxpr(lambda p, x: ep_moe.fwd_held(
        p, x, topk=topk, routed_scale=1.0))(params, s((t, d), bf))
    eqns = list(_all_equations(closed.jaxpr, into_kernels=False))
    products = [q for q in eqns
                if q.primitive.name.startswith("ragged_dot")]
    kernels = [q for q in eqns if q.primitive.name == "pallas_call"]
    if impl == "kernel":
        laid_out = rows + 128 * n_held
        assert (len(products), len(kernels)) == (0, 1)
        assert [v.aval.shape for v in kernels[0].outvars] == [(laid_out, d)]
        assert (laid_out < pairs) == (t == 2064)
    else:
        laid_out = rows
        assert (len(products), len(kernels)) == (3, 0)
        assert sorted(q.outvars[0].aval.shape for q in products) == [
            (rows, f), (rows, f), (rows, d)]
    if not passes_over_all:
        wide = [v.aval.shape for q in eqns for v in q.outvars
                if getattr(v.aval, "ndim", 0) >= 2
                and v.aval.shape[-1] in (d, f)
                and int(np.prod(v.aval.shape[:-1])) > max(
                    laid_out, pairs - 1)]
        assert wide == []


def _pool_results(hlo: str, pool_shape, dtype: str = "bf16"):
    """``(op, line)`` of every instruction of ANY computation of an
    optimised HLO module whose result holds an array of ``pool_shape``:
    the entry's, a ``while``'s body's, a fusion's."""
    pool = dtype + "[" + ",".join(map(str, pool_shape)) + "]"
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if m and pool in m.group(1):
            out.append((m.group(2), line.strip()[:200]))
    return out


@pytest.mark.parametrize("program", ["decode", "fused-128", "fused-512"])
def test_compiled_looped_steps_keep_the_pool_in_place(v5e, mosaic, program):
    """``models.looped``'s paged steps (layers applied several times
    over stacked weights, a cache a pass: PR 48), lowered for one v5e
    chip as the serving engine jits them, at the attention sizes and the
    pool geometry of ``ouro-2.6b-1chip.fewshot`` (16 heads over 16 KV
    heads of 128: ONE head a group; 6 slots of 6 pages, 37 pages of 128)
    with 8 layers applied four times and a narrow FFN (a pool of 32
    layers, 2.5 GB a side: at 6 pool layers, 0.47 GB, XLA's memory-space
    assignment stages the whole pool through another space and back in
    the 512-row program, which the cell's 192 layers leave it no room
    for). The pool is written
    INSIDE the scans over passes and layers, so ``pool_copies``, which
    reads the entry computation, does not apply: here every instruction
    of every computation that yields the pool is a parameter, a tuple
    or its element, a loop, or an update in place (alone or as all a
    fusion does to it), in the one row-major layout. Both paged kernels
    lower through Mosaic once for the 32 layer applications (the pool's
    layer is an operand). Here and not in a file of its own: one worker
    holds libtpu. Compile only."""
    from jax.sharding import NamedSharding
    from triton_dist_tpu.models import looped
    from triton_dist_tpu.serving.blocks import PagedKVCache, pool_shardings

    cfg = ModelConfig.tiny_looped(
        vocab_size=1024, hidden_size=2048, intermediate_size=512,
        num_hidden_layers=8, num_passes=4, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, rope_theta=1e6)
    slots, page, p_max, pages = 6, 128, 6, 37
    mesh = tdt.make_mesh(tp=1, devices=v5e.devices[:1])
    dt = jnp.bfloat16

    def on_mesh(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=(
                    s if isinstance(s, NamedSharding)
                    else NamedSharding(mesh, s))),
            tree, specs, is_leaf=lambda s: isinstance(s, P))

    specs = looped.param_specs(cfg, "tp")
    params = on_mesh(jax.eval_shape(lambda: looped.init_params(
        jax.random.PRNGKey(0), cfg, dt)), specs)
    kv_spec = looped.paged_cache_specs("tp")
    kv_sh = pool_shardings(mesh, kv_spec)
    _, per_token, keeps = looped.paged_pool(cfg)
    cache = on_mesh(jax.eval_shape(lambda: PagedKVCache.empty(
        keeps["layers"], pages, page, *per_token, num_slots=slots,
        p_max=p_max, dtype=dt)), kv_sh)
    assert cache.k_pages.shape == (32, 37, 16, 128, 128)
    ints = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=NamedSharding(mesh, P()))
    if program == "decode":
        step = lambda p, t, c: looped.decode_step_paged(
            p, t, c, cfg, attn_impl="flash")
        in_specs = (specs, P(None), kv_spec)
        out_specs = (P(None, None), kv_spec, P(None))
        args = (params, ints(slots), cache)
    else:
        step = lambda p, t, c, row, start, wfrom, valid, d: (
            looped.chunk_decode_paged(
                p, t, d, c, row, cfg, start=start, wfrom=wfrom,
                valid=valid, attn_impl="flash", decode_attn_impl="flash"))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P(),
                    P(None))
        out_specs = (P(None), P(None, None), kv_spec, P(None))
        args = (params, ints(int(program[6:])), cache, ints(p_max), ints(),
                ints(), ints(), ints(slots))
    lowered = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(args.index(cache),),
        out_shardings=tuple(kv_sh if s is kv_spec else NamedSharding(mesh, s)
                            for s in out_specs)).lower(*args)
    text = lowered.as_text()
    assert text.count('kernel_name = "paged_flash_decode"') == 1
    assert text.count('kernel_name = "paged_flash_qblock"') == (
        program != "decode")
    compiled = lowered.compile()
    hlo = compiled.as_text()
    yields = _pool_results(hlo, cache.k_pages.shape)
    in_place = {"parameter", "tuple", "get-tuple-element", "bitcast",
                "while", "dynamic-update-slice", "fusion"}
    assert yields and [ln for op, ln in yields if op not in in_place] == []
    assert {"while", "dynamic-update-slice"} <= {op for op, _ in yields}
    pool = "bf16[32,37,16,128,128]"
    assert set(re.findall(re.escape(pool) + r"\{([\d,]+)", hlo)) == {
        "4,3,2,1,0"}
    # Donated: both sides' bytes are aliased outputs, and the program's
    # temporaries are far smaller than one side of the pool.
    mem = compiled.memory_analysis()
    side = 2 * int(np.prod(cache.k_pages.shape))
    assert mem.alias_size_in_bytes >= 2 * side
    assert mem.temp_size_in_bytes < side // 8


@pytest.mark.parametrize("program", ["decode", "chunk-512", "fused-512"])
def test_compiled_window_steps_keep_both_pools_in_place(v5e, mosaic,
                                                        program):
    """``models.window_moe``'s paged steps (window layers whose pages
    are a ring in a pool of their own beside the global layers': PR 52),
    lowered for one v5e chip as the serving engine jits them, at the
    attention sizes and the page geometry of
    ``k-exaone-236b-1chip.longdocs`` (64 heads over 8 KV heads of 128,
    pages of 128, window 128, a ring of 18 for buckets up to 2,048) with
    narrow FFNs, and with the cell's 8 slots of 129 pages (shapes only:
    a pool of tens of megabytes XLA's memory-space assignment stages
    through another space and back, which the cell's 0.54 and 0.23 GB a
    side leave it no room for). Both pools are written and read
    with the layer an operand, in place: every instruction of every
    computation that yields either pool is a parameter, a tuple or its
    element, or an update in place, in the one row-major layout, and
    both are aliased outputs. Each paged kernel lowers through Mosaic
    TWICE a program, once with the window and once without, for the
    eight layers. Here and not in a file of its own: one worker holds
    libtpu. Compile only."""
    from jax.sharding import NamedSharding
    from triton_dist_tpu.models import window_moe
    from triton_dist_tpu.serving.blocks import PagedKVCache, pool_shardings

    cfg = ModelConfig.tiny_window_moe(
        vocab_size=1024, hidden_size=1024, intermediate_size=512,
        num_attention_heads=64, num_key_value_heads=8, head_dim=128,
        sliding_window=128, rope_theta=1e6, moe_intermediate_size=256,
        shared_expert_intermediate_size=256, num_experts=16,
        num_held_experts=4)
    slots, page, p_max = 8, 128, 129
    mesh = tdt.make_mesh(tp=1, devices=v5e.devices[:1])
    dt = jnp.bfloat16

    def on_mesh(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=(
                    s if isinstance(s, NamedSharding)
                    else NamedSharding(mesh, s))),
            tree, specs, is_leaf=lambda s: isinstance(s, P))

    specs = window_moe.param_specs(cfg, "tp")
    params = on_mesh(jax.eval_shape(lambda: window_moe.init_params(
        jax.random.PRNGKey(0), cfg, dt)), specs)
    _, per_token, keeps = window_moe.paged_pool(cfg)
    window = keeps["window"].sized(page, 2048, slots)
    assert (window.ring, window.num_pages) == (18, 145)
    kv_spec = window_moe.paged_cache_specs("tp", ring=window.ring)
    kv_sh = pool_shardings(mesh, kv_spec)
    cache = on_mesh(jax.eval_shape(lambda: PagedKVCache.empty(
        keeps["layers"], 1 + slots * p_max, page, *per_token,
        num_slots=slots, p_max=p_max, dtype=dt, window=window)), kv_sh)
    pools = {cache.k_pages.shape: "global", cache.win["k"].shape: "window"}
    assert pools == {(2, 1033, 8, 128, 128): "global",
                     (6, 145, 8, 128, 128): "window"}
    assert cache.block_table.shape == (slots, p_max + 18)
    ints = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=NamedSharding(mesh, P()))
    flash = dict(attn_impl="flash")
    if program == "decode":
        step = lambda p, t, c: window_moe.decode_step_paged(
            p, t, c, cfg, **flash)
        in_specs = (specs, P(None), kv_spec)
        out_specs = (P(None, None), kv_spec, P(None))
        args = (params, ints(slots), cache)
    elif program.startswith("chunk-"):
        step = lambda p, t, c, row, start, wfrom, valid: (
            window_moe.prefill_chunk_paged(
                p, t, c, row, cfg, start=start, wfrom=wfrom, valid=valid,
                **flash))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P())
        out_specs = (P(None), kv_spec, P(None))
        args = (params, ints(int(program[6:])), cache, ints(p_max + 18),
                ints(), ints(), ints())
    else:
        step = lambda p, t, c, row, start, wfrom, valid, d: (
            window_moe.chunk_decode_paged(
                p, t, d, c, row, cfg, start=start, wfrom=wfrom,
                valid=valid, decode_attn_impl="flash", **flash))
        in_specs = (specs, P(None), kv_spec, P(None), P(), P(), P(),
                    P(None))
        out_specs = (P(None), P(None, None), kv_spec, P(None))
        args = (params, ints(int(program[6:])), cache, ints(p_max + 18),
                ints(), ints(), ints(), ints(slots))
    lowered = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=(args.index(cache),),
        out_shardings=tuple(kv_sh if s is kv_spec else NamedSharding(mesh, s)
                            for s in out_specs)).lower(*args)
    text = lowered.as_text()
    assert text.count('kernel_name = "paged_flash_decode"') == 2 * (
        not program.startswith("chunk-"))
    assert text.count('kernel_name = "paged_flash_qblock"') == 2 * (
        program != "decode")
    compiled = lowered.compile()
    hlo = compiled.as_text()
    in_place = {"parameter", "tuple", "get-tuple-element", "bitcast",
                "dynamic-update-slice", "fusion", "custom-call"}
    for shape in pools:
        yields = _pool_results(hlo, shape)
        assert yields and [ln for op, ln in yields
                           if op not in in_place] == []
        # The Mosaic kernels READ a pool; none gives one back.
        assert not [ln for op, ln in yields if op == "custom-call"
                    and ln.split(" = ")[1].startswith("bf16[")]
        pool = "bf16[" + ",".join(map(str, shape)) + "]"
        assert set(re.findall(re.escape(pool) + r"\{([\d,]+)", hlo)) == {
            "4,3,2,1,0"}
    mem = compiled.memory_analysis()
    sides = 2 * 2 * sum(int(np.prod(shape)) for shape in pools)
    assert mem.alias_size_in_bytes >= sides
    assert mem.temp_size_in_bytes < sides // 8
