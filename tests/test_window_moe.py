"""``models.window_moe`` on the CPU at a tiny size: window layers whose
pages are a bounded ring beside global layers that keep every position,
the lower bound of the paged kernels, the leading dense layer and the
held experts behind a sigmoid router, and the server around them,
against the benchmark family's plain reference
(``benchmark/families/swa_moe.py``, which imports nothing of the program
and writes the mask over whole sequences) on seeded weights.

The preset is the real block small: d 64; two periods ``LLLG``; window
8, pages of 4, so that with chunk buckets (4, 16) a slot's ring is
``(8 + 16) / 4 + 1 = 7`` pages, 28 positions; 4 query heads over 2 KV
heads of 16; layer 0 dense (96 wide), the rest 8 experts of width 32, 2
a token and 2 held, one shared expert of 32.
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import triton_dist_tpu as tdt
from benchmark.harness import loader, reference, weights as W
from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.models import Engine, ModelConfig, window_moe
from triton_dist_tpu.ops import chunked_prefill as cp
from triton_dist_tpu.ops.paged_flash_decode import paged_flash_decode
from triton_dist_tpu.ops.paged_flash_qblock import (paged_flash_qblock,
                                                    window_walk_pages)
from triton_dist_tpu.serving.blocks import (BlockManager, OutOfPagesError,
                                            PagedKVCache, WindowLayers)

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
F = loader.load_family("swa_moe", [loader.DATA_ROOT])
SYS = loader.sibling(F.__file__, "swa_moe_system")
SEED = 13
PAGE, BUCKETS, SLOTS, P_MAX = 4, (4, 16), 3, 24


@pytest.fixture(scope="module")
def tiny():
    """(config file, dims, ModelConfig, mesh, seeded params)."""
    with open(os.path.join(DATA, "configs", "tiny-swa.json")) as f:
        config = json.load(f)
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    return (config, F.dims(config), SYS.model_config(config), mesh,
            SYS.make_params(config, mesh, SEED))


def _on_mesh(mesh, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _sized(cfg, slots=SLOTS):
    _, per_token, keeps = window_moe.paged_pool(cfg)
    return per_token, keeps["layers"], keeps["window"].sized(
        PAGE, max(BUCKETS), slots)


def _empty(cfg, *, pages=1 + SLOTS * P_MAX, rings=SLOTS):
    per_token, layers, window = _sized(cfg)
    window = window._replace(num_pages=1 + rings * window.ring)
    return PagedKVCache.empty(layers, pages, PAGE, *per_token,
                              num_slots=SLOTS, p_max=P_MAX,
                              dtype=jnp.float32, window=window), window


# -- the configuration ------------------------------------------------------

def _catalog():
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")


def test_config_reads_the_catalogs_entry_verbatim():
    """36 window layers among 12 global ones, one leading dense layer,
    and the name's 236B from ``param_specs`` shapes alone (nothing is
    allocated)."""
    pub = _catalog()["config"]
    with pytest.raises(NotImplementedError, match="multi-token"):
        ModelConfig.from_hf_config(pub)
    cfg = ModelConfig.from_hf_config(pub, leave_out=("mtp",))
    assert cfg.attn_pattern == "LLLG" * 12
    assert (cfg.num_window_layers, cfg.num_paged_layers,
            cfg.num_moe_layers, cfg.first_dense_layers,
            cfg.sliding_window) == (36, 12, 47, 1, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.shared_expert_intermediate_size) == (128, 8, 2048, 18432,
                                                     2048)
    assert (cfg.moe_scoring, cfg.routed_scaling_factor, cfg.rms_norm_eps,
            cfg.qk_norm, cfg.rope_theta) == ("sigmoid", 2.5, 1e-5, True,
                                             1e6)
    shapes = jax.eval_shape(lambda: window_moe.init_params(
        jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    sizes = [sum(math.prod(x.shape) for x in jax.tree.leaves(lp))
             for lp in shapes["layers"]]
    attn = 6144 * (8192 + 2 * 1024) + 8192 * 6144 + 2 * 128 + 2 * 6144
    assert sizes[0] == attn + 3 * 6144 * 18432
    assert set(sizes[1:]) == {attn + 6144 * 128 + 128
                              + 129 * 3 * 6144 * 2048}
    total = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert round(total / 1e9, 1) == 236.6                  # the name's 236B
    active = total - 47 * (128 - 8) * 3 * 6144 * 2048
    assert round(active / 1e9, 1) == 23.7                  # and its A23B
    specs = window_moe.param_specs(cfg, "tp")
    assert jax.tree.structure(specs["layers"], is_leaf=lambda s: isinstance(
        s, P)) == jax.tree.structure(shapes["layers"])


@pytest.mark.parametrize("change, what", [
    ({"n_group": 2}, "group-limited"),
    ({"topk_group": 2}, "group-limited"),
    ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}},
     "rope_type"),
    ({"layer_types": ["chunked_attention"] * 48}, "layer_types"),
    ({"sliding_windows": [64] * 48}, "sliding_windows"),
    ({"mlp_layer_types": ["sparse"] * 48}, "mlp_layer_types"),
    ({"scoring_func": "softmax"}, "sigmoid router")])
def test_what_is_not_computed_is_refused_by_the_reader(change, what):
    with pytest.raises(NotImplementedError, match=what):
        ModelConfig.from_hf_config(dict(_catalog()["config"], **change),
                                   leave_out=("mtp",))


@pytest.mark.parametrize("model_type, more", [
    ("qwen3", {}), ("qwen3_moe", {"num_experts": 8}),
    ("seed_oss", {}), ("llama", {}), ("ouro", {"total_ut_steps": 2}),
    ("mistral4", {"kv_lora_rank": 16, "q_lora_rank": 16,
                  "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
                  "v_head_dim": 8})])
@pytest.mark.parametrize("window", [
    {"layer_types": ["sliding_attention", "full_attention"],
     "sliding_window": 128},
    {"sliding_window": 4096, "use_sliding_window": True}])
def test_a_window_is_refused_by_a_family_that_reads_every_key(
        model_type, more, window):
    """ROADMAP R4's first bullet: a sliding-window schedule on a family
    whose layers all read every key is an error that names the family,
    not a load that serves it with full attention."""
    base = dict(model_type=model_type, vocab_size=64, hidden_size=32,
                num_hidden_layers=2, num_attention_heads=4, **more)
    ModelConfig.from_hf_config(base)                  # sound without it
    if model_type != "ouro":     # which refuses the key whatever it says
        ModelConfig.from_hf_config(dict(base, sliding_window=4096,
                                        use_sliding_window=False))
    with pytest.raises(NotImplementedError, match=model_type):
        ModelConfig.from_hf_config(dict(base, **window))


def test_the_tiny_file_builds_the_programs_config(tiny):
    config, dims, cfg, _, params = tiny
    assert cfg == ModelConfig.tiny_window_moe(model_name="tiny-swa")
    assert [F.layer_kind(dims, li) for li in (0, 1, 3)] == [
        "window_dense", "window_sparse", "global_sparse"]
    assert "mlp" in params["layers"][0] and "moe" in params["layers"][1]
    assert params["layers"][1]["moe"]["w_up"].shape == (2, 64, 32)
    _, _, window = _sized(cfg)
    assert window == WindowLayers(6, 8, ring=7, num_pages=1 + SLOTS * 7)
    assert window_moe.step_kernels(cfg, 16, decode_rows=3, page=PAGE,
                                   dtype=jnp.float32) == ()


# -- the kernels' lower bound ------------------------------------------------

def _ring_pool(seed, *, layers=2, ring=7, kvh=2, hd=16, slots=2):
    """A window pool whose every page holds noise, and each slot's ring
    a shuffled set of its pages."""
    rng = np.random.default_rng(seed)
    pages = 1 + slots * ring
    pool = lambda: jnp.asarray(rng.normal(
        size=(layers, pages, kvh, PAGE, hd)), jnp.float32)
    table = np.stack([1 + s * ring + rng.permutation(ring)
                      for s in range(slots)]).astype(np.int32)
    return pool(), pool(), jnp.asarray(table)


def _at(table, slot, pos):
    """(page id, offset) of position ``pos`` in ``slot``'s ring."""
    ring = table.shape[1]
    return int(table[slot, (pos // PAGE) % ring]), pos % PAGE


def _read_chunk(impl, q, kp, vp, table, qpos, w):
    """Row block of slot 1, layer 1, through one of the three walks."""
    if impl == "flash":
        return paged_flash_qblock(q[None], kp, vp, table[1:2], qpos[None],
                                  layer=1, window=w)[0]
    first = max(int(qpos[0]) - (w - 1), 0) // PAGE
    kd, key_pos = cp.gather_ring_dense(kp[1], table[1], first,
                                       table.shape[1])
    vd, _ = cp.gather_ring_dense(vp[1], table[1], first, table.shape[1])
    return cp.window_attend(q[None], kd[None], vd[None], qpos[None],
                            key_pos[None], w)[0]


def _read_decode(impl, q, kp, vp, table, kv_len, w):
    if impl == "flash":
        return paged_flash_decode(q, kp, vp, table, kv_len, layer=1,
                                  axis=None, window=w)
    n = window_walk_pages(1, w, PAGE)
    first = jnp.maximum(kv_len - w, 0) // PAGE
    kd, key_pos = cp.gather_ring_dense(kp[1], table, first, n)
    vd, _ = cp.gather_ring_dense(vp[1], table, first, n)
    return cp.window_attend(q[:, None], kd, vd, (kv_len - 1)[:, None],
                            key_pos, w)[:, 0]


@pytest.mark.parametrize("impl", ["flash", "ref"])
def test_the_windows_edge_in_a_chunks_rows(impl):
    """Rows at positions 41..48 of a slot three rings deep, window 8:
    row ``i`` is left as it was by a change to key ``i - 8`` and moved
    by one to key ``i - 7``, through the Q-block kernel (interpreted)
    and through the gather walk; both agree."""
    w = 8
    kp, vp, table = _ring_pool(0)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(8, 4, 16)),
                    jnp.float32)
    qpos = jnp.arange(41, 49, dtype=jnp.int32)
    base = np.asarray(_read_chunk(impl, q, kp, vp, table, qpos, w))
    other = np.asarray(_read_chunk("ref" if impl == "flash" else "flash",
                                   q, kp, vp, table, qpos, w))
    np.testing.assert_allclose(base, other, rtol=1e-5, atol=1e-5)
    for row in (0, 3, 7):
        i = 41 + row
        pid, off = _at(table, 1, i - w)
        out = np.asarray(_read_chunk(
            impl, q, kp.at[1, pid, :, off].add(5.0),
            vp.at[1, pid, :, off].add(5.0), table, qpos, w))
        np.testing.assert_array_equal(out[row], base[row])
        pid, off = _at(table, 1, i - w + 1)
        out = np.asarray(_read_chunk(
            impl, q, kp, vp.at[1, pid, :, off].add(5.0), table, qpos, w))
        assert np.abs(out[row] - base[row]).max() > 1e-3
        # and that key lies behind every later row's window
        np.testing.assert_array_equal(out[row + 1:], base[row + 1:])


@pytest.mark.parametrize("impl", ["flash", "ref"])
def test_the_windows_edge_in_decode_rows(impl):
    """One query a slot at lengths 30 and 45 (the query at ``len - 1``):
    key ``len - 1 - 8`` changes nothing, key ``len - 8`` does; a slot
    of length 3 reads its three keys."""
    w = 8
    kp, vp, table = _ring_pool(2, slots=3)
    q = jnp.asarray(np.random.default_rng(3).normal(size=(3, 4, 16)),
                    jnp.float32)
    lens = jnp.asarray([30, 45, 3], jnp.int32)
    base = np.asarray(_read_decode(impl, q, kp, vp, table, lens, w))
    other = np.asarray(_read_decode("ref" if impl == "flash" else "flash",
                                    q, kp, vp, table, lens, w))
    np.testing.assert_allclose(base, other, rtol=1e-5, atol=1e-5)
    for slot, n in ((0, 30), (1, 45)):
        pid, off = _at(table, slot, n - 1 - w)
        out = np.asarray(_read_decode(
            impl, q, kp.at[1, pid, :, off].add(5.0),
            vp.at[1, pid, :, off].add(5.0), table, lens, w))
        np.testing.assert_array_equal(out, base)
        pid, off = _at(table, slot, n - w)
        out = np.asarray(_read_decode(
            impl, q, kp, vp.at[1, pid, :, off].add(5.0), table, lens, w))
        assert np.abs(out[slot] - base[slot]).max() > 1e-3
    pid, off = _at(table, 2, 0)
    out = np.asarray(_read_decode(
        impl, q, kp, vp.at[1, pid, :, off].add(5.0), table, lens, w))
    assert np.abs(out[2] - base[2]).max() > 1e-3


@pytest.mark.parametrize("start", [0, 5, 41, 127])
def test_a_chunks_walk_reads_no_page_out_of_reach(start):
    """Every page of the pool that holds no key in reach of the block's
    rows is NaN, the other layer too: the kernel's output is finite and
    the gather walk's, so no such page was fetched (a fetched page's
    values meet the softmax's zeros as NaN). The walk is
    ``window_walk_pages`` pages at most, whatever the context."""
    w, rows = 8, 8
    assert window_walk_pages(rows, w, PAGE) == 5 < 7
    assert window_walk_pages(512, 128, 128) == 6
    kp, vp, table = _ring_pool(4, ring=40)
    qpos = jnp.arange(start, start + rows, dtype=jnp.int32)
    q = jnp.asarray(np.random.default_rng(5).normal(size=(rows, 4, 16)),
                    jnp.float32)
    want = np.asarray(_read_chunk("ref", q, kp, vp, table, qpos, w))
    reach = {_at(table, 1, p)[0]
             for p in range(max(start - w + 1, 0), start + rows)}
    keep = np.zeros(kp.shape[:2], bool)
    keep[1, sorted(reach)] = True
    poison = jnp.where(jnp.asarray(keep)[:, :, None, None, None], 0.0,
                       jnp.nan)
    got = np.asarray(_read_chunk("flash", q, kp + poison, vp + poison,
                                 table, qpos, w))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_window_zero_is_the_kernel_it_was():
    """``window=0`` traces the Q-block and decode kernels with the
    operands and the grid they had: no ring, no lower bound."""
    kp, vp, table = _ring_pool(6)
    q = jnp.ones((2, 8, 4, 16), jnp.float32)
    qpos = jnp.tile(jnp.arange(8, dtype=jnp.int32), (2, 1))
    plain = jax.make_jaxpr(lambda *a: paged_flash_qblock(*a, layer=1))(
        q, kp, vp, table, qpos)
    zero = jax.make_jaxpr(lambda *a: paged_flash_qblock(
        *a, layer=1, window=0))(q, kp, vp, table, qpos)
    assert str(plain) == str(zero)
    with pytest.raises(ValueError, match="ring has"):
        paged_flash_qblock(q, kp, vp, table[:, :3], qpos, layer=1, window=8)


# -- the expert layer -------------------------------------------------------

def test_the_shares_add_up(tiny):
    """The routed parts the four chips of the tiny deployment compute
    plus the shared expert counted ONCE are the uncut layer: the
    program's held-experts layer share by share against the reference
    with all 8 experts (and the reference's own shares against
    itself)."""
    _, dims, cfg, _, _ = tiny
    whole = dataclasses.replace(dims, held=8, first_held=0)
    w = {k: v.astype(jnp.float32) for k, v in W.make_layer(
        W.root_key(SEED), 1, F.layer_leaves(whole, "window_sparse"),
        F.LEAF_IDS, jnp.float32).items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (48, dims.d), jnp.float32)
    want = F.experts(x, w, whole, reference._dot) - x
    y = reference.rms(x, w["ln_mlp"], dims.eps)
    always = {"router": w["router"], "router_bias": w["router_bias"],
              "w_shared_gate": w["shared_gate"],
              "w_shared_up": w["shared_up"],
              "w_shared_down": w["shared_down"]}
    once = ep_moe.shared_expert_out(always, y)
    total, ref_total, pairs = once, once, 0
    for first in range(0, 8, 2):
        held = slice(first, first + 2)
        moe = dict(always, w_gate=w["experts_gate"][held],
                   w_up=w["experts_up"][held],
                   w_down=w["experts_down"][held])
        out, stats = ep_moe.fwd_held(
            moe, y, topk=cfg.num_experts_per_tok, first=first,
            routed_scale=cfg.routed_scaling_factor, scoring="sigmoid")
        total = total + (out - once)
        pairs += int(stats[0])
        share = dataclasses.replace(dims, held=2, first_held=first)
        ws = dict(w, **{k: w[k][held] for k in (
            "experts_gate", "experts_up", "experts_down")})
        ref_total = ref_total + (
            F.experts(x, ws, share, reference._dot) - x - once)
    assert pairs == 48 * 2               # every pair fell to one share
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(ref_total, want, rtol=1e-4, atol=2e-5)


# -- chunks, then decode, through both pools ---------------------------------

def _programs(cfg, mesh, impl="ref"):
    _, _, window = _sized(cfg)
    specs = window_moe.param_specs(cfg, "tp")
    kv = window_moe.paged_cache_specs("tp", ring=window.ring)
    chunk = _on_mesh(
        mesh, lambda p, t, c, row, start, valid:
        window_moe.prefill_chunk_paged(p, t, c, row, cfg, start=start,
                                       wfrom=0, valid=valid,
                                       attn_impl=impl)[:2],
        (specs, P(None), kv, P(None), P(), P()), (P(None), kv))
    decode = _on_mesh(
        mesh, lambda p, t, c: window_moe.decode_step_paged(
            p, t, c, cfg, attn_impl=impl),
        (specs, P(None), kv), (P(None, None), kv, P(None)))
    fused = _on_mesh(
        mesh, lambda p, t, dd, c, row, start, valid:
        window_moe.chunk_decode_paged(p, t, dd, c, row, cfg, start=start,
                                      wfrom=0, valid=valid, attn_impl=impl,
                                      decode_attn_impl=impl)[:3],
        (specs, P(None), P(None), kv, P(None), P(), P()),
        (P(None), P(None, None), kv))
    return chunk, decode, fused


def _prefill(chunk, params, cache, row, seq, splits, bucket=16):
    """``seq`` through ``chunk`` in pieces of ``splits`` rows, each
    padded to ``bucket``; returns every piece's logits."""
    start, out = 0, []
    for n in splits:
        toks = np.zeros(bucket, np.int32)
        toks[:n] = seq[start:start + n]
        logits, cache = chunk(params, jnp.asarray(toks), cache, row, start,
                              n)
        out.append(np.asarray(logits))
        start += n
    assert start == len(seq)
    return out, cache


def _rows(manager, slots):
    return [jnp.asarray(manager.table_row(s), jnp.int32) for s in slots]


def _batch(cache, manager, lens):
    """``cache`` with the decode batch's table, lengths and live mask:
    a slot of length 0 parked (an all-zero row, both tables)."""
    rows = [manager.table_row(s) if n else [0] * manager.table_width
            for s, n in enumerate(lens)]
    return dataclasses.replace(
        cache, block_table=jnp.asarray(rows, jnp.int32),
        lens=jnp.asarray(lens, jnp.int32),
        live=jnp.asarray([int(n > 0) for n in lens], jnp.int32))


def test_chunks_then_decode_equal_the_reference_several_rings_deep(tiny):
    """An 80-token sequence in slot 1, whose ring holds 28 positions: 64
    tokens prefilled in chunks whose ends lie off page boundaries (every
    chunk padded to its bucket of 16), then 16 decode steps fed the
    sequence's own tokens, slot 0 parked beside it and slot 2 live with
    another sequence. The logits after every chunk and after each
    decoded token equal the reference's full forward at those
    positions: float32 on both sides, 2e-4 absolute."""
    _, dims, cfg, mesh, params = tiny
    chunk, decode, _ = _programs(cfg, mesh)
    rng = np.random.default_rng(5)
    seq, other = (rng.integers(0, dims.vocab, size=80) for _ in range(2))
    cache, window = _empty(cfg)
    manager = BlockManager(1 + SLOTS * P_MAX, PAGE, P_MAX, window=window)
    manager.alloc_prefill(1, seq.tolist())
    manager.alloc_prefill(2, other[:70].tolist())
    row1, row2 = _rows(manager, (1, 2))
    splits = (16, 13, 16, 3, 16)
    firsts, cache = _prefill(chunk, params, cache, row1, seq[:64], splits)
    _, cache = _prefill(chunk, params, cache, row2, other[:37],
                        (16, 16, 5))
    got, got2 = [firsts[-1]], []
    cache = _batch(cache, manager, [0, 64, 37])
    for t, t2 in zip(seq[64:79], other[37:52]):
        logits, cache, _ = decode(
            params, jnp.asarray([0, t, t2], jnp.int32), cache)
        got.append(np.asarray(logits)[1])
        got2.append(np.asarray(logits)[2])
    assert cache.lens.tolist() == [0, 79, 52]
    ends = list(np.cumsum(splits) - 1)
    want_chunks, want, want2 = reference.logits_at(
        SEED, F, dims, jnp.float32,
        [seq.tolist(), seq.tolist(), other.tolist()],
        [ends, list(range(63, 79)), list(range(37, 52))])
    assert want.std() > 0.1
    np.testing.assert_allclose(np.stack(firsts), want_chunks, rtol=0,
                               atol=2e-4)
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=2e-4)
    np.testing.assert_allclose(np.stack(got2), want2, rtol=0, atol=2e-4)


def test_a_second_request_in_a_slot_never_reads_the_firsts_keys(tiny):
    """Slot 1 served a request 60 tokens long and is freed; nothing is
    zeroed, and the next request takes the SAME ring (the window pool
    has two). Its chunks run in the program that also
    decodes (``chunk_decode_paged``), slot 0 live beside it: the chunk's
    logits and the riding row's are the reference's. What bounds the
    reads is the inequality and the slot's length, not the pool's
    contents."""
    _, dims, cfg, mesh, params = tiny
    chunk, _, fused = _programs(cfg, mesh)
    rng = np.random.default_rng(8)
    old, new, rider = (rng.integers(0, dims.vocab, size=n)
                       for n in (60, 45, 40))
    cache, window = _empty(cfg, rings=2)      # so that a ring comes back
    manager = BlockManager(1 + SLOTS * P_MAX, PAGE, P_MAX, window=window)
    manager.alloc_prefill(0, rider.tolist())
    manager.alloc_prefill(1, old.tolist())
    row0, row1 = _rows(manager, (0, 1))
    _, cache = _prefill(chunk, params, cache, row1, old, (16, 16, 16, 12))
    _, cache = _prefill(chunk, params, cache, row0, rider[:32], (16, 16))
    manager.free_slot(1)
    assert manager.stats["window_pages_recycled"] == 15 - 7
    manager.alloc_prefill(1, new.tolist())
    assert _rows(manager, (1,))[0].tolist()[P_MAX:] == (
        row1.tolist()[P_MAX:])
    assert float(jnp.abs(cache.win["k"]).max()) > 0
    row1 = _rows(manager, (1,))[0]
    firsts, cache = _prefill(chunk, params, cache, row1, new[:13], (13,))
    cache = _batch(cache, manager, [32, 0, 0])
    got_rider, start = [], 13
    for step, n in enumerate((16, 16)):
        toks = np.zeros(16, np.int32)
        toks[:n] = new[start:start + n]
        logits, dec, cache = fused(
            params, jnp.asarray(toks),
            jnp.asarray([rider[32 + step], 0, 0], jnp.int32), cache, row1,
            start, n)
        got_rider.append(np.asarray(dec)[0])
        start += n
    assert cache.lens.tolist() == [34, 0, 0]
    want_new, want_rider = reference.logits_at(
        SEED, F, dims, jnp.float32, [new.tolist(), rider.tolist()],
        [[12, 44], [32, 33]])
    np.testing.assert_allclose(np.stack([firsts[0], np.asarray(logits)]),
                               want_new, rtol=0, atol=2e-4)
    np.testing.assert_allclose(np.stack(got_rider), want_rider, rtol=0,
                               atol=2e-4)


def test_the_kernels_serve_the_same_logits(tiny):
    """The chunk + decode program with both paged kernels
    (``attn_impl="flash"``, interpreted): a 16-row chunk 40 positions
    deep with a decode row aboard gives the gather walk's logits."""
    _, dims, cfg, mesh, params = tiny
    rng = np.random.default_rng(9)
    seq, rider = (rng.integers(0, dims.vocab, size=n) for n in (56, 30))
    out = {}
    for impl in ("ref", "flash"):
        chunk, _, fused = _programs(cfg, mesh, impl)
        cache, window = _empty(cfg)
        manager = BlockManager(1 + SLOTS * P_MAX, PAGE, P_MAX,
                               window=window)
        manager.alloc_prefill(0, rider.tolist())
        manager.alloc_prefill(1, seq.tolist())
        row0, row1 = _rows(manager, (0, 1))
        if impl == "ref":      # the context is the gather walk's either way
            _, cache = _prefill(chunk, params, cache, row1, seq[:40],
                                (16, 16, 8))
            _, cache = _prefill(chunk, params, cache, row0, rider[:25],
                                (16, 9))
            context = cache
        cache = _batch(context, manager, [25, 0, 0])
        logits, dec, cache = fused(
            params, jnp.asarray(seq[40:56], jnp.int32),
            jnp.asarray([rider[25], 0, 0], jnp.int32), cache, row1, 40, 16)
        out[impl] = np.asarray(logits), np.asarray(dec)[0]
    for a, b in zip(out["ref"], out["flash"]):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-4)


# -- the manager ------------------------------------------------------------

def test_the_manager_allocates_frees_and_counts_both_pools():
    window = WindowLayers(6, 8).sized(PAGE, 16, num_slots=2)
    assert (window.ring, window.num_pages) == (7, 15)
    m = BlockManager(40, PAGE, 12, window=window)
    assert m.table_width == 19
    m.alloc_prefill(0, list(range(30)))
    for pos in range(30, 44):
        m.append(0, pos)
    # 44 tokens are 11 pages of the global pool; the ring stays 7.
    row = m.table_row(0)
    assert len(row) == 19 and row[11] == 0 and all(row[:11])
    assert m.window_pages(0) == 7 and sorted(row[12:]) == list(range(1, 8))
    frag = m.fragmentation()
    assert (frag["used_pages"], frag["window_used_pages"],
            frag["window_pages_a_slot"]) == (11, 7, 7)
    m.alloc_prefill(1, [1, 2, 3])
    assert m.window_pages(1) == 7        # the whole ring, whatever the prompt
    with pytest.raises(OutOfPagesError, match="window page pool"):
        m.alloc_prefill(2, [1])
    assert m.fragmentation()["used_pages"] == 12   # the roll-back
    m.free_slot(0)
    m.free_slot(1)
    frag = m.fragmentation()
    assert (frag["free_pages"], frag["window_free_pages"]) == (39, 14)
    assert frag["window_pages_recycled"] == 11 - 7
    assert m.table_row(0) == [0] * 19
    # The global pool's own error keeps its words.
    small = BlockManager(3, PAGE, 12, window=window)
    with pytest.raises(OutOfPagesError, match=r"^page pool exhausted"):
        small.alloc_prefill(0, list(range(30)))
    assert small.fragmentation()["window_used_pages"] == 0
    snap = m.snapshot()
    m.alloc_prefill(0, [5] * 9)
    fresh = BlockManager(40, PAGE, 12, window=window)
    fresh.load_snapshot(m.snapshot())
    assert fresh.table_row(0) == m.table_row(0)
    fresh.load_snapshot(snap)
    assert fresh.window_pages(0) == 0
    with pytest.raises(ValueError, match="prefix_reuse"):
        BlockManager(40, PAGE, 12, window=window, prefix_reuse=True)
    with pytest.raises(ValueError, match="not sized"):
        BlockManager(40, PAGE, 12, window=WindowLayers(6, 8))


# -- the server -------------------------------------------------------------

def _engine(tiny, **kw):
    config, _, cfg, mesh, params = tiny
    return Engine(cfg, mesh, model=window_moe, mode="xla",
                  dtype=jnp.float32, max_len=96, params=params,
                  fallback=None, **kw)


def test_the_server_serves_it_and_counts_both_pools(tiny):
    """Five requests over two slots (so slots are reused), prompts 9 to
    61 tokens against a ring of 28 positions: every served token is the
    reference's best at its position, and ``stats()`` says what the two
    pools hold beside what one table would."""
    _, dims, cfg, _, _ = tiny
    srv = _engine(tiny).serving(num_slots=2, page=PAGE,
                                prefill_buckets=BUCKETS,
                                telemetry="spans")
    assert srv.cache.block_table.shape == (2, 24 + 7)
    assert srv.cache.win["k"].shape == (6, 15, 2, PAGE, 16)
    assert srv.cache.k_pages.shape == (2, 49, 2, PAGE, 16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, dims.vocab, size=n).tolist()
               for n in (37, 50, 9, 61, 30)]
    outs = srv.generate(prompts, max_new_tokens=6)
    seqs, wanted = zip(*(reference.served_positions(p, t)
                         for p, t in zip(prompts, outs)))
    for rows, toks in zip(reference.logits_at(
            SEED, F, dims, jnp.float32, list(seqs), list(wanted)), outs):
        assert reference.gaps(rows, toks).max() <= 1e-4
    st = srv.stats()
    a_page = 2 * 2 * PAGE * 16 * 4
    assert (st["window_layers"], st["global_layers"],
            st["window_pages_a_slot"]) == (6, 2, 7)
    assert st["pool_bytes_global"] == a_page * 2 * 49
    assert st["pool_bytes_window"] == a_page * 6 * 15
    assert st["pool_bytes_one_table"] == a_page * 8 * 49
    assert (st["pool_bytes_global"] + st["pool_bytes_window"]
            < 0.5 * st["pool_bytes_one_table"])
    # Ring entries written over: each request's pages past its ring.
    pages = [-(-(len(p) + 5) // PAGE) for p in prompts]
    assert st["window_pages_recycled"] == sum(max(n - 7, 0) for n in pages)
    assert st["pool"]["window_used_pages"] == 0
    assert st["expert_pairs_held"] > 0
    chunks = [s for s in srv.obs.log.spans() if s.kind == "prefill_chunk"]
    assert chunks and {s.attrs["window_pages"] for s in chunks} == {7}
    assert srv.prefill_cache_size() <= len(BUCKETS)


@pytest.mark.parametrize("knob, what", [
    ({"spec_k": 2}, "spec_k"),
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"prefill_buckets": None}, "monolithic"),
    ({"kv_tiers": "host"}, "kv_tiers")])
def test_what_two_tables_rule_out_is_refused_by_name(tiny, knob, what):
    kw = dict(num_slots=2, page=PAGE, prefill_buckets=BUCKETS)
    kw.update(knob)
    if "kv_tiers" in knob:
        from triton_dist_tpu.serving.tiers import KVTierStore
        kw["kv_tiers"] = KVTierStore(host_pages=8)
    with pytest.raises(NotImplementedError, match=what):
        _engine(tiny).serving(**kw)


def test_the_other_refusals(tiny):
    """Verification rows and the disaggregated prefill worker, which
    move pages by the one table, and the Engine's dense-cache path."""
    _, _, cfg, _, _ = tiny
    assert not hasattr(window_moe, "verify_step_paged")
    from triton_dist_tpu.models import paged_step
    rows = paged_step.Rows(token_ids=jnp.zeros((2, 3), jnp.int32))
    with pytest.raises(NotImplementedError, match="verification rows"):
        paged_step.kv_attend(rows, "ref", "ref", window=8)
    from triton_dist_tpu.serving.disagg import PrefillWorker
    with pytest.raises(NotImplementedError, match="another pool"):
        PrefillWorker(_engine(tiny), page=PAGE, p_max=24, num_slots=2,
                      buckets=BUCKETS)
    with pytest.raises(NotImplementedError, match="rings"):
        window_moe.prefill()
    with pytest.raises(ValueError, match="unquantized"):
        PagedKVCache.empty(2, 9, PAGE, 2, 16, num_slots=2, p_max=4,
                           kv_dtype="int8",
                           window=WindowLayers(6, 8).sized(PAGE, 16, 2))
