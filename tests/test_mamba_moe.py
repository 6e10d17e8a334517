"""``models.mamba_moe`` on the CPU at a tiny size: the Mamba-2 scan, the
state a sequence keeps beside its pages, the sigmoid router and the
latent squared-ReLU experts, and the server around them, against the
benchmark family's plain reference
(``benchmark/families/mamba_latent_moe.py``, which imports nothing of the
program and runs the recurrence a token at a time) on seeded weights.

The preset is the real block small: d 64; layers ``MEM*EM``; 8 Mamba
heads of 16 over 2 groups of 16, 4 taps, scan chunks of 8 rows; 4 query
heads over 2 KV heads of 16; 16 experts of width 48 in a latent of 32,
4 a token and 4 held, one shared expert of 96.
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
from triton_dist_tpu.models import Engine, ModelConfig, mamba_moe
from triton_dist_tpu.ops import mamba2, mamba2_chunk_scan
from triton_dist_tpu.serving.blocks import PagedKVCache

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
F = loader.load_family("mamba_latent_moe", [loader.DATA_ROOT])
SYS = loader.sibling(F.__file__, "mamba_latent_moe_system")
SEED = 13


@pytest.fixture(scope="module")
def tiny():
    """(config file, dims, ModelConfig, mesh, seeded params)."""
    with open(os.path.join(DATA, "configs", "tiny-mamba.json")) as f:
        config = json.load(f)
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    return (config, F.dims(config), SYS.model_config(config), mesh,
            SYS.make_params(config, mesh, SEED))


def _on_mesh(mesh, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _empty(cfg, *, pages=15, page=8, slots=3, p_max=8, dtype=jnp.float32):
    _, per_token, keeps = mamba_moe.paged_pool(cfg)
    return PagedKVCache.empty(keeps["layers"], pages, page, *per_token,
                              num_slots=slots, p_max=p_max, dtype=dtype,
                              seq_state=keeps["seq_state"])


# -- the configuration ------------------------------------------------------

def _catalog():
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")


def test_config_reads_the_catalogs_entry_verbatim():
    """40 / 40 / 8 layers by kind, and the name's 120B from
    ``param_specs`` shapes alone (nothing is allocated)."""
    cfg = ModelConfig.from_hf_config(_catalog()["config"])
    assert [cfg.layer_pattern.count(c) for c in "ME*"] == [40, 40, 8]
    assert (cfg.num_moe_layers, cfg.num_paged_layers) == (40, 8)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.mamba_conv_kernel,
            cfg.mamba_chunk_size) == (128, 64, 8, 128, 4, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_latent_size,
            cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size) == (512, 22, 1024, 2688,
                                                     5376)
    assert (cfg.moe_scoring, cfg.moe_act, cfg.routed_scaling_factor,
            cfg.rms_norm_eps, cfg.qk_norm) == ("sigmoid", "relu2", 5, 1e-5,
                                               False)
    shapes = jax.eval_shape(lambda: mamba_moe.init_params(
        jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    by_kind = {}
    for letter, lp in zip(cfg.layer_pattern, shapes["layers"]):
        by_kind.setdefault(letter, set()).add(
            sum(math.prod(x.shape) for x in jax.tree.leaves(lp)))
    # An expert's two matrices are STORED 3,072 wide for the published
    # 2,688, the rest zeros (``ep_moe.expert_store_width``): no
    # parameter of the model.
    padding = 512 * 2 * 1024 * (3072 - 2688)
    assert ep_moe.expert_store_width(2688) == 3072
    assert by_kind == {"M": {109_640_064}, "*": {35_655_680},
                       "E": {54_530_560 + 512 * 5_505_024 + padding}}
    total = sum(math.prod(x.shape)
                for x in jax.tree.leaves(shapes)) - 40 * padding
    assert total == 120_668_707_840                       # 120.67 B
    # Active a token: 22 of an expert layer's 512 experts.
    active = total - 40 * (512 - 22) * 5_505_024
    assert round(active / 1e9, 2) == 12.77
    specs = mamba_moe.param_specs(cfg, "tp")
    assert jax.tree.structure(specs["layers"], is_leaf=lambda s: isinstance(
        s, P)) == jax.tree.structure(shapes["layers"])


@pytest.mark.parametrize("change, what", [
    ({"n_group": 2}, "group-limited"),
    ({"topk_group": 2}, "group-limited"),
    ({"hybrid_override_pattern": "M-" * 44}, "hybrid_override_pattern"),
    ({"mlp_hidden_act": "silu"}, "squared-ReLU"),
    ({"use_bias": True}, "no bias")])
def test_what_is_not_computed_is_refused_by_the_reader(change, what):
    with pytest.raises(NotImplementedError, match=what):
        ModelConfig.from_hf_config(dict(_catalog()["config"], **change))


def test_the_tiny_file_builds_the_programs_config(tiny):
    config, dims, cfg, _, params = tiny
    assert cfg.layer_pattern == "MEM*EM" == dims.pattern
    assert (cfg.num_experts, cfg.held_experts, cfg.first_held_expert) == (
        16, 4, 0)
    assert [F.layer_kind(dims, i) for i in range(6)] == [
        "mamba", "experts", "mamba", "attention", "experts", "mamba"]
    m = params["layers"][0]["mamba"]
    # The three a published implementation keeps in float32, the seeded
    # leaf plus the family's constant.
    leaf = W.make_layer(W.root_key(SEED), 0, F.layer_leaves(dims, "mamba"),
                        F.LEAF_IDS, jnp.float32)
    for name, off in (("dt_bias", F.DT_BIAS_OFFSET),
                      ("a_log", F.A_LOG_OFFSET)):
        assert m[name].dtype == jnp.float32
        np.testing.assert_allclose(m[name], leaf[name] + off, rtol=1e-6)
    assert set(params["layers"][1]["moe"]) == {
        "router", "router_bias", "w_latent_in", "w_up", "w_down",
        "w_latent_out", "w_shared_up", "w_shared_down"}
    assert params["layers"][1]["moe"]["w_up"].shape == (4, 32, 48)


# -- the scan ----------------------------------------------------------------

def _scan_by_token(x, dt, A, B, C, D, initial_state=None):
    """The oracle: ``ssd_step`` under a ``lax.scan``, a token at a time.
    Arguments and returns as ``mamba2.ssd_chunked``."""
    if initial_state is None:
        initial_state = jnp.zeros(x.shape[1:] + B.shape[-1:], jnp.float32)

    def step(S, row):
        y, S = mamba2.ssd_step(S, row[0], row[1], A, row[2], row[3], D)
        return S, y

    S, y = jax.lax.scan(step, initial_state, (x, dt, B, C))
    return y, S


@pytest.mark.parametrize("decay", [0.5, 0.9, 0.99, 0.9999])
@pytest.mark.parametrize("chunk, rows", [(128, 300), (128, 128), (8, 45),
                                         (7, 20)])
def test_the_chunked_scan_is_the_token_sequential_one(decay, chunk, rows):
    """At decays from 0.5 to 0.9999 a step, at the published chunk of
    128 against lengths that are no multiple of it, from a state that is
    not zero; then a second call that carries the first one's state."""
    k = jax.random.split(jax.random.PRNGKey(rows), 6)
    h, p, g, n = 4, 8, 2, 16
    x = jax.random.normal(k[0], (rows, h, p))
    b = jax.random.normal(k[1], (rows, g, n))
    c = jax.random.normal(k[2], (rows, g, n))
    dt = jax.nn.softplus(jax.random.normal(k[3], (rows, h)))
    a = jnp.full((h,), math.log(decay)) / jnp.mean(dt)
    d = 1.0 + 0.1 * jax.random.normal(k[4], (h,))
    s0 = jax.random.normal(k[5], (h, p, n))
    want_y, want_s = _scan_by_token(x, dt, a, b, c, d, s0)
    got_y, got_s = mamba2.ssd_chunked(x, dt, a, b, c, d, s0, chunk=chunk)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=1e-5 * scale)
    cut = rows // 3
    y1, s1 = mamba2.ssd_chunked(x[:cut], dt[:cut], a, b[:cut], c[:cut], d,
                                s0, chunk=chunk)
    y2, s2 = mamba2.ssd_chunked(x[cut:], dt[cut:], a, b[cut:], c[cut:], d,
                                s1, chunk=chunk)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want_y, rtol=0,
                               atol=1e-5 * scale)
    np.testing.assert_allclose(s2, want_s, rtol=0, atol=1e-5 * scale)


def test_a_row_with_no_step_size_neither_decays_nor_writes():
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(k[0], (24, 4, 8))
    b, c = (jax.random.normal(kk, (24, 2, 16)) for kk in k[1:3])
    dt = jax.nn.softplus(jax.random.normal(k[3], (24, 4)))
    dt = jnp.where(jnp.arange(24)[:, None] < 17, dt, 0.0)
    a, d = -jnp.ones((4,)), jnp.ones((4,))
    _, s_pad = mamba2.ssd_chunked(x, dt, a, b, c, d, chunk=8)
    _, s_cut = _scan_by_token(x[:17], dt[:17], a, b[:17], c[:17], d)
    np.testing.assert_allclose(s_pad, s_cut, rtol=0, atol=1e-5)


# -- the scan as one Pallas kernel (interpreted here) ------------------------

# Sizes the kernel tiles that the interpreter holds (no buffer past 64
# KB: docs/testing.md): scan chunks of 128 rows, 2 groups of 2 heads of
# 64 (one 128-lane slab a group), a state of 128.
KH, KP, KG, KN = 4, 64, 2, 128


def _scan_inputs(rows, decay, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed + rows), 6)
    x = jax.random.normal(k[0], (rows, KH, KP))
    b = jax.random.normal(k[1], (rows, KG, KN))
    c = jax.random.normal(k[2], (rows, KG, KN))
    dt = jax.nn.softplus(jax.random.normal(k[3], (rows, KH)))
    a = jnp.full((KH,), math.log(decay)) / jnp.mean(dt)
    d = 1.0 + 0.1 * jax.random.normal(k[4], (KH,))
    s0 = jax.random.normal(k[5], (KH, KP, KN))
    return x, dt, a, b, c, d, s0


@pytest.mark.parametrize("decay", [0.5, 0.9, 0.99, 0.9999])
def test_the_scan_kernel_is_the_token_sequential_scan(decay):
    """Three scan chunks of 128 rows from a state that is not zero, at
    decays from 0.5 to 0.9999 a step: the kernel (what ``ssd_prefill``
    picks at these sizes) against ``ssd_step`` a token at a time, and
    against ``ssd_chunked``, whose sums it repeats."""
    x, dt, a, b, c, d, s0 = _scan_inputs(384, decay)
    assert mamba2.chunk_scan_impl(384, KH, KP, KG, KN, 128) == "kernel"
    want_y, want_s = _scan_by_token(x, dt, a, b, c, d, s0)
    got_y, got_s = mamba2.ssd_prefill(x, dt, a, b, c, d, s0, chunk=128)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=1e-5 * scale)
    xla_y, xla_s = mamba2.ssd_chunked(x, dt, a, b, c, d, s0, chunk=128)
    np.testing.assert_allclose(got_y, xla_y, rtol=0, atol=2e-6 * scale)
    np.testing.assert_allclose(got_s, xla_s, rtol=0, atol=2e-6 * scale)


def test_the_scan_kernel_carries_a_state_from_call_to_call():
    """One call over three chunks is a call over the first and a call
    over the other two that starts from what the first left; no state
    given is a state of zeros."""
    x, dt, a, b, c, d, s0 = _scan_inputs(384, 0.99, seed=1)
    scan = mamba2_chunk_scan.ssd_chunk_scan
    want_y, want_s = scan(x, dt, a, b, c, d, s0, chunk=128)
    y1, s1 = scan(x[:128], dt[:128], a, b[:128], c[:128], d, s0, chunk=128)
    y2, s2 = scan(x[128:], dt[128:], a, b[128:], c[128:], d, s1, chunk=128)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want_y, rtol=0,
                               atol=1e-6 * scale)
    np.testing.assert_allclose(s2, want_s, rtol=0, atol=1e-6 * scale)
    none_y, none_s = scan(x[:128], dt[:128], a, b[:128], c[:128], d,
                          chunk=128)
    zero_y, zero_s = scan(x[:128], dt[:128], a, b[:128], c[:128], d,
                          jnp.zeros_like(s0), chunk=128)
    np.testing.assert_array_equal(none_y, zero_y)
    np.testing.assert_array_equal(none_s, zero_s)


def test_a_row_with_no_step_size_stays_out_of_the_scan_kernel():
    """Rows past ``valid`` and padding have ``dt = 0``: in the kernel
    too they neither decay the state nor write to it, inside the last
    chunk and as a whole chunk of nothing."""
    x, dt, a, b, c, d, s0 = _scan_inputs(384, 0.9, seed=2)
    dt = jnp.where(jnp.arange(384)[:, None] < 200, dt, 0.0)
    y_pad, s_pad = mamba2_chunk_scan.ssd_chunk_scan(x, dt, a, b, c, d, s0,
                                                    chunk=128)
    y_cut, s_cut = _scan_by_token(x[:200], dt[:200], a, b[:200], c[:200],
                                  d, s0)
    scale = float(jnp.max(jnp.abs(y_cut)))
    np.testing.assert_allclose(s_pad, s_cut, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(y_pad[:200], y_cut, rtol=0,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("sizes, impl", [
    ((2048, 128, 64, 8, 128, 128), "kernel"),    # the cell's two buckets
    ((512, 128, 64, 8, 128, 128), "kernel"),
    ((384, KH, KP, KG, KN, 128), "kernel"),      # these tests'
    ((16, 8, 16, 2, 16, 8), "xla"),              # the tiny file's
    ((2048, 128, 64, 8, 128, 8), "xla"),         # a chunk of 8 rows
    ((2000, 128, 64, 8, 128, 128), "xla"),       # a ragged T
    ((2048, 128, 48, 8, 128, 128), "xla"),       # heads that fill no slab
    ((2048, 128, 64, 8, 64, 128), "xla"),        # a state of half a tile
    ((2048, 12, 64, 8, 128, 128), "xla"),        # heads in no whole groups
    ((2048, 128, 64, 1, 128, 128), "xla")])      # a group VMEM does not hold
def test_the_scans_form_is_a_pure_function_of_sizes(sizes, impl):
    assert mamba2.chunk_scan_impl(*sizes) == impl
    if impl == "xla":
        rows, h, p, g, n, chunk = sizes
        with pytest.raises(ValueError, match="cannot tile"):
            mamba2_chunk_scan.ssd_chunk_scan(
                jnp.zeros((rows, h, p)), jnp.zeros((rows, h)),
                jnp.zeros((h,)), jnp.zeros((rows, g, n)),
                jnp.zeros((rows, g, n)), jnp.zeros((h,)), chunk=chunk)


def _stated(cfg, rows):
    """What ``mamba_moe.step_kernels`` states for a program of ``rows``
    rows in all."""
    return mamba_moe.step_kernels(cfg, rows, decode_rows=0, page=8,
                                  dtype=jnp.float32)


def test_the_model_states_the_scans_form_for_its_sizes(tiny):
    """``mamba_moe.step_kernels`` states the op's rule at the model's
    sizes: the kernel at the published Mamba-2 sizes in both of the
    cell's buckets, the XLA form for the tiny file."""
    _, _, cfg, _, _ = tiny
    assert not any("scan" in _stated(cfg, r) for r in (8, 16, 128))
    cell = ModelConfig.tiny_mamba_moe(
        mamba_num_heads=128, mamba_head_dim=64, mamba_n_groups=8,
        ssm_state_size=128, mamba_chunk_size=128)
    assert ["scan" in _stated(cell, r) for r in (2048, 512, 200)
            ] == [True, True, False]


# -- the router and the experts ---------------------------------------------

def _route_twin(w, x, k, bias, scale=1.0):
    """Ten lines: sigmoid scores, the bias for the choice alone, the
    chosen scores renormalised."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w, np.float64))))
    ids = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1,
                     kind="stable")[:, :k]
    got = np.take_along_axis(s, ids, axis=-1)
    return ids, scale * got / got.sum(axis=-1, keepdims=True)


def test_route_sigmoid_with_a_selection_only_bias():
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    w = jax.random.normal(k[0], (32, 24)) * 32 ** -0.5
    x = jax.random.normal(k[1], (50, 32))
    bias = 0.3 * jax.random.normal(k[2], (24,))
    ids, wts = ep_moe.route(w, x, 5, scoring="sigmoid", bias=bias)
    want_ids, want_w = _route_twin(w, x, 5, bias)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(wts, want_w, rtol=1e-5)
    # The bias moved the choice (else the test shows nothing) and not
    # the weights of what was chosen.
    plain_ids, _ = ep_moe.route(w, x, 5, scoring="sigmoid")
    assert (np.asarray(plain_ids) != np.asarray(ids)).any()
    with pytest.raises(ValueError, match="scoring"):
        ep_moe.route(w, x, 5, scoring="tanh")


def test_route_softmax_is_what_it_was_bit_for_bit():
    """The softmax top-k path, with the arguments the other families
    pass, traces to the equations it traced to before ``scoring`` and
    ``bias`` existed, and gives the same bits."""
    def before(router_w, x, topk):
        logits = jnp.dot(x.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        topk_w, topk_ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         topk)
        topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
        return topk_ids.astype(jnp.int32), topk_w

    k = jax.random.split(jax.random.PRNGKey(5), 2)
    w = jax.random.normal(k[0], (32, 24)) * 32 ** -0.5
    x = jax.random.normal(k[1], (50, 32))
    now = lambda w_, x_: ep_moe.route(w_, x_, 4)
    assert (str(jax.make_jaxpr(now)(w, x).jaxpr)
            == str(jax.make_jaxpr(lambda w_, x_: before(w_, x_, 4))(
                w, x).jaxpr))
    for got, want in zip(now(w, x), before(w, x, 4)):
        np.testing.assert_array_equal(got, want)


def test_experts_are_stored_at_whole_tiles_and_no_other_width_is_served():
    """Wide experts are stored at whole tiles of the ragged product
    (2,688 -> 3,072), the padding zeros; narrow ones as they are. The
    padded layer gives what the published width gives (an all-pairs
    twin at 1,030 columns: zeros added to each sum), and ``fwd_held``
    refuses the unpadded tree instead of serving it on slower tiles."""
    assert [ep_moe.expert_store_width(f) for f in (48, 768, 1024, 2048,
                                                   2688, 3072)] == [
        48, 768, 1024, 2048, 3072, 3072]
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    d, f, e = 32, 1030, 4
    moe = {"router": jax.random.normal(k[0], (d, 8)) * d ** -0.5,
           "w_up": jax.random.normal(k[1], (e, d, f)) * d ** -0.5,
           "w_down": jax.random.normal(k[2], (e, f, d)) * f ** -0.5}
    gate = jax.random.normal(k[4], (e, d, f)) * d ** -0.5
    x = jax.random.normal(k[3], (24, d))
    ids, w = ep_moe.route(moe["router"], x, 2)

    def twin(gated):
        h = jnp.einsum("td,edf->tef", x, moe["w_up"])
        a = (jax.nn.silu(jnp.einsum("td,edf->tef", x, gate)) * h if gated
             else jnp.square(jax.nn.relu(h)))
        y = jnp.einsum("tef,efd->ted", a, moe["w_down"])
        mine = jnp.take_along_axis(y, jnp.minimum(ids, e - 1)[..., None],
                                   axis=1)
        return jnp.sum(jnp.where((ids < e)[..., None],
                                 mine * w[..., None], 0.0), axis=1)

    with pytest.raises(ValueError, match="whole 512s"):
        ep_moe.fwd_held(moe, x, topk=2, act="relu2")
    up, down, gate_p = ep_moe.pad_expert_width(moe["w_up"], moe["w_down"],
                                               gate)
    assert (up.shape, down.shape, gate_p.shape) == (
        (e, d, 1536), (e, 1536, d), (e, d, 1536))
    assert not up[..., f:].any() and not down[:, f:].any()
    stored = dict(moe, w_up=up, w_down=down)
    got, _ = ep_moe.fwd_held(stored, x, topk=2, act="relu2")
    np.testing.assert_allclose(got, twin(False), rtol=1e-4, atol=1e-5)
    got, _ = ep_moe.fwd_held(dict(stored, w_gate=gate_p), x, topk=2)
    np.testing.assert_allclose(got, twin(True), rtol=1e-4, atol=1e-5)


def test_shared_expert_takes_the_form_its_parameters_hold():
    rng = np.random.default_rng(1)
    p = {"w_shared_up": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
         "w_shared_down": jnp.asarray(rng.normal(size=(8, 16)),
                                      jnp.float32)}
    x = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    want = jnp.square(jax.nn.relu(x @ p["w_shared_up"])) @ p["w_shared_down"]
    np.testing.assert_allclose(ep_moe.shared_expert_out(p, x), want,
                               rtol=1e-5, atol=1e-5)


def test_the_shares_add_up(tiny, monkeypatch):
    """The routed parts the four chips of the deployment compute, each
    through the latent's way out, plus the shared expert counted once,
    are the uncut layer: the program's held-experts layer share by share
    against the reference with all 16 experts (and the reference's own
    shares against itself)."""
    _, dims, cfg, _, _ = tiny
    whole = dataclasses.replace(dims, held=16, first_held=0)
    w = {k: v.astype(jnp.float32) for k, v in W.make_layer(
        W.root_key(SEED), 1, F.layer_leaves(whole, "experts"), F.LEAF_IDS,
        jnp.float32).items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (48, dims.d), jnp.float32)
    want = F.experts(x, w, whole, reference._dot) - x
    y = reference.rms(x, w["ln"], dims.eps)
    always = {"router": w["router"], "router_bias": w["router_bias"],
              "w_latent_in": w["w_latent_in"],
              "w_latent_out": w["w_latent_out"],
              "w_shared_up": w["shared_up"],
              "w_shared_down": w["shared_down"]}
    once = ep_moe.shared_expert_out(always, y)
    total, ref_total, pairs = once, once, 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        moe = dict(always, w_up=w["experts_up"][held],
                   w_down=w["experts_down"][held])
        out, stats = ep_moe.fwd_held(
            moe, y, topk=cfg.num_experts_per_tok, first=first,
            routed_scale=cfg.routed_scaling_factor, scoring="sigmoid",
            act="relu2")
        total = total + (out - once)
        pairs += int(stats[0])
        share = dataclasses.replace(dims, held=4, first_held=first)
        ws = dict(w, experts_up=w["experts_up"][held],
                  experts_down=w["experts_down"][held])
        ref_total = ref_total + (
            F.experts(x, ws, share, reference._dot) - x - once)
    assert pairs == 48 * 4               # every pair fell to one share
    monkeypatch.setattr(F, "CAPACITY_FACTOR", 1)
    assert F.expert_capacity(whole, 48) == 12
    np.testing.assert_allclose(
        F.experts(x, w, whole, reference._dot) - x, want, rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(ref_total, want, rtol=1e-4, atol=2e-5)
    with pytest.raises(ValueError, match="act"):
        ep_moe.fwd_held(moe, y, topk=4, act="gelu")


# -- chunks, then decode, through the pool and the sequences' state ----------

def _programs(cfg, mesh):
    specs = mamba_moe.param_specs(cfg, "tp")
    kv = mamba_moe.paged_cache_specs("tp")
    chunk = _on_mesh(
        mesh, lambda p, t, c, row, start, valid, slot:
        mamba_moe.prefill_chunk_paged(p, t, c, row, cfg, start=start,
                                      wfrom=0, valid=valid, slot=slot)[:2],
        (specs, P(None), kv, P(None), P(), P(), P()), (P(None), kv))
    decode = _on_mesh(
        mesh, lambda p, t, c: mamba_moe.decode_step_paged(p, t, c, cfg),
        (specs, P(None), kv), (P(None, None), kv, P(None)))
    fused = _on_mesh(
        mesh, lambda p, t, dd, c, row, start, valid, slot:
        mamba_moe.chunk_decode_paged(p, t, dd, c, row, cfg, start=start,
                                     wfrom=0, valid=valid, slot=slot)[:3],
        (specs, P(None), P(None), kv, P(None), P(), P(), P()),
        (P(None), P(None, None), kv))
    return chunk, decode, fused


def _prefill(chunk, params, cache, row, seq, splits, slot, bucket=16):
    """``seq`` through ``chunk`` in pieces of ``splits`` rows, each
    padded to ``bucket``; returns the last piece's logits."""
    start = 0
    for n in splits:
        toks = np.zeros(bucket, np.int32)
        toks[:n] = seq[start:start + n]
        logits, cache = chunk(params, jnp.asarray(toks), cache, row, start,
                              n, slot)
        start += n
    assert start == len(seq)
    return np.asarray(logits), cache


ROWS = {0: [4, 2, 7, 1, 5, 0, 0, 0], 1: [3, 6, 8, 9, 10, 0, 0, 0],
        2: [11, 12, 13, 14, 0, 0, 0, 0]}


@pytest.mark.parametrize("splits", [(16, 8), (7, 9, 8), (3, 16, 5),
                                    (11, 13)])
def test_chunks_of_any_split_then_decode_equal_the_reference(tiny, splits):
    """A 40-token sequence in slot 1: 24 tokens prefilled in chunks of
    ANY split (none a multiple of the scan's chunk of 8 but the first;
    every chunk padded to its bucket of 16), then 16 decode steps fed
    the sequence's own tokens, slot 0 parked beside it and slot 2 live
    with another sequence. The logits after the prompt and after each
    decoded token equal the reference's full forward at those
    positions: float32 on both sides, 2e-4 absolute on logits of std
    ~0.17 (orders of summation: the scan in chunks against a token at a
    time, grouped products against gathered ones)."""
    _, dims, cfg, mesh, params = tiny
    chunk, decode, _ = _programs(cfg, mesh)
    rng = np.random.default_rng(5)
    seq, other = (rng.integers(0, dims.vocab, size=40) for _ in range(2))
    row1, row2 = (jnp.asarray(ROWS[s], jnp.int32) for s in (1, 2))
    cache = _empty(cfg)
    first, cache = _prefill(chunk, params, cache, row1, seq[:24], splits, 1)
    _, cache = _prefill(chunk, params, cache, row2, other[:10], (10,), 2)
    parked = {k: np.asarray(v) for k, v in cache.seq.items()}
    got, got2 = [first], []
    cache = dataclasses.replace(
        cache, block_table=jnp.stack([jnp.zeros_like(row1), row1, row2]),
        lens=jnp.asarray([0, 24, 10], jnp.int32),
        live=jnp.asarray([0, 1, 1], jnp.int32))
    for t, t2 in zip(seq[24:39], other[10:25]):
        logits, cache, _ = decode(
            params, jnp.asarray([0, t, t2], jnp.int32), cache)
        got.append(np.asarray(logits)[1])
        got2.append(np.asarray(logits)[2])
    assert cache.lens.tolist() == [0, 39, 25]
    # The parked slot's state and tail are what they were; the live
    # ones moved.
    for name, slot_axis in ((mamba_moe.STATE, 1), (mamba_moe.TAIL, 2)):
        now = np.asarray(cache.seq[name])
        np.testing.assert_array_equal(now.take(0, slot_axis),
                                      parked[name].take(0, slot_axis))
        assert (now.take(1, slot_axis)
                != parked[name].take(1, slot_axis)).any()
    want, want2 = reference.logits_at(
        SEED, F, dims, jnp.float32, [seq.tolist(), other.tolist()],
        [list(range(23, 39)), list(range(10, 25))])
    assert want.std() > 0.1
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=2e-4)
    np.testing.assert_allclose(np.stack(got2), want2, rtol=0, atol=2e-4)


def test_a_slot_reused_starts_from_nothing_and_decode_rows_ride(tiny):
    """Slot 1 served a first request; a second request's first chunk
    (``start`` 0) resets what the first left. Its later chunks run in
    the program that also decodes (``chunk_decode_paged``), slot 0 live
    beside it: the chunk's logits and the riding row's are the
    reference's, and the chunk's own slot, parked in the batch, is
    moved by the chunk alone."""
    _, dims, cfg, mesh, params = tiny
    chunk, decode, fused = _programs(cfg, mesh)
    rng = np.random.default_rng(8)
    old, new, rider = (rng.integers(0, dims.vocab, size=n)
                       for n in (20, 30, 40))
    row0, row1 = (jnp.asarray(ROWS[s], jnp.int32) for s in (0, 1))
    cache = _empty(cfg)
    _, cache = _prefill(chunk, params, cache, row1, old, (16, 4), 1)
    assert float(jnp.abs(cache.seq[mamba_moe.STATE][:, 1]).max()) > 0
    _, cache = _prefill(chunk, params, cache, row0, rider[:16], (16,), 0)
    # The new request in slot 1: 13 rows alone, then 12 and 5 with the
    # rider decoding aboard.
    _, cache = _prefill(chunk, params, cache, row1, new[:13], (13,), 1)
    cache = dataclasses.replace(
        cache, block_table=jnp.stack([row0, jnp.zeros_like(row0),
                                      jnp.zeros_like(row0)]),
        lens=jnp.asarray([16, 0, 0], jnp.int32),
        live=jnp.asarray([1, 0, 0], jnp.int32))
    got_rider, start = [], 13
    for step, n in enumerate((12, 5)):
        toks = np.zeros(16, np.int32)
        toks[:n] = new[start:start + n]
        logits, dec, cache = fused(
            params, jnp.asarray(toks),
            jnp.asarray([rider[16 + step], 0, 0], jnp.int32), cache, row1,
            start, n, 1)
        got_rider.append(np.asarray(dec)[0])
        start += n
    assert cache.lens.tolist() == [18, 0, 0]
    want_new, want_rider = reference.logits_at(
        SEED, F, dims, jnp.float32, [new.tolist(), rider.tolist()],
        [[29], [16, 17]])
    np.testing.assert_allclose(np.asarray(logits), want_new[0], rtol=0,
                               atol=2e-4)
    np.testing.assert_allclose(np.stack(got_rider), want_rider, rtol=0,
                               atol=2e-4)


def test_the_state_is_stored_in_the_parameters_type_and_stepped_in_float32(
        tiny, monkeypatch):
    """What a sequence keeps is allocated in the pool's type, which is
    the parameters' (bfloat16 as served: the configuration's
    ``assumed.state_cache``), and rounded only where it is stored. A
    float32 pool therefore equals the reference (the tests above); a
    state rounded to bfloat16 INSIDE a chunk, at every scan chunk's
    end, does not: the tiny comparison sees the rounding."""
    _, dims, cfg, mesh, params = tiny
    served = _empty(cfg, dtype=jnp.bfloat16)
    assert {v.dtype for v in served.seq.values()} == {jnp.dtype(jnp.bfloat16)}
    assert served.k_pages.dtype == jnp.bfloat16
    assert served.seq[mamba_moe.STATE].shape == (3, 3, 8, 16, 16)
    assert served.seq[mamba_moe.TAIL].shape == (3, 3, 3, 8 * 16 + 2 * 2 * 16)
    seq = np.random.default_rng(5).integers(0, dims.vocab, size=40)
    row = jnp.asarray(ROWS[1], jnp.int32)
    want = reference.logits_at(SEED, F, dims, jnp.float32, [seq.tolist()],
                               [[23]])[0][0]

    def last_logits():
        chunk, _, _ = _programs(cfg, mesh)
        return _prefill(chunk, params, _empty(cfg), row, seq[:24], (16, 8),
                        1)[0]

    assert np.abs(last_logits() - want).max() < 1e-5
    # The model asks ``ssd_prefill``, which at these sizes is
    # ``ssd_chunked``, looked up where it is patched below.
    assert not any("scan" in _stated(cfg, rows) for rows in (16, 8))
    sound = mamba2.ssd_chunked

    def rounded_inside(x, dt, a, b, c, d, state=None, *, chunk):
        ys = []
        for lo in range(0, x.shape[0], chunk):
            cut = slice(lo, lo + chunk)
            y, state = sound(x[cut], dt[cut], a, b[cut], c[cut], d, state,
                             chunk=chunk)
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            ys.append(y)
        return jnp.concatenate(ys), state

    monkeypatch.setattr(mamba2, "ssd_chunked", rounded_inside)
    assert np.abs(last_logits() - want).max() > 1e-4


# -- the server ---------------------------------------------------------------

def _serve(cfg, mesh, params, prompts, **kw):
    eng = Engine(cfg, mesh, model=mamba_moe, mode="xla",
                 dtype=jnp.float32, max_len=64, params=params)
    srv = eng.serving(num_slots=3, page=8, prefill_buckets=(8, 16),
                      telemetry="spans", **kw)
    return srv, srv.generate(prompts, max_new_tokens=12)


def test_the_server_serves_it_and_counts_what_the_sequences_keep(tiny):
    """Chunked prefill with the decode batch riding, slots reused by
    later requests, a slot preempted when the pool runs dry and its
    request prefilled again from nothing: the tokens are the
    reference's greedy ones, and ``stats()`` says what the pool
    stated."""
    _, dims, cfg, mesh, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, size=n).tolist()
               for n in (21, 37, 9, 30, 26)]
    roomy, want = _serve(cfg, mesh, params, prompts)
    tight, got = _serve(cfg, mesh, params, prompts, num_pages=12)
    assert got == want
    for prompt, tokens in zip(prompts, want):
        seq, at = reference.served_positions(prompt, tokens)
        rows = reference.logits_at(SEED, F, dims, jnp.float32, [seq],
                                   [at])[0]
        assert reference.gaps(rows, tokens).max() < 1e-3
    st, rs = tight.stats(), roomy.stats()
    assert st["preemptions"] > 0 and rs["preemptions"] == 0
    assert rs["seq_state_resets"] == 5          # one a request given a slot
    assert st["seq_state_resets"] == 5 + st["preemptions"]
    for s in (st, rs):
        assert s["decode_dispatches_fused"] > 0
        assert s["tokens_picked_on_device"] == s["tokens_generated"] == 60
        assert (s["seq_state_layers"], s["paged_layers"],
                s["seq_state_slots"]) == (3, 1, 3)
        # 3 layers x 3 slots x (8 x 16 x 16 + 3 x 192) float32 values.
        assert s["seq_state_bytes"] == 3 * 3 * (2048 + 576) * 4
        assert s["kv_bytes_per_token"] == 2 * 2 * 16 * 4    # one layer's
        assert s["expert_pairs_routed"] > s["expert_pairs_held"] > 0
        assert s["expert_rows_mean"] == pytest.approx(
            s["expert_pairs_held"] / (4 * 2 * s["expert_steps"]))
    events = [e for e in tight.obs.log.spans() if e.kind == "expert_load"]
    assert events and all(
        e.attrs["held_pairs"] <= e.attrs["routed_pairs"]
        == e.attrs["rows"] * 4 * 2 for e in events)
    assert isinstance(tight.cache, PagedKVCache)
    assert tight.cache.k_pages.shape == (1, 12, 2, 8, 16)
    # A chunk of this pool names the slot whose state it carries: none
    # is taken for slot 0's.
    with pytest.raises(ValueError, match="slot="):
        tight.chunker.step(params, np.zeros(8, np.int32), tight.cache,
                           np.zeros(4, np.int32), 0, 0, 8)
    assert tight.decode_cache_size() == 1 and tight.prefill_cache_size() <= 2


def test_the_server_counts_its_chunk_dispatches_by_their_scan(tiny):
    """A configuration the scan kernel tiles (interpreted here) runs it
    in every chunk program: ``chunk_dispatches_kernel_scan`` is
    ``prefill_chunks`` and every ``prefill_chunk`` span says so, while
    the tiny file's sizes fall to the XLA form and count none. The same
    for the held experts' kernel (``chunk_dispatches_kernel_experts``,
    ``experts_kernel``): a latent and experts of whole lanes, and
    programs of 128 + 2 and 256 + 2 rows whose pass is three row
    tiles."""
    cfg = ModelConfig.tiny_mamba_moe(
        num_hidden_layers=3, layer_pattern="M*E", mamba_num_heads=KH,
        mamba_head_dim=KP, mamba_n_groups=KG, ssm_state_size=KN,
        mamba_chunk_size=128, max_position_embeddings=512,
        moe_latent_size=128, moe_intermediate_size=128)
    assert ["experts" in _stated(cfg, rows) for rows in (130, 258, 2)
            ] == [True, True, False]
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    params = mamba_moe.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (150, 300)]

    def serve():
        eng = Engine(cfg, mesh, model=mamba_moe, mode="xla",
                     dtype=jnp.float32, max_len=512, params=params)
        srv = eng.serving(num_slots=2, page=128, prefill_buckets=(128, 256),
                          telemetry="spans")
        return srv, srv.generate(prompts, max_new_tokens=3)

    srv, out = serve()
    st = srv.stats()
    assert st["chunk_dispatches_kernel_scan"] == st["prefill_chunks"] == 4
    assert st["chunk_dispatches_kernel_experts"] == 4
    assert st["chunk_dispatches_kernel_walk"] == 0
    assert st["expert_pairs_held"] > 0
    chunks = [e.attrs for e in srv.obs.log.spans()
              if e.kind == "prefill_chunk"]
    assert sorted((a["bucket"], a["scan_kernel"], a["experts_kernel"],
                   a["walk_kernel"]) for a in chunks) == [
        (128, 1, 1, 0), (128, 1, 1, 0), (128, 1, 1, 0), (256, 1, 1, 0)]
    _, _, tiny_cfg, tiny_mesh, tiny_params = tiny
    small, _ = _serve(tiny_cfg, tiny_mesh, tiny_params, [prompts[0][:21]])
    assert small.stats()["prefill_chunks"] > small.stats()[
        "chunk_dispatches_kernel_scan"] == small.stats()[
        "chunk_dispatches_kernel_experts"] == 0
    assert {(e.attrs["scan_kernel"], e.attrs["experts_kernel"])
            for e in small.obs.log.spans()
            if e.kind == "prefill_chunk"} == {(0, 0)}


@pytest.mark.parametrize("knob, what", [
    ({"spec_k": 3}, "spec_k"), ({"prefix_reuse": True}, "prefix_reuse"),
    ({"prefill_buckets": None}, "monolithic prompt"),
    ({"kv_tiers": True}, "kv_tiers")])
def test_what_a_state_beside_the_pages_rules_out_is_refused(tiny, knob,
                                                            what):
    _, _, cfg, mesh, params = tiny
    eng = Engine(cfg, mesh, model=mamba_moe, mode="xla", dtype=jnp.float32,
                 max_len=64, params=params)
    kw = dict(num_slots=2, page=8, prefill_buckets=(8,))
    kw.update(knob)
    with pytest.raises(NotImplementedError, match=what):
        eng.serving(**kw)
    assert not hasattr(mamba_moe, "verify_step_paged")
    with pytest.raises(NotImplementedError, match="paged pool"):
        mamba_moe.prefill(None, None, cfg)


def test_the_other_pools_keep_nothing_a_sequence():
    """An empty mapping, so no leaf: the programs of the families that
    keep pages alone are the ones they were."""
    from triton_dist_tpu.serving.blocks import LatentPagedCache

    dense = PagedKVCache.empty(2, 5, 8, 2, 16, num_slots=2, p_max=4)
    latent = LatentPagedCache.empty(2, 5, 8, 24, num_slots=2, p_max=4)
    assert dict(dense.seq) == dict(latent.seq) == {}
    assert len(jax.tree.leaves(dense)) == 5
    assert len(jax.tree.leaves(latent)) == 4
    again = jax.tree.unflatten(*reversed(jax.tree.flatten(dense)))
    assert again.seq == {} and again.k_pages.shape == dense.k_pages.shape
