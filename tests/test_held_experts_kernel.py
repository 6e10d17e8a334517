"""The held experts' MLP of a pass as one Pallas kernel (PR 50):
``ops.group_gemm.tile_layout`` (an expert-major layout whose row tiles
belong to one expert each) and ``grouped_mlp_tiles`` (up, activation,
down over it), interpreted here at widths that tile, against
``grouped_swiglu`` / ``grouped_relu2``, which stay the definition; and
the rule on sizes that picks between them (``ep_moe.experts_impl``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.models import latent_moe, mamba_moe
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.ops import group_gemm as G

E, D, F, TM, ROWS = 4, 128, 256, 16, 64

# name: the groups of a window of 64 sorted rows over 4 experts.
_GROUPS = {
    "even": [16, 16, 16, 16],
    "every-pair-on-one-expert": [0, 0, 64, 0],
    "experts-with-no-row": [0, 40, 0, 24],
    "nothing-held": [0, 0, 0, 0],
    "just-under-a-tile": [15, 15, 15, 15],
    "just-over-a-tile": [17, 17, 17, 13],
    "a-window-not-full": [5, 0, 33, 1],
}


def _weights(dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(ks[0], (E, D, F), dtype) * D ** -0.5,
            jax.random.normal(ks[1], (E, D, F), dtype) * D ** -0.5,
            jax.random.normal(ks[2], (E, F, D), dtype) * F ** -0.5,
            jax.random.normal(ks[3], (ROWS, D), dtype))


def _through_the_layout(x, sizes, w_gate, w_up, w_down, act, tf):
    """The window's rows gathered into the layout, through the kernel,
    and read back at each window row's layout row."""
    tile_expert, n_used, src, shift = G.tile_layout(sizes, ROWS, TM)
    y = G.grouped_mlp_tiles(
        x[jnp.maximum(src, 0)], w_up, w_down, tile_expert, n_used,
        w_gate=w_gate if act == "swiglu" else None, act=act, tf=tf)
    n = int(np.sum(sizes))
    expert = np.repeat(np.arange(E), np.asarray(sizes))
    return np.asarray(y)[np.arange(n) + np.asarray(shift)[expert]]


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("groups", list(_GROUPS))
def test_the_kernel_is_the_grouped_mlp_at_every_routing(groups, act):
    """Over the layout the kernel gives every window row what the XLA
    form gives it in sorted order: an even routing, one expert given
    everything, experts with no row, nothing held, an expert just under
    and just over a row tile. ``f`` in two tiles for the gated form (the
    float32 sum over them), whole for the other (no scratch)."""
    w_gate, w_up, w_down, x = _weights()
    sizes = jnp.asarray(_GROUPS[groups], jnp.int32)
    n = int(np.sum(sizes))
    if act == "swiglu":
        want = G.grouped_swiglu(x, w_gate, w_up, w_down, sizes)
    else:
        want = G.grouped_relu2(x, w_up, w_down, sizes)
    got = _through_the_layout(x, sizes, w_gate, w_up, w_down, act,
                              tf=128 if act == "swiglu" else F)
    assert got.shape == (n, D)
    np.testing.assert_allclose(got, np.asarray(want)[:n], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("lo", [0, 64, 128])
def test_a_window_that_cuts_a_group_lays_out_its_part(lo):
    """A second and a third pass: the window's cut of the groups
    (``window_group_sizes``) is laid out like any groups, the expert
    the window cuts taking the rows of it that lie inside."""
    w_gate, w_up, w_down, _ = _weights()
    groups = jnp.asarray([40, 50, 0, 60], jnp.int32)      # 150 sorted rows
    x = jax.random.normal(jax.random.PRNGKey(5), (192, D), jnp.float32)
    sizes = G.window_group_sizes(groups, lo, ROWS)
    assert np.asarray(sizes).tolist() == {
        0: [40, 24, 0, 0], 64: [0, 26, 0, 38], 128: [0, 0, 0, 22]}[lo]
    want = G.grouped_swiglu(x, w_gate, w_up, w_down, groups)
    got = _through_the_layout(x[lo:lo + ROWS], sizes, w_gate, w_up, w_down,
                              "swiglu", tf=F)
    n = int(np.sum(sizes))
    np.testing.assert_allclose(got, np.asarray(want)[lo:lo + n],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("groups", list(_GROUPS))
def test_the_layout_gives_every_tile_one_expert(groups):
    """``tile_layout``: an expert with ``n`` rows has ``ceil(n / tm)``
    tiles from a tile boundary on, every window row is held once, a
    used tile holds rows of its expert alone, the tiles past the last
    used one repeat its expert (so the kernel's index maps do not
    move), and ``rows // tm + E`` tiles are always enough."""
    sizes = np.asarray(_GROUPS[groups])
    tile_expert, n_used, src, shift = map(
        np.asarray, G.tile_layout(jnp.asarray(sizes, jnp.int32), ROWS, TM))
    assert tile_expert.shape == (ROWS // TM + E,)
    assert src.shape == (tile_expert.shape[0] * TM,)
    tiles = -(-sizes // TM)
    assert int(n_used[0]) == tiles.sum() <= tile_expert.shape[0]
    assert shift.tolist() == ((np.cumsum(tiles) - tiles) * TM
                              - (np.cumsum(sizes) - sizes)).tolist()
    assert sorted(src[src >= 0].tolist()) == list(range(sizes.sum()))
    owner = np.repeat(np.arange(E), sizes)       # a window row's expert
    for r in np.flatnonzero(src >= 0):
        assert tile_expert[r // TM] == owner[src[r]]
        assert r // TM < n_used[0]
    used = tile_expert[:int(n_used[0])]
    assert used.tolist() == np.repeat(np.arange(E), tiles).tolist()
    last = used[-1] if len(used) else 0
    assert (tile_expert[int(n_used[0]):] == last).all()


def _primitives(jaxpr, into_kernels=True):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub, into_kernels)


def test_the_layout_is_built_with_no_scatter():
    """Sums over comparisons against ``(E,)`` tables: the TPU takes a
    scatter a row at a time (``sort_pairs`` counts by comparison for the
    same reason), and a gather of scalars or a running sum little
    better (0.56 ms a pass for the layout built with them: PERF.md, PR
    50). A whole layer under the kernel has no scatter either."""
    closed = jax.make_jaxpr(lambda s: G.tile_layout(s, 2688))(
        jax.ShapeDtypeStruct((32,), jnp.int32))
    names = {q.primitive.name for q in _primitives(closed.jaxpr)}
    assert not any(w in n for n in names
                   for w in ("scatter", "gather", "cum", "sort")), names
    s, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    params = {"router": s((256, 16), bf), "w_gate": s((4, 256, 128), bf),
              "w_up": s((4, 256, 128), bf), "w_down": s((4, 128, 256), bf)}
    assert ep_moe.experts_impl(
        ep_moe.held_pass_rows(512, 2, 4, 16), 4, 256, 128, bf) == "kernel"
    closed = jax.make_jaxpr(lambda p, x: ep_moe.fwd_held(p, x, topk=2))(
        params, s((512, 256), bf))
    names = {q.primitive.name for q in _primitives(closed.jaxpr)}
    assert "pallas_call" in names
    assert not any("scatter" in n for n in names), names


@pytest.mark.parametrize("cell, t, topk, e, held, d, f, act, tiles", [
    ("mistral 2048+16", 2064, 4, 128, 32, 4096, 2048, "swiglu", (128, 512)),
    ("mistral 512+16", 528, 4, 128, 32, 4096, 2048, "swiglu", (128, 512)),
    ("nemotron 2048+16", 2064, 22, 512, 128, 1024, 3072, "relu2",
     (128, 3072)),
    ("nemotron 512+16", 528, 22, 512, 128, 1024, 3072, "relu2",
     (128, 3072)),
])
def test_the_kernels_traced_body_stays_small(cell, t, topk, e, held, d, f,
                                             act, tiles):
    """The set-up budget (PERF.md, PR 38, 39, 46): a step program is
    traced and lowered at every start. The kernel has no loop but its
    grid, so its body is a few dozen equations at the four program
    shapes of the two ``longdocs`` cells; 60 is held. The tiles are the
    ones ``mlp_tiles`` picks from the shapes: a gated expert of 4096 x
    2048 in four ``f`` tiles, an ungated 1024 x 3072 one whole (a
    second tile of the same expert fetches nothing). Trace only."""
    rows = ep_moe.held_pass_rows(t, topk, held, e)
    mats = 3 if act == "swiglu" else 2
    assert G.mlp_tiles(rows, d, f, 2, mats) == tiles
    s, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    n_tiles = rows // G.ROW_TILE + held
    gate = (s((held, d, f), bf),) if act == "swiglu" else ()
    closed = jax.make_jaxpr(
        lambda x, te, nu, up, down, *g: G.grouped_mlp_tiles(
            x, up, down, te, nu, w_gate=g[0] if g else None, act=act))(
        s((n_tiles * G.ROW_TILE, d), bf), s((n_tiles,), jnp.int32),
        s((1,), jnp.int32), s((held, d, f), bf), s((held, f, d), bf), *gate)
    kernels = [q for q in _primitives(closed.jaxpr)
               if q.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    body = sum(1 for _ in _primitives(kernels[0].params["jaxpr"]))
    assert body <= 60, body


@pytest.mark.parametrize("sizes, impl", [
    # (pass rows, held, d, f, dtype): the two cells' four chunk programs
    ((2688, 32, 4096, 2048, jnp.bfloat16), "kernel"),
    ((896, 32, 4096, 2048, jnp.bfloat16), "kernel"),
    ((14208, 128, 1024, 3072, jnp.bfloat16), "kernel"),
    ((3712, 128, 1024, 3072, jnp.bfloat16), "kernel"),
    # 16 decode rows: mistral's 64 pairs are one pass and no whole
    # tile, nemotron's 352 go in passes of one tile
    ((64, 32, 4096, 2048, jnp.bfloat16), "xla"),
    ((128, 128, 1024, 3072, jnp.bfloat16), "kernel"),
    # float32 halves the f tile, not the answer
    ((2688, 32, 4096, 2048, jnp.float32), "kernel"),
    # widths that are no whole lanes; the published 2,688 is 21 x 128
    ((2688, 32, 4000, 2048, jnp.bfloat16), "xla"),
    ((2688, 32, 4096, 2000, jnp.bfloat16), "xla"),
    ((14208, 128, 1024, 2688, jnp.bfloat16), "kernel"),
    # no expert held, and a d whose narrowest blocks pass the budget
    ((2688, 0, 4096, 2048, jnp.bfloat16), "xla"),
    ((2688, 32, 32768, 2048, jnp.bfloat16), "xla"),
])
def test_the_experts_form_is_a_pure_function_of_sizes(sizes, impl):
    assert ep_moe.experts_impl(*sizes) == impl


def _experts_form(model, cfg, rows, dtype):
    """What ``model.step_kernels`` states of the held experts' MLP in a
    program of ``rows`` rows in all."""
    ran = model.step_kernels(cfg, rows, decode_rows=0, page=128,
                             dtype=dtype)
    return "kernel" if "experts" in ran else "xla"


def test_the_models_state_the_experts_form_for_their_sizes():
    """``latent_moe.step_kernels`` and ``mamba_moe.step_kernels`` state
    the layer's rule at the model's sizes and the pass its rows give:
    the two ``longdocs`` configurations' chunk programs run the kernel,
    the CPU presets' narrow experts and a pattern with no ``E`` layer
    the XLA form."""
    bf = jnp.bfloat16
    mistral = ModelConfig.tiny_latent_moe(
        hidden_size=4096, moe_intermediate_size=2048, num_experts=128,
        num_experts_per_tok=4, num_held_experts=32)
    assert [_experts_form(latent_moe, mistral, r, bf)
            for r in (2064, 528, 2048, 16)] == ["kernel"] * 3 + ["xla"]
    # The decode rows aboard count: 2048 + 16 is 2064 above.
    assert "experts" in latent_moe.step_kernels(
        mistral, 2048, decode_rows=16, page=128, dtype=bf)
    nemotron = ModelConfig.tiny_mamba_moe(
        moe_latent_size=1024, moe_intermediate_size=2688, num_experts=512,
        num_experts_per_tok=22, num_held_experts=128)
    assert ep_moe.expert_store_width(2688) == 3072
    # 16 decode rows' 352 pairs go in passes of ONE row tile: whole tiles.
    assert ep_moe.held_pass_rows(16, 22, 128, 512) == 128
    assert [_experts_form(mamba_moe, nemotron, r, bf)
            for r in (2064, 528, 16, 2)] == ["kernel"] * 3 + ["xla"]
    for rows in (8, 16, 128, 2064):
        assert _experts_form(latent_moe, ModelConfig.tiny_latent_moe(),
                             rows, jnp.float32) == "xla"
        assert _experts_form(mamba_moe, ModelConfig.tiny_mamba_moe(),
                             rows, jnp.float32) == "xla"
    assert _experts_form(
        mamba_moe,
        ModelConfig.tiny_mamba_moe(
            layer_pattern="M*", num_hidden_layers=2, moe_latent_size=1024,
            moe_intermediate_size=3072, num_experts=512,
            num_experts_per_tok=22, num_held_experts=128),
        2064, bf) == "xla"


def test_what_the_kernel_cannot_take_is_refused():
    w_gate, w_up, w_down, x = _weights()
    te, nu = jnp.zeros((4,), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="gate"):
        G.grouped_mlp_tiles(x, w_up, w_down, te, nu, act="swiglu")
    with pytest.raises(ValueError, match="gate"):
        G.grouped_mlp_tiles(x, w_up, w_down, te, nu, w_gate=w_gate,
                            act="relu2")
    with pytest.raises(ValueError, match="does not divide"):
        G.grouped_mlp_tiles(x, w_up, w_down, te, nu, act="relu2", tf=96)
    with pytest.raises(ValueError, match="tiles"):
        G.grouped_mlp_tiles(x[:63], w_up, w_down, te, nu, act="relu2")
    assert G.mlp_tiles(128, 128, 256, 4) == (128, 256)
    assert G.mlp_tiles(100, 128, 256, 4) is None


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("act, latent", [
    ("swiglu", False), ("swiglu", True), ("relu2", False), ("relu2", True)])
def test_a_layer_under_the_kernel_is_the_layer_under_xla(act, latent,
                                                         skewed,
                                                         monkeypatch):
    """``fwd_held`` whole, the form the rule picks (the kernel,
    interpreted) against the XLA form at the same sizes: both kinds of
    expert, with and without a latent the routed experts work in, at a
    routing one pass holds and at one where every token picks the same
    two held experts (two passes, an expert of two row tiles, the second
    pass's window cutting a group)."""
    t, topk, n_held, e, d, width, f = 256, 2, 4, 16, 64, 128, 128
    d_model = d if latent else width
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    params = {
        "router": jax.random.normal(ks[0], (d_model, e)) * d_model ** -0.5,
        "w_up": jax.random.normal(ks[1], (n_held, width, f)) * width ** -0.5,
        "w_down": jax.random.normal(ks[2], (n_held, f, width)) * f ** -0.5,
        "w_shared_up": jax.random.normal(ks[3], (d_model, 32)) * 0.1,
        "w_shared_down": jax.random.normal(ks[4], (32, d_model)) * 0.1}
    if act == "swiglu":
        params["w_gate"] = (jax.random.normal(ks[5], (n_held, width, f))
                            * width ** -0.5)
    if latent:
        params["w_latent_in"] = (jax.random.normal(ks[6], (d, width))
                                 * d ** -0.5)
        params["w_latent_out"] = (jax.random.normal(ks[7], (width, d))
                                  * width ** -0.5)
    x = jax.random.normal(jax.random.PRNGKey(8), (t, d_model))
    if skewed:
        x = x.at[:, 0].set(6.0)
        params["router"] = params["router"].at[0, 1:3].set(4.0)
    rows = ep_moe.held_pass_rows(t, topk, n_held, e)
    assert rows == 384 < t * topk
    assert ep_moe.experts_impl(rows, n_held, width, f,
                               jnp.float32) == "kernel"
    layer = lambda p, v: ep_moe.fwd_held(
        p, v, topk=topk, act=act, routed_scale=2.5,
        scoring="sigmoid" if act == "relu2" else "softmax")
    got, stats = jax.jit(layer)(params, x)
    monkeypatch.setattr(ep_moe, "experts_impl", lambda *a: "xla")
    want, want_stats = jax.jit(layer)(params, x)
    assert stats.tolist() == want_stats.tolist()
    assert stats[2] == (2 if skewed else 1)
    if skewed:
        assert stats.tolist()[:2] == [2 * t, t]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
