"""The seam between ``models.paged_step`` and the model families: what
the rows of a step program cost where a half is absent, the four names
every family binds and the keyword arguments they take, and the ONE
statement of kernels-by-sizes the server counts its chunk dispatches
by. Tiny sizes; nothing here runs a layer's arithmetic but the last
test."""

import inspect
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu as tdt
from triton_dist_tpu.models import (Engine, ModelConfig, dense, latent_moe,
                                    looped, mamba_moe, paged_step, qwen_moe,
                                    window_moe)
from triton_dist_tpu.models.dense import FwdContexts

FAMILIES = {
    "dense": (dense, ModelConfig.tiny()),
    "latent_moe": (latent_moe, ModelConfig.tiny_latent_moe()),
    "mamba_moe": (mamba_moe, ModelConfig.tiny_mamba_moe()),
    "looped": (looped, ModelConfig.tiny_looped()),
    "window_moe": (window_moe, ModelConfig.tiny_window_moe()),
}
TRUNK = {"dense": dense.paged_layers, "latent_moe": latent_moe._layers,
         "mamba_moe": mamba_moe._layers, "looped": looped._passes,
         "window_moe": window_moe._layers}
C, S, PAGE, P_MAX = 8, 3, 4, 6


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr under it,
    one for each time an inner function is called."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, primitive)
    return n


def _shapes(model, cfg):
    """(params, cache) of ``model`` at ``cfg``, shapes only."""
    params = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), cfg))
    pool_cls, per_token, *keeps = model.paged_pool(cfg)
    keeps = dict(keeps[0]) if keeps else {}
    layers = keeps.pop("layers", cfg.num_hidden_layers)
    if "window" in keeps:          # sized as the server sizes them
        keeps["window"] = keeps["window"].sized(PAGE, C, S)
    cache = jax.eval_shape(lambda: pool_cls.empty(
        layers, 1 + S * P_MAX, PAGE, *per_token, num_slots=S, p_max=P_MAX,
        dtype=jnp.float32, **keeps))
    return params, cache


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


# -- (a) a half that is absent costs nothing ----------------------------------

@pytest.mark.parametrize("step", ["prefill_chunk_paged",
                                  "decode_step_paged"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_step_of_one_half_adds_no_concatenation_to_its_trunk(family,
                                                               step):
    """``prefill_chunk_paged`` has no decode rows and ``decode_step_paged``
    no chunk rows: the builder's embedding, positions, split and head
    trace exactly the ``concatenate`` equations the family's trunk
    traces alone on the same rows, so the program of one half is the
    text it was before the halves shared a builder."""
    model, cfg = FAMILIES[family]
    params, cache = _shapes(model, cfg)
    slot = {"slot": 1} if hasattr(cache, "seq") and cache.seq else {}
    impls = dict(mode="xla", axis="tp", attn_impl="ref")
    if step == "prefill_chunk_paged":
        args = (params, _i32(C), cache, _i32(P_MAX), _i32(), _i32(), _i32())

        def whole(p, toks, c, row, start, wfrom, valid):
            return model.prefill_chunk_paged(
                p, toks, c, row, cfg, start=start, wfrom=wfrom, valid=valid,
                **slot, **impls)

        def trunk(p, toks, c, row, start, wfrom, valid):
            rows = paged_step.Rows(chunk_toks=toks, table_row=row,
                                   start=start, wfrom=wfrom, valid=valid,
                                   **slot)
            return TRUNK[family](p, rows, c, cfg, decode_attn_impl="ref",
                                 **impls)
    else:
        args = (params, _i32(S), cache)

        def whole(p, toks, c):
            return model.decode_step_paged(p, toks, c, cfg, **impls)

        def trunk(p, toks, c):
            return TRUNK[family](p, paged_step.Rows(token_ids=toks), c, cfg,
                                 decode_attn_impl="ref", **impls)

    trace = lambda fn: jax.make_jaxpr(fn, axis_env=[("tp", 1)])(*args).jaxpr
    assert (_count(trace(whole), "concatenate")
            == _count(trace(trunk), "concatenate"))


@pytest.mark.parametrize("halves, concatenates, slices", [
    ("chunk", 0, 0), ("decode", 0, 0), ("both", 4, 4)])
def test_the_rows_of_a_step_slice_and_join_only_where_both_halves_ride(
        halves, concatenates, slices):
    """``Rows`` itself, with functions that do nothing: the ids, the
    positions, a split and the head's rows are one concatenation each
    where both halves ride (two slices each for the split and the
    head), and none of either where one half is absent."""
    chunk = dict(chunk_toks=_i32(C), table_row=_i32(P_MAX), start=_i32(),
                 wfrom=_i32(), valid=_i32())
    given = {"chunk": chunk, "decode": dict(token_ids=_i32(S)),
             "both": dict(chunk, token_ids=_i32(S))}[halves]

    def every_method(given, lens, x):
        rows = paged_step.Rows(**given)
        keep = lambda cache, x: (x, cache)
        out, _ = rows.split(keep, keep, None, x)
        return (rows.tokens(),
                rows.positions(types.SimpleNamespace(lens=lens)), out,
                rows.head_rows(x))

    n = (C if "chunk_toks" in given else 0) + (S if "token_ids" in given
                                               else 0)
    jaxpr = jax.make_jaxpr(every_method)(
        given, _i32(S), jax.ShapeDtypeStruct((n, 16), jnp.float32)).jaxpr
    assert _count(jaxpr, "concatenate") == concatenates
    assert _count(jaxpr, "slice") == slices
    assert _count(jaxpr, "gather") == 0
    # The chunk's last valid row, wherever a chunk rides.
    assert _count(jaxpr, "dynamic_slice") == (halves != "decode")


# -- (b) the names and their keyword arguments --------------------------------

_CHUNK = ("params", "chunk_toks", "cache", "table_row", "cfg")
_DECODE = ("params", "token_ids", "cache", "cfg")
_BOTH = ("params", "chunk_toks", "token_ids", "cache", "table_row", "cfg")
_AT = ("start", "wfrom", "valid")
_KW = {"mode": "xla", "axis": "tp", "ctxs": FwdContexts(),
       "attn_impl": "ref"}
_EP = {"moe_impl": "tp", "ep_ctx": None, "transport": None,
       "replicas": None, "with_expert_counts": False}
# module -> (its own keyword arguments; the chunk steps' required ones
# past ``_AT``; whether it verifies): the signatures of commit 9dc1a11,
# before the builder, but for ``qwen_moe.chunk_decode_paged``, which
# took ``_EP``'s first two alone and now takes, and ignores, the rest as
# its twins always did.
_OWN = {
    "dense": ({"ffn_fn": None}, (), True),
    "qwen_moe": (_EP, (), True),
    "latent_moe": ({}, (), True),
    "mamba_moe": ({}, ("slot",), False),
    "looped": ({}, (), False),
    "window_moe": ({}, (), False),
}
_STEPS = {
    "prefill_chunk_paged": (_CHUNK, True, {}),
    "decode_step_paged": (_DECODE, False, {}),
    "chunk_decode_paged": (_BOTH, True, {"decode_attn_impl": "ref"}),
    "verify_step_paged": (_DECODE, False, {"budget": None}),
}


@pytest.mark.parametrize("step", list(_STEPS))
@pytest.mark.parametrize("module", list(_OWN))
def test_every_family_binds_the_steps_with_the_keywords_callers_pass(
        module, step):
    """``serving/chunked.py``, ``serving/server.py``, five test files
    and the benchmark's ``*_system.py`` files call these by name with
    these keyword arguments; none of them is edited when a family's
    steps come from the builder."""
    model = {"qwen_moe": qwen_moe, **{k: v[0] for k, v in FAMILIES.items()}
             }[module]
    own, chunk_needs, verifies = _OWN[module]
    positional, has_chunk, more = _STEPS[step]
    if step == "verify_step_paged" and not verifies:
        assert not hasattr(model, step)
        return
    got = inspect.signature(getattr(model, step)).parameters.values()
    assert tuple(p.name for p in got
                 if p.kind is p.POSITIONAL_OR_KEYWORD) == positional
    assert tuple(p.name for p in got if p.kind is p.KEYWORD_ONLY
                 and p.default is p.empty) == (
        _AT + chunk_needs if has_chunk else ())
    assert {p.name: p.default for p in got if p.kind is p.KEYWORD_ONLY
            and p.default is not p.empty} == {**_KW, **more, **own}
    assert not [p for p in got if p.kind in (p.VAR_KEYWORD,
                                             p.VAR_POSITIONAL)]


def test_a_slot_is_asked_of_a_pool_with_sequence_state_and_of_no_other():
    toks, row = jnp.zeros((C,), jnp.int32), jnp.zeros((P_MAX,), jnp.int32)
    at = dict(start=0, wfrom=0, valid=C)
    with pytest.raises(TypeError, match="slot"):
        mamba_moe.prefill_chunk_paged(None, toks, None, row, None, **at)
    with pytest.raises(TypeError, match="slot"):
        dense.prefill_chunk_paged(None, toks, None, row, None, slot=1, **at)
    with pytest.raises(TypeError, match="ffn_fn"):
        looped.decode_step_paged(None, toks, None, None, ffn_fn=None)


# -- (c) the server reads one statement ---------------------------------------

@pytest.mark.parametrize("family", ["latent_moe", "mamba_moe", "dense"])
def test_the_server_counts_its_chunk_dispatches_by_the_models_statement(
        family, monkeypatch):
    """One ``step_kernels`` a model, asked once a bucket with the decode
    rows aboard, the pool's page and the parameters' type; the three
    ``chunk_dispatches_kernel_*`` counters and the three ``*_kernel``
    stats of ``tdt.prefill_chunk`` follow it on both sides of each
    rule (here a statement that differs by bucket; the tiny sizes' real
    rules, which the other tests of the two families hold to the
    programs, give nothing). A model that states nothing counts
    nothing."""
    model, cfg = FAMILIES[family]
    stated = {8: ("walk",), 32: ("scan", "experts")}
    asked = []
    if family != "dense":
        real = model.step_kernels

        def step_kernels(cfg_, rows, **sizes):
            asked.append((rows, sizes))
            assert real(cfg_, rows, **sizes) == ()
            return stated[rows]

        monkeypatch.setattr(model, "step_kernels", step_kernels)
    else:
        assert not hasattr(model, "step_kernels")
        stated = {8: (), 32: ()}
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    eng = Engine(cfg, mesh, model=model, mode="xla", dtype=jnp.float32,
                 max_len=64,
                 params=model.init_params(jax.random.PRNGKey(3), cfg))
    srv = eng.serving(num_slots=2, page=8, prefill_buckets=(8, 32),
                      telemetry="spans")
    rng = np.random.default_rng(4)
    out = srv.generate([rng.integers(0, 256, size=n).tolist()
                        for n in (5, 40)], max_new_tokens=2)
    assert [len(o) for o in out] == [2, 2]
    chunks = [e.attrs for e in srv.obs.log.spans()
              if e.kind == "prefill_chunk"]
    assert sorted(a["bucket"] for a in chunks) == [8, 8, 32]
    st = srv.stats()
    assert st["prefill_chunks"] == 3
    for block in paged_step.STEP_KERNELS:
        assert st[f"chunk_dispatches_kernel_{block}"] == sum(
            block in stated[a["bucket"]] for a in chunks)
        assert all(a[f"{block}_kernel"] == (block in stated[a["bucket"]])
                   for a in chunks)
    if family != "dense":
        sizes = dict(decode_rows=srv.chunker.decode_rows, page=8,
                     dtype=jnp.float32)
        assert srv.chunker.decode_rows == 2
        assert sorted(asked, key=lambda a: a[0]) == [(8, sizes),
                                                     (32, sizes)]
