"""The cover of a prompt by chunk buckets
(``ops.chunked_prefill.plan_chunks``): the rule on sizes, its covers at
the benchmark mixes' edges, and that what is served does not depend on
the cover. Where the greedy cover of what is left takes three or more
programs and the smallest bucket that holds it has at most a third more
rows than they, ONE padded program of that bucket takes their place.

The families are served on the CPU at their tiny sizes
(``tests/benchmark/data/configs``) over buckets (8, 32), which cover as
the cells' (128, 512) and (512, 2048) do: a tail of more than two small
buckets' rows is one padded large program.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu as tdt
from benchmark.harness import loader, reference
from triton_dist_tpu.models import Engine
from triton_dist_tpu.ops.chunked_prefill import padded_up, plan_chunks
from triton_dist_tpu.serving import chunked

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
SEED = 17


def greedy(n_tokens, buckets):
    """The cover as it was: the largest bucket that fits, then the
    smallest bucket covering the remainder."""
    bs, out, rem = sorted(set(buckets)), [], n_tokens
    while rem > 0:
        fit = [b for b in bs if b <= rem]
        b = max(fit) if fit else min(x for x in bs if x >= rem)
        out.append((b, min(b, rem)))
        rem -= out[-1][1]
    return out


# -- the rule ---------------------------------------------------------------

BUCKET_LISTS = [(128, 512), (512, 2048), (128, 512, 2048), (4, 16),
                (8, 16), (8, 32), (4, 8, 16, 64), (16,), (100, 250, 700)]


@pytest.mark.parametrize("buckets", BUCKET_LISTS, ids=str)
def test_the_cover_holds_its_rules_at_every_length(buckets):
    small, large = min(buckets), max(buckets)
    for n in range(0, 3 * large + small + 2):
        plan = plan_chunks(n, buckets)
        assert plan == plan_chunks(n, list(reversed(buckets)))
        assert sum(v for _, v in plan) == n
        assert all(b in buckets and 0 < v <= b for b, v in plan)
        # Only the last chunk is padded.
        assert all(v == b for b, v in plan[:-1])
        old = greedy(n, buckets)
        assert len(plan) <= len(old)
        # A stream that asks by the tokens left walks this cover.
        if plan:
            assert plan[1:] == plan_chunks(n - plan[0][1], buckets)
        # One padded chunk at most, in the place of three or more
        # programs of at least three quarters its rows: never over a
        # tail of one or two.
        ups = [(b, v) for b, v in plan if padded_up(b, v, buckets)]
        if not ups:
            assert plan == old
            continue
        (b, v), = ups
        assert plan[-1] == (b, v) and plan[:-1] == old[:len(plan) - 1]
        tail = greedy(v, buckets)
        assert len(tail) >= 3 and 3 * b <= 4 * sum(x for x, _ in tail)
        assert b == min(x for x in buckets if x >= v)


# The mixes' edges: `fewshot` 320-704 over (128, 512), `docs` 1024-1920
# over the same, both `longdocs` tails and the harness's warm-up prompts
# (sum(buckets) - 3) over (512, 2048); ISSUE 49's three-bucket cases.
F512, F2048 = (512, 512), (2048, 2048)
COVERS = [
    ((128, 512), 320, [(512, 320)]),
    ((128, 512), 384, [(512, 384)]),
    ((128, 512), 385, [(512, 385)]),
    ((128, 512), 511, [(512, 511)]),
    ((128, 512), 512, [F512]),
    ((128, 512), 637, [F512, (128, 125)]),
    ((128, 512), 640, [F512, (128, 128)]),
    ((128, 512), 704, [F512, (128, 128), (128, 64)]),
    ((128, 512), 768, [F512, (128, 128), (128, 128)]),
    ((128, 512), 769, [F512, (512, 257)]),
    ((128, 512), 1920, [F512, F512, F512, (512, 384)]),
    ((128, 512), 256, [(128, 128), (128, 128)]),
    ((128, 512), 257, [(512, 257)]),
    ((512, 2048), 1024, [F512, F512]),
    ((512, 2048), 1025, [(2048, 1025)]),
    ((512, 2048), 1536, [(2048, 1536)]),
    ((512, 2048), 2047, [(2048, 2047)]),
    ((512, 2048), 2557, [F2048, (512, 509)]),
    ((512, 2048), 12288, [F2048] * 6),
    ((512, 2048), 4096 + 1025, [F2048, F2048, (2048, 1025)]),
    ((128, 512, 2048), 1100, [F512, F512, (128, 76)]),
    ((128, 512, 2048), 1900, [(2048, 1900)]),
    ((128, 512, 2048), 1400, [F512, F512, (512, 376)]),
    ((4, 16), 21, [(16, 16), (4, 4), (4, 1)]),
    ((4, 16), 27, [(16, 16), (16, 11)]),
]


@pytest.mark.parametrize("buckets, n, want", COVERS,
                         ids=[f"{b}-{n}" for b, n, _ in COVERS])
def test_the_covers_at_the_mixes_edges(buckets, n, want):
    assert plan_chunks(n, buckets) == want
    assert [padded_up(b, v, buckets) for b, v in want] == [
        (b, v) != old for (b, v), old in zip(want, greedy(n, buckets))]


def test_both_lanes_plan_the_one_cover():
    """The layer lane's and the megakernel lane's chunkers answer
    ``plan`` and ``next_chunk`` from ``plan_chunks`` alone."""
    for cls in (chunked.ChunkedPrefill, chunked.MegaChunkedPrefill):
        lane = types.SimpleNamespace(buckets=(4, 16))
        lane.plan = types.MethodType(cls.plan, lane)
        for n in (3, 11, 12, 21, 27, 40):
            assert lane.plan(n) == plan_chunks(n, (4, 16))
            assert cls.next_chunk(lane, n) == plan_chunks(n, (4, 16))[0]


# -- what is served does not depend on the cover ----------------------------

FAMILIES = {  # program family: (benchmark family, its tiny configuration)
    "dense": ("dense", "tiny"),
    "latent_moe": ("mla_moe", "tiny-mla"),
    "mamba_moe": ("mamba_latent_moe", "tiny-mamba"),
    "looped": ("looped", "tiny-ouro"),
}
BUCKETS = (8, 32)
# 29 and 19: one padded 32-row program each where the greedy cover took
# four and three of 8; 45 = 32 + 8 + 5 and 7 keep their covers.
PROMPTS = (29, 19, 45, 7)


def _served(name, max_len=64, slots=2):
    """(family, dims, server) of the tiny configuration of ``name``
    over ``BUCKETS``, seeded weights, float32."""
    family, file = FAMILIES[name]
    with open(os.path.join(DATA, "configs", file + ".json")) as f:
        config = json.load(f)
    F = loader.load_family(family, [loader.DATA_ROOT])
    build = loader.sibling(F.__file__, family + "_system")
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    eng = Engine(build.model_config(config), mesh, mode="xla",
                 dtype=jnp.float32, max_len=max_len,
                 params=build.make_params(config, mesh, SEED),
                 **build.engine_kwargs(config))
    srv = eng.serving(num_slots=slots, page=8, prefill_buckets=BUCKETS,
                      telemetry="spans")
    return F, F.dims(config), srv


def _slot_state(srv, h):
    """What slot ``h.slot`` holds: its length, its pages' rows by
    position up to it, and what the sequence keeps beside them."""
    slot, n = h.slot, int(srv._lens[h.slot])
    pids = np.asarray(srv.manager.table_row(slot))
    cache, out = srv.cache, {"lens": np.asarray(n)}
    for name in ("k_pages", "v_pages", "pages"):
        if hasattr(cache, name):
            got = np.asarray(getattr(cache, name))[:, pids]
            # (L, P, ..., page) or (L, P, KV, page, hd): positions up.
            got = np.moveaxis(got, 3, 2)
            out[name] = got.reshape(got.shape[0], -1, *got.shape[3:])[:, :n]
    _, _, *keeps = srv._prefiller.engine.model.paged_pool(srv.cfg)
    for key, spec in (keeps[0].get("seq_state", {}) if keeps else {}).items():
        out[key] = np.asarray(cache.seq[key]).take(slot, spec.slot_axis)
    return out


def _serve(srv, prompts, new_tokens):
    """The first prompt prefilled alone and its slot read, then all of
    them served: (tokens, chunks a prompt, the slot's state)."""
    first = srv.submit(prompts[0], max_new_tokens=new_tokens)
    while first.status in ("queued", "prefill"):
        srv.step()
    state = _slot_state(srv, first)
    rest = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts[1:]]
    srv.run()
    hs = [first] + rest
    assert all(h.status == "done" for h in hs)
    return ([h.tokens for h in hs],
            [[(b, v) for _, b, v in h.chunks] for h in hs], state)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_served_tokens_do_not_depend_on_the_cover(name, monkeypatch):
    """The same server serves the same prompts under this cover and
    held to the greedy one (its two chunk programs, and no third): the
    tokens are equal and the reference's, and the slot of a prompt that
    took ONE padded 32-row program holds the length, the pages' rows
    and the sequence's state that four programs of 8 leave (float32,
    another order of summation). The counters say which chunks were
    padded up, and ``prefill_fetch`` how many programs a prompt took."""
    F, dims, srv = _served(name)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, dims.vocab, size=n).tolist()
               for n in PROMPTS]
    got, chunks, state = _serve(srv, prompts, 6)
    assert chunks == [[(32, 29)], [(32, 19)], [(32, 32), (8, 8), (8, 5)],
                      [(8, 7)]]
    st = srv.stats()
    assert (st["prefill_chunks"], st["chunk_dispatches_padded_up"],
            st["prefill_rows_padded"]) == (6, 2, 3 + 13 + 3 + 1)
    spans = srv.obs.log.spans()
    assert [s.attrs["chunks"] for s in spans
            if s.kind == "prefill_fetch"] == [1, 1, 3, 1]
    assert sorted((s.attrs["bucket"], s.attrs["valid"],
                   s.attrs["padded_up"]) for s in spans
                  if s.kind == "prefill_chunk") == [
        (8, 5, 0), (8, 7, 0), (8, 8, 0), (32, 19, 1), (32, 29, 1),
        (32, 32, 0)]

    monkeypatch.setattr(chunked, "plan_chunks", greedy)
    want, old_chunks, old_state = _serve(srv, prompts, 6)
    assert [len(c) for c in old_chunks] == [4, 3, 3, 1]
    assert srv.stats()["chunk_dispatches_padded_up"] == 2    # none new
    assert srv.stats()["prefill_chunks"] == 6 + 11
    assert srv.prefill_cache_size() <= len(BUCKETS)
    assert got == want
    assert state.keys() == old_state.keys() and int(state["lens"]) >= 29
    assert set(state) - {"lens"} == {
        "dense": {"k_pages", "v_pages"}, "looped": {"k_pages", "v_pages"},
        "latent_moe": {"pages"},
        "mamba_moe": {"k_pages", "v_pages", "ssm_state", "conv_tail"}}[name]
    for key in state:
        np.testing.assert_allclose(state[key], old_state[key], rtol=0,
                                   atol=1e-4, err_msg=key)
    for prompt, tokens in zip(prompts, got):
        seq, at = reference.served_positions(prompt, tokens)
        rows = reference.logits_at(SEED, F, dims, jnp.float32, [seq],
                                   [at], pad_to=64)[0]
        assert reference.gaps(rows, tokens).max() < 1e-3


def test_a_padded_buckets_rows_past_the_slots_row_go_to_the_scratch_page():
    """A slot's row holds 56 positions; a 51-token prompt's second
    program is a padded 32-row one at position 32, so its last 8
    padding rows lie past the row's last page: they are written
    nowhere a reader looks, and the prompt beside it in the pool is
    served as alone."""
    F, dims, srv = _served("dense", max_len=56)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, dims.vocab, size=n).tolist()
               for n in (51, 20)]
    got, chunks, _ = _serve(srv, prompts, 5)
    assert chunks == [[(32, 32), (32, 19)], [(32, 20)]]
    assert srv.stats()["chunk_dispatches_padded_up"] == 2
    assert srv.stats()["pool"]["used_pages"] == 0
    for prompt, tokens in zip(prompts, got):
        seq, at = reference.served_positions(prompt, tokens)
        rows = reference.logits_at(SEED, F, dims, jnp.float32, [seq],
                                   [at], pad_to=64)[0]
        assert reference.gaps(rows, tokens).max() < 1e-3
