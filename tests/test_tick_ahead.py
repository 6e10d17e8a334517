"""The tick in two halves (docs/serving.md): ``ServingEngine.step()``
launches tick T+1 before it lands tick T wherever nothing reads a token's
value between the two, and every request's tokens are then those of the
engine that lands each tick in the step that launched it.

The in-order engine is the SAME engine with a watchdog armed
(``timeout_s``: one of the reasons ``_in_order_by`` names), not a flag:
each case serves its traffic launched ahead, arms the watchdog, serves
it again in order, and compares. The five program families run at their
tiny sizes (``tests/benchmark/data/configs``) over buckets (8, 32),
page 8, three slots.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu as tdt
from benchmark.harness import loader
from triton_dist_tpu.models import Engine

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
SEED = 23
FAMILIES = {  # program family: (benchmark family, its tiny configuration)
    "dense": ("dense", "tiny"),
    "latent_moe": ("mla_moe", "tiny-mla"),
    "mamba_moe": ("mamba_latent_moe", "tiny-mamba"),
    "looped": ("looped", "tiny-ouro"),
    "window_moe": ("swa_moe", "tiny-swa"),
}
BUCKETS, PAGE, SLOTS, MAX_LEN = (8, 32), 8, 3, 64
# The deeper tiny configurations cut to one layer of each kind: a
# process that meets a family compiles its three programs.
CUT = {
    "mamba_moe": {"num_hidden_layers": 3, "hybrid_override_pattern": "M*E"},
    "window_moe": {"num_hidden_layers": 2,
                   "layer_types": ["sliding_attention", "full_attention"],
                   "sliding_windows": [8, 0],
                   "mlp_layer_types": ["dense", "sparse"]},
}
COUNTED = ("ticks_launched_ahead", "ticks_in_order", "rows_discarded",
           "preemptions", "seq_state_resets", "decode_dispatches_fused",
           "tokens_generated")


@functools.lru_cache(maxsize=None)
def _engine(name):
    """(vocabulary, engine) of family ``name``'s tiny configuration,
    seeded weights, float32: built once a process."""
    family, file = FAMILIES[name]
    with open(os.path.join(DATA, "configs", file + ".json")) as f:
        config = dict(json.load(f), **CUT.get(name, {}))
    F = loader.load_family(family, [loader.DATA_ROOT])
    build = loader.sibling(F.__file__, family + "_system")
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    eng = Engine(build.model_config(config), mesh, mode="xla",
                 dtype=jnp.float32, max_len=MAX_LEN,
                 params=build.make_params(config, mesh, SEED),
                 **build.engine_kwargs(config))
    return F.dims(config).vocab, eng


@functools.lru_cache(maxsize=None)
def _server(name):
    """One server a family and process: every case drains it, so each
    also finds the slots and pages the last one left."""
    vocab, eng = _engine(name)
    return vocab, eng.serving(num_slots=SLOTS, page=PAGE,
                              prefill_buckets=BUCKETS, telemetry="spans")


def _prompts(vocab, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lengths]


def _serve(srv, requests, *, in_order, first=0, after_first=None):
    """Serve ``requests`` (``(prompt, Request kwargs)``): the first
    ``first`` are submitted and stepped until they decode before the
    rest arrive. ``in_order`` arms the watchdog. Returns the handles
    and what the counters of ``COUNTED`` and ``in_order_by`` gained."""
    srv.timeout_s = 3600.0 if in_order else None
    before = srv.stats()
    hs = [srv.submit(p, **kw) for p, kw in requests[:first]]
    while any(not h.tokens for h in hs):
        srv.step()
    if after_first is not None:
        after_first(hs)
    hs += [srv.submit(p, **kw) for p, kw in requests[first:]]
    srv.run()
    srv.timeout_s = None
    after = srv.stats()
    assert all(h.status == "done" for h in hs)
    assert srv.sched.idle and srv._flight is None
    assert after["pool"]["used_pages"] == before["pool"]["used_pages"]
    gained = {k: after[k] - before[k] for k in COUNTED}
    gained["in_order_by"] = {
        k: v - before["in_order_by"].get(k, 0)
        for k, v in after["in_order_by"].items()
        if v - before["in_order_by"].get(k, 0)}
    return hs, gained


def _both(name, requests, **kw):
    """``requests`` served launched ahead, then in order by the same
    engine: (handles ahead, what it gained, handles in order)."""
    _, srv = _server(name)
    ahead, gained = _serve(srv, requests, in_order=False, **kw)
    order, by = _serve(srv, requests, in_order=True, **kw)
    assert by["ticks_launched_ahead"] == 0 and by["rows_discarded"] == 0
    assert set(by["in_order_by"]) == {"watchdog"}
    assert by["in_order_by"]["watchdog"] == by["ticks_in_order"] > 0
    assert srv.decode_cache_size() == 1
    assert srv.prefill_cache_size() <= len(BUCKETS)
    # The token feed: one shape a kind of program it reads from.
    assert srv._feed._cache_size() <= 2
    return ahead, gained, order


def _greedy(prompts, new_tokens):
    return [(p, {"max_new_tokens": n}) for p, n in zip(prompts, new_tokens)]


def _expect_ahead(gained):
    assert gained["ticks_launched_ahead"] > 0
    assert gained["ticks_in_order"] == 0 and not gained["in_order_by"]


CASES = {
    # Several lengths, twice the slots: one chunk, a padded one, three
    # chunks; ticks that ride, parked chunks, decode-only ticks.
    "mixed": ((29, 7, 45, 19, 12, 33), (6, 9, 5, 7, 4, 8), 0),
    # A request of ONE token among others: it ends by count with its
    # prompt's last chunk, alone (first tick) and in a riding tick.
    "one_token": ((9, 20, 7, 30, 11), (1, 5, 1, 1, 6), 0),
    # A decoder is live when an 8-token prompt arrives: its one chunk
    # carries the batch, the slot goes live at that launch and decodes
    # in the next tick, its input token still on the device.
    "consecutive": ((5, 8), (12, 6), 1),
    # Two prompts of one chunk each arrive beside a decoder: both
    # finish in the one riding tick (8 + 8 rows of 32).
    "two_finish": ((5, 8, 6), (12, 5, 5), 1),
}


@pytest.mark.parametrize("name", sorted(FAMILIES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_tokens_launched_ahead_are_the_in_order_ones(name, case):
    lengths, new_tokens, first = CASES[case]
    vocab, srv = _server(name)
    requests = _greedy(_prompts(vocab, lengths), new_tokens)
    ahead, gained, order = _both(name, requests, first=first)
    assert [h.tokens for h in ahead] == [h.tokens for h in order]
    assert [len(h.tokens) for h in ahead] == list(new_tokens)
    _expect_ahead(gained)
    assert gained["rows_discarded"] == 0
    assert gained["tokens_generated"] == sum(new_tokens)
    if first:
        assert gained["decode_dispatches_fused"] > 0
    if case == "two_finish":
        # Both prompts' chunks ran in one tick, under one decode batch.
        ids = {h.request.request_id for h in ahead[1:]}
        ticks = [s.attrs["tick"] for s in srv.obs.log.spans()
                 if s.kind == "prefill_chunk" and s.request_id in ids]
        assert len(ticks) == 2 and ticks[0] == ticks[1]


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_stop_token_mid_stream_discards_one_row(name):
    """A request with ``eos_id`` that stops early: the landing retires
    it, the row it had in the tick launched ahead is dropped
    (``rows_discarded`` 1), no token follows the stop, and the slot's
    next tenant (twice the slots are queued) is served as in order; a
    family whose sequences keep state resets the slot's at the
    tenant's first chunk."""
    vocab, srv = _server(name)
    prompts = _prompts(vocab, (10, 21, 6, 14, 27, 9), seed=5)
    plain = _greedy(prompts, (10,) * 6)
    want, _ = _serve(srv, plain, in_order=True)
    # The first request's first token that none before it equals,
    # past its first and short of its last.
    toks = want[0].tokens
    k = next(i for i in range(1, 9) if toks[i] not in toks[:i])
    stopped = [(prompts[0], {"max_new_tokens": 10, "eos_id": toks[k]})]
    ahead, gained, order = _both(name, stopped + plain[1:])
    assert ahead[0].tokens == order[0].tokens == toks[:k + 1]
    assert [h.tokens for h in ahead[1:]] == [h.tokens for h in want[1:]]
    assert [h.tokens for h in order[1:]] == [h.tokens for h in want[1:]]
    _expect_ahead(gained)
    assert gained["rows_discarded"] == 1
    if srv.stats().get("seq_state_bytes"):
        assert gained["seq_state_resets"] == len(prompts)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_dry_pool_lands_the_tick_in_flight_before_it_preempts(name):
    """The pool runs dry mid-decode (all but five of its pages are held
    back): the victim requeues with the tokens it has, so the tick in
    flight lands first; the resumed request, re-prefilled from prompt
    and tokens, is token-exact."""
    vocab, srv = _server(name)
    requests = _greedy(_prompts(vocab, (13, 14, 11), seed=7), (9, 9, 7))
    want, _ = _serve(srv, requests, in_order=True)
    free = srv.manager._free
    held = [free.pop() for _ in range(len(free) - 5)]
    try:
        ahead, gained, order = _both(name, requests)
    finally:
        free.extend(held)
    assert gained["preemptions"] > 0
    assert gained["ticks_launched_ahead"] > 0
    assert [h.tokens for h in ahead] == [h.tokens for h in want]
    assert [h.tokens for h in order] == [h.tokens for h in want]


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_sampled_request_keeps_its_ticks_in_order(name):
    """One request that samples among greedy ones: while it holds a
    slot the ticks land in order (``in_order_by["sampled"]``), the
    others before and after it run ahead, and every request's tokens
    are the in-order engine's under its seed."""
    vocab, srv = _server(name)
    prompts = _prompts(vocab, (12, 9, 25, 17, 8), seed=11)
    requests = _greedy(prompts, (6, 12, 5, 7, 9))
    requests[3] = (prompts[3], {"max_new_tokens": 7, "temperature": 0.8,
                                "top_k": 8, "seed": 5})
    ahead, gained, order = _both(name, requests)
    assert [h.tokens for h in ahead] == [h.tokens for h in order]
    assert set(gained["in_order_by"]) == {"sampled"}
    assert gained["in_order_by"]["sampled"] == gained["ticks_in_order"] > 0
    assert gained["ticks_launched_ahead"] > 0


@pytest.mark.parametrize("name", ["dense", "looped"])
def test_a_checkpoint_between_two_ticks_holds_nothing_in_flight(name):
    """``checkpoint()`` between two ticks lands the one in flight
    first: the snapshot's handles hold every token a launched program
    owed them, and ``restore`` (into the engine, once it has drained)
    continues each request exactly."""
    vocab, srv = _server(name)
    requests = _greedy(_prompts(vocab, (11, 20, 9), seed=13), (9, 8, 10))
    want, _ = _serve(srv, requests, in_order=True)
    snaps = []

    def snapshot(hs):
        for _ in range(2):
            srv.step()
        assert srv._flight is not None
        snaps.append(srv.checkpoint())
        assert srv._flight is None
        assert all(h.in_flight == 0 for h in hs)

    ahead, gained = _serve(srv, requests, in_order=False, first=3,
                           after_first=snapshot)
    assert [h.tokens for h in ahead] == [h.tokens for h in want]
    snap, = snaps
    held = {h["request"]["request_id"]: h for h in snap["handles"]}
    assert all(h["status"] == "running" and 1 < len(h["tokens"]) < 10
               for h in held.values())
    revived = srv.restore(snap)
    srv.run()
    by_prompt = {tuple(h.request.prompt): h.tokens for h in want}
    assert len(revived) == 3
    for h in revived:
        assert h.status == "done"
        assert h.tokens == by_prompt[tuple(h.request.prompt)]


# -- the contract the harness leans on (benchmark/harness/system.py) --------

def test_the_stream_has_every_token_before_the_status_turns():
    """``stream_cb`` has delivered a request's last token before its
    ``status`` reads ``done``; while a token is in flight the scheduler
    is not idle, the status is not terminal and ``handle.slot`` reads
    the slot the request held, also once the slot is another's."""
    vocab, srv = _server("dense")
    seen = {}
    last, relet = [], []

    def cb(tok, h):
        assert not h.done, "a token after the status turned"
        assert not srv.sched.idle
        seen.setdefault(h.request.request_id, []).append(tok)
        if len(h.tokens) == h.request.max_new_tokens:
            # The last token, known by count a launch ago: the slot
            # went back to admission then, the handle still says which
            # it was, and the request is not done before this returns.
            assert h.status == "running" and h.slot is not None
            assert any(x is h for x in srv.sched.landing)
            last.append(h)
            relet.append(srv.sched.slots.get(h.slot) is not None)

    prompts = _prompts(vocab, (9, 12, 7, 10, 8, 11), seed=17)
    hs = [srv.submit(p, max_new_tokens=4, stream_cb=cb) for p in prompts]
    slots = {}
    while not srv.sched.idle:
        srv.step()
        assert srv._flight is None or not srv.sched.idle
        assert not srv.sched.landing      # all retired in the landing
        for h in hs:
            if h.slot is not None:
                assert slots.setdefault(id(h), h.slot) == h.slot
            if h.done:
                assert seen[h.request.request_id] == h.tokens
                assert h.slot is None and len(h.tokens) == 4
    assert all(h.status == "done" for h in hs)
    assert len(slots) == len(last) == len(hs)
    assert any(relet), "no slot was re-let while its last token flew"
    assert srv.stats()["pool"]["used_pages"] == 0


def test_a_step_with_nothing_to_launch_lands_what_is_in_flight():
    """So ``run()`` and the harness's ``run_until_idle`` end: the last
    tick's tokens arrive in a ``step()`` that dispatches nothing."""
    vocab, srv = _server("dense")
    h, = [srv.submit(p, max_new_tokens=2)
          for p in _prompts(vocab, (6,), seed=19)]
    ticks = 0
    while not h.done:
        srv.step()
        ticks += 1
        assert ticks < 10
    # The chunk and the first decode step launched together; the second
    # step() found the request at its count, launched nothing, landed.
    assert ticks == 2 and len(h.tokens) == 2
    assert srv.sched.idle and srv._flight is None
    assert srv.step() == 0


def test_the_same_traffic_leaves_both_engines_the_same_programs():
    """An engine that launches ahead and one with a watchdog armed hold
    the same compiled programs after the same traffic: one decode
    program, one a bucket, whichever way the token was fed."""
    vocab, eng = _engine("dense")
    requests = _greedy(_prompts(vocab, (29, 7, 45, 19, 12), seed=21),
                       (6, 9, 5, 7, 4))
    sizes, tokens = [], []
    for timeout_s in (None, 3600.0):
        srv = eng.serving(num_slots=SLOTS, page=PAGE,
                          prefill_buckets=BUCKETS, timeout_s=timeout_s)
        hs = [srv.submit(p, **kw) for p, kw in requests]
        srv.run()
        tokens.append([h.tokens for h in hs])
        sizes.append((srv.decode_cache_size(), srv.prefill_cache_size()))
        st = srv.stats()
        assert bool(st["ticks_launched_ahead"]) == (timeout_s is None)
        assert bool(st["ticks_in_order"]) == (timeout_s is not None)
    assert sizes[0] == sizes[1] == (1, len(BUCKETS))
    assert tokens[0] == tokens[1]


def test_a_fed_token_leaves_the_step_programs_text_as_it_was():
    """The token fed from the device is no new operand: a chunk program
    and the decode program lower to the same text for the tokens as the
    host uploads them and as ``_feed`` hands them on (what
    ``scripts/lowered_sums.py`` sums, for the cells' programs, against
    the parent's tree)."""
    import dataclasses

    vocab, srv = _server("dense")
    host = jax.device_put(np.zeros((SLOTS,), np.int32), srv._row_sh)
    picked = jax.device_put(np.arange(1 + SLOTS, dtype=np.int32),
                            srv._row_sh)
    fed = srv._feed(host, np.asarray([1, -1, 3], np.int32), picked)
    assert np.asarray(fed).tolist() == [1, 0, 3]
    assert fed.sharding == host.sharding and fed.committed
    cache = dataclasses.replace(
        srv.cache,
        block_table=jnp.zeros(srv.cache.block_table.shape, jnp.int32),
        lens=jnp.zeros((SLOTS,), jnp.int32),
        live=jnp.zeros((SLOTS,), jnp.int32))
    params = srv.engine.params
    texts = [srv._decode.lower(params, toks, cache).as_text()
             for toks in (host, fed)]
    assert texts[0] == texts[1]
    chunk = [srv.chunker._chunk.lower(
        params, jnp.zeros((8,), jnp.int32), cache,
        jnp.zeros((srv.manager.table_width,), jnp.int32), np.int32(0),
        np.int32(0), np.int32(8), toks).as_text() for toks in (host, fed)]
    assert chunk[0] == chunk[1]
