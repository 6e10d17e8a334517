"""Speculative multi-token decoding battery.

The contract under test: speculation changes THROUGHPUT, never tokens
— greedy outputs through the K-token verification dispatch are
bit-identical to the non-speculative serving run (which is itself
token-exact vs ``Engine.serve``), across draft quality, rollback,
preemption mid-draft, and fault injection; and the verification
dispatch never re-specializes (K is static, acceptance is data).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.resilience import faults
from triton_dist_tpu.serving import (
    NgramDraft, OutOfPagesError, Request, ServingEngine, accept_greedy,
)

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


# ---------------------------------------------------------------------------
# draft proposer + acceptance rule (pure host logic)
# ---------------------------------------------------------------------------

def test_ngram_draft_proposes_from_history():
    d = NgramDraft(n=2)
    # trailing (2, 3) last occurred earlier, followed by 9, 2:
    assert d.propose([1, 2, 3, 9, 2, 3], 2) == [9, 2]
    # no earlier match anywhere: repeat the last token
    assert d.propose([5, 6, 7], 3) == [7, 7, 7]
    # short continuation CYCLES the matched suffix
    assert d.propose([4, 8, 4, 8], 3) == [4, 8, 4]
    # deterministic: same history, same proposal
    h = list(np.random.RandomState(0).randint(0, 9, 30))
    assert d.propose(h, 4) == d.propose(list(h), 4)


def test_accept_greedy_rule():
    # t_1 always commits; t_j commits iff t_{j-1} == d_j.
    assert accept_greedy([5, 7, 8, 9], [7, 8, 9, 1]) == 4   # exact draft
    assert accept_greedy([5, 7, 8, 9], [7, 8, 2, 1]) == 3   # d_4 != t_3
    assert accept_greedy([5, 0, 0, 0], [7, 8, 9, 1]) == 1   # miss at once
    assert accept_greedy([5], [7]) == 1                     # K=1 degenerate


# ---------------------------------------------------------------------------
# token-exactness: acceptance + rollback determinism vs the non-spec run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec_k", [1, 2, 4])
def test_spec_token_exact_vs_nonspec(engine, spec_k):
    """Greedy outputs are bit-identical with speculation on, for the
    K=1 degenerate case (exact self-draft) through K=4 (mixed
    accept/reject rollback every dispatch)."""
    prompts = [[1, 2, 3, 1, 2, 3], [4, 5], [6, 7, 8, 9], [5, 5, 5]]
    want = [_baseline(engine, p, 10) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=spec_k)
    got = srv.generate(prompts, max_new_tokens=10)
    assert got == want
    st = srv.stats()
    assert st["spec"]["k"] == spec_k
    if spec_k > 1:
        # The repetitive prompts must have amortized some dispatches.
        assert st["spec"]["tokens_per_dispatch"] > 1.0


def test_spec_fewer_dispatches_on_repetitive_trace(engine):
    """The point of the feature: accepted tokens amortize dispatches
    (the CPU bench's serving_tokens_per_s_spec ratio rides this)."""
    prompts = [[1, 2, 3, 1, 2, 3, 1, 2], [7, 8, 7, 8, 7, 8]]
    base = ServingEngine(engine, num_slots=1, page=PAGE)
    spec = ServingEngine(engine, num_slots=1, page=PAGE, spec_k=4)
    want = base.generate(prompts, max_new_tokens=24)
    got = spec.generate(prompts, max_new_tokens=24)
    assert got == want
    d_base = base.stats()["decode_dispatches"]
    d_spec = spec.stats()["decode_dispatches"]
    assert d_spec < d_base, (d_spec, d_base)
    assert spec.stats()["spec"]["accepted"] > 0


def test_spec_eos_and_budget_mid_block(engine):
    """EOS landing mid-verification-block and a max_new_tokens budget
    smaller than K both truncate emission exactly like the sequential
    run (the over-budget candidates' writes land in scratch)."""
    want = _baseline(engine, [1, 2, 3], 3)
    srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=4)
    h = srv.submit([1, 2, 3], max_new_tokens=3)   # budget < K
    srv.run()
    assert h.tokens == want
    # EOS: pick the baseline's second token as eos — the spec run must
    # stop at it even when the block carried more accepted tokens.
    eos = want[1]
    want_eos = want[:want.index(eos) + 1]
    srv2 = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=4)
    h2 = srv2.submit([1, 2, 3], max_new_tokens=10, eos_id=eos)
    srv2.run()
    assert h2.tokens == want_eos


def test_spec_sampled_requests_commit_one_exact_token(engine):
    """Non-greedy requests ride the same dispatch but commit exactly
    one token per dispatch from position 0's exact logits — identical
    to their non-spec sampled run (same seed fold)."""
    req = dict(max_new_tokens=6, temperature=0.8, top_k=4, seed=11)
    base = ServingEngine(engine, num_slots=2, page=PAGE)
    hb = base.submit([3, 1, 4], **req)
    base.run()
    spec = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=4)
    hs = spec.submit([3, 1, 4], **req)
    spec.run()
    assert hs.tokens == hb.tokens


# ---------------------------------------------------------------------------
# fixed shape / no recompile
# ---------------------------------------------------------------------------

def test_spec_fixed_shape_no_recompile(engine):
    """The verification dispatch compiles ONCE: requests joining and
    leaving, full/partial acceptance, and budget-clamped tail blocks
    are all data."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=3)
    srv.generate([[1, 2]], max_new_tokens=2)        # warmup
    assert srv.decode_cache_size() == 1
    prompts = [[1, 2, 3, 1, 2, 3], [4, 5], [6, 7, 8], [9], [2, 4, 6]]
    srv.generate(prompts, max_new_tokens=9)
    assert srv.decode_cache_size() == 1, "verify dispatch re-specialized"


# ---------------------------------------------------------------------------
# preemption + rollback machinery
# ---------------------------------------------------------------------------

def test_spec_preemption_mid_draft_token_exact(engine):
    """Pool exhaustion while pre-allocating a draft block's pages
    preempts that request (pages freed, requeued, resumed via the
    deterministic re-prefill) — outputs still bit-exact."""
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]]
    want = [_baseline(engine, p, 4) for p in prompts]
    srv = ServingEngine(engine, num_slots=2, page=PAGE, num_pages=3,
                        spec_k=4)
    hs = [srv.submit(p, max_new_tokens=4) for p in prompts]
    srv.run()
    assert [h.status for h in hs] == ["done", "done"]
    assert [h.tokens for h in hs] == want
    assert srv.stats()["preemptions"] >= 1


def test_spec_truncate_never_frees_prefix_shared_pages(engine):
    """Rollback's page-level truncate keeps the slot's prefix-hit run:
    two same-prefix requests sharing pages decode speculatively
    without ever freeing (or corrupting) the shared pages."""
    shared = list(range(1, PAGE + 1))       # exactly one full page
    p1 = shared + [20, 21]
    p2 = shared + [30]
    want = [_baseline(engine, p, 6) for p in (p1, p2)]
    srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=4,
                        prefix_reuse=True)
    got = srv.generate([p1, p2], max_new_tokens=6)
    assert got == want
    assert srv.stats()["pool"]["prefix_hits"] >= 1


# ---------------------------------------------------------------------------
# fault containment
# ---------------------------------------------------------------------------

def test_spec_dropped_verification_fails_one_request(engine):
    """A fault plan dropping a verification dispatch fails the
    scheduler's victim, not the server; the survivor's output stays
    token-exact."""
    srv = ServingEngine(engine, num_slots=2, page=PAGE, spec_k=3)
    doomed = srv.submit([1, 2], max_new_tokens=6)
    srv.step()                                # doomed decodes first
    ok = srv.submit([6, 7, 8], max_new_tokens=5)
    with faults.inject(faults.get_plan("fail_kth_call",
                                       op="spec_verify", k=0)):
        srv.run()
    assert doomed.status == "failed"
    assert isinstance(doomed.error, faults.InjectedFault)
    assert ok.status == "done"
    assert ok.tokens == _baseline(engine, [6, 7, 8], 5)
    assert srv.stats()["pool"]["used_pages"] == 0, "pages leaked"


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------

# One megakernel engine per build config for the whole module —
# engine builds dominate wall clock, and reuse is the serving layer's
# slot-recycling contract (positions rewrite, lengths mask).
_MK_CACHE: dict = {}

# The megakernel tests' micro config: an interpreted step costs in
# proportion to the tasks in its queue, and the heads and the FFN's width
# add theirs (77 tasks a step at ``tiny(vocab_size=128)``, 49 here).
MK_CFG = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                          intermediate_size=32, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=8)


def _mk_engine(**kw):
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    key = tuple(sorted(kw.items()))
    if key not in _MK_CACHE:
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        base = dict(batch=2, max_len=32, tile_w=16, t_tile=16,
                    paged=True, page=16, num_pages=5)
        base.update(kw)
        _MK_CACHE[key] = MegaKernelEngine(MK_CFG, mesh, **base)
    return _MK_CACHE[key]


def test_megakernel_spec_token_exact_vs_nonspec():
    """The converted mk-reject: spec_k=2 on the megakernel under
    schedule='dynamic' (the scoreboard claims the verification chains)
    is token-exact vs the non-spec mk run on the repetitive trace —
    the Q-block verification rows' logits are bit-identical to the
    sequential decode body's, so greedy acceptance commits exactly
    the sequential tokens — with > 1 tokens per dispatch measured."""
    rep = [[1, 2, 1, 2], [7, 8, 7]]
    want = ServingEngine(_mk_engine()).generate(rep, max_new_tokens=8)
    srv = ServingEngine(_mk_engine(spec_k=2, schedule="dynamic"),
                        spec_k=2)
    got = srv.generate(rep, max_new_tokens=8)
    assert got == want
    st = srv.stats()
    assert st["spec"]["k"] == 2
    assert st["spec"]["tokens_per_dispatch"] > 1.0, st["spec"]
    assert st["mk_spec"] == 2
    # The verification dispatch never re-specializes: requests
    # joining/leaving, acceptance patterns, and budget-clamped tails
    # are all data.
    n = srv.decode_cache_size()
    srv.generate([[4, 4]], max_new_tokens=2)
    assert srv.decode_cache_size() == n, "mk verify re-specialized"


@pytest.mark.parametrize("case", ["budget", "eos", "sampled"])
def test_megakernel_spec_eos_budget_and_sampled(case):
    """EOS mid-block, a max_new budget smaller than K (over-budget
    rows MASKED in-kernel, never touching real pages), and sampled
    requests (one exact token per dispatch) all match the non-spec
    megakernel run."""
    req = dict(max_new_tokens=3)                    # budget < K
    if case == "sampled":
        req.update(temperature=0.8, top_k=4, seed=11)
    base = ServingEngine(_mk_engine())
    hb = base.submit([1, 2], **req)
    base.run()
    want = hb.tokens
    if case == "eos":
        req.update(max_new_tokens=10, eos_id=want[1])
        want = want[:want.index(want[1]) + 1]
    spec = ServingEngine(_mk_engine(spec_k=4), spec_k=4)
    hs = spec.submit([1, 2], **req)
    spec.run()
    assert hs.tokens == want


def test_megakernel_spec_knob_validation():
    """spec_k is an ENGINE knob on the mk lane: serving/engine
    mismatch, non-paged builds, and hybrid builds fail loudly."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="spec_k mismatch"):
        ServingEngine(_mk_engine(), spec_k=2)
    with pytest.raises(ValueError, match="paged"):
        MegaKernelEngine(ModelConfig.tiny(vocab_size=128), mesh,
                         batch=2, max_len=32, tile_w=16, t_tile=16,
                         spec_k=2)
    hcfg = ModelConfig.tiny_next(vocab_size=128, num_key_value_heads=4,
                                 full_attn_interval=2)
    with pytest.raises(NotImplementedError, match="hybrid"):
        MegaKernelEngine(hcfg, mesh, batch=2, max_len=32, tile_w=16,
                         t_tile=16, paged=True, page=16, spec_k=2)
