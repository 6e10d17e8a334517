"""The traffic generator: the same seed gives the same schedule, lengths
and token ids; another seed gives the same SET of sizes and gaps in
another order."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import loader, loadgen


def _mix(name):
    with open(os.path.join(loader.DATA_ROOT, "traffic", name + ".json")) as f:
        return json.load(f)


# An open-loop mix at a chat's sizes (no committed cell runs one yet).
CHAT = {"loop": "open", "rate_per_s": 1.6,
        "prompt_tokens": {"law": "lognormal", "median": 192, "sigma": 0.9,
                          "min": 32, "max": 1024},
        "output_tokens": {"law": "lognormal", "median": 64, "sigma": 0.7,
                          "min": 16, "max": 256}}


def _sig(reqs):
    return [(round(r.due_s, 9), len(r.prompt), r.max_new_tokens,
             r.prompt[:3]) for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_arrivals(seed):
    mix = CHAT
    a = loadgen.Plan(mix, 151936, seed).arrivals(45.0)
    b = loadgen.Plan(mix, 151936, seed).arrivals(45.0)
    assert _sig(a) == _sig(b)
    assert len(a) == round(mix["rate_per_s"] * 45.0)
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 45.0


def test_another_seed_same_sizes_in_another_order():
    mix = CHAT
    n = loadgen.BLOCK * 2         # whole blocks: the very same multiset
    sec = n / mix["rate_per_s"]
    a = loadgen.Plan(mix, 151936, 1).arrivals(sec)
    b = loadgen.Plan(mix, 151936, 2).arrivals(sec)
    assert _sig(a) != _sig(b)
    assert sorted(len(r.prompt) for r in a) == sorted(
        len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in b)
    gaps = lambda rs: np.sort(np.diff([0.0] + [r.due_s for r in rs]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_laws_keep_to_their_limits_and_medians():
    mix = CHAT
    p = loadgen.quantiles(mix["prompt_tokens"], 64)
    o = loadgen.quantiles(mix["output_tokens"], 64)
    assert p.min() >= 32 and p.max() <= 1024 and o.min() >= 16
    assert o.max() <= 256
    assert abs(np.median(p) - 192) <= 6 and abs(np.median(o) - 64) <= 3
    d = loadgen.quantiles(_mix("docs")["prompt_tokens"], 64)
    assert d.min() >= 1024 and d.max() <= 1920
    assert loadgen.quantiles({"law": "constant", "value": 32}, 5).tolist() \
        == [32] * 5
    with pytest.raises(ValueError):
        loadgen.quantiles({"law": "zipf"}, 4)


def test_closed_loop_documents_repeat_by_seed():
    mix = _mix("docs")
    a = loadgen.Plan(mix, 155136, 5)
    b = loadgen.Plan(mix, 155136, 5)
    first = [a.next() for _ in range(70)]     # past one block of 64
    again = [b.next() for _ in range(70)]
    assert _sig(first) == _sig(again)
    assert all(1024 <= len(r.prompt) <= 1920 and r.max_new_tokens == 32
               for r in first)
    assert max(max(r.prompt) for r in first) < 155136
    other = [loadgen.Plan(mix, 155136, 6).next() for _ in range(3)]
    assert _sig(other) != _sig(first[:3])


def test_exponential_gaps_have_the_stated_mean():
    g = loadgen.exp_gaps(2.0, 1000)
    assert abs(g.mean() - 0.5) < 0.01
