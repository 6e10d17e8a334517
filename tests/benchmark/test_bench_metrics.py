"""Metric arithmetic on hand-made samples, and the serving loops on a
toy system with a clock of its own."""

import math

import pytest

from benchmark.harness import loadgen, metrics as M, serve_loop as L


def _rec(due, times, prompt=10, status="done"):
    r = L.Record(0, [1] * prompt, len(times), due_at=due)
    r.token_times, r.tokens = list(times), [7] * len(times)
    r.status = status
    r.finished_at = times[-1] if times else None
    return r


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 5.5), (90, 9.1),
                                    (95, 9.55), (100, 10.0)])
def test_percentile_is_linear_between_order_statistics(q, want):
    assert M.percentile(range(1, 11), q) == pytest.approx(want)


def test_percentile_of_nothing_is_nan():
    assert math.isnan(M.percentile([], 50))


def test_ttft_counts_from_when_the_request_was_due():
    recs = [_rec(1.0, [1.25, 1.30]), _rec(2.0, [2.10]), _rec(3.0, [])]
    assert M.ttfts_ms(recs) == pytest.approx([250.0, 100.0])


def test_gaps_are_all_gaps_that_close_inside_the_window():
    recs = [_rec(0, [1.0, 1.02, 1.05]), _rec(0, [1.9, 2.1])]
    assert sorted(M.itl_gaps_ms(recs, 2.0)) == pytest.approx([20.0, 30.0])
    assert len(M.itl_gaps_ms(recs, 3.0)) == 3


def test_tokens_per_s_counts_prompt_and_output_of_completed_requests():
    recs = [_rec(0, [1.0, 2.0], prompt=100),            # 102, inside
            _rec(0, [3.0, 11.0], prompt=50),            # ends outside
            _rec(0, [4.0], prompt=30, status="failed")]
    assert M.tokens_per_s(recs, 0.0, 10.0) == pytest.approx(10.2)
    e = M.end_to_end(recs, 0.0, 10.0)
    assert e["tokens_per_s"] == pytest.approx(10.2)
    assert e["_samples"] == {"ttft": 3, "itl": 1}


class Toy:
    """One slot, a token a step, a queue of ``cap``; time moves only
    when the loop steps or sleeps."""

    def __init__(self, cap=2, step_s=0.01):
        self.now, self.cap, self.step_s = 0.0, cap, step_s
        self.queue, self.running = [], None

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += max(s, 1e-4)

    def submit(self, planned, on_token):
        if len(self.queue) >= self.cap:
            raise L.Refused("full")
        h = {"left": planned.max_new_tokens, "cb": on_token,
             "status": "queued"}
        self.queue.append(h)
        return h

    def busy(self):
        return bool(self.queue or self.running)

    def status(self, h):
        return h["status"]

    def slot(self, h):
        return 0 if h is self.running else None

    def step(self):
        self.now += self.step_s
        if self.running is None and self.queue:
            self.running = self.queue.pop(0)
            self.running["status"] = "running"
            return 0
        if self.running is None:
            return 0
        h = self.running
        h["cb"](42)
        h["left"] -= 1
        if h["left"] == 0:
            h["status"], self.running = "done", None
        return 1


def test_open_loop_times_from_due_and_counts_refusals():
    toy = Toy(cap=1)
    arrivals = [loadgen.Planned(i, 0.001 * i, [1, 2, 3], 5)
                for i in range(4)]      # a burst: the queue holds one
    win = L.run_open(toy, arrivals, 1.0, drain_s=5.0, clock=toy.clock,
                     sleep=toy.sleep)
    c = L.counts(win)
    assert c["attempted"] == 4 and c["failed"] == c["by_status"]["refused"]
    assert c["by_status"]["done"] + c["failed"] == 4 and c["failed"] >= 1
    done = [r for r in win.records if r.status == "done"]
    assert all(len(r.tokens) == 5 and r.tokens == [42] * 5 for r in done)
    assert M.ttfts_ms(done)[0] == pytest.approx(
        (done[0].token_times[0] - done[0].due_at) * 1e3)
    assert all(t.end - t.start == pytest.approx(0.01) for t in win.ticks)
    assert any(t.prefill for t in win.ticks) and any(
        t.decoded and not t.prefill for t in win.ticks)


def test_open_loop_fires_hooks_once_in_order():
    toy, seen = Toy(), []
    arrivals = [loadgen.Planned(0, 0.0, [1], 30)]
    L.run_open(toy, arrivals, 0.2, hooks=[(0.1, lambda: seen.append("b")),
                                          (0.05, lambda: seen.append("a"))],
               clock=toy.clock, sleep=toy.sleep)
    assert seen == ["a", "b"]


def test_closed_loop_keeps_its_clients_waiting_for_replies():
    class Docs:
        n = 0

        def next(self):
            Docs.n += 1
            return loadgen.Planned(Docs.n, 0.0, [1] * 8, 2)

    toy = Toy(cap=8)
    win = L.run_closed(toy, Docs(), 3, 1.0, clock=toy.clock)
    done = [r for r in win.records if r.status == "done"]
    assert len(done) >= 20 and L.counts(win)["failed"] == 0
    assert {r.client for r in win.records} == {0, 1, 2}
    # Never more in the system than there are clients.
    assert len(win.records) - len(done) <= 3
    assert M.tokens_per_s(win.records, win.start, win.end) == \
        pytest.approx(10 * sum(r.finished_at <= win.end for r in done))


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 77])
def test_the_compared_sample_has_the_longest_and_every_slot(seed):
    from benchmark.harness.reference import pick_sample

    recs = []
    for i in range(60):
        r = _rec(0, [1.0, 2.0], prompt=10 + i)
        r.rid, r.slot = i, (7 if i == 33 else i % 7)  # slot 7: one request
        recs.append(r)
    got = pick_sample(recs, 8, seed)
    assert len(got) == 8 and len({r.rid for r in got}) == 8
    assert got[0].rid == 59                    # the longest
    assert {r.slot for r in got} == set(range(8))
    assert [r.rid for r in pick_sample(recs, 8, seed)] == [r.rid
                                                           for r in got]
    # Fewer asked for than slots: the longest and what fits; more: filled.
    assert len(pick_sample(recs, 3, seed)) == 3
    assert len({r.rid for r in pick_sample(recs, 20, seed)}) == 20
    assert len(pick_sample(recs[:2], 8, seed)) == 2
    assert pick_sample([], 8, seed) == []
