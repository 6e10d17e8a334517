"""Drive a served system through a window: open loop (arrivals on a
schedule, latency from when a request was DUE) or closed loop (clients
that each wait for a reply). One thread; the host clock stamps every
token as the system streams it and every tick around ``system.step()``.

``system`` is anything with ``submit(planned, on_token) -> handle``
(raising ``Refused``), ``step() -> int`` (sequences that decoded),
``busy() -> bool``, ``status(handle) -> str`` and ``slot(handle)`` (the
decode slot the request holds, or None); ``system.py`` wraps the program
so, and the tests wrap a toy.
"""

from __future__ import annotations

import dataclasses
import time

TERMINAL = ("done", "failed", "timeout", "refused")
WAITING = ("queued", "prefill")


class Refused(Exception):
    """The system's own queue turned the request away."""


@dataclasses.dataclass
class Record:
    rid: int
    prompt: list
    max_new_tokens: int
    due_at: float
    submitted_at: float = None
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    status: str = "planned"
    finished_at: float = None
    client: int = None
    slot: int = None      # the decode slot it held, for the check's sample
    handle: object = None


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    decoded: int          # sequences the tick's decode dispatch served
    prefill: bool         # a request was waiting or mid-prefill at its start


@dataclasses.dataclass
class Window:
    records: list
    ticks: list
    start: float
    end: float            # start + seconds: where the measured window closes
    late_s: list          # how late the generator submitted each request
    stopped: float = None  # when the loop (drain included) returned


def _submit(system, planned, due_at, clock, client=None) -> Record:
    rec = Record(planned.rid, planned.prompt, planned.max_new_tokens,
                 due_at, client=client)

    def on_token(tok):
        rec.token_times.append(clock())
        rec.tokens.append(int(tok))

    try:
        rec.handle = system.submit(planned, on_token)
        rec.status = "queued"
    except Refused:
        rec.status = "refused"
    rec.submitted_at = clock()
    return rec


def _settle(system, inflight, clock):
    """Move finished requests out of ``inflight``; returns them."""
    ended = []
    for rec in list(inflight):
        rec.status = system.status(rec.handle)
        slot = system.slot(rec.handle)
        if slot is not None:
            rec.slot = int(slot)
        if rec.status in TERMINAL:
            rec.finished_at = (rec.token_times[-1] if rec.token_times
                               else clock())
            rec.handle = None
            inflight.remove(rec)
            ended.append(rec)
    return ended


def _tick(system, win, inflight, clock) -> list:
    """One ``step()`` with the host clock around it; returns the
    requests that ended in it."""
    waiting = any(r.status in WAITING for r in inflight)
    ta = clock()
    decoded = system.step()
    win.ticks.append(Tick(ta, clock(), int(decoded), waiting))
    return _settle(system, inflight, clock)


def _fire(hooks, elapsed):
    while hooks and hooks[0][0] <= elapsed:
        hooks.pop(0)[1]()


DRAIN_S = 30.0   # the open loop gives what is in flight this long to end


def run_open(system, arrivals, seconds, *, drain_s=DRAIN_S, hooks=(),
             clock=time.perf_counter, sleep=time.sleep) -> Window:
    """Submit each planned request when it is due, step the system
    whenever it has work, stop when every request has ended (or
    ``drain_s`` after the window closed). ``hooks``: (offset_s, fn) pairs
    called once the window is that old."""
    hooks = sorted(hooks, key=lambda h: h[0])
    t0 = clock()
    win = Window([], [], t0, t0 + seconds, [])
    todo = list(arrivals)
    inflight = []
    while True:
        now = clock()
        _fire(hooks, now - t0)
        while todo and t0 + todo[0].due_s <= now:
            planned = todo.pop(0)
            rec = _submit(system, planned, t0 + planned.due_s, clock)
            win.late_s.append(rec.submitted_at - rec.due_at)
            win.records.append(rec)
            if rec.status != "refused":
                inflight.append(rec)
        if not todo and not inflight:
            break
        if now - t0 > seconds + drain_s:
            break
        if not system.busy():
            if not todo:
                break
            sleep(max(min(t0 + todo[0].due_s - clock(), 0.001), 0.0))
            continue
        _tick(system, win, inflight, clock)
    _fire(hooks, float("inf"))
    win.stopped = clock()
    return win


def run_closed(system, plan, clients, seconds, *, hooks=(),
               clock=time.perf_counter) -> Window:
    """``clients`` callers, each sending its next request when the last
    is answered, for ``seconds``; what is in flight then is left."""
    hooks = sorted(hooks, key=lambda h: h[0])
    t0 = clock()
    win = Window([], [], t0, t0 + seconds, [])
    inflight = []

    def send(client):
        rec = _submit(system, plan.next(), clock(), clock, client=client)
        win.late_s.append(rec.submitted_at - rec.due_at)
        win.records.append(rec)
        if rec.status != "refused":
            inflight.append(rec)

    for c in range(clients):
        send(c)
    while clock() - t0 < seconds and inflight:
        _fire(hooks, clock() - t0)
        for rec in _tick(system, win, inflight, clock):
            if clock() - t0 < seconds:
                send(rec.client)
    _fire(hooks, float("inf"))
    win.stopped = clock()
    return win


def run_until_idle(system, planned_list, clock=time.perf_counter) -> list:
    """Warm-up: submit all, step until every one has ended."""
    recs = [_submit(system, p, clock(), clock) for p in planned_list]
    inflight = [r for r in recs if r.status != "refused"]
    while inflight:
        system.step()
        _settle(system, inflight, clock)
    return recs


def counts(win: Window) -> dict:
    """Requests by how they ended; ``failed`` is refused, failed or
    timed out; what the loop left in flight is neither."""
    by = {}
    for r in win.records:
        by[r.status] = by.get(r.status, 0) + 1
    bad = sum(by.get(s, 0) for s in ("failed", "timeout", "refused"))
    return {"attempted": len(win.records), "failed": bad, "by_status": by}
