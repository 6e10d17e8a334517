"""Process-level fault domain: the serving tick loop in a supervised
CHILD process, with crash/hang recovery that resumes client streams
token-exact.

Everything below the serving API already tolerates *injected* faults
(dropped transfers, wedged dispatches, corrupted payloads) — but an
actual process death (OOM kill, segfault in a native dep, a wedged
interpreter thread) takes the whole engine with it, and no in-process
machinery can recover from its own demise.  The supervisor splits the
fault domain:

- the **child** (``python -m triton_dist_tpu.resilience.supervisor
  --child``) owns the engine: it builds it from an importable factory
  (``module:qualname``), runs the tick loop, prints a heartbeat line
  every loop and a ``tok`` ack line for every emitted token, and
  writes a journaled keep-last-K checkpoint ring
  (``ckpt-<seq>.pkl`` + atomic ``ring.json``) every
  ``checkpoint_every`` working ticks;
- the **parent** (:class:`ServingSupervisor`) owns the request queue
  and the client-visible streams: it submits work over the child's
  stdin, folds ack lines into per-request token lists, and watches for
  failure — a child exit (any code, or code 0 with work left) is a
  *crash*; heartbeat silence past ``heartbeat_timeout_s`` is a
  *stall* (SIGKILLed, since a wedged thread cannot be cancelled).

Recovery: the parent picks the newest *good* snapshot by walking the
ring journal newest-first through
:func:`~triton_dist_tpu.serving.server.load_checkpoint` — a corrupt
entry (:class:`~triton_dist_tpu.resilience.integrity.
CheckpointCorruptError`) bumps ``restore_fallbacks`` and the walk
continues to its predecessor — then respawns the child with
``--restore`` and re-submits every non-terminal request.  The restored
child re-emits the FULL token history of every revived handle; the
parent dedupes acks by ``(request_id, token_index)`` — a replayed
index must carry an identical token (anything else is a divergence
bug and raises), a fresh index appends and fires the client
``stream_cb`` exactly once.  Replay is therefore idempotent and the
resumed stream is token-exact, even when the SIGKILL landed between a
token's emission and its ack reaching the pipe: acks are flushed
before the checkpoint that contains them is written, so a restored
snapshot can only ever be *behind* the acked stream, never ahead.

Usage::

    from triton_dist_tpu.resilience.supervisor import ServingSupervisor
    sup = ServingSupervisor("tests.test_supervisor:make_engine",
                            checkpoint_dir="/tmp/ring",
                            heartbeat_timeout_s=30.0,
                            checkpoint_every=2)
    sup.start()
    h = sup.submit([3, 1, 2], max_new_tokens=8)
    sup.run_until_done(deadline_s=120)     # pumps acks + liveness
    assert h.status == "done"
    sup.stop()

``run_supervised_soak`` in :mod:`~triton_dist_tpu.resilience.chaos`
drives this through a seeded SIGKILL/stall/corruption schedule and
gates every finished stream against an in-process oracle.

Who owns the device: a chip belongs to one process at a time, so the
parent must never initialise a JAX backend — and it does not. It
imports the package (which imports ``jax``) but only handles JSON lines,
files and the numpy contents of a snapshot; the engine, and with it the
backend, exists in the child alone, and a respawn starts only after the
previous child is gone. What is NOT ready for a chip is the child's
environment: ``harness._child_env`` is the fault battery's and pins
``JAX_PLATFORMS=cpu``, so a supervised engine runs on the CPU mesh
wherever it is started (ROADMAP D9).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple, Union

__all__ = ["CheckpointRing", "ServingSupervisor", "SupervisedHandle",
           "SupervisorProtocolError"]

# Child -> parent line protocol marker.  Every structured event is one
# line: the prefix + a compact JSON object with an ``ev`` tag.  Lines
# without the prefix (stray library prints in the child) are ignored.
_SUP_PREFIX = "TDT-SUP "

_TERMINAL = ("done", "failed", "timeout", "shed")


class SupervisorProtocolError(RuntimeError):
    """The child's ack stream violated the protocol (a token index gap,
    or a replayed index with a different token) — a supervisor bug, not
    a survivable fault; never silently re-emit."""


# ---------------------------------------------------------------------------
# Checkpoint ring (written by the child, walked by the parent)
# ---------------------------------------------------------------------------

class CheckpointRing:
    """Journaled keep-last-K snapshot ring in one directory.

    Files: ``ckpt-<seq>.pkl`` (versioned envelopes via
    :func:`~triton_dist_tpu.serving.server.save_checkpoint`) plus
    ``ring.json`` — the journal, written atomically (tmp + rename) so
    a crash mid-append leaves the previous journal intact.  The
    journal lists entries oldest-first; :meth:`entries` returns them
    newest-first, which is the parent's restore walk order.
    """

    JOURNAL = "ring.json"

    def __init__(self, dirpath: str, *, keep: int = 3):
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.dir = dirpath
        self.keep = keep
        os.makedirs(dirpath, exist_ok=True)
        self._journal = self._read_journal()
        self._seq = (self._journal[-1]["seq"] + 1) if self._journal \
            else 0

    def _read_journal(self) -> List[dict]:
        path = os.path.join(self.dir, self.JOURNAL)
        try:
            with open(path, "r", encoding="utf-8") as f:
                data = json.load(f)
            return list(data.get("entries", []))
        except (OSError, ValueError):
            return []

    def _write_journal(self) -> None:
        path = os.path.join(self.dir, self.JOURNAL)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"entries": self._journal}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def append(self, snap, *, tick: int) -> str:
        """Write one snapshot, journal it, prune past ``keep``.
        Returns the checkpoint path."""
        from triton_dist_tpu.serving.server import save_checkpoint
        seq = self._seq
        self._seq += 1
        name = f"ckpt-{seq:06d}.pkl"
        path = os.path.join(self.dir, name)
        save_checkpoint(snap, path)
        self._journal.append({"seq": seq, "file": name, "tick": tick})
        pruned = self._journal[:-self.keep]
        self._journal = self._journal[-self.keep:]
        self._write_journal()
        for ent in pruned:
            try:
                os.remove(os.path.join(self.dir, ent["file"]))
            except OSError:
                pass
        return path

    def entries(self) -> List[dict]:
        """Journal entries newest-first (each: seq / file / tick),
        re-read from disk — the parent calls this on a ring the child
        wrote."""
        return list(reversed(self._read_journal()))

    def newest_good(self, *, on_fallback: Optional[
            Callable[[str, Exception], None]] = None) -> Optional[str]:
        """Path of the newest loadable snapshot, walking past corrupt
        entries (``on_fallback(path, exc)`` fires per skip).  ``None``
        when the ring has no loadable snapshot."""
        from triton_dist_tpu.resilience.integrity import (
            CheckpointCorruptError)
        from triton_dist_tpu.serving.server import load_checkpoint
        for ent in self.entries():
            path = os.path.join(self.dir, ent["file"])
            try:
                load_checkpoint(path)
                return path
            except (CheckpointCorruptError, FileNotFoundError) as e:
                if on_fallback is not None:
                    on_fallback(path, e)
        return None


# ---------------------------------------------------------------------------
# Parent-side request handle
# ---------------------------------------------------------------------------

class SupervisedHandle:
    """Parent-side mirror of one request's stream.  ``tokens`` only
    ever grows by deduped, verified acks; ``stream_cb`` fires exactly
    once per token index across any number of child restarts."""

    def __init__(self, request_id: str, prompt: List[int],
                 kwargs: dict,
                 stream_cb: Optional[Callable[[int], None]] = None):
        self.request_id = request_id
        self.prompt = list(prompt)
        self.kwargs = dict(kwargs)
        self.stream_cb = stream_cb
        self.tokens: List[int] = []
        self.status = "queued"
        self.error: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.status in _TERMINAL

    def __repr__(self) -> str:
        return (f"SupervisedHandle({self.request_id!r}, "
                f"status={self.status!r}, n={len(self.tokens)})")


# ---------------------------------------------------------------------------
# Parent
# ---------------------------------------------------------------------------

class ServingSupervisor:
    """Run a serving engine's tick loop in a supervised child process
    (module docstring has the full protocol).

    ``factory`` is an importable ``"module:qualname"`` string (or a
    module-level callable, stringified) returning an engine exposing
    ``submit / step / checkpoint / restore / _drained``;
    ``factory_kwargs`` must be JSON-serializable.  ``heartbeat_
    timeout_s`` only arms after the first heartbeat — child startup
    (imports + engine build + first-tick compile) is covered by the
    separate ``startup_timeout_s`` grace.
    """

    def __init__(self, factory: Union[str, Callable], *,
                 checkpoint_dir: str,
                 heartbeat_timeout_s: float = 30.0,
                 checkpoint_every: int = 4,
                 ring_k: int = 3,
                 factory_kwargs: Optional[dict] = None,
                 startup_timeout_s: float = 300.0,
                 max_restarts: int = 50,
                 tick_throttle_s: float = 0.0,
                 telemetry: str = "counters"):
        if isinstance(factory, str):
            self.factory_spec = factory
        else:
            self.factory_spec = (f"{factory.__module__}:"
                                 f"{factory.__qualname__}")
        self.checkpoint_dir = checkpoint_dir
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.checkpoint_every = int(checkpoint_every)
        self.ring_k = int(ring_k)
        self.factory_kwargs = dict(factory_kwargs or {})
        self.startup_timeout_s = float(startup_timeout_s)
        self.max_restarts = int(max_restarts)
        # A warmed-up tiny engine ticks in microseconds — faster than
        # the parent's pump cadence — so fault drills that must land
        # MID-stream (tests, the supervised soak) pace the child.
        # Production pacing is 0: the engine runs flat out.
        self.tick_throttle_s = float(tick_throttle_s)

        from triton_dist_tpu.obs.telemetry import Telemetry
        self.obs = Telemetry(telemetry)
        self.counters: Dict[str, int] = {
            "restarts": 0, "crashes": 0, "stalls": 0,
            "acked_tokens": 0, "dedup_dropped": 0,
            "restore_fallbacks": 0, "resubmitted": 0,
            "checkpoints": 0,
        }
        self.last_recovery_ms: Optional[float] = None
        self.handles: Dict[str, SupervisedHandle] = {}
        self._order: List[str] = []
        self._ids = 0
        self._proc: Optional[subprocess.Popen] = None
        self._buf = b""
        self._last_hb: Optional[float] = None
        self._spawned_at: Optional[float] = None
        self._recovery_t0: Optional[float] = None
        self._stopping = False
        self._child_n = 0

    # -- lifecycle ----------------------------------------------------

    def start(self) -> None:
        if self._proc is not None:
            raise RuntimeError("supervisor already started")
        self._spawn(restore=None)

    def __enter__(self) -> "ServingSupervisor":
        if self._proc is None:
            self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def _spawn(self, restore: Optional[str]) -> None:
        from triton_dist_tpu.resilience.harness import (
            _child_env, _repo_root)
        cmd = [sys.executable, "-m",
               "triton_dist_tpu.resilience.supervisor", "--child",
               "--factory", self.factory_spec,
               "--factory-kwargs", json.dumps(self.factory_kwargs),
               "--checkpoint-dir", self.checkpoint_dir,
               "--checkpoint-every", str(self.checkpoint_every),
               "--ring-k", str(self.ring_k)]
        if self.tick_throttle_s > 0:
            cmd += ["--tick-sleep", str(self.tick_throttle_s)]
        if restore is not None:
            cmd += ["--restore", restore]
        # Child stderr goes to a per-incarnation log file, not a pipe:
        # an undrained stderr pipe can wedge the child on a full
        # buffer, and the log is the post-mortem for a crash.
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        self._child_n += 1
        log_path = os.path.join(
            self.checkpoint_dir, f"child-{self._child_n:03d}.log")
        self._stderr_log = open(log_path, "wb")
        self._proc = subprocess.Popen(
            cmd, env=_child_env(), cwd=_repo_root(),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._stderr_log)
        os.set_blocking(self._proc.stdout.fileno(), False)
        self._buf = b""
        self._last_hb = None
        self._spawned_at = time.monotonic()

    def stop(self) -> None:
        """Graceful shutdown: ask the child to exit, then make sure."""
        proc = self._proc
        if proc is None:
            return
        self._stopping = True
        try:
            self._send({"cmd": "shutdown"})
        except (OSError, ValueError):
            pass
        try:
            proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        self._drain_output()
        for f in (proc.stdin, proc.stdout):
            try:
                f.close()
            except OSError:
                pass
        try:
            self._stderr_log.close()
        except OSError:
            pass
        self._proc = None

    # -- request API --------------------------------------------------

    def submit(self, prompt, *, request_id: Optional[str] = None,
               stream_cb: Optional[Callable[[int], None]] = None,
               **kwargs) -> SupervisedHandle:
        """Queue one request on the child.  ``kwargs`` pass through to
        the engine's ``Request`` (``max_new_tokens``, ``eos_id``,
        ``temperature``, ``top_k``, ``seed``) and must be
        JSON-serializable — they are replayed verbatim on every
        re-submit after a restart."""
        if self._proc is None:
            raise RuntimeError("supervisor not started")
        if request_id is None:
            request_id = f"sup-{self._ids}"
            self._ids += 1
        if request_id in self.handles:
            raise ValueError(f"duplicate request_id {request_id!r}")
        h = SupervisedHandle(request_id, list(prompt), kwargs,
                             stream_cb=stream_cb)
        self.handles[request_id] = h
        self._order.append(request_id)
        self._send_submit(h)
        return h

    def _send_submit(self, h: SupervisedHandle) -> None:
        self._send({"cmd": "submit", "rid": h.request_id,
                    "prompt": h.prompt, **h.kwargs})

    def _send(self, obj: dict) -> None:
        proc = self._proc
        if proc is None or proc.stdin is None:
            raise OSError("no child")
        data = (json.dumps(obj, separators=(",", ":")) + "\n").encode()
        try:
            proc.stdin.write(data)
            proc.stdin.flush()
        except (BrokenPipeError, OSError):
            # Child died with commands in flight; liveness check will
            # recover and re-submit from parent state.
            pass

    # -- fault injection hooks (tests / chaos) ------------------------

    def kill_child(self) -> None:
        """SIGKILL the child outright (the external-crash model)."""
        if self._proc is not None:
            self._proc.kill()

    def inject_crash(self) -> None:
        """Ask the child to ``os._exit`` at the next loop top (the
        internal-crash model — exercises the nonzero-exit path)."""
        self._send({"cmd": "crash"})

    def inject_stall(self, seconds: float = 3600.0) -> None:
        """Ask the child to stop heartbeating (sleep) — exercises the
        heartbeat-stall detection path."""
        self._send({"cmd": "stall", "s": float(seconds)})

    def inject_fault(self, plan: str, **plan_kw) -> None:
        """Activate a named fault plan inside the child for exactly one
        tick (the in-process fault families, e.g. ``corrupt_payload``)."""
        self._send({"cmd": "fault", "plan": plan, "kw": plan_kw})

    def checkpoint_now(self) -> None:
        """Force a ring checkpoint at the child's next tick boundary."""
        self._send({"cmd": "ckpt"})

    # -- pump ---------------------------------------------------------

    def pump(self) -> int:
        """Process pending child output, then run failure detection.
        Returns the number of protocol events handled.  Call this in
        the client's wait loop (or use :meth:`run_until_done`)."""
        n = self._drain_output()
        self._check_liveness()
        return n

    def run_until_done(self, *, deadline_s: float = 600.0,
                       poll_s: float = 0.02) -> None:
        """Pump until every submitted request is terminal."""
        t0 = time.monotonic()
        while not all(h.done for h in self.handles.values()):
            self.pump()
            if time.monotonic() - t0 > deadline_s:
                open_rids = [r for r, h in self.handles.items()
                             if not h.done]
                raise TimeoutError(
                    f"supervised run exceeded {deadline_s}s with "
                    f"{len(open_rids)} open requests: {open_rids[:8]}")
            time.sleep(poll_s)

    def stats(self) -> dict:
        out = dict(self.counters)
        out["last_recovery_ms"] = self.last_recovery_ms
        out["child_alive"] = bool(
            self._proc is not None and self._proc.poll() is None)
        out["open_requests"] = sum(
            1 for h in self.handles.values() if not h.done)
        return out

    # -- child output -------------------------------------------------

    def _drain_output(self) -> int:
        proc = self._proc
        if proc is None or proc.stdout is None:
            return 0
        fd = proc.stdout.fileno()
        while True:
            try:
                chunk = os.read(fd, 65536)
            except BlockingIOError:
                break
            except (OSError, ValueError):
                break
            if not chunk:
                break
            self._buf += chunk
        n = 0
        while b"\n" in self._buf:
            line, self._buf = self._buf.split(b"\n", 1)
            text = line.decode("utf-8", "replace")
            if not text.startswith(_SUP_PREFIX):
                continue
            try:
                ev = json.loads(text[len(_SUP_PREFIX):])
            except ValueError:
                continue
            self._on_event(ev)
            n += 1
        return n

    def _on_event(self, ev: dict) -> None:
        kind = ev.get("ev")
        now = time.monotonic()
        if kind == "hb" or kind == "hello":
            self._last_hb = now
            if self._recovery_t0 is not None:
                # Recovery completes at the restored child's first
                # sign of life: detection -> kill -> ring walk ->
                # respawn -> engine rebuilt and restored.
                self.last_recovery_ms = \
                    (now - self._recovery_t0) * 1000.0
                self.obs.complete_span(
                    "supervise_restart", self._recovery_t0, now,
                    restarts=self.counters["restarts"])
                self._recovery_t0 = None
        elif kind == "tok":
            self._on_tok(ev["rid"], int(ev["i"]), int(ev["tok"]))
        elif kind == "done":
            h = self.handles.get(ev.get("rid"))
            if h is not None and not h.done:
                h.status = ev.get("status", "done")
                h.error = ev.get("error")
        elif kind == "ckpt":
            self.counters["checkpoints"] += 1
        elif kind == "reject":
            h = self.handles.get(ev.get("rid"))
            if h is not None and not h.done:
                h.status = "failed"
                h.error = ev.get("error", "rejected")

    def _on_tok(self, rid: str, i: int, tok: int) -> None:
        h = self.handles.get(rid)
        if h is None:
            return
        if i < len(h.tokens):
            # Replay of an already-acked index (restored child
            # re-emits full history): must be identical.
            if h.tokens[i] != tok:
                raise SupervisorProtocolError(
                    f"request {rid!r} token {i} diverged on replay: "
                    f"acked {h.tokens[i]}, child re-sent {tok}")
            self.counters["dedup_dropped"] += 1
            return
        if i > len(h.tokens):
            # Acks are flushed before the checkpoint containing them
            # is written, so a restored child can never legitimately
            # skip ahead of the acked stream.
            raise SupervisorProtocolError(
                f"request {rid!r} ack gap: have {len(h.tokens)} "
                f"tokens, child sent index {i}")
        h.tokens.append(tok)
        self.counters["acked_tokens"] += 1
        if h.stream_cb is not None:
            h.stream_cb(tok)

    # -- failure detection + recovery ---------------------------------

    def _check_liveness(self) -> None:
        proc = self._proc
        if proc is None:
            return
        rc = proc.poll()
        now = time.monotonic()
        if rc is not None:
            if self._stopping:
                return
            # Final lines may still sit in the pipe (incl. acks
            # emitted just before death) — fold them in BEFORE
            # deciding what needs re-submitting.
            self._drain_output()
            if all(h.done for h in self.handles.values()) and rc == 0:
                return  # clean exit with nothing left: not a crash
            self.counters["crashes"] += 1
            self._recover(reason=f"child exit rc={rc}")
        elif self._last_hb is None:
            if (self._spawned_at is not None
                    and now - self._spawned_at > self.startup_timeout_s):
                self.counters["stalls"] += 1
                self._recover(reason="startup timeout")
        elif now - self._last_hb > self.heartbeat_timeout_s:
            self.counters["stalls"] += 1
            self._recover(reason="heartbeat stall")

    def _recover(self, *, reason: str) -> None:
        if self.counters["restarts"] >= self.max_restarts:
            raise RuntimeError(
                f"supervisor exceeded max_restarts="
                f"{self.max_restarts} (last: {reason})")
        self._recovery_t0 = time.monotonic()
        self.obs.event("supervise_restart_begin", reason=reason)
        proc = self._proc
        if proc is not None:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            self._drain_output()
            for f in (proc.stdin, proc.stdout):
                try:
                    f.close()
                except OSError:
                    pass
            try:
                self._stderr_log.close()
            except OSError:
                pass
            self._proc = None

        def _fb(path, exc):
            self.counters["restore_fallbacks"] += 1
            self.obs.event("restore_fallback", path=path,
                           error=type(exc).__name__)

        ring = CheckpointRing(self.checkpoint_dir, keep=self.ring_k)
        restore = ring.newest_good(on_fallback=_fb)
        self.counters["restarts"] += 1
        self._spawn(restore=restore)
        # Re-submit everything non-terminal (in submission order).
        # The restored child ignores rids its snapshot already
        # revived; a request the snapshot predates (or a fresh child
        # with no snapshot) re-runs from the prompt — deterministic
        # decode regenerates the same tokens and the ack dedupe makes
        # the replay invisible to the client stream.
        for rid in self._order:
            h = self.handles[rid]
            if not h.done:
                self._send_submit(h)
                self.counters["resubmitted"] += 1


# ---------------------------------------------------------------------------
# Child entry
# ---------------------------------------------------------------------------

def _resolve_factory(spec: str) -> Callable:
    mod_name, _, qual = spec.partition(":")
    if not mod_name or not qual:
        raise ValueError(
            f"factory spec must be 'module:qualname', got {spec!r}")
    import importlib
    obj = importlib.import_module(mod_name)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


def _child_out(ev: str, **kw) -> None:
    print(_SUP_PREFIX
          + json.dumps({"ev": ev, **kw}, separators=(",", ":")),
          flush=True)


def _child_main(args) -> int:
    from triton_dist_tpu.resilience import faults
    from triton_dist_tpu.serving.scheduler import Request

    factory = _resolve_factory(args.factory)
    srv = factory(**json.loads(args.factory_kwargs))
    ring = CheckpointRing(args.checkpoint_dir, keep=args.ring_k)

    handles: Dict[str, object] = {}
    emitted: Dict[str, int] = {}
    reported_done = set()

    if args.restore:
        from triton_dist_tpu.serving.server import load_checkpoint
        snap = load_checkpoint(args.restore)  # parent pre-validated
        for h in srv.restore(snap):
            rid = h.request.request_id
            handles[rid] = h
            # Re-emit the FULL history: the parent dedupes, and this
            # closes the window where an ack line died with the
            # previous child before reaching the pipe.
            emitted[rid] = 0
    _child_out("hello", pid=os.getpid(),
               restored=sorted(handles))

    # Raw non-blocking stdin with manual line assembly: buffered
    # readline() would slurp SEVERAL pending command lines into
    # Python's buffer while returning one, and select() on the then-
    # empty fd would leave the rest unread until new bytes arrive.
    stdin_fd = sys.stdin.fileno()
    os.set_blocking(stdin_fd, False)
    cmd_buf = b""
    tick = 0
    ticks_since_ckpt = 0
    force_ckpt = False
    crash_armed = False
    stall_s: Optional[float] = None
    one_tick_plan = None
    last_hb = 0.0
    shutdown = False

    def flush_acks() -> None:
        for rid, h in handles.items():
            toks = h.tokens
            for i in range(emitted[rid], len(toks)):
                _child_out("tok", rid=rid, i=i, tok=int(toks[i]))
            emitted[rid] = len(toks)
            if h.done and rid not in reported_done:
                reported_done.add(rid)
                err = getattr(h, "error", None)
                _child_out("done", rid=rid, status=h.status,
                           n=len(toks),
                           error=repr(err) if err else None)

    while True:
        # Drain every pending command before stepping.
        while True:
            try:
                chunk = os.read(stdin_fd, 65536)
            except BlockingIOError:
                break
            if not chunk:
                return 0  # parent closed stdin: orderly exit
            cmd_buf += chunk
        while b"\n" in cmd_buf:
            line, cmd_buf = cmd_buf.split(b"\n", 1)
            try:
                cmd = json.loads(line)
            except ValueError:
                continue
            op = cmd.get("cmd")
            if op == "submit":
                rid = cmd["rid"]
                if rid in handles:
                    continue  # restore already owns this stream
                kw = {k: v for k, v in cmd.items()
                      if k not in ("cmd", "rid", "prompt")}
                try:
                    h = srv.submit(Request(
                        prompt=list(cmd["prompt"]), request_id=rid,
                        **kw))
                except Exception as e:  # queue full / bad request
                    _child_out("reject", rid=rid, error=repr(e))
                    continue
                handles[rid] = h
                emitted[rid] = 0
            elif op == "crash":
                crash_armed = True
            elif op == "stall":
                stall_s = float(cmd.get("s", 3600.0))
            elif op == "fault":
                one_tick_plan = faults.get_plan(
                    cmd["plan"], **cmd.get("kw", {}))
            elif op == "ckpt":
                force_ckpt = True
            elif op == "shutdown":
                shutdown = True
        if crash_armed:
            os._exit(13)
        if stall_s is not None:
            # Model a wedged engine: no heartbeats, no acks.  The
            # parent SIGKILLs us mid-sleep; if it somehow doesn't,
            # resume (the sleep is the whole fault).
            time.sleep(stall_s)
            stall_s = None
        if shutdown:
            flush_acks()
            _child_out("bye", tick=tick)
            return 0

        # A prefill-only tick returns 0 decoded slots but is still
        # work — "worked" means a step RAN, so heartbeats and the
        # checkpoint cadence track ticks, not decode occupancy.
        worked = 0
        if not srv._drained():
            if one_tick_plan is not None:
                with faults.inject(one_tick_plan):
                    srv.step()
                one_tick_plan = None
            else:
                srv.step()
            worked = 1
            tick += 1
            ticks_since_ckpt += 1
            if args.tick_sleep > 0:
                time.sleep(args.tick_sleep)

        # Ack order matters: tokens reach the pipe BEFORE the
        # checkpoint containing them is written, so a restored
        # snapshot is never ahead of the acked stream.
        flush_acks()
        now = time.monotonic()
        if worked or now - last_hb >= 0.05:
            _child_out("hb", tick=tick)
            last_hb = now
        if force_ckpt or (args.checkpoint_every > 0 and worked
                          and ticks_since_ckpt >= args.checkpoint_every):
            path = ring.append(srv.checkpoint(), tick=tick)
            ticks_since_ckpt = 0
            force_ckpt = False
            _child_out("ckpt", path=path, tick=tick)
        if not worked:
            time.sleep(0.005)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true", required=True)
    p.add_argument("--factory", required=True)
    p.add_argument("--factory-kwargs", default="{}")
    p.add_argument("--checkpoint-dir", required=True)
    p.add_argument("--checkpoint-every", type=int, default=4)
    p.add_argument("--ring-k", type=int, default=3)
    p.add_argument("--tick-sleep", type=float, default=0.0)
    p.add_argument("--restore", default=None)
    return _child_main(p.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
