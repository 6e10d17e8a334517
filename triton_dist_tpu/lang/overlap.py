"""Shared overlap engine: rank-swizzled chunk schedules, prefetch-depth
panel staging, and coalesced per-chunk signalling.

The signature perf trick of the reference (the threadblock swizzle of
``allgather_gemm.py:~200`` and its gemm_rs / all-to-all siblings) is
reordering each rank's chunk traversal so compute starts on
locally-resident data while remote chunks are still in flight. Until
this module, that machinery lived only inside ``ops/ag_gemm.py``; every
other fused op hand-rolled a simpler (or no) overlap schedule. This
module is the one place the three reusable pieces live:

(a) **Schedule generator** — :func:`chunk_at` / :func:`step_of` /
    :func:`schedule`: a pure function family mapping grid step to chunk
    id per ``swizzle_mode``:

    - ``"ag"``  (all-gather consumer):   chunk ``(rank - step) % world``
      — the local chunk first, then ring-arrival order.
    - ``"rs"``  (reduce-scatter producer): chunk
      ``(rank - step - 1) % world`` — each chunk's running sum visits
      ranks in ring sequence, finishing at its owner.
    - ``"a2a"`` (all-to-all consumer):   chunk ``(rank + step) % world``
      — the local chunk first, then peers by ring offset.
    - ``"identity"``: chunk ``step`` — the unswizzled baseline every
      swizzled schedule is parity-tested (and benchmarked) against.

(b) **Panel stager** — :class:`PanelStager` + :func:`choose_depth`: the
    prefetch-depth-parameterized generalization of ag_gemm's hardcoded
    two-buffer cross-chunk prefetch. ``depth`` panels are in flight at
    once (1 = stage-and-wait, 2 = classic double buffering, 3 = deeper
    pipelining for when one panel of lead time cannot cover the
    arrival/HBM latency). :func:`stream_scoped` packages the same
    buffer-parity/semaphore algebra as a *scoped-VMEM block stream*
    (``pl.run_scoped`` scratch allocated per grid body, the
    ``paged_flash_decode`` per-parity prefetch idiom) — the staging
    core of the pipelined ``ag_gemm`` variant; :func:`stream_plan` is
    its staging schedule as a pure host function.

(c) **Coalesced signalling** — :func:`a2a_slot` (the handshake-free
    arrival-slot arithmetic shared by every all-to-all-shaped sender/
    receiver pair) and :func:`drain_sends` (consume outstanding
    per-chunk send credits before kernel exit). Sub-tile results are
    staged locally and each chunk rides ONE remote put + ONE semaphore
    signal — never per-tile signals.

Interpret-mesh rule: remote puts must be
rank-CONVERGENT — the same put sites in the same order on every rank.
Swizzle modes therefore only reorder *waits and compute*; the put
schedule of an op never depends on the mode (the "identity" mode of a
ring op pumps the whole ring convergently before compute instead).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import shmem_device as dl

__all__ = [
    "SWIZZLE_MODES",
    "schedule",
    "chunk_at",
    "step_of",
    "a2a_slot",
    "ring_chunk",
    "pump_ring",
    "pump_ring_event",
    "PanelStager",
    "choose_depth",
    "stream_plan",
    "stream_scoped",
    "drain_sends",
]

SWIZZLE_MODES = ("ag", "rs", "a2a", "identity")


def _check_mode(mode: str) -> None:
    if mode not in SWIZZLE_MODES:
        raise ValueError(f"unknown swizzle_mode {mode!r} "
                         f"(expected one of {SWIZZLE_MODES})")


def _rem(x, n: int):
    """``x % n`` for either Python ints or traced values (``n`` static,
    ``x`` possibly negative by less than ``n``)."""
    if isinstance(x, int):
        return x % n
    return jax.lax.rem(x + n, n)


def chunk_at(step, rank, world: int, mode: str):
    """Chunk id computed at grid ``step`` by ``rank`` under ``mode``.

    Pure arithmetic: works on Python ints (host-side schedule
    construction, tests) and on traced values (inside kernels and
    BlockSpec index maps) alike.
    """
    _check_mode(mode)
    if mode == "ag":
        return _rem(rank - step, world)
    if mode == "rs":
        return _rem(rank - step - 1, world)
    if mode == "a2a":
        if isinstance(step, int) and isinstance(rank, int):
            return (rank + step) % world
        return jax.lax.rem(rank + step, world)
    return step  # identity


def step_of(chunk, rank, world: int, mode: str):
    """Inverse of :func:`chunk_at`: the grid step at which ``rank``
    computes ``chunk``."""
    _check_mode(mode)
    if mode == "ag":
        return _rem(rank - chunk, world)
    if mode == "rs":
        return _rem(rank - chunk - 1, world)
    if mode == "a2a":
        return _rem(chunk - rank, world)
    return chunk  # identity


def schedule(rank: int, world: int, n_chunks: int, mode: str):
    """Full traversal order as a tuple (host-side form of
    :func:`chunk_at` — the reference's threadblock-swizzle table).

    ``n_chunks`` must equal ``world`` for the ring modes; for
    ``identity`` any count is allowed.
    """
    _check_mode(mode)
    if mode != "identity" and n_chunks != world:
        raise ValueError(f"mode {mode!r} schedules exactly world="
                         f"{world} chunks (got n_chunks={n_chunks})")
    return tuple(chunk_at(s, rank, world, mode) for s in range(n_chunks))


def ring_chunk(event, rank, world: int):
    """Chunk delivered to ``rank`` by ring event ``event`` (the
    ``event``-th hop of a rightward all-gather ring): ``event = 0`` is
    the local chunk, event ``r`` >= 1 the chunk that left rank
    ``rank - r``."""
    return _rem(rank - event, world)


def a2a_slot(src, dst, world: int):
    """Arrival-semaphore slot for chunk ``src`` landing at ``dst`` in an
    all-to-all-shaped exchange: ``(src - dst) % world - 1``.

    Both sides derive it from rank arithmetic — no handshake. ``dst``
    processes ``src``'s chunk at step ``(dst - src) % world`` of the
    "a2a" schedule, i.e. slot ``world - step - 1``; per-source slots
    mean a consumer never blocks on traffic it does not read, whatever
    order chunks arrive (or are consumed) in.
    """
    return _rem(src - dst, world) - 1


def pump_ring(events, *, me, world: int, right, chunk_of: Callable,
              send_sem, recv_sem, axis: str, ctx,
              sim_src_of: Optional[Callable] = None):
    """Process all-gather ring events ``events`` (an iterable of static
    ints >= 1, ascending): certify ring chunk ``r``'s arrival (slot
    ``r - 1``), then issue the put delivering ring chunk ``r + 1`` into
    slot ``r`` (real mode: forward my just-received chunk right; sim
    mode: a self-put sourcing the true data from ``sim_src_of``).

    Event 0 — the kickoff put delivering ring chunk 1 — is the caller's
    entry-body job (its source is the local input, which only the
    caller can name).
    """
    for r in events:
        assert 1 <= r <= world - 1, f"ring event {r} out of range"
        c = ring_chunk(r, me, world)
        dl.wait_arrivals(recv_sem.at[r - 1], chunk_of(c), 1)
        if r < world - 1:
            if sim_src_of is not None:
                nxt = ring_chunk(r + 1, me, world)
                dl.remote_put(sim_src_of(nxt), chunk_of(nxt),
                              send_sem.at[r], recv_sem.at[r], me,
                              axis=axis, ctx=ctx)
            else:
                dl.remote_put(chunk_of(c), chunk_of(c), send_sem.at[r],
                              recv_sem.at[r], right, axis=axis, ctx=ctx)


def pump_ring_event(event, *, me, world: int, right, chunk_of: Callable,
                    send_sem, recv_sem, axis: str, ctx,
                    sim_src_of: Optional[Callable] = None) -> None:
    """Process ONE ring event whose index is a TRACED value (the "ag"
    schedule processes event ``k`` at grid chunk boundary ``k``, where
    ``k`` is a grid index): certify ring chunk ``event``'s arrival (slot
    ``event - 1``) and issue the put delivering ring chunk ``event + 1``
    into slot ``event`` (skipped via ``pl.when`` past the last hop).

    The put site is rank-uniform (the event index is the same grid
    value on every rank) — safe on the interpret mesh.
    """
    c = ring_chunk(event, me, world)
    dl.wait_arrivals(recv_sem.at[event - 1], chunk_of(c), 1)

    @pl.when(event < world - 1)
    def _():
        if sim_src_of is not None:
            nxt = ring_chunk(event + 1, me, world)
            dl.remote_put(sim_src_of(nxt), chunk_of(nxt),
                          send_sem.at[event], recv_sem.at[event], me,
                          axis=axis, ctx=ctx)
        else:
            dl.remote_put(chunk_of(c), chunk_of(c), send_sem.at[event],
                          recv_sem.at[event], right, axis=axis, ctx=ctx)


def choose_depth(requested: int, panel_bytes: int, budget: int,
                 chunk_len: Optional[int], n_panels: int) -> int:
    """Resolve a ``prefetch_depth`` request against the VMEM budget and
    the grid geometry.

    ``requested = 0`` means auto (the historical policy: 2 when a
    double-buffered pair fits and there are >= 2 bodies per chunk).
    Explicit depths are clamped — never rejected — so one tuned config
    stays runnable across shapes: depth can only help when there are at
    least ``depth`` panels and the buffers fit the budget, and
    cross-chunk prefetch needs >= 2 bodies per chunk.

    ``chunk_len = None`` declares that staging is NOT cross-chunk —
    every panel's source needs no arrival certification (local input,
    or block-granular staging inside one chunk) — so the >= 2-bodies
    guard does not apply and only the panel count and VMEM budget
    clamp the depth.
    """
    if requested < 0 or requested > 3:
        raise ValueError(f"prefetch_depth must be 0 (auto) or 1..3, got "
                         f"{requested}")
    d = 2 if requested == 0 else requested
    d = min(d, max(n_panels, 1))
    while d > 1 and d * panel_bytes > budget:
        d -= 1
    if chunk_len is not None and chunk_len < 2:
        d = 1  # no body ahead of the boundary to hide staging under
    return max(d, 1)


class PanelStager:
    """Depth-``d`` rotating panel buffers over per-buffer DMA semaphores.

    ``panel_ref`` is a ``(depth, ...)`` VMEM scratch and ``sem`` a
    ``(depth,)`` DMA-semaphore array: each buffer waits on its own
    semaphore, so up to ``depth - 1`` staging DMAs may be in flight at
    once without completion-order ambiguity (a shared semaphore cannot
    tell WHICH panel landed).

    Panels are identified by a GLOBAL panel index ``p`` (monotone
    across chunk boundaries, e.g. ``k * n_i + i``), so consecutive
    panels rotate buffers even across chunks. The staging discipline —
    who stages which panel when — is the caller's (see the staging-plan
    comment below for the closed-form rule); this class owns only
    buffers, semaphores, and waits.
    """

    def __init__(self, panel_ref, sem, depth: int):
        self.panel = panel_ref
        self.sem = sem
        self.depth = depth

    def buf(self, p):
        """Buffer slot of global panel ``p``."""
        if self.depth == 1:
            return 0
        return _rem(p, self.depth)

    def start(self, src_ref, p) -> None:
        """Begin staging ``src_ref`` into panel ``p``'s buffer."""
        b = self.buf(p)
        pltpu.make_async_copy(src_ref, self.panel.at[b],
                              self.sem.at[b]).start()

    def wait(self, p) -> None:
        """Block until panel ``p``'s staging DMA completed."""
        b = self.buf(p)
        pltpu.make_async_copy(self.panel.at[b], self.panel.at[b],
                              self.sem.at[b]).wait()

    def read(self, p):
        """The staged panel value (post-:meth:`wait`)."""
        return self.panel[self.buf(p)]

    # -- the staging plan (pure index arithmetic) -------------------------
    #
    # With depth d, a chunk's panel offsets split into two responsibility
    # ranges, covering every offset exactly once:
    #
    # - ``lead_range``: offsets 0 .. min(d-1, n_i)-1, staged AHEAD of
    #   the chunk — at the warm-up site for the schedule's first chunk,
    #   and at the previous chunk's boundary body (post-certification)
    #   for every later chunk;
    # - in-chunk: at panel offset ``i``'s wait point, stage offset
    #   ``i + d - 1`` when it is still inside the chunk (a traced
    #   predicate the kernel emits: ``i + d - 1 < n_i``). Offsets below
    #   d-1 never match (i >= 0), so the ranges cannot double-stage.
    #
    # Buffer safety: offset q's buffer (q % d) was last used by global
    # panel q - d, whose compute completed strictly before either
    # staging site runs (grid bodies are sequential, and the boundary
    # body stages only d-1 ahead — never the buffer of a panel still
    # computing).

    def lead_range(self, n_i: int) -> range:
        """Panel offsets a chunk needs staged ahead of its first wait
        (see the plan above)."""
        if self.depth == 1:
            return range(0)
        return range(min(self.depth - 1, max(n_i, 1)))


def stream_plan(total: int, depth: int):
    """Staging schedule of a depth-``depth`` block stream over ``total``
    blocks, as pure host data (the plan :func:`stream_scoped` executes).

    Returns ``(lead, stages)``:

    - ``lead``: block indices staged BEFORE the stream loop (the cold
      lead loads — ``PanelStager.lead_range`` specialized to a stream
      whose source needs no arrival certification);
    - ``stages``: per step ``t`` of the loop, the tuple of block
      indices whose staging DMA is issued at ``t``'s prefetch site
      (right after block ``t``'s wait). ``depth == 1`` degenerates to
      stage-and-wait: block ``t`` is staged at step ``t`` itself.

    Invariants the unit tests pin down (and the kernels rely on):
    every block in ``range(total)`` is staged exactly once, and block
    ``q``'s buffer (``q % depth``) is never restaged before step
    ``q - depth``'s compute finished (the prefetch site of ``q`` is
    step ``q - depth + 1``, strictly after).
    """
    if total < 0:
        raise ValueError(f"total must be >= 0, got {total}")
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    if depth == 1:
        return (), tuple((t,) for t in range(total))
    lead = tuple(range(min(depth - 1, total)))
    stages = tuple(
        ((t + depth - 1,) if t + depth - 1 < total else ())
        for t in range(total))
    return lead, stages


def stream_scoped(*, total: int, depth: int, buffers: dict,
                  start: Callable, body: Callable) -> None:
    """Depth-buffered block stream over scoped VMEM — the
    buffer-parity/semaphore core of the pipelined ``ag_gemm`` variant
    (and the generalization the ``paged_flash_decode`` per-parity page
    prefetch hand-rolls at depth 2).

    Allocates, inside ``pl.run_scoped`` (so the buffers live only for
    this grid body), one ``(depth,) + shape`` VMEM rotating buffer and
    one ``(depth,)`` DMA-semaphore array per named stream, wraps each
    in a :class:`PanelStager`, and drives the staging plan of
    :func:`stream_plan`: lead blocks staged cold, then per step ``t``
    wait block ``t`` on every stream, issue block ``t + depth - 1``'s
    prefetch behind it, and hand the resident blocks to ``body``.

    ``buffers``: ordered ``{name: (block_shape, dtype)}``.
    ``start(t, stagers)``: issue block ``t``'s staging copies — call
    ``stagers[name].start(src_ref, t)`` for every stream (the caller
    owns source selection, e.g. ``pl.when`` branching between a local
    input and a ring workspace). ``t`` may be traced.
    ``body(t, stagers)``: consume block ``t`` via
    ``stagers[name].read(t)`` — every stream's block ``t`` is resident.

    Scoped scratch is per-body: all DMAs started here complete before
    the scope closes (the final waits), so nothing leaks across grid
    bodies — which is exactly why the source's *arrival* (ring chunk
    certification) must be handled by the caller before the stream
    runs (``choose_depth(chunk_len=None)`` is the matching depth
    resolver).
    """
    if total <= 0:
        return
    names = list(buffers)

    def scoped(*refs):
        stagers = {name: PanelStager(refs[2 * ix], refs[2 * ix + 1], depth)
                   for ix, name in enumerate(names)}

        def wait(t):
            for name in names:
                stagers[name].wait(t)

        if depth > 1:
            for t, _ in zip(range(depth - 1), range(total)):
                start(jnp.int32(t), stagers)

        def step(t, carry):
            if depth == 1:
                start(t, stagers)
            wait(t)
            if depth > 1:
                @pl.when(t + (depth - 1) < total)
                def _():
                    start(t + (depth - 1), stagers)
            body(t, stagers)
            return carry

        jax.lax.fori_loop(0, total, step, 0)

    scratch = []
    for name in names:
        shape, dtype = buffers[name]
        scratch.append(pltpu.VMEM((depth,) + tuple(shape), dtype))
        scratch.append(pltpu.SemaphoreType.DMA((depth,)))
    pl.run_scoped(scoped, *scratch)


def drain_sends(send_sem, ref, slots: Sequence[int]) -> None:
    """Consume one send credit per slot before kernel exit (a comm
    kernel must not retire with outstanding DMA semaphores)."""
    for s in slots:
        dl.wait_arrivals(send_sem.at[s], ref, 1)
