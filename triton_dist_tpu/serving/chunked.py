"""Per-bucket jitted chunked-prefill driver (the prefill-worker core).

One :class:`ChunkedPrefill` owns the jitted chunk dispatch for one
(engine, page pool) pair: prompts stream through it in bucketed
fixed-shape chunks (:func:`ops.chunked_prefill.plan_chunks`), each
chunk one call of :func:`models.dense.prefill_chunk_paged` under
``jit(shard_map)`` with the pool DONATED and its output shardings
PINNED — so the decode dispatch compiled against the same pool never
re-specializes, and the prefill jit cache is bounded by the bucket
count instead of the distinct-prompt-length count (the PR-4 known
limit this subsystem removes).

Used two ways: in-place by :class:`~triton_dist_tpu.serving.server.
ServingEngine` (``prefill_buckets=...`` — chunks write straight into
the serving pool), and by the disaggregated prefill worker
(:mod:`~triton_dist_tpu.serving.disagg` — chunks write into the
worker's staging pool, whole pages migrate to the decode worker
afterwards).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from triton_dist_tpu.obs import scope
from triton_dist_tpu.ops.chunked_prefill import plan_chunks

__all__ = ["ChunkedPrefill", "MegaChunkedPrefill", "DEFAULT_BUCKETS",
           "greedy_tokens", "picked_with_stats"]

# Production default (the e.g. of ROADMAP Open item 1); tests and tiny
# models pass their own. Sizing guidance in docs/serving.md.
DEFAULT_BUCKETS = (128, 512, 2048)


@scope("pick")
def greedy_tokens(logits):
    """The greedy token of each logits row, picked inside the step
    program: ``np.argmax``'s rule (the first index of the maximum, a
    NaN counting as one), so the host reads the integers it would have
    computed from the rows."""
    import jax.numpy as jnp

    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@scope("pick")
def picked_with_stats(picked, stats):
    """A step program's picked tokens with its model's ``STEP_STATS``
    behind them, one int32 array: the counts leave the chip in the copy
    the tick makes anyway. Rows are read by slot from the front, the
    stats from the back."""
    import jax.numpy as jnp

    return jnp.concatenate([picked, stats.astype(jnp.int32)])


class ChunkedPrefill:
    """Bucketed chunk dispatch over one engine + paged pool.

    ``engine`` is a layer :class:`~triton_dist_tpu.models.Engine` whose
    model exposes ``prefill_chunk_paged``; ``cache_shardings`` is the
    pool's NamedSharding pytree (the decode dispatch's compiled
    expectation — chunk outputs are pinned to it); ``buckets`` the
    chunk lengths. ``attn_impl``: ``"ref"`` (the gather-path default)
    | ``"flash"`` (the paged Q-block Pallas kernel — no dense-row
    materialization; positions stay data, so the bucket-count bound
    below is unchanged). The jit cache of :attr:`_chunk` holds at most
    one entry per bucket — :meth:`step` asserts that invariant after
    every dispatch (the prefill half of the serving no-recompilation
    gate).

    ``decode_rows``: how many decode rows every chunk program carries
    beside its chunk (``decode_attn`` their attention). 0, the default,
    is the chunk program alone: a disaggregated worker's, whose pool no
    decoder reads. The in-place serving engine passes its ``num_slots``:
    each bucket's ONE program is then
    :func:`models.dense.chunk_decode_paged`, the chunk and the decode
    batch's step with every weight read once. :meth:`step_decode` gives
    it a decode batch; :meth:`step` parks all its decode rows. Either
    way one program a bucket, so the gate above is unchanged.

    Every program's first output is its rows' greedy tokens
    (:func:`greedy_tokens`), int32 ``(1 + decode_rows,)``: row 0 the
    chunk's last valid row, rows 1.. the decode rows (and behind them
    the model's ``STEP_STATS``, where it has any). A greedy request's
    token is read from there; the logits stay outputs, on the device,
    for a request that samples.
    """

    def __init__(self, engine, cache_shardings, buckets: Sequence[int],
                 *, attn_impl: str = "ref", telemetry=None,
                 decode_rows: int = 0, decode_attn: str = "ref"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"prefill buckets must be positive ints, "
                             f"got {buckets!r}")
        model = engine.model
        if not hasattr(model, "prefill_chunk_paged"):
            raise NotImplementedError(
                f"model {getattr(model, '__name__', model)!r} has no "
                "prefill_chunk_paged — chunked prefill needs the paged "
                "chunk contract (models.dense / models.qwen_moe)")
        if attn_impl not in ("ref", "flash"):
            raise ValueError(
                f"chunk attn_impl must be 'ref' | 'flash', got "
                f"{attn_impl!r} (the one-query 'kernel' value is the "
                "DECODE dispatch's knob)")
        self.engine = engine
        self.buckets = buckets
        self.attn_impl = attn_impl
        # Optional obs.Telemetry sink: per-bucket dispatch counters +
        # host-side dispatch-time histogram (the owning engine passes
        # its own; a standalone ChunkedPrefill records nothing).
        self.telemetry = telemetry
        cfg, mesh, axis = engine.cfg, engine.mesh, engine.axis
        # Chunk steps take only the regime kwargs — transport/replica/
        # counts are decode-dispatch knobs the chunk contract ignores.
        mk = {k: v for k, v in engine.model_kwargs.items()
              if k in ("moe_impl", "ep_ctx")}
        # Quantized pools carry per-page scale leaves — the chunk
        # dispatch's cache spec must match the pool it writes.
        # (and, for a pool with window layers, the ring its tree holds).
        ring = getattr(cache_shardings, "ring", 0)
        kv_spec = model.paged_cache_specs(
            axis, quantized=cache_shardings.quantized,
            **({"ring": ring} if ring else {}))
        # A model with ``STEP_STATS`` (counts of the step) or
        # ``ROW_STATS`` (one number a head row) returns them last from
        # every step; they ride the picked tokens out
        # (:func:`picked_with_stats`).
        stats = bool(getattr(model, "STEP_STATS", ())
                     or getattr(model, "ROW_STATS", ()))
        # A pool whose sequences keep state beside their pages
        # (``PagedKVCache.seq``): the chunk program is told the slot
        # whose state its rows carry, one more int32 scalar.
        self._slotted = slotted = bool(cache_shardings.seq)

        def slot_of(more):
            return {"slot": more[0]} if slotted else {}

        self.decode_rows = int(decode_rows)
        # What :meth:`step` feeds the decode rows (see
        # :meth:`_parked_rows`), made at its first call, and the
        # sharding their tokens go up under.
        self._parked = None
        self._row_sh = NamedSharding(mesh, P(None))
        if not self.decode_rows:
            def _chunk(params, toks, cache, table_row, start, wfrom,
                       valid, *more):
                logits, cache, *st = model.prefill_chunk_paged(
                    params, toks, cache, table_row, cfg, start=start,
                    wfrom=wfrom, valid=valid, mode=engine.mode,
                    axis=axis, ctxs=engine.ctxs, attn_impl=attn_impl,
                    **slot_of(more), **mk)
                picked = greedy_tokens(logits[None])
                if stats:
                    picked = picked_with_stats(picked, st[0])
                return picked, logits, cache

            dec_in = dec_out = dec_sh = ()
        elif not hasattr(model, "chunk_decode_paged"):
            raise NotImplementedError(
                f"model {getattr(model, '__name__', model)!r} has no "
                "chunk_decode_paged — decode rows ride a chunk program "
                "through that contract (models.dense / models.qwen_moe)")
        else:
            # Under the chunk program's name: one program a bucket,
            # at the same place in a profile.
            def _chunk(params, toks, cache, table_row, start, wfrom,
                       valid, *more):
                logits, dec, cache, *st = model.chunk_decode_paged(
                    params, toks, more[-1], cache, table_row, cfg,
                    start=start, wfrom=wfrom, valid=valid,
                    mode=engine.mode, axis=axis, ctxs=engine.ctxs,
                    attn_impl=attn_impl, decode_attn_impl=decode_attn,
                    **slot_of(more), **mk)
                picked = jnp.concatenate(
                    [greedy_tokens(logits[None]), greedy_tokens(dec)])
                if stats:
                    picked = picked_with_stats(picked, st[0])
                return picked, logits, dec, cache

            dec_in, dec_out = (P(None),), (P(None, None),)
            dec_sh = (NamedSharding(mesh, P(None, None)),)

        self._chunk = jax.jit(
            jax.shard_map(
                _chunk, mesh=mesh,
                in_specs=(engine._specs, P(None), kv_spec, P(None),
                          P(), P(), P()) + (P(),) * slotted + dec_in,
                out_specs=(P(None), P(None)) + dec_out + (kv_spec,),
                check_vma=False),
            donate_argnums=(2,),
            out_shardings=(NamedSharding(mesh, P(None)),) * 2 + dec_sh
            + (cache_shardings,))

    def plan(self, n_tokens: int) -> List[Tuple[int, int]]:
        """Deterministic ``[(bucket, valid), ...]`` cover of
        ``n_tokens``, a function of it and ``buckets`` alone
        (:func:`ops.chunked_prefill.plan_chunks`): greedy, but a tail
        of three or more programs is one padded chunk of the next
        bucket up. Still one program a bucket: the padded chunk's
        ``valid`` rides as data."""
        return plan_chunks(n_tokens, self.buckets)

    def next_chunk(self, remaining: int) -> Tuple[int, int]:
        """The next (bucket, valid) for ``remaining`` tokens."""
        return self.plan(remaining)[0]

    def step(self, params, toks: np.ndarray, cache, table_row,
             start: int, wfrom: int, valid: int, *,
             slot: Optional[int] = None):
        """Dispatch one chunk of decode slot ``slot`` (asked of a model
        whose sequences keep state beside their pages, and read by no
        other); returns
        ``(picked (1 + decode_rows,), logits (vocab,), cache)``,
        ``picked[0]`` the greedy token of ``logits``. ``toks`` is
        (bucket,) int32 padded; scalars ride as int32 data so the trace
        signature depends only on the bucket length. A program that carries decode rows runs with all of
        them parked (and returns the pool with no slot live)."""
        more = self._slot_arg(slot)
        if not self.decode_rows:
            return self._dispatch(params, toks, cache, table_row, start,
                                  wfrom, valid, *more)
        dec_toks, cache = self._parked_rows(cache)
        picked, logits, _, cache = self._dispatch(
            params, toks, cache, table_row, start, wfrom, valid, *more,
            dec_toks)
        return picked, logits, cache

    def step_decode(self, params, toks: np.ndarray, cache, table_row,
                    start: int, wfrom: int, valid: int, dec_toks, *,
                    slot: Optional[int] = None):
        """Dispatch one chunk with a decode batch aboard (a chunker
        built with ``decode_rows``): ``dec_toks`` (decode_rows,) are the
        batch's input tokens and ``cache`` carries its block table,
        lengths and live mask, as the decode dispatch's does. Returns
        ``(picked (1 + decode_rows,), chunk logits (vocab,), decode
        logits (decode_rows, vocab), cache)`` with the live slots'
        lengths advanced; ``picked[1 + slot]`` is the greedy token of
        decode row ``slot``."""
        return self._dispatch(params, toks, cache, table_row, start,
                              wfrom, valid, *self._slot_arg(slot), dec_toks)

    def _slot_arg(self, slot: Optional[int]) -> tuple:
        """The chunk's slot as the program takes it: one int32 scalar
        for a pool whose sequences keep state, nothing otherwise."""
        if not self._slotted:
            return ()
        if slot is None:
            raise ValueError(
                "this pool's sequences keep state beside their pages: "
                "a chunk names the decode slot whose state it carries "
                "(slot=)")
        return (np.int32(slot),)

    def _parked_rows(self, cache):
        """``(dec_toks, cache)`` with every decode row parked: scratch
        table row, length 0, not live. Uploaded as the serving engine
        uploads a live batch's (the tokens committed under the sharding
        a program's picked tokens come back with, since a live batch's
        may be fed from those on the device; the rest plain host
        arrays), so a parked and a ridden dispatch share one compiled
        program; the three cache leaves anew at every call, since the
        pool's donation takes them along."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        if self._parked is None:
            self._parked = (
                jax.device_put(np.zeros((self.decode_rows,), np.int32),
                               self._row_sh),
                np.zeros(cache.block_table.shape, np.int32),
                np.zeros(cache.lens.shape, np.int32))
        dec_toks, table, rows = self._parked
        return dec_toks, dataclasses.replace(
            cache, block_table=jnp.asarray(table), lens=jnp.asarray(rows),
            live=jnp.asarray(rows))

    def _dispatch(self, params, toks, cache, table_row, start, wfrom,
                  valid, *more):
        import jax.numpy as jnp

        tel = self.telemetry
        t0 = tel.now() if tel is not None and tel.enabled else None
        out = self._chunk(
            params, jnp.asarray(toks, jnp.int32), cache,
            jnp.asarray(table_row, jnp.int32), np.int32(start),
            np.int32(wfrom), np.int32(valid), *more)
        if t0 is not None:
            # Host dispatch time (the chunk result is async; the
            # request-level wait is the server's prefill_chunk span) +
            # which bucket this chunk rode — the padding-efficiency
            # counter docs/observability.md describes.
            tel.observe("chunk_dispatch", tel.now() - t0)
            tel.count(f"chunk_bucket_{toks.shape[0]}")
        # The no-growth gate, enforced inline: every chunk shape comes
        # from `buckets`, so more cache entries than buckets means a
        # shape leak (exactly the recompile-per-length failure this
        # subsystem exists to prevent). A real raise, not an assert —
        # this is the production-side half of the contract and must
        # survive python -O.
        n = self.cache_size()
        if n > len(self.buckets):
            raise RuntimeError(
                f"chunked-prefill jit cache grew to {n} entries > "
                f"{len(self.buckets)} buckets {self.buckets} — the "
                "chunk dispatch re-specialized on something other "
                "than the bucket length")
        return out

    def cache_size(self) -> int:
        """Jit-cache entries of the chunk dispatch (≤ bucket count) —
        the prefill half of the serving no-recompilation gate."""
        return self._chunk._cache_size()


class MegaChunkedPrefill:
    """Chunk driver over a megakernel engine's in-kernel chunk steps —
    the :class:`ChunkedPrefill` duck type the serving chunk stream
    drives (same ``buckets``/``plan``/``next_chunk``/``step``/
    ``cache_size`` surface), for a
    :class:`~triton_dist_tpu.megakernel.engine.MegaKernelEngine` built
    with ``prefill_buckets=...``. The KV pool lives inside the engine
    (its aliased step operands), so the layer-path ``params``/``cache``
    arguments are ignored and the cache is returned untouched; the
    chunk's scalar cursors become the sign-encoded per-row position
    codes the WRITE_KV_CHUNK/ATTN_CHUNK tasks decode
    (:func:`~triton_dist_tpu.ops.chunked_prefill.chunk_row_codes`).
    """

    def __init__(self, engine, telemetry=None):
        buckets = getattr(engine, "prefill_buckets", None)
        if not buckets:
            raise ValueError(
                "MegaChunkedPrefill needs a MegaKernelEngine built "
                "with prefill_buckets=(...) — the chunk task pair is "
                "compiled at engine construction")
        self.engine = engine
        self.buckets = tuple(buckets)
        self.telemetry = telemetry

    def plan(self, n_tokens: int) -> List[Tuple[int, int]]:
        """Deterministic ``[(bucket, valid), ...]`` cover of
        ``n_tokens`` — the SAME :func:`plan_chunks` cover as the layer
        path, so the two lanes chunk a prompt identically."""
        return plan_chunks(n_tokens, self.buckets)

    def next_chunk(self, remaining: int) -> Tuple[int, int]:
        """The next (bucket, valid) for ``remaining`` tokens."""
        return self.plan(remaining)[0]

    def step(self, params, toks: np.ndarray, cache, table_row,
             start: int, wfrom: int, valid: int, *, slot: int = 0):
        """Dispatch one chunk through the megakernel chunk task pair;
        returns ``(None, logits (vocab,), cache)`` — the last VALID
        row's logits, bit-identical to the one-token prefill lane's at
        that position; this lane's programs pick no token, so the
        caller picks from the row."""
        from triton_dist_tpu.ops.chunked_prefill import chunk_row_codes

        tel = self.telemetry
        t0 = tel.now() if tel is not None and tel.enabled else None
        codes = chunk_row_codes(start, len(toks), valid, wfrom)
        logits = self.engine.prefill_chunk(toks, codes, table_row)
        if t0 is not None:
            tel.observe("chunk_dispatch", tel.now() - t0)
            tel.count(f"chunk_bucket_{len(toks)}")
        return None, logits[int(valid) - 1], cache

    def cache_size(self) -> int:
        """Jit-cache entries across the per-bucket chunk steps (≤
        bucket count) — the engine gates this inline after every
        dispatch."""
        return self.engine.chunk_cache_size()
