"""ModelBuilder: record a decode step as tasks, schedule natively, run
as ONE persistent Pallas kernel.

Reference: ``mega_triton_kernel/models/model_builder.py:86``
``ModelBuilder`` — records ops via task builders (:192), ``compile()``
:514 (dep opt → enqueue → codegen → import), ``run()`` :557 launching
``MEGA_TRITON_KERNEL[grid=(NUM_SMS,)]``.

TPU differences: instead of generating Triton source text, the kernel
is a *task interpreter* — grid = the core's work queue, task descriptors
arrive via scalar prefetch, dispatch is ``lax.switch``
(``megakernel/kernels.py``); the C++ scheduler orders/packs the queue.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import core_call, comm_compiler_params
from triton_dist_tpu.megakernel import kernels as K
from triton_dist_tpu.megakernel.graph import Graph, comm_priority
from triton_dist_tpu.megakernel.scheduler import (
    prune_deps, schedule_dyn, schedule_mc, simulate_static)
from triton_dist_tpu.megakernel.task import ARGS_MAX, TaskType
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.parallel.mesh import MeshContext


def _cdiv(a, b):
    return -(-a // b)


# Region kinds a serving checkpoint must carry: the KV pools and their
# quantization scales, the hybrid recurrent state, and the in-arena
# counters (everything else is weights — repacked from params — or
# per-step activation scratch).
SNAPSHOT_KINDS = ("kv", "scale", "state", "counter")

# Kinds that occupy rows of the (rows, w) arena itself; the rest are
# named DEVICE BUFFERS (KV pools, scale tables, GDN state) that ride
# beside the arena through the kernel's aliased operands.
ARENA_KINDS = ("weight", "activation", "workspace", "counter", "io")


@dataclasses.dataclass(frozen=True)
class ArenaRegion:
    """One named region of the megakernel's memory layout.

    In-arena kinds (``weight``/``activation``/``workspace``/
    ``counter``/``io``) describe ``rows`` rows at ``offset`` of the
    (arena_rows, w) arena; buffer kinds (``kv``/``scale``/``state``)
    describe a standalone device array of ``shape``/``dtype`` that the
    kernel addresses through its own aliased operand."""

    name: str
    kind: str
    offset: int = 0
    rows: int = 0
    shape: Tuple[int, ...] = ()
    dtype: str = "float32"

    @property
    def in_arena(self) -> bool:
        return self.kind in ARENA_KINDS


class ArenaSchema:
    """Described memory layout of a megakernel build: every region —
    weight tiles, activation tiles, the allreduce workspace, MoE
    router counters, KV pools and their per-(layer, page, kv_head)
    scale tables, GDN state — by name, with offset/rows (in-arena) or
    shape/dtype (device buffers). Replaces the bare ``_alloc`` cursor
    arithmetic: consumers (engine checkpoint/restore, the chaos
    sweep's arena-coherence check, docs) address regions by NAME, so
    adding a region is one ``alloc``/``add_buffer`` call, never
    offset bookkeeping (see docs/megakernel.md, "Arena schema")."""

    def __init__(self, w: int):
        self.w = int(w)
        self._regions: "Dict[str, ArenaRegion]" = {}
        self._cursor = 0

    # -- building ----------------------------------------------------
    def alloc(self, name: str, rows: int, kind: str = "activation"
              ) -> int:
        """Claim ``rows`` arena rows for ``name``; returns the offset
        (the cursor allocator, now with provenance)."""
        if kind not in ARENA_KINDS:
            raise ValueError(f"kind {kind!r} is not an in-arena kind "
                             f"{ARENA_KINDS}")
        if name in self._regions:
            raise ValueError(f"arena region {name!r} already allocated")
        off = self._cursor
        self._regions[name] = ArenaRegion(name=name, kind=kind,
                                          offset=off, rows=int(rows))
        self._cursor += int(rows)
        return off

    def add_buffer(self, name: str, shape, dtype, kind: str) -> None:
        """Register a named device buffer (KV pool, scale table, GDN
        state) that lives beside the row arena."""
        if kind in ARENA_KINDS:
            raise ValueError(f"kind {kind!r} is an in-arena kind — use "
                             "alloc()")
        if name in self._regions:
            raise ValueError(f"arena region {name!r} already allocated")
        self._regions[name] = ArenaRegion(
            name=name, kind=kind, shape=tuple(int(s) for s in shape),
            dtype=str(dtype))

    # -- reading -----------------------------------------------------
    @property
    def rows(self) -> int:
        """Total arena rows claimed so far (the pack/zero footprint)."""
        return self._cursor

    def __contains__(self, name: str) -> bool:
        return name in self._regions

    def __iter__(self):
        return iter(self._regions.values())

    def region(self, name: str) -> ArenaRegion:
        return self._regions[name]

    def regions(self, kind: Optional[str] = None):
        """All regions, or just one kind's, in allocation order."""
        return [r for r in self._regions.values()
                if kind is None or r.kind == kind]

    def snapshot_regions(self):
        """The regions a checkpoint snapshots by name (KV + scales +
        counters + GDN state — bit-exact at any kv_dtype)."""
        return [r for r in self._regions.values()
                if r.kind in SNAPSHOT_KINDS]

    def check_disjoint(self) -> None:
        """Arena coherence: in-arena regions must tile [0, rows) with
        no overlap and no gap — the invariant the chaos sweep asserts
        per tick (a drifted offset would silently alias a weight tile
        onto an activation or counter)."""
        spans = sorted((r.offset, r.offset + r.rows, r.name)
                       for r in self._regions.values() if r.in_arena)
        at = 0
        for start, end, name in spans:
            if start != at:
                kind = "overlaps the previous region" \
                    if start < at else "leaves an unclaimed gap"
                raise ValueError(
                    f"arena region {name!r} at [{start}, {end}) {kind} "
                    f"(cursor was at {at})")
            at = end
        if at != self._cursor:
            raise ValueError(
                f"arena regions cover {at} rows but the cursor claims "
                f"{self._cursor}")

    def describe(self):
        """Plain-data region table (docs / diagnostics)."""
        out = []
        for r in self._regions.values():
            if r.in_arena:
                out.append({"name": r.name, "kind": r.kind,
                            "offset": r.offset, "rows": r.rows})
            else:
                out.append({"name": r.name, "kind": r.kind,
                            "shape": list(r.shape), "dtype": r.dtype})
        return out


def calibrate_cost_table(observations) -> dict:
    """Profile-feedback calibration: solve per-task-type unit times
    from wall-clock observations of whole megakernel steps.

    observations: list of (unit_counts, wall_seconds) where
    ``unit_counts`` is :meth:`ModelBuilder.task_unit_counts` for that
    build — at least as many observations as distinct task types, from
    builds that vary the type mix (layer count, batch, seq). Solves the
    least-squares system ``counts @ x = wall`` (x >= 0) and returns a
    ``cost_table`` {task_type: weight} normalized so the smallest
    positive weight is 1.0 — feed it back into
    ``ModelBuilder(cost_table=...)`` to re-schedule ``cost_lpt`` from
    measured times (reference ``enable_runtime_scheduler``,
    ``model_builder.py:521-524``, answered at schedule time).

    Raises ``ValueError`` when the observation mix is rank-deficient
    (e.g. proportional count vectors): the minimum-norm solution would
    be weights proportional to counts — garbage the schedule (and a
    Perfetto export labeled "calibrated") would then trust. Vary the
    shapes until every type's unit time is identifiable.
    """
    types = sorted({k for counts, _ in observations for k in counts})
    a = np.array([[counts.get(k, 0) for k in types]
                  for counts, _ in observations], np.float64)
    b = np.array([w for _, w in observations], np.float64)
    rank = np.linalg.matrix_rank(a)
    if rank < len(types):
        raise ValueError(
            f"calibrate_cost_table: observation matrix rank {rank} < "
            f"{len(types)} task types — per-type unit times are not "
            "identifiable; add observations with different type mixes "
            "(vary layer count / batch / seq)")
    x, *_ = np.linalg.lstsq(a, b, rcond=None)
    x = np.clip(x, 0.0, None)
    pos = x[x > 0]
    if pos.size == 0:
        return {k: 1.0 for k in types}
    x = x / pos.min()
    return {k: float(v) for k, v in zip(types, x)}


class ModelBuilder:
    """Builds the Qwen3 dense decode step as a megakernel."""

    def __init__(self, cfg: ModelConfig, mesh, *, batch: int,
                 max_len: int, axis: str = "tp",
                 tile_w: Optional[int] = None, t_tile: Optional[int] = None,
                 num_cores: int = 1, strategy: str = "round_robin",
                 schedule: str = "static",
                 seq: int = 1, paged: bool = False,
                 page: Optional[int] = None, profile: bool = False,
                 cost_table: Optional[dict] = None,
                 expert_load=None, kv_quant: Optional[str] = None,
                 qblock: bool = False, chunk: bool = False,
                 counts_rows: Optional[int] = None):
        """``num_cores`` > 1 packs tasks onto per-core queues executed
        over a CORE_PARALLEL grid dimension (TPU megacore; v4/v5p have
        two TensorCores) with cross-core deps enforced by edge
        semaphores — the reference's per-SM queues + scoreboard
        (``core/scheduler.py:42-100``). ``strategy="cost_lpt"`` is the
        static load-balanced analogue of the reference's
        ``enable_runtime_scheduler`` (TPU cores share no atomic queue
        head, so balancing happens at schedule time from task costs).

        ``schedule="dynamic"`` replaces the per-core slot lists with
        the dynamic scoreboard scheduler: a comm-priority-ordered claim
        list popped at run time via a claim counter in the scoreboard
        workspace (SMEM counter + per-priority-bucket claim
        semaphores), so no slot carries a precomputed task binding and
        the merged-order NOOP padding disappears — the closest TPU form
        of the reference's in-kernel atomic queue head. ``strategy`` is
        ignored in dynamic mode; the claim order comes from
        ``graph.comm_priority`` (remote-peer-unblocking collectives
        first, critical path as tiebreak), sharpened by the same
        ``cost_table`` feedback ``cost_lpt`` uses."""
        if getattr(cfg, "attention_bias", False) or not getattr(
                cfg, "qk_norm", True):
            raise NotImplementedError(
                "megakernel task set covers the Qwen3 layer shape "
                "(no attention biases, per-head q/k norm); serve "
                "bias-carrying / norm-free checkpoints (Seed-OSS) "
                "through the layer Engine")
        if getattr(cfg, "gdn_conv_kernel", 0) or getattr(
                cfg, "attn_gate", False):
            raise NotImplementedError(
                "megakernel hybrid tasks cover the simplified "
                "(conv-free) GDN cell; serve HF qwen3_next checkpoints "
                "(conv + attention gate) through the layer Engine")
        self.cfg = cfg
        self.mesh = mesh
        self.mctx = MeshContext.from_mesh(mesh)
        self.axis = axis
        self.n = self.mctx.size(axis)
        self.batch = batch
        self.max_len = max_len
        self.num_cores = num_cores
        self.strategy = strategy
        if schedule not in ("static", "dynamic"):
            raise ValueError(f"schedule must be 'static' or 'dynamic', "
                             f"got {schedule!r}")
        self.schedule = schedule
        # Scoreboard progress tracing (see _kernel): env-gated so the
        # resilience harness can attribute a wedged schedule to its
        # last-completed queue slot.
        self.trace_progress = os.environ.get(
            "TRITON_DIST_TPU_TRACE_PROGRESS") == "1"
        # profile=True: the step emits a 4th output — one (task_type,
        # arg0) row per executed queue slot — feeding core_activity()
        # (the reference megakernel's SM-activity metric,
        # model_builder.py:164-190) and the Perfetto exporter.
        self.profile = profile
        # cost_table: measured per-unit weights {int(TaskType): float}
        # multiplying the static unit estimates — the profile-feedback
        # loop (calibrate_cost_table) re-schedules cost_lpt from
        # MEASURED task times, the static-TPU answer to the reference's
        # runtime scheduler (model_builder.py:521-524: no cross-core
        # atomic queue head exists here, so balance moves to schedule
        # time but from silicon numbers).
        self.cost_table = dict(cost_table) if cost_table else None
        # expert_load: per-expert weights (the serving layer's load
        # EWMA) biasing the DYNAMIC claim order toward hot experts'
        # group-GEMM/combine chains (graph.comm_priority expert_load).
        # Refresh between steps via reprioritize() — claim tables are
        # host data, so no graph rebuild is needed.
        self.expert_load = (list(expert_load) if expert_load is not None
                            else None)
        # seq > 1: batched prefill — ``batch`` counts ROWS (B*S, b-major)
        # and the attention/cache tasks use the causal prefill bodies.
        # qblock=True instead selects the Q-BLOCK VERIFICATION pair
        # (WRITE_KV_QBLOCK/ATTN_QBLOCK): seq = K rows per slot, each
        # row at its OWN per-row position (len_s[row]; < 0 masks the
        # row) — the speculative-decode verification chain as one
        # megakernel launch.
        self.seq = seq
        self.qblock = bool(qblock)
        # chunk=True selects the PREFILL-CHUNK pair (WRITE_KV_CHUNK/
        # ATTN_CHUNK): one C-row prompt chunk per launch (batch = seq
        # = C, one slot), per-row positions sign-encoded in the
        # cache_len vector (kernels._chunk_apos) — the bucketed
        # chunked-prefill contract (ops/chunked_prefill) as megakernel
        # tasks.
        self.chunk = bool(chunk)
        # Engine-wide moe_counts region height: every builder sharing
        # one arena must claim the SAME offset AND rows for the
        # counters, or a smaller builder's next region starts inside a
        # larger one's counter span (the engine passes the max batch
        # over all its builders).
        self.counts_rows = (int(counts_rows) if counts_rows is not None
                            else None)
        if batch % seq:
            raise ValueError(f"batch rows {batch} not divisible by "
                             f"seq {seq}")
        if self.qblock and self.chunk:
            raise ValueError("qblock and chunk are mutually exclusive "
                             "task-set selectors (verification rows vs "
                             "prompt-chunk rows)")
        if self.qblock:
            if seq < 2:
                raise ValueError("qblock builds verify K >= 2 "
                                 f"candidates per slot (seq={seq})")
            if not paged:
                raise ValueError("the Q-block verification task set "
                                 "addresses the cache through block "
                                 "tables — build with paged=True")
        if self.chunk:
            if batch != seq:
                raise ValueError(
                    "chunk builds run ONE prompt chunk per launch: "
                    f"batch ({batch}) must equal seq ({seq}) — the "
                    "chunk rows ARE the batch rows")
            if not paged:
                raise ValueError("the prefill-chunk task set addresses "
                                 "the cache through block tables — "
                                 "build with paged=True")
        # kv_quant: int8/fp8 pools with per-(layer, page, kv_head)
        # fp32 scale tables riding as extra aliased operands —
        # quantize fused into write_kv, dequant into every cache read.
        # qmax comes from the layer path's ONE quantization table
        # (kv_quant_spec), so the in-kernel quantizer can never
        # silently diverge from serving.blocks._quantize.
        self.kv_qmax = 0.0
        if kv_quant is not None:
            from triton_dist_tpu.serving.blocks import kv_quant_spec

            qdtype, qmax = kv_quant_spec(kv_quant)
            if qdtype is None:
                kv_quant = None
            else:
                self.kv_qmax = float(qmax)
        if kv_quant is not None:
            if not paged:
                raise ValueError(
                    "quantized megakernel KV needs paged=True (scales "
                    "are per (layer, page, kv_head))")
            if seq > 1 and not (self.qblock or self.chunk):
                raise NotImplementedError(
                    "the batched-prefill bodies have no fused-quant "
                    "write; quantized engines stream prompts through "
                    "the prefill lane (decode kernel) or chunk tasks")
        self.kv_quant = kv_quant
        hd = cfg.head_dim
        self.w = tile_w or max(128, hd)
        if self.w % hd:
            raise ValueError(f"tile width {self.w} must be a multiple of "
                             f"head_dim {hd}")
        self.t_tile = t_tile or min(128, max_len)
        if max_len % self.t_tile:
            raise ValueError(f"t_tile={self.t_tile} must divide max_len={max_len}")
        # Paged KV: the caches become page pools + a block table
        # (reference mega_triton_kernel paged flash_decode). Alignment
        # contract for single-slice access (kernels._kv_slice): cache
        # reads span t_tile and prefill writes span seq, so both must
        # divide the page; prefill bases must be seq-aligned.
        self.paged = paged
        self.page = 0
        self.p_max = 0
        if paged:
            self.page = page or max(self.t_tile, seq)
            # qblock/chunk rows write one position each (never a
            # seq-span block store), so only the t_tile and max_len
            # alignment applies there.
            seq_align = seq > 1 and not (self.qblock or self.chunk)
            if (self.page % self.t_tile
                    or (seq_align and self.page % seq)
                    or max_len % self.page):
                raise ValueError(
                    f"page={self.page} needs t_tile|page, seq|page and "
                    f"page|max_len (t_tile={self.t_tile}, seq={seq}, "
                    f"max_len={max_len})")
            self.p_max = max_len // self.page

        n = self.n
        self.h_loc = cfg.num_attention_heads // n
        self.kv_loc = cfg.num_key_value_heads // n
        self.d_tiles = _cdiv(cfg.hidden_size, self.w)
        self.hq_tiles = _cdiv(self.h_loc * hd, self.w)
        self.kv_tiles = _cdiv(self.kv_loc * hd, self.w)
        self.ff_tiles = _cdiv(cfg.intermediate_size // n, self.w)
        # Hybrid (qwen_next): GDN layers carry a recurrent state
        # buffer instead of KV rows; decode-only in the megakernel
        # (prefill via MegaKernelEngine.prefill_chain / the layer
        # engine). Head slices must sit inside lane tiles.
        self.hybrid = cfg.is_hybrid
        if self.hybrid:
            if self.kv_quant:
                raise NotImplementedError(
                    "quantized KV covers the attention families; the "
                    "hybrid GDN state is fp32 recurrent, not paged")
            if self.qblock:
                raise NotImplementedError(
                    "Q-block verification needs position-addressed KV; "
                    "the hybrid GDN recurrent state cannot rewind a "
                    "rejected draft")
            if self.chunk:
                raise NotImplementedError(
                    "prefill-chunk tasks need position-addressed KV; "
                    "the hybrid GDN recurrent state is sequential — "
                    "prefill via prefill_chain")
            if self.seq > 1:
                raise ValueError("hybrid megakernel is decode-only "
                                 "(seq == 1); prefill via prefill_chain")
            if cfg.is_moe:
                raise NotImplementedError(
                    "hybrid+MoE megakernel not wired; the layer Engine "
                    "serves qwen_next MoE")
            if cfg.gdn_num_heads % n:
                raise ValueError(f"gdn_num_heads={cfg.gdn_num_heads} "
                                 f"not divisible by tp={n}")
            self.gdn_h_loc = cfg.gdn_num_heads // n
            if (self.w % cfg.gdn_head_dim_k or self.w % cfg.gdn_head_dim_v
                    or self.gdn_h_loc > self.w):
                raise ValueError(
                    "GDN head dims must divide the tile width and local "
                    f"heads fit one tile (w={self.w}, "
                    f"dk={cfg.gdn_head_dim_k}, dv={cfg.gdn_head_dim_v}, "
                    f"h_loc={self.gdn_h_loc})")
            self.gq_tiles = _cdiv(self.gdn_h_loc * cfg.gdn_head_dim_k,
                                  self.w)
            self.gv_tiles = _cdiv(self.gdn_h_loc * cfg.gdn_head_dim_v,
                                  self.w)
            from triton_dist_tpu.models.qwen_next import _layer_kinds
            self.layer_kinds, _, self.n_gdn = _layer_kinds(cfg)
        # MoE (qwen_moe): per-expert ffn dim sharded over tp (the TP
        # regime); decode computes EVERY expert and weight-combines —
        # fully static task graph, the same small-batch trade as
        # ep_moe.fwd_decode. Router logits must fit one lane tile.
        self.moe = cfg.is_moe
        if self.moe:
            if cfg.num_experts > self.w:
                raise ValueError(
                    f"megakernel MoE needs num_experts={cfg.num_experts}"
                    f" <= tile width {self.w} (router logits tile)")
            if cfg.moe_intermediate_size % n:
                raise ValueError(
                    f"moe_intermediate_size={cfg.moe_intermediate_size} "
                    f"not divisible by tp={n}")
            if cfg.num_experts_per_tok > cfg.num_experts:
                raise ValueError(
                    f"num_experts_per_tok={cfg.num_experts_per_tok} > "
                    f"num_experts={cfg.num_experts} (the static top-k "
                    "loop would pick zero-probability padded columns)")
            self.ffe_tiles = _cdiv(cfg.moe_intermediate_size // n,
                                   self.w)

        self._offsets: Dict[str, int] = {}
        self.schema = ArenaSchema(self.w)
        self.graph = Graph()
        self._weight_entries: List[Tuple[str, int]] = []
        self._build()

    # ---------------- arena layout -------------------------------------
    # The described memory layout: every _alloc lands in the schema
    # with a name + kind, so consumers (checkpoint/restore, the chaos
    # arena sweep, docs) address regions by NAME instead of trusting
    # cursor arithmetic.
    def _alloc(self, name: str, rows: int,
               kind: str = "activation") -> int:
        off = self.schema.alloc(name, rows, kind)
        self._offsets[name] = off
        return off

    def _alloc_act(self, name: str, tiles: int) -> int:
        return self._alloc(name, tiles * self.batch)

    # ---------------- recording helpers --------------------------------
    def _linear(self, in_off, w_off, out_off, k_tiles, n_tiles, *,
                layer, in_rows, w_rows, expert: int = -1):
        b = self.batch
        for j in range(n_tiles):
            self.graph.add(
                TaskType.LINEAR,
                (in_off, w_off, out_off, k_tiles, n_tiles, j),
                reads=[(in_off, in_rows), (w_off, w_rows)],
                writes=[(out_off + j * b, b)], layer=layer,
                expert=expert)

    def _build(self):
        cfg, b, w = self.cfg, self.batch, self.w
        d_t, hq_t, kv_t, ff_t = (self.d_tiles, self.hq_tiles,
                                 self.kv_tiles, self.ff_tiles)

        # Weights region (per layer) — order defines pack_arena.
        def walloc(name, k_tiles, n_tiles):
            rows = k_tiles * n_tiles * w
            off = self._alloc(name, rows, kind="weight")
            self._weight_entries.append((name, rows))
            return off

        def vecalloc(name, tiles):
            off = self._alloc(name, tiles, kind="weight")
            self._weight_entries.append((name, tiles))
            return off

        # lm_head is vocab-sharded along tp (models.dense.param_specs);
        # each shard holds vocab/n rows.
        if cfg.vocab_size % self.n:
            raise ValueError(f"vocab_size={cfg.vocab_size} not divisible "
                             f"by tp={self.n}")
        self.vocab_loc = cfg.vocab_size // self.n
        self.vloc_tiles = _cdiv(self.vocab_loc, w)
        L = cfg.num_hidden_layers
        for li in range(L):
            if self.hybrid and self.layer_kinds[li][0] == "gdn":
                gq_t, gv_t = self.gq_tiles, self.gv_tiles
                walloc(f"l{li}.gwq", d_t, gq_t)
                walloc(f"l{li}.gwk", d_t, gq_t)
                walloc(f"l{li}.gwv", d_t, gv_t)
                walloc(f"l{li}.gwg", d_t, 1)
                walloc(f"l{li}.gwb", d_t, 1)
                vecalloc(f"l{li}.g_bias", 1)
                walloc(f"l{li}.gwo", gv_t, d_t)
            else:
                walloc(f"l{li}.wq", d_t, hq_t)
                walloc(f"l{li}.wk", d_t, kv_t)
                walloc(f"l{li}.wv", d_t, kv_t)
                walloc(f"l{li}.wo", hq_t, d_t)
            if self.moe:
                walloc(f"l{li}.router", d_t, 1)
                for e in range(cfg.num_experts):
                    walloc(f"l{li}.e{e}.w_gate", d_t, self.ffe_tiles)
                    walloc(f"l{li}.e{e}.w_up", d_t, self.ffe_tiles)
                    walloc(f"l{li}.e{e}.w_down", self.ffe_tiles, d_t)
            else:
                walloc(f"l{li}.w_gate", d_t, ff_t)
                walloc(f"l{li}.w_up", d_t, ff_t)
                walloc(f"l{li}.w_down", ff_t, d_t)
            vecalloc(f"l{li}.ln_attn", d_t)
            vecalloc(f"l{li}.ln_mlp", d_t)
            if not (self.hybrid and self.layer_kinds[li][0] == "gdn"):
                vecalloc(f"l{li}.q_norm", 1)
                vecalloc(f"l{li}.k_norm", 1)
        vecalloc("ln_f", d_t)
        # Embedding table vocab-sharded like lm_head: vocab/n entries
        # per rank; the gather task zero-fills off-shard tokens and an
        # allreduce sums the single real contribution.
        vecalloc("embed", self.vocab_loc * d_t)
        walloc("lm_head_T", d_t, self.vloc_tiles)

        # MoE expert-load counters: one (counts_rows, w) arena region
        # the router epilogue ACCUMULATES its top-k selection mask
        # into, every layer, every step — the decode dispatch's
        # on-device expert telemetry (read back by
        # engine.expert_counts(); the serving layer diffs snapshots
        # per tick). Monotonic: arena packs zeroed, so no per-step
        # reset task is needed. Placed directly after the (batch-
        # independent) weight region and sized engine-wide, so every
        # builder sharing the arena claims the SAME [offset, rows)
        # span — chunk/verify/prefill launches accumulate into the
        # decode counters instead of scribbling them with activations
        # (the old layout put moe_counts after the batch-dependent
        # ar_ws/x regions, so any batched prefill builder's
        # activation tail aliased the decode builder's counters).
        self.moe_counts_off = 0
        if self.moe:
            self.moe_counts_off = self._alloc(
                "moe_counts", max(b, self.counts_rows or 0),
                kind="counter")

        # Allreduce workspace + I/O regions.
        ar_max_tiles = max(d_t, 1)
        self.ar_ws_off = self._alloc("ar_ws", self.n * ar_max_tiles * b,
                                     kind="workspace")
        self.ar_max_tiles = ar_max_tiles
        x_off = self._alloc_act("x", d_t)
        self.x_off = x_off

        # Embedding lookup inside the kernel (token ids via prefetch),
        # then an allreduce to sum the vocab-shard contributions.
        self.graph.add(TaskType.GATHER,
                       (self._offsets["embed"], x_off, d_t,
                        self.vocab_loc),
                       reads=[(self._offsets["embed"],
                               self.vocab_loc * d_t)],
                       writes=[(x_off, d_t * b)])
        self.graph.add(TaskType.ALLREDUCE, (x_off, d_t),
                       reads=[(x_off, d_t * b)],
                       writes=[(x_off, d_t * b),
                               (self.ar_ws_off,
                                self.n * ar_max_tiles * b)])

        # Per-layer tasks.
        g = self.graph
        o = self._offsets
        for li in range(L):
            t0 = self._alloc_act(f"l{li}.t0", d_t)
            if not (self.hybrid and self.layer_kinds[li][0] == "gdn"):
                q = self._alloc_act(f"l{li}.q", hq_t)
                kx = self._alloc_act(f"l{li}.k", kv_t)
                vx = self._alloc_act(f"l{li}.v", kv_t)
                attn = self._alloc_act(f"l{li}.attn", hq_t)
            opart = self._alloc_act(f"l{li}.opart", d_t)
            x1 = self._alloc_act(f"l{li}.x1", d_t)
            t1 = self._alloc_act(f"l{li}.t1", d_t)
            if not self.moe:
                gx = self._alloc_act(f"l{li}.g", ff_t)
                ux = self._alloc_act(f"l{li}.u", ff_t)
                hx = self._alloc_act(f"l{li}.h", ff_t)
            mpart = self._alloc_act(f"l{li}.mpart", d_t)
            x2 = self._alloc_act(f"l{li}.x2", d_t)

            g.add(TaskType.RMSNORM,
                  (x_off, o[f"l{li}.ln_attn"], t0, d_t),
                  reads=[(x_off, d_t * b), (o[f"l{li}.ln_attn"], d_t)],
                  writes=[(t0, d_t * b)], layer=li)
            if self.hybrid and self.layer_kinds[li][0] == "gdn":
                # GDN mixer: q/k/v/g/beta projections then the
                # recurrent delta-rule step (state in the states
                # buffer; ordinal = position among GDN layers).
                gq_t, gv_t = self.gq_tiles, self.gv_tiles
                ordinal = self.layer_kinds[li][1]
                gq = self._alloc_act(f"l{li}.gq", gq_t)
                gk = self._alloc_act(f"l{li}.gk", gq_t)
                gv = self._alloc_act(f"l{li}.gv", gv_t)
                graw = self._alloc_act(f"l{li}.graw", 1)
                braw = self._alloc_act(f"l{li}.braw", 1)
                go = self._alloc_act(f"l{li}.go", gv_t)
                self._linear(t0, o[f"l{li}.gwq"], gq, d_t, gq_t,
                             layer=li, in_rows=d_t * b,
                             w_rows=d_t * gq_t * w)
                self._linear(t0, o[f"l{li}.gwk"], gk, d_t, gq_t,
                             layer=li, in_rows=d_t * b,
                             w_rows=d_t * gq_t * w)
                self._linear(t0, o[f"l{li}.gwv"], gv, d_t, gv_t,
                             layer=li, in_rows=d_t * b,
                             w_rows=d_t * gv_t * w)
                self._linear(t0, o[f"l{li}.gwg"], graw, d_t, 1,
                             layer=li, in_rows=d_t * b, w_rows=d_t * w)
                self._linear(t0, o[f"l{li}.gwb"], braw, d_t, 1,
                             layer=li, in_rows=d_t * b, w_rows=d_t * w)
                g.add(TaskType.GDN_DECODE,
                      (gq, gk, gv, graw, braw, o[f"l{li}.g_bias"], go,
                       ordinal),
                      reads=[(gq, gq_t * b), (gk, gq_t * b),
                             (gv, gv_t * b), (graw, b), (braw, b),
                             (o[f"l{li}.g_bias"], 1), (go, gv_t * b)],
                      writes=[(go, gv_t * b)], layer=li)
                self._linear(go, o[f"l{li}.gwo"], opart, gv_t, d_t,
                             layer=li, in_rows=gv_t * b,
                             w_rows=gv_t * d_t * w)
            else:
                self._linear(t0, o[f"l{li}.wq"], q, d_t, hq_t, layer=li,
                             in_rows=d_t * b, w_rows=d_t * hq_t * w)
                self._linear(t0, o[f"l{li}.wk"], kx, d_t, kv_t, layer=li,
                             in_rows=d_t * b, w_rows=d_t * kv_t * w)
                self._linear(t0, o[f"l{li}.wv"], vx, d_t, kv_t, layer=li,
                             in_rows=d_t * b, w_rows=d_t * kv_t * w)
                kv_layer = (self.layer_kinds[li][1] if self.hybrid
                            else li)
                if self.chunk:
                    wk_type = TaskType.WRITE_KV_CHUNK
                    at_type = TaskType.ATTN_CHUNK
                elif self.qblock:
                    wk_type = TaskType.WRITE_KV_QBLOCK
                    at_type = TaskType.ATTN_QBLOCK
                elif self.seq == 1:
                    wk_type = TaskType.WRITE_KV
                    at_type = TaskType.ATTN_DECODE
                else:
                    wk_type = TaskType.WRITE_KV_PREFILL
                    at_type = TaskType.ATTN_PREFILL
                g.add(wk_type,
                      (kx, vx, kv_layer, o[f"l{li}.k_norm"]),
                      reads=[(kx, kv_t * b), (vx, kv_t * b),
                             (o[f"l{li}.k_norm"], 1)],
                      writes=[], layer=li)
                # ATTN reads the cache written by WRITE_KV — encode the
                # ordering as an artificial region keyed off the task
                # above.
                attn_task = g.add(at_type,
                                  (q, attn, kv_layer,
                                   o[f"l{li}.q_norm"]),
                                  reads=[(q, hq_t * b),
                                         (o[f"l{li}.q_norm"], 1)],
                                  writes=[(attn, hq_t * b)], layer=li)
                attn_task.deps.append(g.tasks[-2].task_id)  # after W_KV
                self._linear(attn, o[f"l{li}.wo"], opart, hq_t, d_t,
                             layer=li, in_rows=hq_t * b,
                             w_rows=hq_t * d_t * w)
            g.add(TaskType.ALLREDUCE, (opart, d_t),
                  reads=[(opart, d_t * b)],
                  writes=[(opart, d_t * b),
                          (self.ar_ws_off, self.n * ar_max_tiles * b)],
                  layer=li)
            g.add(TaskType.ADD, (x_off, opart, x1, d_t),
                  reads=[(x_off, d_t * b), (opart, d_t * b)],
                  writes=[(x1, d_t * b)], layer=li)
            g.add(TaskType.RMSNORM,
                  (x1, o[f"l{li}.ln_mlp"], t1, d_t),
                  reads=[(x1, d_t * b), (o[f"l{li}.ln_mlp"], d_t)],
                  writes=[(t1, d_t * b)], layer=li)
            if self.moe:
                # MoE FFN: router → combine weights → every expert's
                # swiglu (ffn-sharded over tp) → weighted accumulate
                # into mpart (partial; summed by the allreduce below).
                E, ffe_t = cfg.num_experts, self.ffe_tiles
                rl = self._alloc_act(f"l{li}.rl", 1)
                wbe = self._alloc_act(f"l{li}.wbe", 1)
                self._linear(t1, o[f"l{li}.router"], rl, d_t, 1,
                             layer=li, in_rows=d_t * b,
                             w_rows=d_t * w)
                # The router epilogue also accumulates its selection
                # mask into the shared counts region — the read+write
                # chains the per-layer MOE_WEIGHTS tasks, which the
                # residual stream serializes anyway.
                g.add(TaskType.MOE_WEIGHTS,
                      (rl, wbe, E, self.moe_counts_off),
                      reads=[(rl, b), (self.moe_counts_off, b)],
                      writes=[(wbe, b), (self.moe_counts_off, b)],
                      layer=li)
                for e in range(E):
                    ge = self._alloc_act(f"l{li}.e{e}.g", ffe_t)
                    ue = self._alloc_act(f"l{li}.e{e}.u", ffe_t)
                    he = self._alloc_act(f"l{li}.e{e}.h", ffe_t)
                    pe = self._alloc_act(f"l{li}.e{e}.part", d_t)
                    self._linear(t1, o[f"l{li}.e{e}.w_gate"], ge, d_t,
                                 ffe_t, layer=li, in_rows=d_t * b,
                                 w_rows=d_t * ffe_t * w, expert=e)
                    self._linear(t1, o[f"l{li}.e{e}.w_up"], ue, d_t,
                                 ffe_t, layer=li, in_rows=d_t * b,
                                 w_rows=d_t * ffe_t * w, expert=e)
                    g.add(TaskType.SILU_MUL, (ge, ue, he, ffe_t),
                          reads=[(ge, ffe_t * b), (ue, ffe_t * b)],
                          writes=[(he, ffe_t * b)], layer=li, expert=e)
                    self._linear(he, o[f"l{li}.e{e}.w_down"], pe, ffe_t,
                                 d_t, layer=li, in_rows=ffe_t * b,
                                 w_rows=ffe_t * d_t * w, expert=e)
                    # init on e==0 writes; later experts accumulate —
                    # the shared (mpart, wbe) read/write regions chain
                    # the experts' combines in order.
                    g.add(TaskType.WEIGHTED_ADD,
                          (mpart, pe, wbe, e, d_t, 1 if e == 0 else 0),
                          reads=[(pe, d_t * b), (wbe, b),
                                 (mpart, d_t * b)],
                          writes=[(mpart, d_t * b)], layer=li,
                          expert=e)
            else:
                self._linear(t1, o[f"l{li}.w_gate"], gx, d_t, ff_t,
                             layer=li, in_rows=d_t * b,
                             w_rows=d_t * ff_t * w)
                self._linear(t1, o[f"l{li}.w_up"], ux, d_t, ff_t,
                             layer=li, in_rows=d_t * b,
                             w_rows=d_t * ff_t * w)
                g.add(TaskType.SILU_MUL, (gx, ux, hx, ff_t),
                      reads=[(gx, ff_t * b), (ux, ff_t * b)],
                      writes=[(hx, ff_t * b)], layer=li)
                self._linear(hx, o[f"l{li}.w_down"], mpart, ff_t, d_t,
                             layer=li, in_rows=ff_t * b,
                             w_rows=ff_t * d_t * w)
            g.add(TaskType.ALLREDUCE, (mpart, d_t),
                  reads=[(mpart, d_t * b)],
                  writes=[(mpart, d_t * b),
                          (self.ar_ws_off, self.n * ar_max_tiles * b)],
                  layer=li)
            g.add(TaskType.ADD, (x1, mpart, x2, d_t),
                  reads=[(x1, d_t * b), (mpart, d_t * b)],
                  writes=[(x2, d_t * b)], layer=li)
            x_off = x2

        out_off = self._alloc_act("x_final", d_t)
        g.add(TaskType.RMSNORM, (x_off, o["ln_f"], out_off, d_t),
              reads=[(x_off, d_t * b), (o["ln_f"], d_t)],
              writes=[(out_off, d_t * b)])
        self.out_off = out_off
        # LM head inside the kernel: logits over this rank's vocab shard.
        logits_off = self._alloc("logits", self.vloc_tiles * b,
                                 kind="io")
        self._linear(out_off, o["lm_head_T"], logits_off, d_t,
                     self.vloc_tiles, layer=-1, in_rows=d_t * b,
                     w_rows=d_t * self.vloc_tiles * w)
        self.logits_off = logits_off
        self.arena_rows = self.schema.rows
        self.schema.check_disjoint()

        # -------- native schedule --------
        # The kernel's allreduce body substitutes the STATIC
        # ar_max_tiles for the (traced) tiles descriptor arg — enforce
        # the contract here so a future task recording a narrower
        # collective fails loudly at build time, not by reducing
        # garbage tiles on device.
        for t in g.tasks:
            if (t.task_type == TaskType.ALLREDUCE
                    and t.args[1] != self.ar_max_tiles):
                raise ValueError(
                    f"ALLREDUCE task {t.task_id} moves {t.args[1]} "
                    f"tiles but the kernel body is specialized to "
                    f"ar_max_tiles={self.ar_max_tiles}")
        src, dst = g.edges()
        # Collectives pin to core 0: the SPMD comm order must match
        # across chips, and the ICI semaphores live on one core.
        pin = np.array(
            [0 if t.task_type == TaskType.ALLREDUCE else -1
             for t in g.tasks], np.int32)
        cost = np.array([self._task_cost(t) for t in g.tasks], np.int32)
        # Prune once so the static packing, the dynamic claim order,
        # and both timed simulators all see the same edge set.
        if len(src):
            psrc, pdst = prune_deps(len(g.tasks), src, dst)
        else:
            psrc = pdst = np.zeros(0, np.int32)
        self._pruned_edges = (psrc, pdst)
        self._pin, self._cost = pin, cost
        if self.schedule == "dynamic":
            self._schedule_dynamic(psrc, pdst, pin, cost)
        else:
            self._schedule_static(psrc, pdst, pin, cost)

    def reprioritize(self, expert_load) -> None:
        """Recompute the DYNAMIC claim order under a fresh per-expert
        load vector (graph.comm_priority ``expert_load``) — the
        between-steps hot-expert rebalance hook. Host-only: the graph,
        arena, and task bodies are untouched; only the claim tables and
        scoreboard edge plan are re-emitted. The engine must rebuild
        its jitted step so the new tables take effect
        (:meth:`MegaKernelEngine.set_expert_load` does both)."""
        if self.schedule != "dynamic":
            raise ValueError(
                "reprioritize() adjusts the dynamic claim order; this "
                f"builder runs schedule={self.schedule!r}")
        self.expert_load = (list(expert_load)
                            if expert_load is not None else None)
        psrc, pdst = self._pruned_edges
        self._schedule_dynamic(psrc, pdst, self._pin, self._cost)

    def _schedule_static(self, src, dst, pin, cost):
        """Precomputed per-core slot lists (round_robin / zig_zag /
        cost_lpt) with merged-order NOOP padding — the original static
        scoreboard."""
        g = self.graph
        sched = schedule_mc(len(g.tasks), src, dst,
                            num_cores=self.num_cores,
                            strategy=self.strategy, task_cost=cost,
                            pin_core=pin, dep_opt=False)
        self.sched = sched
        queue = sched["queue"]                     # (Q, C) ids or -1
        self.qlen = queue.shape[0]
        self.n_edges = sched["n_edges"]
        sim = simulate_static(len(g.tasks), src, dst, queue,
                              task_cost=cost)
        self.idle_units = sim["idle_units"]
        self.makespan = sim["makespan"]
        # Static mode runs no claim protocol; keep the bucket tables
        # at their 1-element placeholders (uniform kernel signature).
        self.n_buckets = 1
        self.bucket_claims = np.zeros(1, np.int32)
        self.claim_bucket = np.zeros(queue.size, np.int32)
        self._emit_slot_tables(queue.reshape(-1), queue.shape, sched)

    def _schedule_dynamic(self, src, dst, pin, cost):
        """Dynamic scoreboard schedule: ONE comm-priority-ordered claim
        list (scheduler.schedule_dyn) the kernel pops via the claim
        counter in the scoreboard workspace — the TPU analogue of the
        reference's in-kernel runtime scheduler (model_builder.py:89,
        124: SMs claiming off an atomic queue head). No merged-order
        padding: the claim order is topological, so idle (NOOP) slots
        shrink to pinning holes + tail round-up."""
        g = self.graph
        prio, bkt, n_buckets = comm_priority(
            g.tasks, n_ranks=self.n, task_cost=cost,
            expert_load=self.expert_load)
        dyn = schedule_dyn(len(g.tasks), src, dst,
                           num_cores=self.num_cores, priority=prio,
                           bucket=bkt, task_cost=cost, pin_core=pin,
                           dep_opt=False)
        self.sched = dyn
        C = self.num_cores
        n_claims = dyn["n_claims"]
        self.n_claims = n_claims
        self.qlen = _cdiv(max(n_claims, 1), C)
        self.n_edges = dyn["n_edges"]
        self.idle_units = dyn["idle_units"]
        self.makespan = dyn["makespan"]
        claims = np.full(self.qlen * C, -1, np.int32)
        claims[:n_claims] = dyn["claim_order"]
        self.claims = claims.reshape(self.qlen, C)
        # Per-claim bucket (holes/tail count against bucket 0) and the
        # per-bucket claim totals the last slot drains the claim
        # semaphores by. EVERY slot signals exactly one bucket, so the
        # totals sum to qlen * C.
        self.n_buckets = n_buckets
        bkt_arr = np.asarray(bkt, np.int32)
        self.claim_bucket = np.where(claims >= 0, bkt_arr[claims], 0
                                     ).astype(np.int32)
        self.bucket_claims = np.bincount(
            self.claim_bucket, minlength=n_buckets).astype(np.int32)
        self._emit_slot_tables(claims, self.claims.shape, dyn)

    def _emit_slot_tables(self, qc, shape, sched):
        """Flat slot list (static merged queue or dynamic claim order)
        → the prefetched type/arg/wait/signal tables."""
        g = self.graph
        noop_args = [0] * ARGS_MAX
        self.task_types = np.array(
            [g.tasks[t].task_type if t >= 0 else int(TaskType.NOOP)
             for t in qc], np.int32).reshape(shape)
        # Static work units per queue slot — the progress-counter →
        # time model's design row (slot_durations()).
        self.slot_units = np.array(
            [self._task_units(g.tasks[t]) if t >= 0 else 0
             for t in qc], np.int64).reshape(shape)
        self.task_args = np.array(
            [g.tasks[t].encoded_args() if t >= 0 else noop_args
             for t in qc], np.int32).reshape(*shape, ARGS_MAX)
        self._used_types = {int(v) for v in np.unique(self.task_types)}
        # Per-slot wait/signal tables (edge-semaphore scoreboard).
        wtab, stab = [], []
        wedges, sedges, scores_ = [], [], []
        for t in qc:
            if t < 0:
                wtab.append((0, 0))
                stab.append((0, 0))
                continue
            ws, wc = sched["wait_start"][t], sched["wait_count"][t]
            ss, sc = sched["sig_start"][t], sched["sig_count"][t]
            wtab.append((len(wedges), wc))
            wedges.extend(sched["wait_edges"][ws:ws + wc])
            stab.append((len(sedges), sc))
            sedges.extend(sched["sig_edges"][ss:ss + sc])
            scores_.extend(sched["sig_cores"][ss:ss + sc])
        self.wait_tab = np.array(wtab, np.int32).reshape(*shape, 2)
        self.sig_tab = np.array(stab, np.int32).reshape(*shape, 2)
        self.wait_edges = np.array(wedges or [0], np.int32)
        self.sig_edges = np.array(sedges or [0], np.int32)
        self.sig_cores = np.array(scores_ or [0], np.int32)

    def noop_slots(self) -> int:
        """Idle scoreboard steps in the schedule: grid slots that
        execute no task (static merged-order padding, or dynamic
        pinning holes + tail round-up). The interpret-mode step counter
        the static-vs-dynamic comparison is scored on."""
        return int((self.task_types == int(TaskType.NOOP)).sum())

    def _task_cost(self, t) -> int:
        """Cost estimate feeding the cost_lpt strategy: static work
        units, optionally reweighted by a measured ``cost_table``."""
        units = self._task_units(t)
        if self.cost_table is None:
            return units
        w = self.cost_table.get(int(t.task_type), 1.0)
        return max(int(round(units * w)), 0)

    def task_unit_counts(self) -> dict:
        """Total static work units per task type over the whole graph —
        the design matrix row for :func:`calibrate_cost_table`."""
        counts = {}
        for t in self.graph.tasks:
            k = int(t.task_type)
            counts[k] = counts.get(k, 0) + self._task_units(t)
        return counts

    def profile_unit_counts(self, prof) -> dict:
        """Unit counts per task type from a warmup step's EXECUTED slot
        records (``profile=True`` output) — the profile-guided
        counterpart of :meth:`task_unit_counts`. Where the static count
        trusts the graph, this counts what the scoreboard actually ran
        (slot tags paired with the schedule's per-slot units), so a
        ``(profile_unit_counts(prof), wall_seconds)`` observation feeds
        :func:`calibrate_cost_table` with measured executions; the
        resulting ``cost_table`` re-schedules BOTH ``cost_lpt`` and the
        dynamic claim order on step 2+."""
        tags = np.asarray(prof)[:, 0].reshape(-1)
        units = np.asarray(self.slot_units).reshape(-1)
        counts = {}
        for tag, u in zip(tags.tolist(), units.tolist()):
            k = int(tag) - 1         # tags are task_type + 1
            if tag <= 0 or k == int(TaskType.NOOP):
                continue
            counts[k] = counts.get(k, 0) + int(u)
        return counts

    def _task_units(self, t) -> int:
        """Static work-unit estimate per task (pre-reweighting)."""
        if t.task_type == TaskType.LINEAR:
            return int(t.args[3])          # k_tiles MXU passes
        if t.task_type == TaskType.ATTN_DECODE:
            return 4 * self.d_tiles
        if t.task_type == TaskType.ATTN_PREFILL:
            # S-row blocked flash attention: the prefill heavyweight.
            return 8 * self.d_tiles * max(self.seq // 8, 1)
        if t.task_type == TaskType.ATTN_QBLOCK:
            # K per-row online-softmax streams per slot.
            return 4 * self.d_tiles * self.seq
        if t.task_type == TaskType.ATTN_CHUNK:
            # C per-row online-softmax streams — the chunk heavyweight
            # (same per-row stream as the Q-block verify body).
            return 4 * self.d_tiles * self.seq
        if t.task_type == TaskType.WRITE_KV_PREFILL:
            return 2 * max(self.seq // 8, 1)
        if t.task_type == TaskType.WRITE_KV_QBLOCK:
            return 2 * self.seq
        if t.task_type == TaskType.WRITE_KV_CHUNK:
            return 2 * self.seq
        if t.task_type == TaskType.ALLREDUCE:
            return 2 * int(t.args[1])
        if t.task_type == TaskType.WEIGHTED_ADD:
            return int(t.args[4])          # tiles copied + fused mul-add
        if t.task_type == TaskType.GDN_DECODE:
            # The body loops every (batch, local-head) pair.
            return 2 * self.batch * self.gdn_h_loc
        return 1

    # ---------------- arena packing ------------------------------------
    def _tile_weight(self, wmat, k_tiles, n_tiles):
        w = self.w
        kpad, npad = k_tiles * w, n_tiles * w
        wm = jnp.zeros((kpad, npad), jnp.float32).at[
            :wmat.shape[0], :wmat.shape[1]].set(wmat.astype(jnp.float32))
        return wm.reshape(k_tiles, w, n_tiles, w).transpose(
            0, 2, 1, 3).reshape(k_tiles * n_tiles * w, w)

    def _pad_vec(self, vec, tiles):
        w = self.w
        out = jnp.zeros((tiles * w,), jnp.float32).at[
            :vec.shape[0]].set(vec.astype(jnp.float32))
        return out.reshape(tiles, w)

    def pack_arena(self, params) -> jax.Array:
        """Per-shard: assemble the weight region + zeroed activation
        region into the (arena_rows, w) arena (traced; run inside
        shard_map so ``params`` are the local shards)."""
        cfg = self.cfg
        d_t, hq_t, kv_t, ff_t = (self.d_tiles, self.hq_tiles,
                                 self.kv_tiles, self.ff_tiles)
        parts = []
        for li in range(cfg.num_hidden_layers):
            lp = params["layers"][li]
            mixer_key = "mixer" if self.hybrid else "attn"
            mx = lp[mixer_key]
            if self.hybrid and self.layer_kinds[li][0] == "gdn":
                gq_t, gv_t = self.gq_tiles, self.gv_tiles
                me = jax.lax.axis_index(self.axis)
                h_loc = self.gdn_h_loc
                # Column-parallel gdn projections: local shards already
                # hold this rank's head columns; g_bias needs slicing
                # (replicated param, like the embedding below).
                parts.append(self._tile_weight(mx["wq"], d_t, gq_t))
                parts.append(self._tile_weight(mx["wk"], d_t, gq_t))
                parts.append(self._tile_weight(mx["wv"], d_t, gv_t))
                parts.append(self._tile_weight(mx["wg"], d_t, 1))
                parts.append(self._tile_weight(mx["wb"], d_t, 1))
                bias = jax.lax.dynamic_slice_in_dim(
                    mx["g_bias"], me * h_loc, h_loc, 0)
                parts.append(self._pad_vec(bias, 1))
                parts.append(self._tile_weight(mx["wo"], gv_t, d_t))
            else:
                parts.append(self._tile_weight(mx["wq"], d_t, hq_t))
                parts.append(self._tile_weight(mx["wk"], d_t, kv_t))
                parts.append(self._tile_weight(mx["wv"], d_t, kv_t))
                parts.append(self._tile_weight(mx["wo"], hq_t, d_t))
            if self.moe:
                mp = lp["moe"]
                parts.append(self._tile_weight(mp["router"], d_t, 1))
                for e in range(cfg.num_experts):
                    parts.append(self._tile_weight(
                        mp["w_gate"][e], d_t, self.ffe_tiles))
                    parts.append(self._tile_weight(
                        mp["w_up"][e], d_t, self.ffe_tiles))
                    parts.append(self._tile_weight(
                        mp["w_down"][e], self.ffe_tiles, d_t))
            else:
                parts.append(self._tile_weight(lp["mlp"]["w_gate"],
                                               d_t, ff_t))
                parts.append(self._tile_weight(lp["mlp"]["w_up"],
                                               d_t, ff_t))
                parts.append(self._tile_weight(lp["mlp"]["w_down"],
                                               ff_t, d_t))
            parts.append(self._pad_vec(lp["ln_attn"], d_t))
            parts.append(self._pad_vec(lp["ln_mlp"], d_t))
            if not (self.hybrid and self.layer_kinds[li][0] == "gdn"):
                parts.append(self._pad_vec(mx["q_norm"], 1))
                parts.append(self._pad_vec(mx["k_norm"], 1))
        parts.append(self._pad_vec(params["ln_f"], d_t))
        # Embedding table shard: this rank's vocab/n rows, laid out as
        # (vocab_loc * d_tiles, w). Params keep embed replicated
        # (dense.param_specs), so slice the local shard here.
        me = jax.lax.axis_index(self.axis)
        emb = jax.lax.dynamic_slice_in_dim(
            params["embed"].astype(jnp.float32), me * self.vocab_loc,
            self.vocab_loc, axis=0)
        vpad = jnp.zeros((self.vocab_loc, d_t * self.w), jnp.float32
                         ).at[:, :cfg.hidden_size].set(emb)
        parts.append(vpad.reshape(self.vocab_loc * d_t, self.w))
        # LM head transposed: x @ lm_head.T with lm_head (vocab_loc, d).
        parts.append(self._tile_weight(params["lm_head"].T, d_t,
                                       self.vloc_tiles))
        weights = jnp.concatenate(parts, axis=0)
        pad = jnp.zeros((self.arena_rows - weights.shape[0], self.w),
                        jnp.float32)
        return jnp.concatenate([weights, pad], axis=0)

    # ---------------- the megakernel -----------------------------------
    def kernel_config(self) -> K.KernelConfig:
        return K.KernelConfig(
            w=self.w, batch=self.batch, h_loc=self.h_loc,
            kv_loc=self.kv_loc, hd=self.cfg.head_dim,
            rope_theta=self.cfg.rope_theta, rms_eps=self.cfg.rms_norm_eps,
            n_ranks=self.n, axis=self.axis, mesh=self.mctx,
            ar_ws_off=self.ar_ws_off, ar_max_tiles=self.ar_max_tiles,
            seq=self.seq, paged=self.paged, page=self.page,
            p_max=self.p_max,
            moe_topk=(self.cfg.num_experts_per_tok if self.moe else 0),
            moe_norm=self.cfg.norm_topk_prob,
            gdn_h_loc=(self.gdn_h_loc if self.hybrid else 0),
            gdn_dk=self.cfg.gdn_head_dim_k,
            gdn_dv=self.cfg.gdn_head_dim_v,
            kv_quant=self.kv_quant,
            qmax=self.kv_qmax,
            qblock=self.qblock,
            chunk=self.chunk)

    def _n_state(self) -> int:
        """Aliased state operands: arena + K/V pools, plus the scale
        tables (quantized) and the GDN state buffer (hybrid)."""
        return (3 + (2 if self.kv_quant else 0)
                + (1 if self.hybrid else 0))

    def _kernel(self, types_s, args_s, wait_tab_s, sig_tab_s,
                wait_edges_s, sig_edges_s, bucket_s, bsizes_s, len_s,
                tok_s, tbl_s, *tail):
        # Inputs are aliased onto the outputs — skip the input refs
        # and unpack the output half (arena, K/V pools, [scales],
        # [states]), then prof, scratches, semaphores.
        tail = tail[self._n_state():]
        arena, k_cache, v_cache = tail[:3]
        tail = tail[3:]
        if self.kv_quant:
            k_scale, v_scale = tail[:2]
            tail = tail[2:]
        else:
            k_scale = v_scale = None
        if self.hybrid:
            states, tail = tail[0], tail[1:]
        else:
            states = None
        if self.profile:
            prof_ref, tail = tail[0], tail[1:]
        else:
            prof_ref = None
        (va, vb, vc, vw, acc, vhd, vkt, vsq) = tail[:8]
        tail = tail[8:]
        if self.hybrid:
            vrow, vrow2, vS = tail[:3]
            tail = tail[3:]
        else:
            vrow = vrow2 = vS = None
        if self.kv_quant:
            vqt, vqd, vscl = tail[:3]
            tail = tail[3:]
        else:
            vqt = vqd = vscl = None
        claim_cnt, claim_sem, edge_sem, send_sem, recv_sem = tail
        cfg = self.kernel_config()
        q = pl.program_id(0)
        c = pl.program_id(1)
        C = self.num_cores
        if self.schedule == "dynamic":
            # Device-side task claiming: no slot carries a precomputed
            # task binding — each grid slot pops the next entry off the
            # claim counter in the scoreboard workspace and executes
            # whatever the counter hands it (reference: the runtime
            # scheduler's atomic queue head, model_builder.py:89,124).
            # Under the sequential merged order the claim sequence is
            # deterministic (slot (q, c) draws claim q*C + c), which is
            # what keeps the SPMD collective order identical across
            # chips; a concurrent megacore claim draws the same values
            # through fetch-add order on the per-core subsequences.
            @pl.when(jnp.logical_and(q == 0, c == 0))
            def _():
                claim_cnt[0] = 0

            slot = claim_cnt[0]
            claim_cnt[0] = slot + 1
            # Per-priority-bucket claim accounting, visible in the
            # scoreboard workspace as semaphore counts (the wait/signal
            # tables' sibling): every slot signals exactly one bucket.
            pltpu.semaphore_signal(claim_sem.at[bucket_s[slot]], 1)
        else:
            slot = q * C + c
        ttype = types_s[slot]
        args = tuple(args_s[j, slot] for j in range(ARGS_MAX))
        refs = {"arena": arena, "k_cache": k_cache, "v_cache": v_cache,
                "va": va, "vb": vb, "vc": vc, "vw": vw, "acc": acc,
                "vhd": vhd, "vkt": vkt, "vsq": vsq, "send_sem": send_sem,
                "recv_sem": recv_sem, "tbl_s": tbl_s, "states": states,
                "vrow": vrow, "vrow2": vrow2, "vS": vS,
                "k_scale": k_scale, "v_scale": v_scale,
                "vqt": vqt, "vqd": vqd, "vscl": vscl}

        # Progress tracing (TRITON_DIST_TPU_TRACE_PROGRESS=1): one line
        # per queue slot as the scoreboard advances. In interpret mode
        # this is the only progress signal that survives a wedged
        # kernel — the resilience harness parses the last line to name
        # the slot a deadlocked schedule stopped at. Dynamic mode
        # reports the CLAIM COUNTER value, not a static queue position:
        # feed it to scheduler.describe_claim to name the claimed task.
        if self.trace_progress:
            if self.schedule == "dynamic":
                pl.debug_print("TDT-PROGRESS claim={} task_type={}",
                               slot, ttype)
            else:
                pl.debug_print("TDT-PROGRESS q={} c={}", q, c)

        # Scoreboard waits: block until every cross-core predecessor's
        # edge semaphore has been signalled (reference
        # scoreboard_wait_deps).
        wstart, wcount = wait_tab_s[0, slot], wait_tab_s[1, slot]

        def wait_step(k, _):
            pltpu.semaphore_wait(edge_sem.at[wait_edges_s[wstart + k]], 1)
            return 0

        jax.lax.fori_loop(0, wcount, wait_step, 0)

        branches = [
            lambda: K.rmsnorm_body(cfg, args, refs),
            lambda: K.linear_body(cfg, args, refs),
            lambda: K.add_body(cfg, args, refs),
            lambda: K.silu_mul_body(cfg, args, refs),
            lambda: K.attn_decode_body(cfg, args, refs, len_s),
            lambda: K.write_kv_body(cfg, args, refs, len_s),
            lambda: K.allreduce_body(cfg, args, refs),
            lambda: K.gather_body(cfg, args, refs, tok_s),
            lambda: None,   # NOOP (queue padding)
            lambda: K.write_kv_prefill_body(cfg, args, refs, len_s),
            lambda: K.attn_prefill_body(cfg, args, refs, len_s),
            lambda: K.moe_weights_body(cfg, args, refs),
            lambda: K.weighted_add_body(cfg, args, refs),
            (lambda: K.gdn_decode_body(cfg, args, refs))
            if self.hybrid else (lambda: None),
            lambda: K.attn_qblock_body(cfg, args, refs, len_s),
            lambda: K.write_kv_qblock_body(cfg, args, refs, len_s),
            lambda: K.attn_chunk_body(cfg, args, refs, len_s),
            lambda: K.write_kv_chunk_body(cfg, args, refs, len_s),
        ]
        # lax.switch traces EVERY branch, scheduled or not — and a body
        # whose geometry does not fit this build (the decode cache
        # bodies under a prefill-shaped cfg where batch counts B*S
        # rows) fails at trace time. Stub types absent from the
        # schedule; the queue can never select them.
        used = self._used_types
        branches = [br if i in used else (lambda: None)
                    for i, br in enumerate(branches)]
        jax.lax.switch(ttype, branches)
        if prof_ref is not None:
            # tag = task_type + 1: the Perfetto exporter treats a
            # (0, 0) row as an unused slot, and RMSNORM is type 0.
            prof_ref[...] = jnp.stack(
                [ttype + 1, args[0]]).astype(jnp.int32).reshape(1, 2)

        # Mark completion: signal each outgoing cross-core edge. (A
        # true CORE_PARALLEL execution additionally needs the signal
        # targeted at the consumer core — sig_cores in the schedule
        # carries that mapping — but no execution environment available
        # here runs that variant, so the kernel does not consume it.)
        sstart, scount = sig_tab_s[0, slot], sig_tab_s[1, slot]

        # Fault hook: a drop_edge plan suppresses one edge's completion
        # signal — the canonical scoreboard failure (a consumer's wait
        # then never satisfies; a blocking backend deadlocks, which the
        # resilience harness must detect and attribute).
        from triton_dist_tpu.resilience import faults

        dropped_edge = faults.edge_drop("megakernel")

        def sig_step(k, _):
            edge = sig_edges_s[sstart + k]
            if dropped_edge is None:
                pltpu.semaphore_signal(edge_sem.at[edge], 1)
            else:
                @pl.when(edge != dropped_edge)
                def _():
                    pltpu.semaphore_signal(edge_sem.at[edge], 1)
            return 0

        jax.lax.fori_loop(0, scount, sig_step, 0)

        if self.schedule == "dynamic":
            # Drain the per-bucket claim semaphores once every claim
            # has been accounted (a TPU kernel must exit with zeroed
            # semaphores). The final slot waits for each bucket's full
            # claim total — by then all qlen*C signals have been (or,
            # concurrently, will be) raised.
            @pl.when(jnp.logical_and(q == self.qlen - 1, c == C - 1))
            def _():
                def drain(k, _):
                    pltpu.semaphore_wait(claim_sem.at[k], bsizes_s[k])
                    return 0

                jax.lax.fori_loop(0, self.n_buckets, drain, 0)

    def step_fn(self):
        """Per-shard decode step:
        (arena, k_cache, v_cache, token_ids (B,), cache_len)
        → (logits (B, vocab_loc), arena, k_cache, v_cache)
        [+ prof (qlen·cores, 2) as a 5th element when ``profile=True``:
        one (task_type+1, arg0) row per queue slot].
        Embedding, the transformer stack, and the vocab-sharded LM head
        all run inside the kernel. Call inside shard_map; donate arena +
        caches at jit level."""
        b, w, d_t = self.batch, self.w, self.d_tiles
        cfg = self.cfg
        # Slot tables are prefetched FLAT (slot-major): static slots
        # index them at q*C + c, dynamic slots at the claim-counter
        # value — one kernel, two binding rules. The slot index is the
        # MINOR dim of the 2-D tables: SMEM pads the minor dim to 128
        # words, so (n_slots, 8) costs n_slots*512 B where (8, n_slots)
        # costs n_slots*32 B — at model widths the slot-major form
        # overflows the 1 MB of SMEM with one layer.
        n_slots = self.qlen * self.num_cores
        types = jnp.asarray(self.task_types).reshape(n_slots)
        args = jnp.asarray(self.task_args).reshape(n_slots, ARGS_MAX).T
        wait_tab = jnp.asarray(self.wait_tab).reshape(n_slots, 2).T
        sig_tab = jnp.asarray(self.sig_tab).reshape(n_slots, 2).T
        wait_edges = jnp.asarray(self.wait_edges)
        sig_edges = jnp.asarray(self.sig_edges)
        bucket = jnp.asarray(self.claim_bucket).reshape(-1)
        bsizes = jnp.asarray(self.bucket_claims)

        def step(arena, k_cache, v_cache, token_ids, cache_len,
                 block_table=None, states=None, k_scale=None,
                 v_scale=None):
            if self.hybrid and states is None:
                raise ValueError("hybrid megakernel step needs the GDN "
                                 "states buffer")
            if self.kv_quant and (k_scale is None or v_scale is None):
                raise ValueError("quantized megakernel step needs the "
                                 "k_scale/v_scale tables")
            # cache_len: scalar (uniform batch, the classic form) or a
            # (batch,) vector of PER-ROW positions — the live-slot
            # serving form (qblock builds: per-ROW verification
            # positions, < 0 masks a row). Either way the kernel sees
            # a (batch,) SMEM vector; write_kv/attn_decode index it
            # per row, the prefill bodies read the shared base at [0].
            len_arr = jnp.broadcast_to(
                jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
            tok_arr = jnp.asarray(token_ids, jnp.int32)
            if block_table is None:
                # Dense mode: a 1-element placeholder keeps the prefetch
                # slot (and the traced signature) uniform.
                block_table = jnp.zeros((1,), jnp.int32)
            tbl_arr = jnp.asarray(block_table, jnp.int32).reshape(-1)

            C = self.num_cores
            n_big = self._n_state()
            out_specs = [pl.BlockSpec(memory_space=pl.ANY)] * n_big
            if self.profile:
                # One (task_type, arg0) row per executed queue slot,
                # written via the regular output pipeline.
                out_specs.append(pl.BlockSpec(
                    (1, 2), lambda q, c, *_: (q * C + c, 0),
                    memory_space=pltpu.VMEM))
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=11,
                grid=(self.qlen, self.num_cores),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_big,
                out_specs=out_specs,
                scratch_shapes=[
                    pltpu.VMEM((b, w), jnp.float32),       # va
                    pltpu.VMEM((b, w), jnp.float32),       # vb
                    pltpu.VMEM((b, w), jnp.float32),       # vc
                    pltpu.VMEM((w, w), jnp.float32),       # vw
                    pltpu.VMEM((b, w), jnp.float32),       # acc
                    pltpu.VMEM((b, self.cfg.head_dim), jnp.float32),
                    pltpu.VMEM((self.t_tile, self.cfg.head_dim),
                               jnp.float32),                # vkt
                    pltpu.VMEM((self.seq, self.cfg.head_dim),
                               jnp.float32),                # vsq
                ] + ([
                    pltpu.VMEM((1, w), jnp.float32),        # vrow
                    pltpu.VMEM((1, w), jnp.float32),        # vrow2
                    pltpu.VMEM((self.cfg.gdn_head_dim_k,
                                self.cfg.gdn_head_dim_v),
                               jnp.float32),                # vS
                ] if self.hybrid else []) + ([
                    pltpu.VMEM((self.t_tile, self.cfg.head_dim),
                               k_cache.dtype),              # vqt
                    pltpu.VMEM((1, self.cfg.head_dim),
                               k_cache.dtype),              # vqd
                    pltpu.VMEM((1, 1), jnp.float32),        # vscl
                ] if self.kv_quant else []) + [
                    pltpu.SMEM((1,), jnp.int32),            # claim_cnt
                    pltpu.SemaphoreType.REGULAR(
                        (max(self.n_buckets, 1),)),         # claim_sem
                    pltpu.SemaphoreType.REGULAR(
                        (max(self.n_edges, 1),)),           # scoreboard
                    pltpu.SemaphoreType.DMA((max(self.n - 1, 1),)),
                    pltpu.SemaphoreType.DMA(()),
                ],
            )
            # Execution model: the grid walks the merged (q-major)
            # interleave of the per-core queues, with every cross-core
            # dependency enforced by explicit edge-semaphore waits and
            # completion signals — the scoreboard protocol, fully
            # active and testable on any part. The scheduler's padding
            # constraint (task merged-index > all preds') makes this
            # order deadlock-free even when executed sequentially. On a
            # megacore part the core dim is hoisted leading and marked
            # CORE_PARALLEL so each TensorCore walks its own queue
            # concurrently; neither this chip (single TensorCore) nor
            # the CPU interpreter (randomized 'parallel' core maps that
            # cannot honor a static cross-core signal plan) can execute
            # that variant, so it is not wired up here rather than
            # pretending coverage we cannot have; the
            # schedule's sig_cores mapping is ready for it.
            out_shape = [
                jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
            ]
            if self.kv_quant:
                out_shape.append(jax.ShapeDtypeStruct(
                    k_scale.shape, k_scale.dtype))
                out_shape.append(jax.ShapeDtypeStruct(
                    v_scale.shape, v_scale.dtype))
            if self.hybrid:
                out_shape.append(jax.ShapeDtypeStruct(
                    states.shape, states.dtype))
            if self.profile:
                out_shape.append(jax.ShapeDtypeStruct(
                    (self.qlen * self.num_cores, 2), jnp.int32))
            outs_fn = core_call(
                self._kernel,
                grid_spec=grid_spec,
                out_shape=tuple(out_shape),
                input_output_aliases={
                    11 + i: i for i in range(n_big)},
                # A rankless megakernel traces no barrier: Mosaic
                # rejects a collective_id without one.
                compiler_params=(comm_compiler_params() if self.n > 1
                                 else pltpu.CompilerParams(
                                     has_side_effects=True)),
            )
            operands = [types, args, wait_tab, sig_tab, wait_edges,
                        sig_edges, bucket, bsizes, len_arr, tok_arr,
                        tbl_arr, arena, k_cache, v_cache]
            if self.kv_quant:
                operands += [k_scale, v_scale]
            if self.hybrid:
                operands.append(states)
            outs = list(outs_fn(*operands))
            arena, k_cache, v_cache = outs[:3]
            outs = outs[3:]
            if self.kv_quant:
                k_scale, v_scale = outs[:2]
                outs = outs[2:]
            if self.hybrid:
                states, outs = outs[0], outs[1:]
            prof = outs[0] if self.profile else None

            lt = self.vloc_tiles
            out_rows = jax.lax.dynamic_slice(
                arena, (self.logits_off, 0), (lt * b, w))
            logits = out_rows.reshape(lt, b, w).transpose(1, 0, 2
                                                          ).reshape(b, lt * w)
            ret = [logits[:, :self.vocab_loc], arena, k_cache, v_cache]
            if self.kv_quant:
                ret += [k_scale, v_scale]
            if self.hybrid:
                ret.append(states)
            if self.profile:
                ret.append(prof)
            return tuple(ret)

        return step

    def prof_tracks(self, prof):
        """Reshape a step's profile output ((qlen·num_cores, 2) rows,
        slot-major) into per-core tracks (num_cores, qlen, 2) — the
        exporter's buffer layout, aligned with
        :meth:`slot_durations`."""
        p = np.asarray(prof).reshape(self.qlen, self.num_cores, 2)
        return np.transpose(p, (1, 0, 2))

    def slot_durations(self, cost_table: dict, unit_s: float):
        """Calibrated progress-counter→time model: per-queue-slot
        durations in seconds, ``units * weight[task_type] * unit_s``
        with weights from a MEASURED :func:`calibrate_cost_table` and
        ``unit_s`` the fit's base unit time. Feed to
        ``profiler.export_to_perfetto_trace(prof_tracks(prof),
        slot_durations=...)`` — the export then carries spans labeled
        ``calibrated`` (model times), never passing reconstructed order
        off as measurement. Returns (num_cores, qlen), matching
        :meth:`prof_tracks`."""
        w = np.array([cost_table.get(int(t), 1.0)
                      for t in self.task_types.reshape(-1)],
                     np.float64).reshape(self.task_types.shape)
        return (self.slot_units * w * unit_s).T

    def core_activity(self, prof) -> "np.ndarray":
        """Per-core busy fraction from a profile output: share of queue
        slots that executed a real task (non-NOOP) — the reference
        megakernel's SM-activity metric (model_builder.py:164-190)."""
        t = np.asarray(prof)[:, 0].reshape(self.qlen, self.num_cores)
        return (t != int(TaskType.NOOP) + 1).mean(axis=0)
