"""Expert-parallel MoE layer.

Reference: ``layers/nvidia/ep_moe.py:65`` ``EP_MoE`` (+ ``EPAll2AllLayer``
``ep_a2a_layer.py:220`` and the low-latency variant): router → dispatch
all-to-all → grouped expert MLP → combine all-to-all.

Decode-path transports (:func:`fwd_decode`): the serving decode batch is
replicated across the ep axis, and the ``transport`` knob picks how its
tokens reach their experts —

- ``"ar"`` (legacy default): no dispatch at all — every rank runs its
  local expert shard over the whole (tiny) batch and one psum completes
  the combine.
- ``"ragged"``: the generic exact-splits :func:`~triton_dist_tpu.ops
  .ep_a2a.ep_dispatch`/``ep_combine`` round-trip (counts exchange +
  ragged transport).
- ``"ll"``: the low-latency path — a count-free, wire-quantized
  :func:`~triton_dist_tpu.ops.low_latency.ll_a2a` exchange statically
  sized at B·K slots per peer (the decode batch's fixed assignment
  count), the reference's ``fast_all_to_all``/``dispatch_kernel_v2``
  shape. Supports hot-expert :func:`replica <init_replicas>` rerouting.
- ``"ll2d"``: the hierarchical 2-hop ll path for (DCN, ICI) 2-axis
  meshes (:class:`~triton_dist_tpu.ops.ep_a2a.EP2DContext`): same
  count-free fixed-slot protocol, but the exchange rides
  :func:`~triton_dist_tpu.ops.ll_a2a_2d.ll_a2a_2d` — an intra-node ICI
  shuffle followed by ONE aggregated slab put per peer node over DCN,
  shrinking DCN puts by the ICI group factor.
- ``"auto"``: the :mod:`~triton_dist_tpu.tune`-persisted winner for
  this (mesh-hierarchy, batch, hidden, dtype) key
  (:func:`tune_transport`), else ``"ll"`` on a flat mesh / ``"ll2d"``
  on a hierarchical one — never a silent ``"ar"`` fallback.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.obs import scope
from triton_dist_tpu.ops.ep_a2a import (EPContext, EP2DContext,
                                        ep_dispatch, ep_combine)
from triton_dist_tpu.ops.ep_fused import EPFusedContext, ep_moe_fused
from triton_dist_tpu.ops.group_gemm import (grouped_mlp_tiles,
                                            grouped_relu2, grouped_swiglu,
                                            mlp_tiles, sort_by_expert,
                                            sort_pairs, tile_layout,
                                            window_group_sizes)

DECODE_TRANSPORTS = ("ar", "ragged", "ll", "ll2d", "auto")


def init(key, cfg, dtype=jnp.float32) -> Dict:
    """cfg needs: hidden_size, moe_intermediate_size, num_experts
    (+ shared_expert_intermediate_size for the qwen3_next-style
    always-on shared expert, 0 = none)."""
    kr, kg, ku, kd, ksg, ksu, ksd, kss = jax.random.split(key, 8)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    scale = d ** -0.5
    p = {
        "router": jax.random.normal(kr, (d, e), dtype) * scale,
        "w_gate": jax.random.normal(kg, (e, d, f), dtype) * scale,
        "w_up": jax.random.normal(ku, (e, d, f), dtype) * scale,
        "w_down": jax.random.normal(kd, (e, f, d), dtype) * (f ** -0.5),
    }
    fs = getattr(cfg, "shared_expert_intermediate_size", 0)
    if fs:
        # Shared expert (Qwen3NextSparseMoeBlock): a dense SwiGLU every
        # token takes, scaled by a sigmoid scalar gate, added to the
        # routed combine.
        p["w_shared_gate"] = jax.random.normal(ksg, (d, fs), dtype) * scale
        p["w_shared_up"] = jax.random.normal(ksu, (d, fs), dtype) * scale
        p["w_shared_down"] = jax.random.normal(
            ksd, (fs, d), dtype) * (fs ** -0.5)
        p["shared_gate"] = jax.random.normal(kss, (d,), dtype) * scale
    return p


def param_specs(axis: str = "ep", cfg=None) -> Dict:
    s = {
        "router": P(None, None),
        "w_gate": P(axis, None, None),  # experts sharded
        "w_up": P(axis, None, None),
        "w_down": P(axis, None, None),
    }
    if cfg is not None and getattr(cfg, "shared_expert_intermediate_size",
                                   0):
        # EP shards experts, not ffn dims: the dense shared expert is
        # replicated and applied to each rank's own tokens.
        s["w_shared_gate"] = P(None, None)
        s["w_shared_up"] = P(None, None)
        s["w_shared_down"] = P(None, None)
        s["shared_gate"] = P(None)
    return s


@scope("router")
def route(router_w, x, topk: int, *, norm_topk_prob: bool = True,
          scoring: str = "softmax", bias=None):
    """The experts a token goes to and their weights: ``(ids (T, topk)
    int32, weights (T, topk) float32)``.

    ``scoring="softmax"`` (Qwen3-MoE; reference ``models/qwen_moe.py``):
    softmax over experts then top-k, weights renormalized.
    ``"sigmoid"``: every expert scored on its own. ``bias`` (E,), a leaf
    of the layer, is added to the scores for the SELECTION alone; the
    weights are the chosen experts' scores without it."""
    # Full float32 products: on the TPU a float32 dot is otherwise one
    # bfloat16 pass, and near-ties among the k-th choices flip on it.
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring={scoring!r}: 'softmax' | 'sigmoid'")
    if bias is None:
        topk_w, topk_ids = jax.lax.top_k(scores, topk)
    else:
        topk_ids = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                 topk)[1]
        topk_w = jnp.take_along_axis(scores, topk_ids, axis=-1)
    if norm_topk_prob:
        topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    return topk_ids.astype(jnp.int32), topk_w


@scope("shared_expert")
def shared_expert_out(params, x):
    """The dense branch every token takes; None when the layer has
    none. A SwiGLU where the parameters hold ``w_shared_gate``; up,
    squared ReLU, down where they hold ``w_shared_up`` and
    ``w_shared_down`` alone. Sigmoid-gated where they hold a
    ``shared_gate`` vector (qwen3_next), plain where they do not (the
    latent-attention family). Under TP ffn-sharded weights the result
    is a PARTIAL sum (the caller's reduce completes it — the sigmoid
    gate uses the replicated ``shared_gate`` vector so every rank
    scales by the same factor); under replicated weights (EP) it is
    the full contribution."""
    if "w_shared_gate" not in params:
        if "w_shared_up" not in params:
            return None
        u = jnp.dot(x, params["w_shared_up"])
        act = jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(x.dtype)
        return jnp.dot(act, params["w_shared_down"],
                       preferred_element_type=jnp.float32)
    g = jnp.dot(x, params["w_shared_gate"])
    u = jnp.dot(x, params["w_shared_up"])
    act = (jax.nn.silu(g.astype(jnp.float32))
           * u.astype(jnp.float32)).astype(x.dtype)
    out = jnp.dot(act, params["w_shared_down"],
                  preferred_element_type=jnp.float32)
    if "shared_gate" not in params:
        return out
    gate = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                  params["shared_gate"]
                                  .astype(jnp.float32)))
    return out * gate[:, None]


# A pass of ``fwd_held`` has room for this many times the even share of
# the pairs (``T * topk * E_held / E_router``).
PASS_MARGIN = 1.25


def held_pass_rows(t: int, topk: int, n_held: int, n_router: int) -> int:
    """The rows one pass of :func:`fwd_held` lays out, from shapes
    alone: ``PASS_MARGIN`` times the even share of the ``t * topk``
    pairs that falls to ``n_held`` of ``n_router`` experts, rounded up
    to an odd number of 128-row tiles, and never more than every pair.
    A few rows, or a layer that holds every expert, is one pass over
    everything.

    Odd, because libtpu's ragged product takes its row tile from the
    largest power of two in its operand's rows (512 at most) and every
    group costs it a tile at least: at ~64 rows an expert a layer of
    the 2048+16-row program takes 4.49 ms with tiles of 128, 4.53 with
    256, 5.24 with 64 and 6.14 with 512 (PERF.md, PR 41)."""
    pairs = t * topk
    room = math.ceil(PASS_MARGIN * pairs * n_held / n_router)
    return min(pairs, (-(-room // 128) | 1) * 128)


def expert_store_width(f: int) -> int:
    """The width a held expert's matrices are STORED at: ``f`` rounded up
    to a whole number of 512 columns once it is 1,024 or more, the
    padding zeros (a zero column of the up-projection gives a zero
    activation under either form of expert, and meets a zero row of the
    down-projection: each sum gains zeros and nothing else).

    libtpu's ragged product takes its N and K tiles from the operand's
    size: at 2,688 = 21 x 128 columns, 128 groups, a product of 14,208
    rows takes 5.24 ms (N) and 4.20 (K); at 3,072 = 6 x 512, 2.67 and
    2.53, for a seventh more weights (PERF.md, PR 43). Narrow experts
    (the CPU presets) are stored as they are. :func:`fwd_held` takes no
    other width: a tree at 2,688 columns is refused there, not served
    on the slower tiles."""
    return f if f < 1024 else -(-f // 512) * 512


def pad_expert_width(w_up, w_down, *more_up):
    """``w_up (E, d, f)``, ``w_down (E, f, d)`` (and a gated form's
    ``w_gate`` in ``more_up``) zero-padded to :func:`expert_store_width`,
    for whoever makes a parameter tree (a model's ``init_params``, the
    benchmark's seeded weights). It is the MAKER that pads, leaf by leaf
    as it makes them: padding a whole tree where it enters ``Engine``
    would hold both copies of every expert at once (9.3 + 8.1 GB of
    the benchmark's period on a 16 GB chip)."""
    pad = expert_store_width(w_up.shape[-1]) - w_up.shape[-1]
    ups = tuple(jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
                for w in (w_up,) + more_up)
    return (ups[0], jnp.pad(w_down, ((0, 0), (0, pad), (0, 0)))) + ups[1:]


def experts_impl(rows: int, n_held: int, d: int, f: int, dtype) -> str:
    """What runs the held experts' MLP over a pass of ``rows`` sorted
    rows (:func:`held_pass_rows`) in :func:`fwd_held`: ``"kernel"``
    (:func:`~triton_dist_tpu.ops.group_gemm.grouped_mlp_tiles` over the
    expert-major layout of :func:`~triton_dist_tpu.ops.group_gemm
    .tile_layout`) where the pass is whole row tiles and the experts'
    ``d`` and ``f`` tile for Mosaic, else ``"xla"``
    (:func:`~triton_dist_tpu.ops.group_gemm.grouped_swiglu` /
    ``grouped_relu2``, which stay the definition). ``d`` is the width
    the routed experts work at, the latent's where there is one. A pure
    function of sizes: the same program on a chip and, interpreted, off
    it; the serving engine counts its chunk dispatches by it."""
    ok = n_held > 0 and mlp_tiles(rows, d, f,
                                  jnp.dtype(dtype).itemsize) is not None
    return "kernel" if ok else "xla"


def fwd_held(params, x, *, topk: int, first: int = 0,
             norm_topk_prob: bool = True, routed_scale: float = 1.0,
             scoring: str = "softmax", act: str = "swiglu"):
    """One chip's share of an expert-parallel layer, with no peer here
    and no exchange: the router is as wide as the deployment's
    (``params["router"]``: every expert), the weights are those of the
    ``E_held`` experts from ``first`` on, and of a token's ``topk``
    choices the ones that fell to a held expert are computed. What the
    absent experts would add is left out (their chips would add it in
    the combine this chip does not take part in); the shared expert,
    which every chip computes alike, is added whole.

    Between the router and the combine every array has the rows of held
    pairs only. The pairs' KEYS are sorted by expert; the sorted order
    is walked in passes of ``C = held_pass_rows(...)`` rows, a static
    size: a pass gathers its ``C`` rows of ``x``, runs the grouped
    SwiGLU over its cut of the groups, and adds each row, weighted in
    float32, to its token's result. ``ceil(held / C)`` passes run: one
    at an even routing, one more over the overflow of a skewed chunk,
    none where nothing was held. No pair is dropped at any routing.

    What a pass does between its gather and its combine is chosen by
    :func:`experts_impl` from sizes alone. ``"xla"``: the rows in sorted
    order through ``grouped_swiglu`` / ``grouped_relu2``, three (two)
    ragged products. ``"kernel"``: the rows gathered into an
    expert-major layout whose 128-row tiles belong to one expert each
    (``tile_layout``: ``C / 128 + E_held`` tiles, built from the pass's
    group sizes without a scatter), through ONE Pallas kernel
    (``grouped_mlp_tiles``) that reads an expert's matrices once a tile
    and keeps the activation in VMEM; the combine reads a pair's result
    at its layout row. The same sums in the same precision either way
    (bf16 operands, float32 sums, the activation rounded once).

    The router is :func:`route`'s (``scoring``; its selection bias is
    the layer's ``router_bias`` leaf, where it has one). ``act`` is the
    routed experts' form: ``"swiglu"`` (``w_gate``, ``w_up``,
    ``w_down``) or ``"relu2"`` (``w_up``, ``w_down``: up, squared ReLU,
    down). Where the parameters hold ``w_latent_in`` and
    ``w_latent_out`` the routed experts work in a LATENT: the rows are
    projected into it once, every pass gathers and combines rows of the
    latent's width, and the weighted sum is projected out once; the
    router and the shared expert read ``x`` itself.

    x: (T, d). Returns ``(out (T, d) float32, stats (3,) int32)``:
    ``stats[0]`` the token-expert pairs that fell to held experts,
    ``stats[1]`` the most rows one held expert was given, ``stats[2]``
    the passes run."""
    t, d = x.shape
    n_held, _, f = params["w_up"].shape
    if f != expert_store_width(f):
        raise ValueError(
            f"held experts {f} columns wide: they are stored at whole "
            f"512s once 1,024 wide ({expert_store_width(f)}; "
            "ep_moe.pad_expert_width)")
    topk_ids, topk_w = route(params["router"], x, topk,
                             norm_topk_prob=norm_topk_prob,
                             scoring=scoring,
                             bias=params.get("router_bias"))
    if act not in ("swiglu", "relu2"):
        raise ValueError(f"act={act!r}: 'swiglu' | 'relu2'")
    w_gate = params["w_gate"] if act == "swiglu" else None
    u = x
    if "w_latent_in" in params:
        with scope("expert_latent"):
            u = jnp.dot(x, params["w_latent_in"])
    with scope("experts"):
        rows = held_pass_rows(t, topk, n_held, params["router"].shape[1])
        kernel = experts_impl(rows, n_held, u.shape[1], f,
                              u.dtype) == "kernel"
        local = topk_ids - first
        held = (local >= 0) & (local < n_held)
        flat = jnp.where(held, local, -1).reshape(-1)
        # Held pairs come first in ``order``; ``place`` is a pair's row
        # in it, under ``n_pairs`` for a held pair and for no other.
        order, group_sizes, place = sort_pairs(flat, n_held)
        n_pairs = jnp.sum(group_sizes)
        passes = jax.lax.div(n_pairs + (rows - 1), rows)
        order = jnp.pad(order, (0, -order.shape[0] % rows))
        place = place.reshape(t, topk)
        w = topk_w * routed_scale

        def one_pass(i, out):
            lo = i * rows
            window = jax.lax.dynamic_slice(order, (lo,), (rows,))
            sizes = window_group_sizes(group_sizes, lo, rows)
            at = place - lo
            here = (at >= 0) & (at < rows) & (place < n_pairs)
            if kernel:
                # A tile's rows are one expert's: a pair's row follows
                # from its expert's first tile and first window row.
                tile_expert, n_used, src, shift = tile_layout(sizes, rows)
                window = window.at[jnp.maximum(src, 0)].get(
                    mode="promise_in_bounds")
                at = at + jnp.sum(jnp.where(
                    local[..., None] == jnp.arange(n_held), shift, 0), -1)
            tokens = jax.lax.div(window, topk)
            rows_in = u.at[tokens].get(mode="promise_in_bounds")
            if kernel:
                y = grouped_mlp_tiles(rows_in, params["w_up"],
                                      params["w_down"], tile_expert,
                                      n_used, w_gate=w_gate, act=act)
            elif act == "swiglu":
                y = grouped_swiglu(rows_in, w_gate, params["w_up"],
                                   params["w_down"], sizes)
            else:
                y = grouped_relu2(rows_in, params["w_up"],
                                  params["w_down"], sizes)
            # Rows past the pass's last group (and a tile's rows past
            # its expert's last) are whatever the product left there:
            # selected away, not multiplied by zero.
            at = jnp.clip(at, 0, y.shape[0] - 1)
            for k in range(topk):
                mine = y.at[at[:, k]].get(mode="promise_in_bounds")
                out = out + jnp.where(
                    here[:, k, None],
                    mine.astype(jnp.float32) * w[:, k, None], 0.0)
            return out

        out = jax.lax.fori_loop(0, passes, one_pass,
                                jnp.zeros(u.shape, jnp.float32))
    if "w_latent_out" in params:
        with scope("expert_latent"):
            out = jnp.dot(out.astype(x.dtype), params["w_latent_out"],
                          preferred_element_type=jnp.float32)
    shared = shared_expert_out(params, x)
    if shared is not None:
        out = out + shared
    with scope("experts"):
        stats = jnp.stack([n_pairs, jnp.max(group_sizes), passes])
    return out, stats.astype(jnp.int32)


def fwd(params, x, ep_ctx: EPContext, *, topk: int,
        norm_topk_prob: bool = True):
    """x: (T_loc, d) — every ep rank holds *its own* tokens (the data
    dimension rides the ep axis, as in DeepEP). Returns (T_loc, d)."""
    topk_ids, topk_w = route(params["router"], x, topk,
                             norm_topk_prob=norm_topk_prob)

    recv_tok, recv_exp, state = ep_dispatch(x, topk_ids, ep_ctx)
    sorted_tok, group_sizes, inv = sort_by_expert(
        recv_tok, recv_exp, ep_ctx.experts_per_rank)
    expert_out = grouped_swiglu(sorted_tok, params["w_gate"],
                                params["w_up"], params["w_down"],
                                group_sizes)
    expert_out = expert_out[inv]  # back to slot order
    y = ep_combine(expert_out, state, topk_w, ep_ctx)
    sh = shared_expert_out(params, x)   # replicated weights: full value
    return y if sh is None else (y + sh.astype(y.dtype))


def fwd_2d(params, x, ep2d_ctx, *, topk: int,
           norm_topk_prob: bool = True):
    """Hierarchical (ICI×DCN) EP forward: same structure as :func:`fwd`
    but the dispatch/combine ride the two-hop schedule
    (``ops/ep_a2a.ep_dispatch_2d`` — ICI hop first, one aggregated DCN
    exchange; reference ``all_to_all_vdev_2d_offset_inter_node.py``)."""
    from triton_dist_tpu.ops.ep_a2a import ep_dispatch_2d, ep_combine_2d

    topk_ids, topk_w = route(params["router"], x, topk,
                             norm_topk_prob=norm_topk_prob)
    recv_tok, recv_exp, state = ep_dispatch_2d(x, topk_ids, ep2d_ctx)
    sorted_tok, group_sizes, inv = sort_by_expert(
        recv_tok, recv_exp, ep2d_ctx.experts_per_rank)
    expert_out = grouped_swiglu(sorted_tok, params["w_gate"],
                                params["w_up"], params["w_down"],
                                group_sizes)
    y = ep_combine_2d(expert_out[inv], state, topk_w, ep2d_ctx)
    sh = shared_expert_out(params, x)
    return y if sh is None else (y + sh.astype(y.dtype))


def fwd_decode(params, x, *, topk: int, axis: str = "ep",
               norm_topk_prob: bool = True, transport: str = "ar",
               ep_ctx: Optional[EPContext] = None, replicas=None,
               layer: int = 0, counts: Optional[List] = None):
    """Replicated-token EP decode: one fixed-shape (B, d) batch,
    identical on all ranks in, identical out.

    ``transport`` picks the expert path (module docstring):

    - ``"ar"`` (default): masked local experts + psum — zero dispatch
      round-trips; at decode M two a2a hops cost more than computing
      E/n experts over a handful of rows.
    - ``"ragged"``: the exact-splits dispatch/combine round-trip
      (:func:`~triton_dist_tpu.ops.ep_a2a.ep_dispatch`); needs
      ``ep_ctx``.
    - ``"ll"``: count-free wire-quantized :func:`~triton_dist_tpu.ops
      .low_latency.ll_a2a` exchange over B·K static slots per peer;
      needs ``ep_ctx``. Consults ``replicas`` (hot-expert weight
      copies, :func:`init_replicas`) for rerouting — replica choice is
      data, not trace, so refreshing it never recompiles. NOTE: ``ll``
      ALWAYS rides a quantized wire — int8 unless ``ctx.wire_dtype``
      picks fp8 — unlike dispatch/combine, where ``wire_dtype=None``
      means full precision; pick ``"ragged"`` when wire-quantization
      tolerance is unacceptable.
    - ``"ll2d"``: the same count-free slot protocol over a
      hierarchical (DCN, ICI) mesh — two single-axis hops with the
      DCN traffic coalesced to one slab per peer node
      (:func:`~triton_dist_tpu.ops.ll_a2a_2d.ll_a2a_2d`); needs an
      :class:`~triton_dist_tpu.ops.ep_a2a.EP2DContext` as ``ep_ctx``.
      Quantizes once per fabric (two wire round-trips total).
    - ``"auto"``: host-side tune-cache resolution
      (:func:`resolve_transport`).

    ``layer`` keys the ll slot parity (two a2a calls per MoE layer get
    distinct static parities). ``counts``, when a list, receives this
    layer's per-expert routed-assignment counts (E,) int32 — the
    on-device expert-load telemetry the serving layer aggregates.
    """
    topk_ids, topk_w = route(params["router"], x, topk,
                             norm_topk_prob=norm_topk_prob)
    if counts is not None:
        num_experts = (ep_ctx.num_experts if ep_ctx is not None
                       else params["router"].shape[1])
        counts.append(jnp.bincount(
            topk_ids.reshape(-1), length=num_experts).astype(jnp.int32))

    if transport == "auto":
        transport = resolve_transport(
            "auto", ctx=ep_ctx, batch=x.shape[0], hidden=x.shape[1],
            dtype=x.dtype, topk=topk)
    if transport not in ("ar", "ragged", "ll", "ll2d"):
        raise ValueError(f"transport must be one of {DECODE_TRANSPORTS},"
                         f" got {transport!r}")
    if transport == "ll2d":
        if not isinstance(ep_ctx, EP2DContext):
            raise ValueError(
                "transport='ll2d' needs a hierarchical EP2DContext "
                "(create_ep2d_context) — flat meshes ride 'll'")
        if replicas is not None:
            raise ValueError(
                "hot-expert replication rides the flat 'll' transport;"
                " transport='ll2d' does not consult replicas")
        out = _fwd_decode_ll2d(params, x, topk_ids, topk_w,
                               ctx=ep_ctx, layer=layer)
        sh = shared_expert_out(params, x)
        return out if sh is None else (out + sh.astype(out.dtype))
    if transport in ("ragged", "ll"):
        if ep_ctx is None or not isinstance(ep_ctx, EPContext):
            raise ValueError(
                f"transport={transport!r} needs a flat EPContext "
                "(hierarchical 2D meshes ride transport='ll2d')")
        if transport == "ragged":
            out = _fwd_decode_ragged(params, x, topk_ids, topk_w,
                                     ctx=ep_ctx)
        else:
            out = _fwd_decode_ll(params, x, topk_ids, topk_w,
                                 ctx=ep_ctx, replicas=replicas,
                                 layer=layer)
        sh = shared_expert_out(params, x)
        return out if sh is None else (out + sh.astype(out.dtype))

    from triton_dist_tpu.parallel.mesh import flat_axis_rank

    if isinstance(axis, (tuple, list)):
        # Hierarchical expert sharding (outer-major rank order, matching
        # EP2DContext and P((outer, inner)) param specs).
        axis = tuple(axis)
    _, me = flat_axis_rank(axis)
    e_loc = params["w_gate"].shape[0]        # local expert shard
    ge = me * e_loc + jnp.arange(e_loc)      # my experts' global ids
    # (B, e_loc) combine weight mass routed to my experts.
    sel = (topk_ids[:, :, None] == ge[None, None, :])
    w_be = jnp.einsum("bk,bke->be", topk_w.astype(jnp.float32),
                      sel.astype(jnp.float32))
    xg = jnp.einsum("bd,edf->ebf", x, params["w_gate"])
    xu = jnp.einsum("bd,edf->ebf", x, params["w_up"])
    act = jax.nn.silu(xg.astype(jnp.float32)) * xu.astype(jnp.float32)
    y = jnp.einsum("ebf,efd->ebd", act.astype(x.dtype),
                   params["w_down"])        # (e_loc, B, d)
    out = jnp.einsum("ebd,be->bd", y.astype(jnp.float32), w_be)
    out = jax.lax.psum(out, axis).astype(x.dtype)
    # Replicated shared-expert weights: the full contribution adds
    # AFTER the reduce (inside it, n ranks would count it n times).
    sh = shared_expert_out(params, x)
    return out if sh is None else (out + sh.astype(out.dtype))


def _fwd_decode_ragged(params, x, topk_ids, topk_w, *, ctx: EPContext):
    """Decode via the generic exact-splits round-trip: every rank
    dispatches the (replicated) batch's assignments, owners run the
    grouped SwiGLU, combine returns each rank its own copies — output
    replicated without a reduce."""
    recv_tok, recv_exp, state = ep_dispatch(x, topk_ids, ctx)
    sorted_tok, group_sizes, inv = sort_by_expert(
        recv_tok, recv_exp, ctx.experts_per_rank)
    expert_out = grouped_swiglu(sorted_tok, params["w_gate"],
                                params["w_up"], params["w_down"],
                                group_sizes)
    return ep_combine(expert_out[inv], state, topk_w, ctx)


def _fwd_decode_ll(params, x, topk_ids, topk_w, *, ctx: EPContext,
                   replicas=None, layer: int = 0):
    """Low-latency decode dispatch: COUNT-FREE fixed-slot exchange.

    Every (token, k) assignment owns static slot ``j = t·K + k`` in a
    (n, B·K, d) wire buffer; rank ``dest[j]`` finds token ``j // K`` in
    slot j and every other destination sees a zero row — no splits
    exchange, no cumsum, no ragged transport: the slot count IS the
    protocol (reference ``dispatch_kernel_v2`` /
    ``low_latency_all_to_all_v2.py:156``). Payload rows are
    wire-quantized inside :func:`~triton_dist_tpu.ops.low_latency
    .ll_a2a` (per-row absmax int8/fp8 + scales); the return hop
    broadcasts each owner's outputs back through the same transport at
    the opposite slot parity.

    ``replicas`` (``None`` = off) reroutes alternate assignments of a
    replicated expert to the replica's rank: ``replica_rank`` (E,)
    names the rank holding a copy, ``slot_expert`` (R,) maps replica
    weight slots to expert ids. Routing is a pure function of
    (topk_ids, replicas), identical on every rank, and the replica
    weights are exact copies — greedy tokens cannot change.
    """
    mesh, axis = ctx.mesh, ctx.axis
    n = mesh.size(axis)
    b, d = x.shape
    k = topk_ids.shape[1]
    e_loc = params["w_gate"].shape[0]
    wire = ctx.wire_dtype if ctx.wire_dtype is not None else jnp.int8

    flat_e = topk_ids.reshape(-1).astype(jnp.int32)       # (BK,)
    owner = flat_e // e_loc
    n_rep = 0 if replicas is None else replicas["slot_expert"].shape[0]
    if n_rep:
        rep_rank = replicas["replica_rank"][flat_e]       # (BK,)
        # Deterministic 50/50 split: an assignment's position among its
        # expert's assignments decides owner vs replica — replicated
        # inputs make every rank compute the same route.
        one_hot = jax.nn.one_hot(flat_e, ctx.num_experts,
                                 dtype=jnp.int32)
        pos = jnp.cumsum(one_hot, axis=0) - 1             # (BK, E)
        pos_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        use_rep = jnp.logical_and(rep_rank >= 0, pos_e % 2 == 1)
        dest = jnp.where(use_rep, rep_rank, owner)
        # Replica-slot id of each assignment's expert (-1 = none).
        slot_match = (replicas["slot_expert"][None, :]
                      == flat_e[:, None])                 # (BK, R)
        rep_slot = jnp.argmax(slot_match, axis=1)
    else:
        use_rep = jnp.zeros(flat_e.shape, bool)
        rep_slot = jnp.zeros(flat_e.shape, jnp.int32)
        dest = owner

    from triton_dist_tpu.ops.low_latency import ll_a2a

    rep_tok = jnp.repeat(x, k, axis=0)                    # (BK, d)
    slots = jnp.arange(b * k)
    send = jnp.zeros((n, b * k, d), x.dtype).at[dest, slots].set(rep_tok)
    recv = ll_a2a(send, ctx=mesh, axis=axis, step=2 * layer,
                  wire_dtype=wire)                        # (n, BK, d)

    me = jax.lax.axis_index(axis)
    # Replicated routing ⇒ every source staged the same slot content;
    # my copy of the batch is the chunk addressed through me.
    tok = jnp.take(recv, me, axis=0)                      # (BK, d)
    # Local group id per slot: owner-routed rows use the local expert
    # shard, replica-routed rows use the replica slots appended after
    # it; rows bound elsewhere sort to the tail (-1).
    loc = jnp.where(use_rep, e_loc + rep_slot, flat_e % e_loc)
    mine = dest == me
    loc = jnp.where(mine, loc, -1).astype(jnp.int32)
    if n_rep:
        w_gate = jnp.concatenate(
            [params["w_gate"],
             replicas["w_gate"].astype(params["w_gate"].dtype)], axis=0)
        w_up = jnp.concatenate(
            [params["w_up"],
             replicas["w_up"].astype(params["w_up"].dtype)], axis=0)
        w_down = jnp.concatenate(
            [params["w_down"],
             replicas["w_down"].astype(params["w_down"].dtype)], axis=0)
    else:
        w_gate, w_up, w_down = (params["w_gate"], params["w_up"],
                                params["w_down"])
    sorted_tok, group_sizes, inv = sort_by_expert(tok, loc,
                                                  e_loc + n_rep)
    y = grouped_swiglu(sorted_tok, w_gate, w_up, w_down,
                       group_sizes)[inv]
    y = jnp.where(mine[:, None], y, 0).astype(x.dtype)    # (BK, d)

    # Return hop: every owner broadcasts its rows to all peers through
    # the opposite-parity slots; back[r, j] = slot j as computed at r.
    back = ll_a2a(jnp.broadcast_to(y[None], (n, b * k, d)),
                  ctx=mesh, axis=axis, step=2 * layer + 1,
                  wire_dtype=wire)
    gathered = back[dest, slots].reshape(b, k, d)
    return jnp.einsum("bkd,bk->bd", gathered.astype(jnp.float32),
                      topk_w.astype(jnp.float32)).astype(x.dtype)


def _fwd_decode_ll2d(params, x, topk_ids, topk_w, *,
                     ctx: EP2DContext, layer: int = 0):
    """Hierarchical low-latency decode dispatch: the :func:`_fwd_decode_ll`
    slot protocol (j = t·K + k, replicated routing, zero rows for
    non-destinations) with the exchange factored over the 2-axis mesh
    by :func:`~triton_dist_tpu.ops.ll_a2a_2d.ll_a2a_2d` — ICI shuffle
    first, then ONE coalesced slab put per peer node over DCN. Global
    rank order is outer-major (``flat_axis_rank`` over
    (outer, inner)), matching ``EP2DContext`` expert ownership
    ``e // experts_per_rank``, so ``dest = flat_e // e_loc`` addresses
    the wire buffer directly.

    Two wire quantizations per hop direction (once per fabric) — the
    acceptance bar is greedy-token parity with ``"ar"``, same as the
    flat ``"ll"`` transport's.
    """
    from triton_dist_tpu.ops.ll_a2a_2d import ll_a2a_2d
    from triton_dist_tpu.parallel.mesh import flat_axis_rank

    mesh = ctx.mesh
    n = mesh.size(ctx.outer_axis) * mesh.size(ctx.inner_axis)
    b, d = x.shape
    k = topk_ids.shape[1]
    e_loc = params["w_gate"].shape[0]
    wire = ctx.wire_dtype if ctx.wire_dtype is not None else jnp.int8

    flat_e = topk_ids.reshape(-1).astype(jnp.int32)       # (BK,)
    dest = flat_e // e_loc                # outer-major global rank
    rep_tok = jnp.repeat(x, k, axis=0)                    # (BK, d)
    slots = jnp.arange(b * k)
    send = jnp.zeros((n, b * k, d), x.dtype).at[dest, slots].set(rep_tok)
    recv = ll_a2a_2d(send, ctx=mesh, outer_axis=ctx.outer_axis,
                     inner_axis=ctx.inner_axis, step=2 * layer,
                     wire_dtype=wire, impl=ctx.impl)      # (n, BK, d)

    _, me = flat_axis_rank((ctx.outer_axis, ctx.inner_axis))
    # Replicated routing ⇒ every source staged the same slot content;
    # my copy of the batch is the chunk addressed through me.
    tok = jnp.take(recv, me, axis=0)                      # (BK, d)
    mine = dest == me
    loc = jnp.where(mine, flat_e % e_loc, -1).astype(jnp.int32)
    sorted_tok, group_sizes, inv = sort_by_expert(tok, loc, e_loc)
    y = grouped_swiglu(sorted_tok, params["w_gate"], params["w_up"],
                       params["w_down"], group_sizes)[inv]
    y = jnp.where(mine[:, None], y, 0).astype(x.dtype)    # (BK, d)

    # Return hop: owners broadcast their rows back through both
    # fabrics at the opposite slot parity; back[r, j] = slot j as
    # computed at global rank r.
    back = ll_a2a_2d(jnp.broadcast_to(y[None], (n, b * k, d)),
                     ctx=mesh, outer_axis=ctx.outer_axis,
                     inner_axis=ctx.inner_axis, step=2 * layer + 1,
                     wire_dtype=wire, impl=ctx.impl)
    gathered = back[dest, slots].reshape(b, k, d)
    return jnp.einsum("bkd,bk->bd", gathered.astype(jnp.float32),
                      topk_w.astype(jnp.float32)).astype(x.dtype)


# --- decode-transport autotune + hot-expert replica state -------------------

def _transport_key(ctx, *, batch: int, hidden: int, dtype,
                   topk: int) -> str:
    from triton_dist_tpu import tune

    if isinstance(ctx, EP2DContext):
        axis = f"{ctx.outer_axis}+{ctx.inner_axis}"
        hier = (f"{ctx.mesh.size(ctx.outer_axis)}"
                f"x{ctx.mesh.size(ctx.inner_axis)}")
    else:
        axis = ctx.axis
        # Flat mesh = degenerate 1×n hierarchy: the hierarchy shape is
        # part of the key, so a 2D tuning can never shadow a flat one
        # (or vice versa) on meshes of equal total size.
        hier = f"1x{ctx.mesh.size(ctx.axis)}"
    return tune.make_key(
        "ep_decode_transport", mesh=tune.mesh_key(ctx.mesh),
        axis=axis, hier=hier, batch=batch, hidden=hidden,
        # Canonicalize: jnp.float32 (a type) and np.dtype("float32")
        # must key identically or a tuned winner is never found.
        dtype=str(jnp.dtype(dtype)),
        topk=topk, experts=ctx.num_experts)


def resolve_transport(transport: str, *, ctx,
                      batch: int, hidden: int, dtype,
                      topk: int) -> str:
    """Host-side resolution of the decode ``transport`` knob.

    Explicit values pass through; ``"auto"`` loads the
    :func:`tune_transport` winner persisted for this
    (mesh-hierarchy, batch, hidden, dtype) key and falls back to the
    latency-optimized default when never tuned — ``"ll"`` on a flat
    :class:`EPContext`, ``"ll2d"`` on a hierarchical
    :class:`~triton_dist_tpu.ops.ep_a2a.EP2DContext` (an untuned 2D
    mesh dispatches over both fabrics rather than silently paying the
    ``"ar"`` full-reduce) — or ``"ar"`` when no EP context exists to
    dispatch over."""
    if transport != "auto":
        return transport
    if isinstance(ctx, EP2DContext):
        from triton_dist_tpu import tune

        cached = tune.load_autotune_data(_transport_key(
            ctx, batch=batch, hidden=hidden, dtype=dtype, topk=topk))
        if cached and cached.get("transport") in ("ar", "ll2d"):
            return cached["transport"]
        return "ll2d"
    if ctx is None or not isinstance(ctx, EPContext):
        return "ar"
    from triton_dist_tpu import tune

    cached = tune.load_autotune_data(_transport_key(
        ctx, batch=batch, hidden=hidden, dtype=dtype, topk=topk))
    if cached and cached.get("transport") in ("ar", "ragged", "ll"):
        return cached["transport"]
    return "ll"


def tune_transport(mesh, params, ctx, *, batch: int,
                   topk: int, norm_topk_prob: bool = True, reps: int = 3,
                   use_cache: bool = True) -> str:
    """OFFLINE transport sweep for one decode shape: time each
    candidate's jitted replicated-batch dispatch on ``mesh`` and
    persist the winner under the (mesh-hierarchy, batch, hidden,
    dtype) key ``transport="auto"`` resolves (the ``tune_schedule``
    pattern). A flat :class:`EPContext` sweeps ``ragged`` vs ``ll``; a
    hierarchical :class:`~triton_dist_tpu.ops.ep_a2a.EP2DContext`
    sweeps ``ar`` vs ``ll2d`` (the two candidates that exist on a 2D
    mesh).

    ``params`` is one MoE layer's param dict (expert-sharded on the
    mesh or replicated — timing only). Returns the winning transport.
    """
    import time as _time

    import numpy as np
    from triton_dist_tpu import tune

    is2d = isinstance(ctx, EP2DContext)
    sweep = ("ar", "ll2d") if is2d else ("ragged", "ll")
    ep_axis = ((ctx.outer_axis, ctx.inner_axis) if is2d else ctx.axis)
    d = params["router"].shape[0]
    dtype = params["w_gate"].dtype
    key = _transport_key(ctx, batch=batch, hidden=d, dtype=dtype,
                         topk=topk)
    if use_cache:
        cached = tune.load_autotune_data(key)
        if cached and cached.get("transport") in (("ar",) + sweep):
            return cached["transport"]

    x = jax.random.normal(jax.random.PRNGKey(0), (batch, d), dtype)
    # Specs keyed off the ACTUAL param tree: layers with a shared
    # expert carry four extra (replicated-under-EP) leaves that a bare
    # param_specs(axis) call would omit, crashing the shard_map.
    shared = {"w_shared_gate": P(None, None),
              "w_shared_up": P(None, None),
              "w_shared_down": P(None, None), "shared_gate": P(None)}
    full = {**param_specs(ep_axis), **shared}
    specs = {k: full[k] for k in params}
    times = {}
    for tr in sweep:
        step = jax.jit(jax.shard_map(
            lambda p, v, _tr=tr: fwd_decode(
                p, v, topk=topk, axis=ep_axis,
                norm_topk_prob=norm_topk_prob, transport=_tr,
                ep_ctx=ctx),
            mesh=mesh, in_specs=(specs, P(None, None)),
            out_specs=P(None, None), check_vma=False))
        np.asarray(step(params, x))            # compile + warmup
        best = float("inf")
        for _ in range(reps):
            t0 = _time.perf_counter()
            np.asarray(step(params, x))
            best = min(best, _time.perf_counter() - t0)
        times[tr] = best
    winner = min(times, key=times.get)
    tune.store_autotune_data(
        key, {"transport": winner,
              "times_ms": {t: round(v * 1e3, 3)
                           for t, v in times.items()}},
        times[winner])
    return winner


def init_replicas(cfg, *, slots: int, num_layers: Optional[int] = None,
                  dtype=jnp.float32) -> Dict:
    """Empty hot-expert replica state consulted by the ``"ll"`` decode
    transport: ``slots`` replica weight slots per MoE layer, all free.

    Layout (all replicated across the mesh — replica slots are few and
    small next to the sharded expert banks): ``w_gate``/``w_up``
    (L, R, d, f), ``w_down`` (L, R, f, d), ``slot_expert`` (L, R)
    global expert id held by each slot (-1 free), ``replica_rank``
    (L, E) rank serving a replica of expert e (-1 none). Contents are
    DATA: the serving layer refreshes them between steps from host-side
    load stats with zero recompilation."""
    L = (num_layers if num_layers is not None
         else getattr(cfg, "num_hidden_layers", 1))
    d, f, e = (cfg.hidden_size, cfg.moe_intermediate_size,
               cfg.num_experts)
    return {
        "w_gate": jnp.zeros((L, slots, d, f), dtype),
        "w_up": jnp.zeros((L, slots, d, f), dtype),
        "w_down": jnp.zeros((L, slots, f, d), dtype),
        "slot_expert": jnp.full((L, slots), -1, jnp.int32),
        "replica_rank": jnp.full((L, e), -1, jnp.int32),
    }


def replica_specs() -> Dict:
    """PartitionSpecs for :func:`init_replicas` state (replicated)."""
    return {"w_gate": P(None, None, None, None),
            "w_up": P(None, None, None, None),
            "w_down": P(None, None, None, None),
            "slot_expert": P(None, None),
            "replica_rank": P(None, None)}


def replica_layer(replicas: Dict, layer: int) -> Dict:
    """One layer's slice of the replica state (what
    :func:`fwd_decode` consumes)."""
    return {k: v[layer] for k, v in replicas.items()}


def install_replica_layers(replicas: Dict, slot: int, expert: int,
                           rank: int, w_gate, w_up, w_down) -> Dict:
    """Host-side batched install: copy ONE expert's weights into slot
    ``slot`` across EVERY layer in one pass. ``w_*`` are (L, d, f) /
    (L, f, d) stacks (layer-major). One ``.at[:, slot].set`` per
    buffer — a per-layer install loop would materialize the full
    replica slab L times. Evicted experts (per layer, whatever held
    the slot) have their routing entries cleared first. Pure —
    returns the updated pytree."""
    L = replicas["slot_expert"].shape[0]
    old = replicas["slot_expert"][:, slot]                # (L,)
    rows = jnp.arange(L)
    rr = replicas["replica_rank"]
    rr = rr.at[rows, jnp.maximum(old, 0)].set(
        jnp.where(old >= 0, -1, rr[rows, jnp.maximum(old, 0)]))
    return {
        "w_gate": replicas["w_gate"].at[:, slot].set(
            w_gate.astype(replicas["w_gate"].dtype)),
        "w_up": replicas["w_up"].at[:, slot].set(
            w_up.astype(replicas["w_up"].dtype)),
        "w_down": replicas["w_down"].at[:, slot].set(
            w_down.astype(replicas["w_down"].dtype)),
        "slot_expert": replicas["slot_expert"].at[:, slot].set(
            int(expert)),
        "replica_rank": rr.at[:, int(expert)].set(int(rank)),
    }




def fwd_fused(params, x, ep_ctx: EPFusedContext, *, topk: int,
              norm_topk_prob: bool = True):
    """Mega-EP forward: dispatch fused into the up-projection grouped
    GEMM, down-projection fused into the combine (``ops/ep_fused.py``).
    Returns ((T_loc, d), num_dropped)."""
    topk_ids, topk_w = route(params["router"], x, topk,
                             norm_topk_prob=norm_topk_prob)
    y, dropped = ep_moe_fused(x, topk_ids, topk_w, params["w_gate"],
                              params["w_up"], params["w_down"], ep_ctx,
                              w_gu=params.get("w_gu"))
    sh = shared_expert_out(params, x)   # replicated weights: full value
    if sh is not None:
        y = y + sh.astype(y.dtype)
    return y, dropped
