"""Grouped GEMM for MoE expert compute.

Reference: ``python/triton_dist/kernels/nvidia/group_gemm.py`` (1102 LoC
persistent grouped GEMM with token-block swizzle) + ``moe_utils.py``.

Three TPU forms:

- :func:`grouped_gemm` / :func:`grouped_swiglu` /
  :func:`grouped_relu2`: tokens sorted by expert
  + ``jax.lax.ragged_dot`` (XLA's native grouped matmul, which tiles
  onto the MXU with group offsets) — the zero-maintenance path. A
  caller that holds some of the experts sorts the keys alone
  (:func:`sort_pairs`) and hands the product a window of the sorted
  rows with the groups cut to it (:func:`window_group_sizes`), so the
  operand has the rows that are computed and no others
  (``layers/ep_moe.fwd_held``).
- :func:`grouped_gemm_tiles`: a Pallas kernel over the ``block_m``-
  aligned expert-major layout of
  :func:`~triton_dist_tpu.ops.ag_moe.prepare_grouped_tokens`. The
  reference's token-block swizzle becomes a scalar-prefetched
  tile→expert map selecting the weight tile in the BlockSpec
  ``index_map`` — the same machinery :func:`~triton_dist_tpu.ops.ag_moe.
  ag_group_gemm` uses, minus the ring; kept local so MoE layers can run
  sorted-layout down-projections without leaving the fused data layout.
- :func:`grouped_mlp_tiles` over :func:`tile_layout`: a held expert's
  whole MLP (up, and gate; the activation; down) as ONE Pallas kernel
  whose row tiles belong to one expert each, for the passes of
  ``layers/ep_moe.fwd_held`` (:func:`mlp_tiles` picks the tiles from
  shapes; ``ep_moe.experts_impl`` picks between this and the first
  form). XLA's ragged product cuts the sorted rows into tiles of 128
  whatever the groups are and multiplies a tile that holds several
  groups once a group: at 64-88 rows an expert the matrices cross HBM
  ~1.6 times a product and the activation ``(rows, f)`` goes through HBM
  in float32 between the products. Here a window's groups are laid out
  expert-major, each from a tile boundary on (sums over comparisons
  against ``(E,)`` tables: no scatter), the same tile -> expert map
  picks the weight blocks, an expert of up to 128 rows reads its
  matrices once, and the activation never leaves VMEM. bf16 operands,
  float32 sums, rounded where :func:`grouped_swiglu` /
  :func:`grouped_relu2` round: they stay the definition and the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import core_call


def sort_pairs(expert_ids, num_experts: int):
    """Sort (slots,) local expert ids, the keys alone (-1 = empty slots
    go last). Returns (order: the slots expert by expert, group_sizes
    (num_experts,), inverse permutation: a slot's place in ``order``)."""
    key = jnp.where(expert_ids < 0, num_experts, expert_ids)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    # Counted by comparison, not by ``bincount``: that is a scatter-add,
    # which the TPU takes a slot at a time.
    group_sizes = jnp.sum(key[:, None] == jnp.arange(num_experts)[None, :],
                          axis=0, dtype=jnp.int32)
    return order, group_sizes, inv


def sort_by_expert(tokens, expert_ids, num_experts: int):
    """Sort (slots, d) tokens by local expert id (-1 = empty slots go
    last). Returns (sorted_tokens, group_sizes (num_experts,), inverse
    permutation to restore slot order)."""
    order, group_sizes, inv = sort_pairs(expert_ids, num_experts)
    return tokens[order], group_sizes, inv


def window_group_sizes(group_sizes, lo, rows: int):
    """The group sizes of rows ``[lo, lo + rows)`` of an expert-sorted
    layout whose groups are ``group_sizes``: each group cut to the part
    of it that lies in the window. They sum to the sorted rows the
    window holds, ``rows`` at most."""
    ends = jnp.cumsum(group_sizes)
    cut = jnp.clip(jnp.stack([ends - group_sizes, ends]) - lo, 0, rows)
    return (cut[1] - cut[0]).astype(jnp.int32)


def grouped_gemm(x, w, group_sizes):
    """x: (M, d) sorted by group; w: (E, d, f); group_sizes: (E,).
    Returns (M, f) with rows of group e multiplied by w[e]."""
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def _gg_tiles_kernel(te_ref, x_ref, w_ref, o_ref, acc_v):
    del te_ref  # consumed by the weight index map
    kk = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kk == 0)
    def _():
        acc_v[...] = jnp.zeros_like(acc_v)

    acc_v[...] += jnp.dot(x_ref[...], w_ref[0],
                          preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _():
        o_ref[...] = acc_v[...].astype(o_ref.dtype)


def grouped_gemm_tiles(x_sorted, w, tile_expert, *, block_n: int = 256,
                       block_k: int = 512, out_dtype=None,
                       interpret=None):
    """Pallas grouped GEMM over a ``block_m``-aligned expert-major layout.

    ``x_sorted``: (S, d) with every row tile owned by one expert;
    ``w``: (E, d, f); ``tile_expert``: (S // block_m,) int32. The row
    tile size is inferred from ``tile_expert``. Returns (S, f).
    """
    s, d = x_sorted.shape
    e, _, f = w.shape
    n_tiles = tile_expert.shape[0]
    if s % n_tiles:
        raise ValueError(f"S={s} not divisible by {n_tiles} tiles")
    tm = s // n_tiles
    # Snap tiles down to divisors so any model shape the ragged_dot path
    # accepts also lowers here.
    tn = min(block_n, f)
    while tn > 1 and f % tn:
        tn //= 2
    tk = min(block_k, d)
    while tk > 1 and d % tk:
        tk //= 2
    n_j, n_k = f // tn, d // tk
    out_dtype = out_dtype or x_sorted.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, n_j, n_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk, te: (i, kk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, tn),
                         lambda i, j, kk, te: (te[i], kk, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, te: (i, j),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return core_call(
        _gg_tiles_kernel,
        grid_spec=grid_spec,
        interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((s, f), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * s * d * f,
            bytes_accessed=(s * d + e * d * f + s * f)
            * x_sorted.dtype.itemsize,
            transcendentals=0,
        ),
    )(tile_expert, x_sorted, w)


def grouped_swiglu(x, w_gate, w_up, w_down, group_sizes):
    """Per-expert SwiGLU MLP over expert-sorted tokens.

    w_*: (E, d, f) / (E, d, f) / (E, f, d).
    """
    g = jax.lax.ragged_dot(x, w_gate, group_sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, w_up, group_sizes,
                           preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.ragged_dot(h, w_down, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def grouped_relu2(x, w_up, w_down, group_sizes):
    """Per-expert ungated MLP over expert-sorted tokens: up, squared
    ReLU, down. w_up: (E, d, f); w_down: (E, f, d)."""
    u = jax.lax.ragged_dot(x, w_up, group_sizes,
                           preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(u)).astype(x.dtype)
    return jax.lax.ragged_dot(h, w_down, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


# ---------------------------------------------------------------------------
# The held experts' MLP of a pass in one kernel: row tiles that belong
# to one expert each (layers/ep_moe.fwd_held).

# A row tile of the expert-major layout: the MXU's height, so a tile
# with one row and a tile with 128 cost the same pass over the expert's
# matrices, and an expert of up to 128 rows is one tile.
ROW_TILE = 128
# What one grid step's weight blocks may take of VMEM, both buffers of
# each: at bf16, three ``(4096, 512)`` blocks of a gated expert or a
# whole ungated ``1024 -> 3072 -> 1024`` one.
WEIGHT_VMEM = 24 * 1024 * 1024
# Mosaic's scoped VMEM for the kernel (its default is 16 MiB of the
# v5e's 128): the weight blocks, the row tile in and out twice each,
# the float32 sum and a step's activation.
VMEM_LIMIT = 48 * 1024 * 1024


def mlp_tiles(rows: int, d: int, f: int, itemsize: int, mats: int = 3):
    """``(tm, tf)`` of :func:`grouped_mlp_tiles` for a pass of ``rows``
    sorted rows through experts ``d -> f -> d`` of ``mats`` matrices
    (3 gated, 2 ungated), or None where the sizes do not tile for
    Mosaic: whole row tiles, whole lanes of ``d`` and ``f``, and the
    widest whole-lane divisor of ``f`` whose blocks fit
    :data:`WEIGHT_VMEM` (all of ``f`` where an expert fits: consecutive
    tiles of one expert then fetch nothing). Pure host arithmetic on
    shapes, the one place the tiles are chosen."""
    if min(rows, d, f) <= 0 or rows % ROW_TILE or d % 128 or f % 128:
        return None
    fit = [tf for tf in range(128, f + 1, 128) if f % tf == 0
           and 2 * mats * d * tf * itemsize <= WEIGHT_VMEM]
    return (ROW_TILE, fit[-1]) if fit else None


def tile_layout(sizes, rows: int, tm: int = ROW_TILE):
    """The expert-major layout of a window of ``rows`` sorted rows whose
    groups are ``sizes`` ``(E,)`` (:func:`window_group_sizes`): an expert
    with ``n`` rows takes ``ceil(n / tm)`` tiles of ``tm`` rows, its
    first on a tile boundary. ``rows // tm + E`` tiles always hold them.

    Returns ``(tile_expert (tiles,), n_used (1,), src (tiles * tm,),
    shift (E,))``, all int32: a tile's expert (the tiles past the last
    used one repeat its expert: the kernel neither computes nor fetches
    for them), the tiles in use, the window row a layout row holds or
    -1, and what takes an expert's window row to its layout row (``a +
    shift[e]``). Sums over comparisons against ``(E,)`` tables and
    nothing else: no scatter, which the TPU takes a row at a time, no
    gather of scalars and no scan; a handful of fused loops."""
    n_held = sizes.shape[0]
    n_tiles = rows // tm + n_held
    sizes = sizes.astype(jnp.int32)
    tiles = (sizes + (tm - 1)) // tm
    e = jnp.arange(n_held, dtype=jnp.int32)
    before = e[None, :] < e[:, None]

    def starts(counts):                 # an exclusive running sum
        return jnp.sum(jnp.where(before, counts[None, :], 0), axis=1)

    tile_start, row_start = starts(tiles), starts(sizes)
    n_used = jnp.sum(tiles)
    i = jnp.arange(n_tiles, dtype=jnp.int32)
    at = jnp.minimum(i, n_used - 1)[:, None]
    owner = (at >= tile_start[None, :]) & (at < (tile_start + tiles)[None, :])

    def of_tile(table):                 # its value for a tile's expert
        return jnp.sum(jnp.where(owner, table[None, :], 0), axis=1)

    # A layout row's place among its expert's rows.
    r = ((i - of_tile(tile_start)) * tm)[:, None] + jnp.arange(
        tm, dtype=jnp.int32)[None, :]
    held = (i < n_used)[:, None] & (r < of_tile(sizes)[:, None])
    src = jnp.where(held, of_tile(row_start)[:, None] + r, -1)
    return (of_tile(e), n_used.reshape(1), src.reshape(-1),
            tile_start * tm - row_start)


def _mlp_tiles_kernel(te_ref, used_ref, x_ref, *refs, act: str):
    """Grid (row tile, ``f`` tile), both sequential. VMEM blocks: the
    tile's rows ``(tm, d)``, its expert's up (and gate) block ``(d,
    tf)`` and down block ``(tf, d)``; out the tile's rows ``(tm, d)``.
    Scratch, where ``f`` is tiled: the float32 sum over the ``f``
    tiles."""
    del te_ref  # consumed by the index maps
    gated = act == "swiglu"
    n_w = 3 if gated else 2
    (*w_gate, w_up, w_down), (o_ref, *acc) = refs[:n_w], refs[n_w:]
    acc = acc[0] if acc else None
    j, n_f = pl.program_id(1), pl.num_programs(1)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        x = x_ref[...]
        u = jnp.dot(x, w_up[0], preferred_element_type=jnp.float32)
        if gated:
            g = jnp.dot(x, w_gate[0][0],
                        preferred_element_type=jnp.float32)
            h = jax.nn.silu(g) * u
        else:
            h = jnp.square(jax.nn.relu(u))
        # Rounded where the XLA forms round: the activation to the
        # operands' type, the sum over f once, at the end.
        part = jnp.dot(h.astype(x.dtype), w_down[0],
                       preferred_element_type=jnp.float32)
        if acc is None:
            o_ref[...] = part.astype(o_ref.dtype)
            return

        @pl.when(j == 0)
        def _():
            acc[...] = part

        @pl.when(j > 0)
        def _():
            acc[...] += part

        @pl.when(j == n_f - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)


def grouped_mlp_tiles(x_tiles, w_up, w_down, tile_expert, n_used, *,
                      w_gate=None, act: str = "swiglu", tf=None,
                      interpret=None):
    """:func:`grouped_swiglu` (``act="swiglu"``, with ``w_gate``) or
    :func:`grouped_relu2` (``"relu2"``) over the expert-major layout of
    :func:`tile_layout`, as ONE Pallas kernel: both (or all three)
    products and the activation between them, which never leaves VMEM.

    ``x_tiles``: ``(tiles * tm, d)``, every row tile one expert's;
    ``w_up`` (and ``w_gate``): ``(E, d, f)``; ``w_down``: ``(E, f, d)``;
    ``tile_expert``: ``(tiles,)`` int32; ``n_used``: ``(1,)`` int32, the
    tiles in use. ``tf`` divides ``f`` (:func:`mlp_tiles` where None).
    Returns ``(tiles * tm, d)`` in ``x_tiles``' type; the rows of tiles
    past ``n_used`` are not written.

    A tile past ``n_used`` maps to the blocks of the last step that
    computed, so the pipeline fetches nothing for it; so does a tile of
    the expert the tile before had, where ``tf`` is all of ``f``: an
    expert of up to ``tm`` rows reads its matrices from HBM once."""
    s, d = x_tiles.shape
    e, _, f = w_up.shape
    n_tiles = tile_expert.shape[0]
    if s % n_tiles:
        raise ValueError(f"{s} rows in {n_tiles} tiles")
    if act not in ("swiglu", "relu2"):
        raise ValueError(f"act={act!r}: 'swiglu' | 'relu2'")
    if (act == "swiglu") != (w_gate is not None):
        raise ValueError(f"act={act!r} with{'out' * (w_gate is None)} a "
                         "gate: 'swiglu' takes one, 'relu2' none")
    tm = s // n_tiles
    ups = (w_up,) if w_gate is None else (w_gate, w_up)
    if tf is None:
        plan = mlp_tiles(tm, d, f, x_tiles.dtype.itemsize, len(ups) + 1)
        if plan is None:
            raise ValueError(f"grouped_mlp_tiles cannot tile d={d} f={f} "
                             f"in tiles of {tm} rows: see "
                             "group_gemm.mlp_tiles")
        tf = plan[1]
    if f % tf:
        raise ValueError(f"tf={tf} does not divide f={f}")
    n_f = f // tf

    def rows_at(i, j, te, used):
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), 0

    def f_at(i, j, used):
        return jnp.where(i < used[0], j, n_f - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_f),
        in_specs=[pl.BlockSpec((tm, d), rows_at, memory_space=pltpu.VMEM)]
        + [pl.BlockSpec((1, d, tf),
                        lambda i, j, te, used: (te[i], 0, f_at(i, j, used)),
                        memory_space=pltpu.VMEM)] * len(ups)
        + [pl.BlockSpec((1, tf, d),
                        lambda i, j, te, used: (te[i], f_at(i, j, used), 0),
                        memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tm, d), rows_at, memory_space=pltpu.VMEM),
        scratch_shapes=([pltpu.VMEM((tm, d), jnp.float32)] if n_f > 1
                        else []),
    )
    size = x_tiles.dtype.itemsize
    return core_call(
        functools.partial(_mlp_tiles_kernel, act=act),
        name="grouped_mlp_tiles",
        grid_spec=grid_spec,
        interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((s, d), x_tiles.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * s * d * f * (len(ups) + 1),
            bytes_accessed=(2 * s * d + (len(ups) + 1) * e * d * f) * size,
            transcendentals=s * f * (w_gate is not None)),
    )(tile_expert, n_used, x_tiles, *ups, w_down)


def grouped_gemm_tiles_tuned(x_sorted, w, tile_expert, *, configs=None):
    """Autotuned grouped GEMM with perf-model pruning: VMEM-infeasible
    block configs are vetoed before any compile (reference pattern:
    ``gemm_perf_model.py`` pruning grouped sweeps)."""
    from triton_dist_tpu.autotuner import autotune
    from triton_dist_tpu.tools.perf_model import grouped_gemm_vmem_bytes

    if configs is None:
        configs = [
            {"block_n": 256, "block_k": 512},
            {"block_n": 512, "block_k": 1024},
            {"block_n": 512, "block_k": 2048},
            {"block_n": 1024, "block_k": 4096},
        ]
    block_m = x_sorted.shape[0] // max(tile_expert.shape[0], 1)

    def _prune(cfg, x_, w_, te_):
        return grouped_gemm_vmem_bytes(
            block_m, cfg.get("block_n", 256), cfg.get("block_k", 512),
            w_.shape[1], w_.shape[2],
            x_.dtype.itemsize) <= 14 * 1024 * 1024

    @autotune("grouped_gemm_tiles", configs,
              key_fn=lambda x_, w_, te_, **kk: {
                  "rows": x_.shape[0], "d": w_.shape[1], "f": w_.shape[2],
                  "e": w_.shape[0], "dtype": str(x_.dtype)},
              prune_fn=_prune)
    def _run(x_, w_, te_, block_n=256, block_k=512):
        return grouped_gemm_tiles(x_, w_, te_, block_n=block_n,
                                  block_k=block_k)

    return _run(x_sorted, w, tile_expert)
