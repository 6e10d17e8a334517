"""Grouped GEMM for MoE expert compute.

Reference: ``python/triton_dist/kernels/nvidia/group_gemm.py`` (1102 LoC
persistent grouped GEMM with token-block swizzle) + ``moe_utils.py``.

Two TPU forms:

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
"""

from __future__ import annotations

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
