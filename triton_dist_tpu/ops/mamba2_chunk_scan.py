"""A prefill chunk's Mamba-2 scan as ONE Pallas kernel.

What :func:`triton_dist_tpu.ops.mamba2.ssd_chunked` computes (and stays
the definition of, and the oracle for), with nothing of size ``Q x Q`` a
head outside VMEM: the XLA form writes the heads' decay matrices
``(T / Q, H, Q, Q)`` and their product with ``C B^T`` to HBM (134 MB
each at 2,048 rows and 128 heads) and walks the chunks' states in a
``lax.scan``; here a grid step holds one scan chunk of one GROUP, and
the group's state stays in a VMEM scratch from chunk to chunk.

Grid ``(G, T / Q)``, the chunk axis innermost and sequential. A step
takes its ``Q`` rows of the group's ``H / G`` heads, side by side as the
model has them (``x`` is ``(T, H P)``, the state ``(N, H P)``: a head's
``P`` columns), and walks them a SLAB of 128 lanes at a time (``128 /
P`` heads; a ``lax.fori_loop``, so the traced body holds one slab
whatever the sizes). With ``b`` the running sum of ``A dt`` along the
chunk (computed outside, the one ``cumsum`` of :func:`ssd_chunked`) and
``cb = C B^T`` once a step, a head ``j`` of the slab gives

    M_j[t, s] = e^(min(b_t - b_s, 0)) [s <= t] cb[t, s]
    y_j       = M_j (dt x)_j                         inside the chunk

and the slab as a whole, every head's own lanes scaled by its own
``b``:

    y    += e^(b_t) (C S^T) + D x                    carried in, skipped
    S^T  <- e^(b_Q) S^T + B^T (e^(b_Q - b_s) dt x)   the chunk's own part

``M_j`` multiplies the WHOLE slab (one right-hand side for its heads,
every product a whole 128-lane tile, no vector cut inside a tile) and
head ``j``'s lanes of the product are kept.

The same arithmetic as the XLA form, sum for sum: every product float32
at ``HIGHEST`` precision, the state float32, every exponent a
difference formed BEFORE the ``exp`` (a decay, never a growth). A row
with ``dt = 0`` neither decays (``b`` does not move) nor writes (its
row of ``dt x`` is zero): padding and rows past ``valid`` stay out.

What a head needs along a chunk's KEYS (``b_s``: one sublane, broadcast
down) comes from a ``(H / G, Q)`` block; what it needs along its ROWS
(``b_t``, ``dt_t``: a column, broadcast across) from a ``(Q, H)`` block
by a masked sum over lanes: one value and zeros, exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.lang import core_call

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
# What a grid step's blocks may take of Mosaic's default 16 MiB of scoped
# VMEM (the rest: a slab's temporaries): a group's x and y blocks and the
# state in, out and carried, the first four double-buffered.
VMEM_BUDGET = 12 * 1024 * 1024


def legal(rows: int, heads: int, head_dim: int, groups: int, state: int,
          chunk: int) -> bool:
    """Whether the kernel tiles at these sizes: whole scan chunks of
    whole lanes (``cb`` and a head's decay are ``(Q, Q)`` tiles), a
    state of whole lanes, heads that fill 128-lane slabs and a group of
    whole slabs whose blocks fit :data:`VMEM_BUDGET`. Pure host
    arithmetic on shapes."""
    if min(rows, heads, head_dim, groups, state, chunk) <= 0:
        return False
    width = heads // groups * head_dim                   # a group's lanes
    return (heads % groups == 0 and chunk % _LANES == 0
            and rows % chunk == 0 and state % _LANES == 0
            and _LANES % head_dim == 0 and width % _LANES == 0
            and 4 * width * (4 * chunk + 5 * state) <= VMEM_BUDGET)


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _kernel(bq_ref, d_ref, x_ref, b_ref, c_ref, bcol_ref, dtcol_ref,
            brow_ref, s0_ref, y_ref, s_ref, st, *, per_group: int,
            head_dim: int):
    """Grid (groups, chunks). SMEM: ``b_Q`` a chunk and head ``(c, H)``,
    ``D`` ``(1, H)``. VMEM blocks: the group's ``x`` ``(Q, r P)``, ``B``
    and ``C`` ``(Q, N)``, every head's ``b`` and ``dt`` by column ``(Q,
    H)``, the group's ``b`` by row ``(r, Q)``, the state carried in
    ``(N, r P)``; out ``y`` ``(Q, r P)`` and the state after the last
    chunk. Scratch: the group's state."""
    g, ci = pl.program_id(0), pl.program_id(1)
    q = x_ref.shape[0]
    per_slab = _LANES // head_dim

    @pl.when(ci == 0)
    def _():
        st[...] = s0_ref[...]

    c_blk = c_ref[...]
    b_t = b_ref[...].T                                   # (N, Q)
    # s <= t; above the diagonal the difference is positive and unused:
    # clamped before the exp, and masked here.
    tril = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    cb = jnp.where(tril, _dot(c_blk, b_t), 0.0)          # (Q, Q)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, bcol_ref.shape, 1)
    head_of = jax.lax.broadcasted_iota(
        jnp.int32, (1, _LANES), 1) // head_dim           # in its slab

    def column(ref, head):
        """(Q, 1): one head's column of a (Q, H) block."""
        return jnp.sum(jnp.where(head_lane == head, ref[...], 0.0),
                       axis=1, keepdims=True)

    def on_lanes(of_head):
        """A value a head of the slab, each on its own head's lanes."""
        out = of_head[0]
        for i in range(1, per_slab):
            out = jnp.where(head_of == i, of_head[i], out)
        return out

    # Traced once and lowered a slab after another: one basic block, so
    # a slab's vector work runs under the last one's products (rolled,
    # the kernel took 0.91 ms for 0.68 at 2,048 rows: PERF.md, PR 46).
    @pl.loop(0, per_group // per_slab, unroll=True)
    def _(slab):
        lanes = pl.ds(pl.multiple_of(slab * _LANES, _LANES), _LANES)
        x = x_ref[:, lanes]                              # (Q, 128)
        s_in = st[:, lanes]                              # (N, 128)
        heads = [g * per_group + slab * per_slab + i
                 for i in range(per_slab)]
        b_col = [column(bcol_ref, head) for head in heads]
        b_q = [jnp.full((1, _LANES), bq_ref[ci, head]) for head in heads]
        d_skip = on_lanes([jnp.full((1, _LANES), d_ref[0, head])
                           for head in heads])
        xdt = x * on_lanes([column(dtcol_ref, head) for head in heads])
        to_end = on_lanes([jnp.exp(q_ - col)
                           for q_, col in zip(b_q, b_col)])
        # The slab's heads against ONE right-hand side; of a head's
        # product its own lanes are kept.
        inside = _dot(jnp.concatenate([
            jnp.exp(jnp.minimum(
                col - brow_ref[pl.ds(slab * per_slab + i, 1), :], 0.0)) * cb
            for i, col in enumerate(b_col)]), xdt)       # (heads Q, 128)
        y = on_lanes([inside[i * q:(i + 1) * q] for i in range(per_slab)])
        y_ref[:, lanes] = (
            y + _dot(c_blk, s_in) * on_lanes([jnp.exp(col) for col in b_col])
            + d_skip * x)
        st[:, lanes] = (jnp.exp(on_lanes(b_q)) * s_in
                        + _dot(b_t, xdt * to_end))

    @pl.when(ci == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = st[...]


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk_scan(x, dt, A, B, C, D, initial_state=None, *,
                   chunk: int = 128):
    """:func:`~triton_dist_tpu.ops.mamba2.ssd_chunked` for sizes that
    are :func:`legal`: x ``(T, H, P)``, dt ``(T, H)``, A and D ``(H,)``,
    B and C ``(T, G, N)``, all float32; ``initial_state`` ``(H, P, N)``
    float32, zeros where None. Returns ``(y (T, H, P), S_final (H, P,
    N))``. Behind one ``jax.jit``: the Mamba-2 layers of a step program
    share its trace and its Mosaic kernel."""
    t, h, p = x.shape
    g, n = B.shape[1:]
    if not legal(t, h, p, g, n, chunk):
        raise ValueError(
            f"ssd_chunk_scan cannot tile rows={t} heads={h} head_dim={p} "
            f"groups={g} state={n} chunk={chunk}: see "
            "mamba2_chunk_scan.legal")
    r, q, c = h // g, chunk, t // chunk
    f32 = jnp.float32
    if initial_state is None:
        initial_state = jnp.zeros((h, p, n), f32)
    # (chunks, Q, H): a head's running log-decay along its chunk.
    b = jnp.cumsum((dt * A).astype(f32).reshape(c, q, h), axis=1)
    # The state lies (N, H P) in the kernel: a head's P columns.
    s0 = initial_state.astype(f32).reshape(g, r * p, n).transpose(0, 2, 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    rows = lambda width: pl.BlockSpec(
        (q, width), lambda gi, ci: (ci, gi), memory_space=pltpu.VMEM)
    state = pl.BlockSpec((None, n, r * p), lambda gi, ci: (gi, 0, 0),
                         memory_space=pltpu.VMEM)
    by_column = pl.BlockSpec((q, h), lambda gi, ci: (ci, 0),
                             memory_space=pltpu.VMEM)
    by_head = pl.BlockSpec((None, r, q), lambda gi, ci: (ci * g + gi, 0, 0),
                           memory_space=pltpu.VMEM)
    y, s = core_call(
        functools.partial(_kernel, per_group=r, head_dim=p),
        name="mamba2_chunk_scan",
        grid=(g, c),
        out_shape=(jax.ShapeDtypeStruct((t, h * p), f32),
                   jax.ShapeDtypeStruct((g, n, r * p), f32)),
        in_specs=[
            smem, smem,                                  # b_Q, D
            rows(r * p), rows(n), rows(n),               # x, B, C
            by_column, by_column,                        # b, dt
            by_head,                                     # b by row
            state,
        ],
        out_specs=(rows(r * p), state),
        scratch_shapes=[pltpu.VMEM((n, r * p), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * q * g * n + 2 * t * h * p * (q + 2 * n),
            bytes_accessed=4 * (2 * t * h * p + 2 * t * g * n
                                + 2 * h * p * n + 3 * t * h),
            transcendentals=t * h * (q + 2)),
    )(b[:, -1], D.astype(f32).reshape(1, h),
      x.astype(f32).reshape(t, h * p), B.astype(f32).reshape(t, g * n),
      C.astype(f32).reshape(t, g * n), b.reshape(t, h), dt.astype(f32),
      b.reshape(c, q, g, r).transpose(0, 2, 3, 1).reshape(c * g, r, q), s0)
    return (y.reshape(t, h, p),
            s.transpose(0, 2, 1).reshape(h, p, n))
