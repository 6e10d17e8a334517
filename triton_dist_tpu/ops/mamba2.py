"""Mamba-2's state-space recurrence (SSD): a plain decayed
outer-product state with ONE decay a head and B and C shared by the
heads of a group. :mod:`triton_dist_tpu.ops.gdn` is the delta rule, which
reads its state before it writes; this rule only decays and adds.

Recurrence (a head ``j`` of group ``j // (H / G)``, state ``S`` of
``P x N``, float32; ``dt_t > 0`` the step size, ``A[j] < 0``):

    a_t = exp(A[j] dt_t)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D[j] x_t

Two forms of the one recurrence, plain XLA both: :func:`ssd_step`, a
token a sequence (decode), and :func:`ssd_chunked`, in chunks of ``Q``
rows (the tests hold it to the step under a ``lax.scan``).
:func:`ssd_prefill` is what a model calls for a prefill chunk's rows:
:func:`ssd_chunked`, or where the sizes tile
(:func:`chunk_scan_impl`) the same sums in one Pallas kernel
(:mod:`triton_dist_tpu.ops.mamba2_chunk_scan`). With ``b_t``
the running sum of ``A dt`` inside a chunk:

    y_t = sum_{s<=t} e^(b_t - b_s) (C_t . B_s) dt_s x_s     inside
          + e^(b_t) S_0 C_t                                  carried in
    S_Q = e^(b_Q) S_0 + sum_s e^(b_Q - b_s) dt_s x_s B_s^T

The parts inside a chunk and each chunk's own contribution to the state
are computed for all chunks at once; only the states' passage from
chunk to chunk is a scan, over ``T / Q`` steps of one multiply-add of
the state. Every exponent is a DIFFERENCE ``b_t - b_s`` with ``s <= t``,
formed before the ``exp``: a decay, never a growth. A row with ``dt =
0`` neither decays nor writes: that is how a caller leaves padding out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from triton_dist_tpu.ops import mamba2_chunk_scan as _scan

CHUNK = 128


def _dot(spec, a, b):
    """A float32 product that stays float32 on the TPU."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _by_head(v, heads: int):
    """(..., G, N) a group -> (..., H, N) a head."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_step(S, x, dt, A, B, C, D):
    """One token a sequence. S: (..., H, P, N) float32; x: (..., H, P);
    dt: (..., H); A, D: (H,); B, C: (..., G, N). Returns ``(y (..., H,
    P), S_new)``."""
    # Multiply and sum, float32 whole: a float32 ``dot`` on the TPU is
    # one bfloat16 pass unless told otherwise, and a product of one row
    # gains nothing from the matrix unit.
    h = x.shape[-2]
    S = (S * jnp.exp(dt * A)[..., None, None]
         + (dt[..., None] * x)[..., None] * _by_head(B, h)[..., None, :])
    y = jnp.sum(S * _by_head(C, h)[..., None, :], axis=-1)
    return y + D[:, None] * x, S


def ssd_chunked(x, dt, A, B, C, D, initial_state=None, *,
                chunk: int = CHUNK):
    """The chunked form (module docstring). x: (T, H, P); dt: (T, H);
    A, D: (H,); B, C: (T, G, N); ``initial_state`` (H, P, N) float32,
    zeros where None. Returns ``(y (T, H, P), S_final (H, P, N))``.
    ``T`` is padded to whole chunks with ``dt = 0``."""
    t, h, p = x.shape
    g, n = B.shape[1:]
    r = h // g
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                       for v in (x, dt, B, C))
    c = (t + pad) // q
    if initial_state is None:
        initial_state = jnp.zeros((h, p, n), jnp.float32)
    xdt = (x * dt[..., None]).reshape(c, q, g, r, p)
    Bc, Cc = B.reshape(c, q, g, n), C.reshape(c, q, g, n)
    # (chunks, H, Q): a head's running log-decay along its chunk.
    b = jnp.cumsum((dt * A).reshape(c, q, h), axis=1).transpose(0, 2, 1)
    # e^(b_t - b_s) for s <= t; above the diagonal the difference is
    # positive and unused: clamped before the exp, then masked.
    decay = jnp.where(
        jnp.tril(jnp.ones((q, q), bool)),
        jnp.exp(jnp.minimum(b[..., :, None] - b[..., None, :], 0.0)), 0.0)
    cb = _dot("ctgn,csgn->cgts", Cc, Bc)                  # (c, G, Q, Q)
    m = decay.reshape(c, g, r, q, q) * cb[:, :, None]
    y = _dot("cgrts,csgrp->ctgrp", m, xdt)
    # Each chunk's own contribution to the state at its end.
    to_end = jnp.exp(b[..., -1:] - b)                     # (c, H, Q)
    own = _dot("csgrp,csgn->cgrpn",
               xdt * to_end.transpose(0, 2, 1).reshape(c, q, g, r, 1), Bc)

    def carry(S, chunk_):
        own_c, through = chunk_
        return through[..., None, None] * S + own_c, S

    S, S_in = jax.lax.scan(
        carry, initial_state.reshape(g, r, p, n),
        (own, jnp.exp(b[..., -1]).reshape(c, g, r)))
    from_start = jnp.exp(b).transpose(0, 2, 1).reshape(c, q, g, r, 1)
    y = y + _dot("ctgn,cgrpn->ctgrp", Cc, S_in) * from_start
    y = y.reshape(c * q, h, p)[:t] + D[:, None] * x[:t]
    return y, S.reshape(h, p, n)


def chunk_scan_impl(rows: int, heads: int, head_dim: int, groups: int,
                    state: int, chunk: int = CHUNK) -> str:
    """What scans a prefill chunk of ``rows`` rows: ``"kernel"``
    (:func:`~triton_dist_tpu.ops.mamba2_chunk_scan.ssd_chunk_scan`)
    where the rows are whole scan chunks and chunk, head and state tile
    for Mosaic, else ``"xla"`` (:func:`ssd_chunked`). A pure function of
    sizes: the same program on a chip and, interpreted, off it; the
    serving engine counts its chunk dispatches by it."""
    ok = _scan.legal(rows, heads, head_dim, groups, state, chunk)
    return "kernel" if ok else "xla"


def ssd_prefill(x, dt, A, B, C, D, initial_state=None, *,
                chunk: int = CHUNK):
    """:func:`ssd_chunked`'s contract, in the form
    :func:`chunk_scan_impl` picks for these sizes."""
    impl = chunk_scan_impl(*x.shape, *B.shape[1:], chunk)
    scan = _scan.ssd_chunk_scan if impl == "kernel" else ssd_chunked
    return scan(x, dt, A, B, C, D, initial_state, chunk=chunk)
