"""Rotary position embeddings: Qwen3's NTK-free rope in the
half-rotation layout of HF transformers, and YaRN's blended frequencies
over interleaved pairs for the latent-attention family."""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float = 1_000_000.0):
    """Inverse frequencies, shape (head_dim // 2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, inv_freq):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, m: float) -> float:
    """YaRN's attention factor for ``mscale`` ``m``."""
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, *, factor: float,
               original: int, beta_fast: float = 32.0,
               beta_slow: float = 1.0):
    """YaRN inverse frequencies, shape (head_dim // 2,): pair ``i``
    keeps ``f_i`` where its wavelength fits the ``original`` length
    ``beta_fast`` times or more, takes ``f_i / factor`` where it fits
    ``beta_slow`` times or fewer, and a linear ramp over the pair index
    between (bounds floored and ceiled, as the published
    initialisation). ``factor`` 1 is :func:`rope_freqs`."""
    f = rope_freqs(head_dim, theta)
    if factor <= 1.0:
        return f

    def pair_of(turns):
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def apply_rope_interleaved(x, positions, inv_freq, scale: float = 1.0):
    """x: (n, ..., head_dim), pairs interleaved: ``(x[2i], x[2i+1])``
    turns by ``positions[n] * inv_freq[i]`` in place; cos and sin times
    ``scale`` (YaRN's ratio of attention factors)."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos = (jnp.cos(ang) * scale).reshape(shape)
    sin = (jnp.sin(ang) * scale).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)
