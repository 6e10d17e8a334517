"""A family whose layers run several times, as test data only, and its
reference half only (the program serves no such model yet, so there is
no ``tiny_looped_system.py`` and no cell). It shows that such a model
arrives as files: it states its own ``trunk`` (``harness/reference.py``
has the contract), and ``benchmark/harness`` knows nothing of it.
Imports nothing of the program.

The model: ``dims.layers`` blocks with FOUR norms each, before and after
each sublayer,

    a  = u + N2(Attn(N1(u)))        u' = a + N4(MLP(N3(a)))

full multi-head attention with rotate-half rope, a SwiGLU MLP, no
biases. The stack runs ``dims.passes`` times over the SAME weights, the
model's final norm ``N_f`` after every pass: ``h_0 = E[ids]``, pass
``t`` runs the blocks over ``h_{t-1}`` to ``u_t`` and ``h_t =
N_f(u_t)``. After every pass an exit gate ``g_t = sigmoid(w . h_t +
b)``, one row that belongs to no layer (index ``dims.layers``, kind
``gate``); ``p_t = g_t prod_{s<t} (1 - g_s)`` and the last pass takes
the remainder. A position leaves at the first pass whose cumulated ``p``
reaches ``dims.exit_threshold`` (the last pass where none does), and its
logits are the head over that pass's ``h_t``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import rms

LEAF_IDS = {n: i for i, n in enumerate((
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln_attn_in",
    "ln_attn_out", "ln_mlp_in", "ln_mlp_out", "exit_row", "exit_bias"))}


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    ff: int
    layers: int
    heads: int
    head_dim: int
    eps: float
    rope_theta: float
    tie: bool
    passes: int
    exit_threshold: float


def dims(c: dict) -> Dims:
    return Dims(vocab=int(c["vocab_size"]), d=int(c["hidden_size"]),
                ff=int(c["intermediate_size"]),
                layers=int(c["num_hidden_layers"]),
                heads=int(c["num_attention_heads"]),
                head_dim=int(c["head_dim"]),
                eps=float(c["rms_norm_eps"]),
                rope_theta=float(c["rope_theta"]),
                tie=bool(c.get("tie_word_embeddings", False)),
                passes=int(c["total_ut_steps"]),
                exit_threshold=float(c["early_exit_threshold"]))


def layer_kind(dims: Dims, li: int) -> str:
    return "block" if li < dims.layers else "gate"


def layer_leaves(dims: Dims, kind: str = "block") -> dict:
    d, ff, q = dims.d, dims.ff, dims.heads * dims.head_dim
    if kind == "gate":
        return {"exit_row": ((d, 1), "w", d ** -0.5),
                "exit_bias": ((1,), "b", None)}
    return {"wq": ((d, q), "w", d ** -0.5), "wk": ((d, q), "w", d ** -0.5),
            "wv": ((d, q), "w", d ** -0.5), "wo": ((q, d), "w", q ** -0.5),
            "w_gate": ((d, ff), "w", d ** -0.5),
            "w_up": ((d, ff), "w", d ** -0.5),
            "w_down": ((ff, d), "w", ff ** -0.5),
            "ln_attn_in": ((d,), "g", None), "ln_attn_out": ((d,), "g", None),
            "ln_mlp_in": ((d,), "g", None), "ln_mlp_out": ((d,), "g", None)}


def _rope(x, theta):
    """x: (S, heads, hd), rotate-half."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(y, w, dims, dot):
    s, h, hd = y.shape[0], dims.heads, dims.head_dim
    q, k, v = (dot(y, w[n]).reshape(s, h, hd) for n in ("wq", "wk", "wv"))
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    sc = jnp.einsum("qhd,khd->hqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    pos = jnp.arange(s)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return dot(o.reshape(s, h * hd), w["wo"])


def layer(x, w, kind: str, dims: Dims, dot):
    """x: (S, d) float32. ``block``: one four-norm block, (S, d).
    ``gate``: the exit gate's scores before the sigmoid, (S, 1)."""
    if kind == "gate":
        return dot(x, w["exit_row"]) + w["exit_bias"]
    a = x + rms(_attend(rms(x, w["ln_attn_in"], dims.eps), w, dims, dot),
                w["ln_attn_out"], dims.eps)
    y = rms(a, w["ln_mlp_in"], dims.eps)
    m = dot(jax.nn.silu(dot(y, w["w_gate"])) * dot(y, w["w_up"]),
            w["w_down"])
    return a + rms(m, w["ln_mlp_out"], dims.eps)


def trunk(x, apply, final_norm, dims: Dims):
    """The passes over the shared blocks, the final norm between them,
    and the pick among passes. Returns, for every position, its exit
    pass's ``u_t``: the head's own final norm makes it ``h_t``."""
    h, out = x, None
    left = jnp.zeros(x.shape[:2] + (1,), bool)
    remaining = jnp.ones(x.shape[:2] + (1,), jnp.float32)
    cumulated = jnp.zeros_like(remaining)
    for t in range(dims.passes):
        u = h
        for li in range(dims.layers):
            u = apply(u, li, "block")
        h = final_norm(u)
        g = jax.nn.sigmoid(apply(h, dims.layers, "gate"))
        last = t == dims.passes - 1
        cumulated = cumulated + (remaining if last else g * remaining)
        remaining = remaining * (1.0 - g)
        leaves = ~left & ((cumulated >= dims.exit_threshold) | last)
        out = jnp.where(leaves, u, 0.0 if out is None else out)
        left = left | leaves
    return out
