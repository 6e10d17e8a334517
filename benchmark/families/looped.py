"""A decoder whose layers run several times over the same weights, as a
model family: what the plain reference and the roofline counts need of
it, and nothing of the program (``looped_system.py`` is the half that
builds the program's side; ``families/dense.py``'s docstring lists the
names a family gives).

The model (``model_type: ouro``; the widths, ``total_ut_steps`` and
``early_exit_threshold`` are the published config's, the rest is the
model's published modelling code and stands under ``assumed`` in the
configuration's file). With ``N`` an RMSNorm with a gain of its own:

    layer l:   a  = u + N2_l(Attn_l(N1_l(u)))
               u' = a + N4_l(MLP_l(N3_l(a)))

four norms a layer, before AND after each sublayer; grouped-query
attention with rotate-half rope, no bias, no q/k norm; a SwiGLU MLP. The
stack runs ``dims.passes`` times over the SAME ``dims.layers`` layers'
weights, the model's final norm ``N_f`` after every pass: ``h_0 =
E[ids]``, pass ``t`` runs the layers over ``h_{t-1}`` to ``u_t`` and
``h_t = N_f(u_t)``. After every pass an exit gate ``g_t = sigmoid(w_g .
h_t + b_g)``, one row and a bias that belong to no layer (index
``dims.layers``, kind ``gate``); ``p_t = g_t prod_{s<t} (1 - g_s)`` and
the last pass takes the remainder. A position leaves at the first pass
whose cumulated ``p`` reaches ``dims.exit_threshold`` (the last pass
where none does), and its logits are the head over that pass's ``h_t``.
Every pass runs for every position whatever the pick: pass ``t`` of
layer ``l`` attends the keys and values pass ``t`` of layer ``l``
computed, so a deployment keeps ``passes x layers`` caches.

Leaf layout (plain ``x @ w``): ``wq`` (d, H*hd), ``wk``/``wv`` (d,
KV*hd), ``wo`` (H*hd, d), ``w_gate``/``w_up`` (d, ff), ``w_down`` (ff,
d), ``exit_row`` (d, 1); query head ``h`` reads KV head ``h // (H //
KV)``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmark.harness.opcount import head_params
from benchmark.harness.reference import rms

# The seeded law of the two norms AFTER a sublayer: their gains are the
# seeded leaf (1 + 0.1 z, as every gain) times this constant, a power of
# two, so that the served bfloat16 leaf times it is exact. At 1 a stack of
# 192 applications of unit-gain sublayers sits at the edge of chaos: a
# pass multiplies a relative perturbation by (|u_L| / |u_0|)^(c^2 - 1),
# c the sublayer's own gain, and whether c is over 1 is the seed's draw
# (float32 against the same forward rounded to bfloat16, at d 256: gaps
# of 0.13-0.46 with 55-61 % of positions keeping their best token, the
# int8 control 0.61-1.24; at 1/8: 0.007-0.015 and 0.033-0.112; PERF.md,
# PR 48). A trained model's gains keep it stable; seeded ones must be
# told to. It belongs to the configuration's ``assumed.seeded_laws``.
POST_NORM_GAIN = 0.125

# A leaf's fold under its layer's key. Never renumbered: the served
# weights of every seed follow from it.
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
    kv_heads: int
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
                kv_heads=int(c["num_key_value_heads"]),
                head_dim=int(c["head_dim"]),
                eps=float(c["rms_norm_eps"]),
                rope_theta=float(c["rope_theta"]),
                tie=bool(c.get("tie_word_embeddings", False)),
                passes=int(c["total_ut_steps"]),
                exit_threshold=float(c["early_exit_threshold"]))


def layer_kind(dims: Dims, li: int) -> str:
    return "block" if li < dims.layers else "gate"


def layer_leaves(dims: Dims, kind: str = "block") -> dict:
    """name -> (shape, kind of leaf, scale); ``weights._leaf`` has the
    kinds."""
    d, ff, hd = dims.d, dims.ff, dims.head_dim
    q, kv = dims.heads * hd, dims.kv_heads * hd
    if kind == "gate":
        return {"exit_row": ((d, 1), "w", d ** -0.5),
                "exit_bias": ((1,), "b", None)}
    return {"wq": ((d, q), "w", d ** -0.5), "wk": ((d, kv), "w", d ** -0.5),
            "wv": ((d, kv), "w", d ** -0.5), "wo": ((q, d), "w", q ** -0.5),
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
    s, h, kv, hd = y.shape[0], dims.heads, dims.kv_heads, dims.head_dim
    q = _rope(dot(y, w["wq"]).reshape(s, h, hd), dims.rope_theta)
    k = _rope(dot(y, w["wk"]).reshape(s, kv, hd), dims.rope_theta)
    v = dot(y, w["wv"]).reshape(s, kv, hd)
    q = q.reshape(s, kv, h // kv, hd)
    sc = jnp.einsum("qcgd,kcd->cgqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    pos = jnp.arange(s)
    sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                   sc, -jnp.inf)
    o = jnp.einsum("cgqk,kcd->qcgd", jax.nn.softmax(sc, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return dot(o.reshape(s, h * hd), w["wo"])


def layer(x, w, kind: str, dims: Dims, dot):
    """x: (S, d) float32. ``block``: one four-norm block, (S, d).
    ``gate``: the exit gate's scores before the sigmoid, (S, 1)."""
    if kind == "gate":
        return dot(x, w["exit_row"]) + w["exit_bias"]
    a = x + rms(_attend(rms(x, w["ln_attn_in"], dims.eps), w, dims, dot),
                w["ln_attn_out"] * POST_NORM_GAIN, dims.eps)
    y = rms(a, w["ln_mlp_in"], dims.eps)
    m = dot(jax.nn.silu(dot(y, w["w_gate"])) * dot(y, w["w_up"]),
            w["w_down"])
    return a + rms(m, w["ln_mlp_out"] * POST_NORM_GAIN, dims.eps)


def trunk(x, apply, final_norm, dims: Dims):
    """The passes over the shared blocks, the final norm between them,
    and the pick among passes (``harness/reference.py`` has the
    contract). Returns, for every position, its exit pass's ``u_t``: the
    head's own final norm makes it ``h_t``."""
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


# -- operations and bytes the algorithm needs, from shapes alone ----------

def layer_params(d: Dims) -> int:
    """Matrix parameters of one layer (the four gains are thousands
    against tens of millions: left out)."""
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return 2 * d.d * q + 2 * d.d * kv + 3 * d.d * d.ff


def kv_bytes_per_token(d: Dims, itemsize: int = 2) -> int:
    """Keys and values of one position: a cache for every pass of every
    layer."""
    return (2 * d.kv_heads * d.head_dim * itemsize * d.layers
            * d.passes)


def _weight_bytes(d: Dims, itemsize: int) -> int:
    """What a step reads of the weights: the layers once a PASS (a pass
    needs the one before it whole, and no chip keeps the stack between
    them), the head once; the gate's row is kilobytes."""
    return (d.passes * d.layers * layer_params(d)
            + head_params(d)) * itemsize


def decode_step_bytes(d: Dims, context_tokens: float, batch: float = 0, *,
                      tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must read for one decode step: the weights as
    above, plus every pass's cached keys and values of
    ``context_tokens`` positions (the running sequences' lengths,
    summed). ``batch`` changes nothing: every matrix is read whatever
    the rows."""
    return (_weight_bytes(d, itemsize)
            + context_tokens * kv_bytes_per_token(d, itemsize)) / tp


def prefill_chunk_flops(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1) -> float:
    """Floating-point operations one chip needs for a prefill chunk of
    ``rows`` tokens: every layer's GEMMs once a pass (2 per parameter
    per row), attention's two products against ``context_mean`` keys a
    row in every pass of every layer, and the head for the one row whose
    logits the chunk returns."""
    applications = d.passes * d.layers
    gemm = 2.0 * rows * applications * layer_params(d)
    attn = 4.0 * rows * context_mean * d.heads * d.head_dim * applications
    return (gemm + attn + 2.0 * head_params(d)) / tp


def prefill_chunk_bytes(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must move for that chunk: the weights as a step
    reads them, every pass's keys and values of the positions before the
    chunk read once and the chunk's own written once. ``context_mean``
    is the mean keys a row attends: the positions before the chunk plus
    half the chunk."""
    before = max(context_mean - (rows + 1) / 2, 0.0)
    return (_weight_bytes(d, itemsize)
            + (before + rows) * kv_bytes_per_token(d, itemsize)) / tp
