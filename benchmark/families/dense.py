"""The dense decoder as a model family: what the plain reference and
the roofline counts need of it, and nothing of the program.

A family is a module with these names, found through the ``family`` key
of a configuration's file (``loader.load_family``); the half that builds
the program's side is ``dense_system.py``.

    dims(config)                      the sizes, frozen and hashable
    LEAF_IDS, layer_leaves(dims, kind)   a layer's seeded leaves
    layer_kind(dims, li)              a static name for layer ``li``
    layer(x, w, kind, dims, dot)      one layer's float32 forward
                                      (``attention`` is its first half)
    decode_step_bytes, prefill_chunk_flops   the algorithm's least needs

and, only where its layers do not run each once in the order of their
index (several passes over the same weights, something between layers,
leaves that belong to no layer),

    trunk(x, apply, final_norm, dims)   everything between the embedding
                                        and the head's norm

whose contract is ``harness/reference.py``'s docstring: ``apply(x, li,
kind)`` runs ``layer`` of that ``kind`` on the seeded leaves of index
``li`` (the same index, the same leaves; an index past ``dims.layers``
for what belongs to no layer) and returns what ``layer`` returns;
``final_norm(x)`` is the head's norm; the int8 control rides ``apply``.
A family without one, as this one, has layers ``0 .. dims.layers - 1``
applied once each. The harness itself reads ``vocab``, ``d``, ``eps``
and ``tie`` of ``dims``, and ``layers`` where there is no ``trunk``.

Pre-norm blocks of grouped-query attention (rotate-half rope, optional
per-head q/k norm, optional q/k/v biases) and a SwiGLU MLP. Leaf layout
(plain ``x @ w``): ``wq`` (d, H*hd), ``wk``/``wv`` (d, KV*hd), ``wo``
(H*hd, d), ``w_gate``/``w_up`` (d, ff), ``w_down`` (ff, d); query head
``h`` reads KV head ``h // (H // KV)``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmark.harness.opcount import head_params
from benchmark.harness.reference import rms

# A leaf's fold under its layer's key. Never renumbered: the served
# weights of every seed follow from it. 14 is the final norm's
# (``weights.py``).
LEAF_IDS = {n: i for i, n in enumerate((
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln_attn",
    "ln_mlp", "q_norm", "k_norm", "bq", "bk", "bv"))}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, read from a configuration file
    (Hugging Face key names)."""
    vocab: int
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    rope_theta: float
    qk_norm: bool
    attention_bias: bool
    tie: bool


def dims(c: dict) -> Dims:
    return Dims(vocab=int(c["vocab_size"]), d=int(c["hidden_size"]),
                ff=int(c["intermediate_size"]),
                layers=int(c["num_hidden_layers"]),
                heads=int(c["num_attention_heads"]),
                kv_heads=int(c["num_key_value_heads"]),
                head_dim=int(c["head_dim"]),
                eps=float(c["rms_norm_eps"]),
                rope_theta=float(c["rope_theta"]),
                qk_norm=bool(c["qk_norm"]),
                attention_bias=bool(c["attention_bias"]),
                tie=bool(c.get("tie_word_embeddings", False)))


def layer_kind(dims: Dims, li: int) -> str:
    """Every layer is the same block."""
    return "block"


def attention_leaves(dims) -> dict:
    """The leaves ``attention`` reads, and the second norm's gain."""
    d, hd = dims.d, dims.head_dim
    q, kv = dims.heads * hd, dims.kv_heads * hd
    out = {
        "wq": ((d, q), "w", d ** -0.5), "wk": ((d, kv), "w", d ** -0.5),
        "wv": ((d, kv), "w", d ** -0.5), "wo": ((q, d), "w", q ** -0.5),
        "ln_attn": ((d,), "g", None), "ln_mlp": ((d,), "g", None),
    }
    if dims.qk_norm:
        out["q_norm"] = ((hd,), "g", None)
        out["k_norm"] = ((hd,), "g", None)
    if dims.attention_bias:
        out["bq"] = ((q,), "b", None)
        out["bk"] = ((kv,), "b", None)
        out["bv"] = ((kv,), "b", None)
    return out


def layer_leaves(dims: Dims, kind: str = "block") -> dict:
    """name -> (shape, kind of leaf, scale); ``weights._leaf`` has the
    kinds."""
    d, ff = dims.d, dims.ff
    out = dict(attention_leaves(dims),
               w_gate=((d, ff), "w", d ** -0.5),
               w_up=((d, ff), "w", d ** -0.5),
               w_down=((ff, d), "w", ff ** -0.5))
    # In the order of their ids, the order they were always made in:
    # the programs that make a layer stay the ones the cache holds.
    return {k: out[k] for k in LEAF_IDS if k in out}


def _rope(x, positions, theta):
    """x: (S, heads, hd); rotate-half form, as the published models."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, dims, dot):
    """The attention half of a layer, its residual added. ``dims`` needs
    ``heads``, ``kv_heads``, ``head_dim``, ``eps``, ``rope_theta``,
    ``qk_norm`` and ``attention_bias``: a family that differs in the
    other half only (``tests/benchmark/data/families/tiny_moe.py``)
    calls this and ``attention_leaves`` with its own."""
    s = x.shape[0]
    h, kv, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(s)
    y = rms(x, w["ln_attn"], dims.eps)
    q, k, v = dot(y, w["wq"]), dot(y, w["wk"]), dot(y, w["wv"])
    if dims.attention_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = (q.reshape(s, h, hd), k.reshape(s, kv, hd),
               v.reshape(s, kv, hd))
    if dims.qk_norm:
        q = rms(q, w["q_norm"], dims.eps)
        k = rms(k, w["k_norm"], dims.eps)
    q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    q = q.reshape(s, kv, h // kv, hd)
    sc = jnp.einsum("qcgd,kcd->cgqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                   sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("cgqk,kcd->qcgd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, h * hd)
    return x + dot(o, w["wo"])


def layer(x, w, kind: str, dims: Dims, dot):
    """One decoder layer over a whole sequence. x: (S, d) float32; ``w``
    the layer's leaves, already float32; ``dot`` the product of the
    linear layers (``highest``, or the control's int8)."""
    x = attention(x, w, dims, dot)
    y = rms(x, w["ln_mlp"], dims.eps)
    return x + dot(jax.nn.silu(dot(y, w["w_gate"])) * dot(y, w["w_up"]),
                   w["w_down"])


# -- operations and bytes the algorithm needs, from shapes alone ----------

def layer_params(d: Dims) -> int:
    """Matrix parameters of one decoder layer (norm gains and biases
    are thousands against hundreds of millions: left out)."""
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return 2 * d.d * q + 2 * d.d * kv + 3 * d.d * d.ff


def kv_bytes_per_token(d: Dims, itemsize: int = 2) -> int:
    """Keys and values of one position, all layers."""
    return 2 * d.kv_heads * d.head_dim * itemsize * d.layers


def decode_step_bytes(d: Dims, context_tokens: float, batch: float = 0, *,
                      tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must read for one decode step: its share of every
    layer's matrices and of the head, once, plus the cached keys and
    values of ``context_tokens`` positions (the running sequences'
    lengths, summed). The embedding rows gathered and the activations
    are kilobytes. ``batch``, the sequences decoding, changes nothing
    here: every matrix is read whatever the rows."""
    weights = (d.layers * layer_params(d) + head_params(d)) * itemsize
    return (weights + context_tokens * kv_bytes_per_token(d, itemsize)) / tp


def prefill_chunk_flops(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1) -> float:
    """Floating-point operations one chip needs for a prefill chunk of
    ``rows`` tokens: every layer's GEMMs (2 per parameter per row),
    attention's two products against ``context_mean`` keys a row (the
    causal mean: positions before the chunk plus half the chunk), and
    the head for the one row whose logits the chunk returns."""
    gemm = 2.0 * rows * d.layers * layer_params(d)
    attn = 4.0 * rows * context_mean * d.heads * d.head_dim * d.layers
    head = 2.0 * head_params(d)
    return (gemm + attn + head) / tp
