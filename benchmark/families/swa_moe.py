"""Window and global attention mixed over routed experts
(``exaone_moe``), as a model family: what the plain reference and the
roofline counts need of it, and nothing of the program. The program's
side is ``swa_moe_system.py``.

Every layer is ``a = x + attn(rms(x, ln_attn))``, ``x' = a + ffn(rms(a,
ln_mlp))``, float32, no biases. Its kind is two words,
``<window|global>_<dense|sparse>`` (``layer_kind``), the first by the
layer's entry in ``layer_types``, the second in ``mlp_layer_types``:

attention (``h`` the normed row; H heads over KV heads of hd):

    q, k, v = h wq, h wk, h wv
    q, k <- rms over a head's hd values (q_norm, k_norm)
    window: q, k rotated (rotate-half rope at rope_theta over all hd
            values); row i reads keys j with  i - window < j <= i
    global: NO rotation; row i reads keys j <= i
    scores q k^T / sqrt(hd), softmax, wo

  the mask written as that inequality over the WHOLE sequence: no cache,
  no pages, no ring.

dense: ``SwiGLU(u)``, d -> intermediate_size -> d.

sparse: ``s = sigmoid(u router)`` over ALL experts; the ``topk`` largest
of ``s + router_bias`` chosen; weights ``scale * s / sum(s)`` over the
chosen; of the chosen experts the ones THIS CHIP HOLDS: ``sum w_e
SwiGLU_e(u)``; plus ``SwiGLU_shared(u)``, once.

Conventions and departures, each also under ``assumed`` in the
configuration's file. CONVENTIONS (``config`` leaves them open; the
family's published code settles them): (1) the residual form ``x +
f(rms(x))`` (EXAONE 4.0's dense models norm the sublayer's OUTPUT
instead); (2) q/k norm over ``head_dim`` BEFORE the rotation, and
rotation on window layers ONLY; (3) the selection bias, DeepSeek-V3's,
used for the choice alone; (4) the shared expert ungated (no sigmoid
gate on its output), ``moe_intermediate_size x num_shared_experts``
wide. DEPARTURES: what the absent experts would add is left out, here as
in the program (the guide's cut: a chip's share); (5) the multi-token-
prediction module is left out.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmark.harness.opcount import head_params
from benchmark.harness.reference import rms

# A leaf's fold under its layer's key. Never renumbered: the served
# weights of every seed follow from it.
LEAF_IDS = {n: i for i, n in enumerate((
    "ln_attn", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln_mlp",
    "w_gate", "w_up", "w_down", "router", "router_bias", "experts_gate",
    "experts_up", "experts_down", "shared_gate", "shared_up",
    "shared_down"))}

ATTN = {"sliding_attention": "window", "full_attention": "global"}
# The routed experts' down-projections are drawn at ROUTED_GAIN of
# fan_in^-1/2 (``mla_moe.py`` and ``mamba_latent_moe.py`` have the
# history). A sigmoid router weighs its 8 choices almost alike, 2.5 / 8
# each, so an eighth and ninth choice that swap on rounding trade an
# expert of that weight in ANY precision, and such swaps, not the
# products' rounding, are what a sound run's widest gap would read.
# Under the published scale of 2.5 a gain of 1/16 leaves the routed sum
# 5/32 of what fan_in^-1/2 gives, the size ``mamba_latent_moe``'s 1/32
# leaves under its scale of 5; int8 in every linear layer still does
# what it did. The router's own leaf stays at fan_in^-1/2: its spread
# scales the scores' gaps and the rounding between them alike. The
# readings are under ``correct.readings`` in the configuration's file.
ROUTED_GAIN = 0.0625
# Query rows the reference attends at once: 64 heads x 128 rows x 12,544
# keys of float32 scores are 0.41 GB.
QUERY_BLOCK = 128
# Rows an expert is given a pass, as a multiple of the even share.
CAPACITY_FACTOR = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes, read from a configuration file (the published key
    names; ``router_outputs`` and ``first_held_expert`` are this
    benchmark's, for the chip's share)."""
    vocab: int
    d: int
    layers: int
    attn: tuple               # "window" | "global", a layer
    ffn: tuple                # "dense" | "sparse", a layer
    window: int
    eps: float
    tie: bool
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    dense_ff: int
    router_experts: int       # the router's width: every expert
    held: int                 # experts whose weights are here
    first_held: int           # the first of them
    topk: int
    expert_ff: int
    shared_ff: int
    norm_topk_prob: bool
    routed_scale: float

    def count(self, word: str) -> int:
        """Layers whose kind holds ``word``."""
        return sum(word in (a, f) for a, f in zip(self.attn, self.ffn))


def dims(c: dict) -> Dims:
    n = int(c["num_hidden_layers"])
    types, mlps = c["layer_types"], c["mlp_layer_types"]
    if len(types) != n or set(types) - set(ATTN):
        raise ValueError(f"layer_types {types!r}: one of {sorted(ATTN)} "
                         f"for each of the {n} layers")
    if len(mlps) != n or set(mlps) - {"dense", "sparse"}:
        raise ValueError(f"mlp_layer_types {mlps!r}: 'dense' or 'sparse' "
                         f"for each of the {n} layers")
    if int(c.get("n_group", 1)) != 1 or int(c.get("topk_group", 1)) != 1:
        raise ValueError("swa_moe routes among all experts (n_group 1, "
                         "topk_group 1)")
    rope = c["rope_parameters"]
    if rope["rope_type"] != "default" or c["scoring_func"] != "sigmoid":
        raise ValueError("swa_moe computes plain rope and a sigmoid router")
    return Dims(vocab=int(c["vocab_size"]), d=int(c["hidden_size"]),
                layers=n, attn=tuple(ATTN[t] for t in types),
                ffn=tuple(mlps), window=int(c["sliding_window"]),
                eps=float(c["rms_norm_eps"]),
                tie=bool(c.get("tie_word_embeddings", False)),
                heads=int(c["num_attention_heads"]),
                kv_heads=int(c["num_key_value_heads"]),
                head_dim=int(c["head_dim"]),
                rope_theta=float(rope["rope_theta"]),
                dense_ff=int(c["intermediate_size"]),
                router_experts=int(c["router_outputs"]),
                held=int(c["num_experts"]),
                first_held=int(c["first_held_expert"]),
                topk=int(c["num_experts_per_tok"]),
                expert_ff=int(c["moe_intermediate_size"]),
                shared_ff=int(c["moe_intermediate_size"])
                * int(c["num_shared_experts"]),
                norm_topk_prob=bool(c["norm_topk_prob"]),
                routed_scale=float(c["routed_scaling_factor"]))


def layer_kind(dims: Dims, li: int) -> str:
    return f"{dims.attn[li]}_{dims.ffn[li]}"


def layer_leaves(dims: Dims, kind: str) -> dict:
    """name -> (shape, kind of leaf, scale); ``weights._leaf`` has the
    kinds. The routed experts are stacked, ``held`` of them. The
    attention's leaves are the same for both kinds of attention."""
    attn, ffn = kind.split("_")
    if attn not in ("window", "global") or ffn not in ("dense", "sparse"):
        raise ValueError(f"kind {kind!r}")
    d, hd = dims.d, dims.head_dim
    q, kv = dims.heads * hd, dims.kv_heads * hd
    out = {
        "ln_attn": ((d,), "g", None),
        "wq": ((d, q), "w", d ** -0.5), "wk": ((d, kv), "w", d ** -0.5),
        "wv": ((d, kv), "w", d ** -0.5), "wo": ((q, d), "w", q ** -0.5),
        "q_norm": ((hd,), "g", None), "k_norm": ((hd,), "g", None),
        "ln_mlp": ((d,), "g", None)}
    if ffn == "dense":
        ff = dims.dense_ff
        out.update(w_gate=((d, ff), "w", d ** -0.5),
                   w_up=((d, ff), "w", d ** -0.5),
                   w_down=((ff, d), "w", ff ** -0.5))
        return out
    e, f, fs = dims.held, dims.expert_ff, dims.shared_ff
    out.update(
        router=((d, dims.router_experts), "w", d ** -0.5),
        router_bias=((dims.router_experts,), "b", None),
        experts_gate=((e, d, f), "w", d ** -0.5),
        experts_up=((e, d, f), "w", d ** -0.5),
        experts_down=((e, f, d), "w", ROUTED_GAIN * f ** -0.5),
        shared_gate=((d, fs), "w", d ** -0.5),
        shared_up=((d, fs), "w", d ** -0.5),
        shared_down=((fs, d), "w", fs ** -0.5))
    return out


# -- the layer -------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def _rope(x, positions, theta):
    """x: (S, heads, hd); rotate-half form, as the published models."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, dims: Dims, dot, window: bool):
    """The attention half, its residual added; the scores are taken
    ``QUERY_BLOCK`` query rows at a time, each block over every key."""
    s, h, kv, hd = x.shape[0], dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(s)
    y = rms(x, w["ln_attn"], dims.eps)
    q = rms(dot(y, w["wq"]).reshape(s, h, hd), w["q_norm"], dims.eps)
    k = rms(dot(y, w["wk"]).reshape(s, kv, hd), w["k_norm"], dims.eps)
    v = dot(y, w["wv"]).reshape(s, kv, hd)
    if window:
        q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    q = q.reshape(s, kv, h // kv, hd)
    block = math.gcd(s, QUERY_BLOCK)

    def rows(args):
        qb, i = args
        i, j = i[None, None, :, None], pos[None, None, None, :]
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k,
                        precision=_HI) * hd ** -0.5
        seen = j <= i
        if window:
            seen = seen & (i - dims.window < j)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(sc, axis=-1), v,
                          precision=_HI)

    o = jax.lax.map(rows, (q.reshape(s // block, block, kv, h // kv, hd),
                           pos.reshape(s // block, block)))
    return x + dot(o.reshape(s, h * hd), w["wo"])


def dense_ffn(x, w, dims: Dims, dot):
    y = rms(x, w["ln_mlp"], dims.eps)
    return x + dot(jax.nn.silu(dot(y, w["w_gate"])) * dot(y, w["w_up"]),
                   w["w_down"])


def route(y, w, dims: Dims, dot):
    """(S, k) expert ids over the whole router and their weights: the
    bias chooses, the scores without it weigh."""
    scores = jax.nn.sigmoid(dot(y, w["router"]))
    top_e = jax.lax.top_k(scores + w["router_bias"], dims.topk)[1]
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if dims.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w * dims.routed_scale


def expert_capacity(dims: Dims, rows: int) -> int:
    even = -(-rows * dims.topk // dims.router_experts)
    return min(rows, CAPACITY_FACTOR * even)


def experts(x, w, dims: Dims, dot):
    """The expert half, its residual added: the shared expert over every
    row, and each HELD expert over the rows routed to it, gathered at a
    fixed shape, ``expert_capacity`` rows an expert a pass, in as many
    passes as the fullest expert needs: no row is ever dropped
    (``mla_moe.experts`` has why)."""
    e = dims.held
    y = rms(x, w["ln_mlp"], dims.eps)
    top_e, top_w = route(y, w, dims, dot)
    local = top_e - dims.first_held
    key = jnp.where((local >= 0) & (local < e), local, e).reshape(-1)
    order = jnp.argsort(key, stable=True)        # pairs, by held expert
    counts = jnp.bincount(key, length=e + 1)[:e]
    starts = jnp.cumsum(counts) - counts
    cap = expert_capacity(dims, x.shape[0])
    weights = top_w.reshape(-1)

    def one_pass(carry):
        n, routed = carry
        j = n * cap + jnp.arange(cap)
        pair = order[jnp.clip(starts[:, None] + j[None], 0,
                              key.shape[0] - 1)]            # (e, cap)
        row = pair // dims.topk
        weight = jnp.where(j[None] < counts[:, None], weights[pair], 0.0)

        def one(routed, args):
            g, u, dn, rows_e, w_e = args
            xe = y[rows_e]
            out = dot(jax.nn.silu(dot(xe, g)) * dot(xe, u), dn)
            return routed.at[rows_e].add(out * w_e[:, None]), None

        routed, _ = jax.lax.scan(one, routed, (
            w["experts_gate"], w["experts_up"], w["experts_down"], row,
            weight))
        return n + 1, routed

    _, routed = jax.lax.while_loop(
        lambda carry: carry[0] * cap < jnp.max(counts), one_pass,
        (jnp.zeros((), jnp.int32), jnp.zeros_like(x)))
    shared = dot(jax.nn.silu(dot(y, w["shared_gate"]))
                 * dot(y, w["shared_up"]), w["shared_down"])
    return x + shared + routed


def layer(x, w, kind: str, dims: Dims, dot):
    """One layer over a whole sequence. x: (S, d) float32; ``w`` the
    layer's leaves, already float32; ``dot`` the product of the linear
    layers (``highest``, or the control's int8)."""
    attn, ffn = kind.split("_")
    x = attention(x, w, dims, dot, window=attn == "window")
    return (dense_ffn if ffn == "dense" else experts)(x, w, dims, dot)


# -- operations and bytes the algorithm needs, from shapes alone ----------

def _attn_params(d: Dims) -> int:
    return 2 * d.d * d.head_dim * (d.heads + d.kv_heads)


def _expert_params(d: Dims) -> int:
    return 3 * d.d * d.expert_ff


def _always_params(d: Dims) -> int:
    """Matrix parameters a row goes through in the whole model, outside
    the routed experts and the head: attention everywhere, the dense
    layers' FFN, the sparse layers' router and shared expert."""
    return (d.layers * _attn_params(d)
            + d.count("dense") * 3 * d.d * d.dense_ff
            + d.count("sparse") * (d.d * d.router_experts
                                   + 3 * d.d * d.shared_ff))


def kv_bytes_per_token(d: Dims, itemsize: int = 2) -> int:
    """Keys and values of one position in ONE layer."""
    return 2 * d.kv_heads * d.head_dim * itemsize


def _pairs(d: Dims, rows: int, held_pairs) -> float:
    """Held token-expert pairs of a ``rows``-row program over ALL its
    sparse layers. ``held_pairs`` is the count as
    ``reducers/roofline_max.served_pairs`` hands it, the program's pairs
    over ``d.layers``; without it: the even share."""
    if held_pairs is None:
        return (d.count("sparse") * rows * d.topk * d.held
                / d.router_experts)
    return held_pairs * d.layers


def experts_chunk_flops(d: Dims, rows: int, held_pairs=None) -> float:
    """Operations of the held experts' products for a ``rows``-row
    program, all sparse layers: the held pairs as served, each through
    one expert."""
    return 2.0 * _pairs(d, rows, held_pairs) * _expert_params(d)


def experts_chunk_bytes(d: Dims, rows: int, held_pairs=None,
                        itemsize: int = 2) -> float:
    """Bytes they must move: the held experts' matrices once each (the
    certain upper bound: at a hundred rows an expert none goes unread),
    a pair's row gathered and its result written."""
    return float(itemsize * (
        d.count("sparse") * d.held * _expert_params(d)
        + 2 * _pairs(d, rows, held_pairs) * d.d))


def attn_window_chunk_flops(d: Dims, rows: int) -> float:
    """Operations of the WINDOW layers' attention for a chunk of
    ``rows`` rows deep in a sequence, all such layers: a row's scores
    against ``window`` keys and the weighted sum of their values,
    whatever form computes them."""
    return float(d.count("window") * 4 * rows * d.window
                 * d.heads * d.head_dim)


def attn_window_chunk_bytes(d: Dims, rows: int,
                            itemsize: int = 2) -> float:
    """Bytes that block must move: the rows' queries read and their
    results written, keys and values of the chunk's own positions and
    of the ``window - 1`` before them read once."""
    per_layer = (2 * rows * d.heads * d.head_dim * itemsize
                 + (rows + d.window - 1) * kv_bytes_per_token(d, itemsize))
    return float(d.count("window") * per_layer)


def decode_step_bytes(d: Dims, context_tokens: float, batch: float = 0, *,
                      tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must move for one decode step: the always-read
    matrices and the head once, the held experts that an EVEN routing
    of ``batch`` rows reaches (``mamba_latent_moe.decode_step_bytes``
    has the count), keys and values of ``context_tokens`` positions in
    every global layer, and in every window layer of ``window``
    positions a sequence at most."""
    read = d.held * (1.0 - (1.0 - d.topk / d.router_experts) ** batch)
    weights = (_always_params(d) + d.count("sparse") * read
               * _expert_params(d) + head_params(d)) * itemsize
    in_window = min(context_tokens, max(batch, 1) * d.window)
    kv = kv_bytes_per_token(d, itemsize) * (
        d.count("global") * context_tokens + d.count("window") * in_window)
    return (weights + kv) / tp


def prefill_chunk_flops(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, held_pairs: float = None) -> float:
    """Floating-point operations one chip needs for a prefill chunk of
    ``rows`` tokens: a row goes through the always-read matrices and
    attends ``context_mean`` keys in each global layer and
    ``min(context_mean, window)`` in each window layer; the held pairs
    go through an expert (``held_pairs`` as :func:`_pairs` reads it);
    the head for the one row whose logits the chunk returns."""
    gemm = 2.0 * rows * _always_params(d)
    keys = (d.count("global") * context_mean
            + d.count("window") * min(context_mean, d.window))
    attn = 4.0 * rows * keys * d.heads * d.head_dim
    return (gemm + experts_chunk_flops(d, rows, held_pairs) + attn
            + 2.0 * head_params(d)) / tp


def prefill_chunk_bytes(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must move for that chunk: the always-read
    matrices, the held experts' and the head once, the chunk's own keys
    and values written once a layer, those of the positions before the
    chunk read once in a global layer and of the last ``window - 1`` of
    them in a window layer. ``context_mean`` is the mean keys a row
    attends in a global layer: the positions before the chunk plus half
    the chunk."""
    before = max(context_mean - (rows + 1) / 2, 0.0)
    weights = (_always_params(d) + d.count("sparse") * d.held
               * _expert_params(d) + head_params(d)) * itemsize
    positions = (d.count("global") * (before + rows)
                 + d.count("window") * (min(before, d.window - 1) + rows))
    return (weights + positions * kv_bytes_per_token(d, itemsize)) / tp
