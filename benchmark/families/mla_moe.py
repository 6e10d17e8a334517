"""Latent attention over routed experts, as a model family: what the
plain reference and the roofline counts need of it, and nothing of the
program. The program's side is ``mla_moe_system.py``.

Every layer is one block (``x`` a row of the residual stream, float32):

    h = rms(x, ln_attn)
    c_q = rms(h w_dq, q_norm);  q = c_q w_uq          (H, d_n + d_r)
    [c_kv | k_r] = h w_dkv;  c = rms(c_kv, kv_norm)   (r_kv), (d_r)
    rope on q's last d_r and on k_r (one vector for all heads): pairs
        interleaved, YaRN frequencies; q *= 1 + beta ln(1 + pos // L0)
    [k_n | v] = c w_ukv                               (H, d_n + d_v)
    s = (q_n . k_n + q_r . k_r) sigma, causal;  o = softmax(s) v
    y = x + concat(o) wo
    h2 = rms(y, ln_mlp);  p = softmax(h2 router) over ALL experts
    the top-k, renormalised, times the routed scale; of them the ones
    THIS CHIP HOLDS (experts first_held .. first_held + held - 1):
    routed = sum w_e down_e(silu(gate_e h2) * up_e h2)
    out = y + shared(h2) + routed          (shared: ungated SwiGLU)

What the absent experts would add is left out, here as in the program
(the guide's cut: a chip's share of an expert-parallel deployment).
Leaf layout is plain ``x @ w``. The cache a server keeps of this block
is ``[c | roped k_r]``, ``r_kv + d_r`` values a token a layer.
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
    "ln_attn", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_ukv",
    "wo", "ln_mlp", "router", "experts_gate", "experts_up",
    "experts_down", "shared_gate", "shared_up", "shared_down"))}

# Two of the seeded leaves are not drawn at fan_in^-1/2, so that six
# seeded layers are a model a comparison of logits can judge. A token's
# fourth and fifth choice swap on rounding in a few of a hundred tokens a
# layer, in any precision; with every leaf at fan_in^-1/2 the swap trades
# an expert of a fifth of the routed weight, the next layers' routers see
# the difference, and the stack is chaotic: on the chip the sound bf16
# program read 1.50-2.05 and the int8 control 1.70-2.85, and no limit
# could tell them apart (PERF.md section 6, PR 37, has every reading).
# The router's leaf is drawn ROUTER_SPREAD times wider: the ORDER of a
# token's experts, so its choices and every expert's load, is the same
# at any spread, but the fourth choice weighs 0.04, not 0.18 (sound
# 0.21-1.07, control 1.25-2.03). The routed experts' down-projections are
# drawn at ROUTED_GAIN of theirs, so a swapped expert moves the residual
# an eighth as far (at a quarter: sound 0.08-0.35, control 0.67-0.87; at
# an eighth: 0.08-0.14 and 0.66-1.00), while int8 in every linear layer
# still does what it did, and a routed path that is wrong still reads
# over the control (every expert's down-projection its neighbour's, at a
# small size on the CPU: 0.28-0.36 against a control of 0.24-0.30 and a
# sound 0.04-0.06).
ROUTER_SPREAD = 4.0
ROUTED_GAIN = 0.125
# Query rows the reference attends at once: 32 heads x 256 rows x 16,640
# keys of float32 scores are 0.55 GB; a whole sequence's are 35 GB.
QUERY_BLOCK = 256
# Rows an expert is given a pass, as a multiple of the even share
# (rows * k / experts): the experts' work is gathered at a fixed shape,
# in as many passes as the fullest expert needs.
CAPACITY_FACTOR = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes, read from a configuration file (the published key
    names; ``router_outputs`` and ``first_held_expert`` are this
    benchmark's, for the chip's share)."""
    vocab: int
    d: int
    layers: int
    heads: int
    d_nope: int
    d_rope: int
    d_v: int
    r_q: int
    r_kv: int
    eps: float
    tie: bool
    router_experts: int       # the router's width: every expert
    held: int                 # experts whose weights are here
    first_held: int           # the first of them
    topk: int
    expert_ff: int
    shared_ff: int
    norm_topk_prob: bool
    routed_scale: float
    rope_theta: float
    rope_factor: float
    rope_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    query_scale_beta: float


def dims(c: dict) -> Dims:
    r = c["rope_parameters"]
    if r["rope_type"] != "yarn" or not c["rope_interleave"]:
        raise ValueError("mla_moe computes interleaved YaRN rope only")
    return Dims(vocab=int(c["vocab_size"]), d=int(c["hidden_size"]),
                layers=int(c["num_hidden_layers"]),
                heads=int(c["num_attention_heads"]),
                d_nope=int(c["qk_nope_head_dim"]),
                d_rope=int(c["qk_rope_head_dim"]),
                d_v=int(c["v_head_dim"]), r_q=int(c["q_lora_rank"]),
                r_kv=int(c["kv_lora_rank"]),
                eps=float(c["rms_norm_eps"]),
                tie=bool(c.get("tie_word_embeddings", False)),
                router_experts=int(c["router_outputs"]),
                held=int(c["n_routed_experts"]),
                first_held=int(c["first_held_expert"]),
                topk=int(c["num_experts_per_tok"]),
                expert_ff=int(c["moe_intermediate_size"]),
                shared_ff=int(c["moe_intermediate_size"])
                * int(c["n_shared_experts"]),
                norm_topk_prob=bool(c["norm_topk_prob"]),
                routed_scale=float(c["routed_scaling_factor"]),
                rope_theta=float(r["rope_theta"]),
                rope_factor=float(r["factor"]),
                rope_original=int(r["original_max_position_embeddings"]),
                beta_fast=float(r["beta_fast"]),
                beta_slow=float(r["beta_slow"]),
                mscale=float(r["mscale"]),
                mscale_all_dim=float(r["mscale_all_dim"]),
                query_scale_beta=float(r["llama_4_scaling_beta"]))


def layer_kind(dims: Dims, li: int) -> str:
    """Every layer is the same block (no leading dense layer)."""
    return "block"


def layer_leaves(dims: Dims, kind: str = "block") -> dict:
    """name -> (shape, kind of leaf, scale); ``weights._leaf`` has the
    kinds. The routed experts are stacked, ``held`` of them."""
    d, h = dims.d, dims.heads
    f, fs, e = dims.expert_ff, dims.shared_ff, dims.held
    q, kv = h * (dims.d_nope + dims.d_rope), h * (dims.d_nope + dims.d_v)
    return {
        "ln_attn": ((d,), "g", None),
        "w_dq": ((d, dims.r_q), "w", d ** -0.5),
        "q_norm": ((dims.r_q,), "g", None),
        "w_uq": ((dims.r_q, q), "w", dims.r_q ** -0.5),
        "w_dkv": ((d, dims.r_kv + dims.d_rope), "w", d ** -0.5),
        "kv_norm": ((dims.r_kv,), "g", None),
        "w_ukv": ((dims.r_kv, kv), "w", dims.r_kv ** -0.5),
        "wo": ((h * dims.d_v, d), "w", (h * dims.d_v) ** -0.5),
        "ln_mlp": ((d,), "g", None),
        "router": ((d, dims.router_experts), "w", ROUTER_SPREAD * d ** -0.5),
        "experts_gate": ((e, d, f), "w", d ** -0.5),
        "experts_up": ((e, d, f), "w", d ** -0.5),
        "experts_down": ((e, f, d), "w", ROUTED_GAIN * f ** -0.5),
        "shared_gate": ((d, fs), "w", d ** -0.5),
        "shared_up": ((d, fs), "w", d ** -0.5),
        "shared_down": ((fs, d), "w", fs ** -0.5),
    }


# -- rope ------------------------------------------------------------------

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dims: Dims):
    """(d_r / 2,) frequencies: a pair whose wavelength fits the original
    length ``beta_fast`` times or more keeps ``f``; one that fits it
    ``beta_slow`` times or fewer takes ``f / factor``; a linear ramp
    over the pair index between the two (the bounds floored and
    ceiled), as the published YaRN initialisation."""
    dim, base, orig = dims.d_rope, dims.rope_theta, dims.rope_original
    f = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def pair_of(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(dims.beta_fast)), 0)
    high = min(math.ceil(pair_of(dims.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return f / dims.rope_factor * ramp + f * (1.0 - ramp)


def rope_scale(dims: Dims) -> float:
    """What cos and sin are multiplied by: 1 when ``mscale`` equals
    ``mscale_all_dim``, as published."""
    return (_mscale(dims.rope_factor, dims.mscale)
            / _mscale(dims.rope_factor, dims.mscale_all_dim))


def softmax_scale(dims: Dims) -> float:
    """``sigma``: the head's width to the -1/2, times the square of
    YaRN's ``mscale_all_dim`` factor."""
    m = _mscale(dims.rope_factor, dims.mscale_all_dim)
    return (dims.d_nope + dims.d_rope) ** -0.5 * m * m


def query_scale(dims: Dims, positions):
    """(S,) what a query is multiplied by at its position."""
    return 1.0 + dims.query_scale_beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / dims.rope_original))


def rope(x, positions, dims: Dims):
    """x: (S, ..., d_r), pairs interleaved: (x[2i], x[2i+1]) turns by
    ``pos * f[i]``, in place."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(dims)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos = (jnp.cos(ang) * rope_scale(dims)).reshape(shape)
    sin = (jnp.sin(ang) * rope_scale(dims)).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# -- the layer -------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def attention(x, w, dims: Dims, dot):
    """The attention half, its residual added. Keys and values are
    expanded from the latent for the whole sequence; the scores are
    taken ``QUERY_BLOCK`` query rows at a time."""
    s, h = x.shape[0], dims.heads
    dn, dr, dv = dims.d_nope, dims.d_rope, dims.d_v
    pos = jnp.arange(s)
    y = rms(x, w["ln_attn"], dims.eps)
    q = dot(rms(dot(y, w["w_dq"]), w["q_norm"], dims.eps), w["w_uq"])
    q = q.reshape(s, h, dn + dr)
    ckv = dot(y, w["w_dkv"])
    c = rms(ckv[:, :dims.r_kv], w["kv_norm"], dims.eps)
    k_r = rope(ckv[:, dims.r_kv:], pos, dims)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, dims)], -1)
    q = q * query_scale(dims, pos)[:, None, None]
    kv = dot(c, w["w_ukv"]).reshape(s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (s, h, dr))], -1)
    v = kv[..., dn:]
    block = math.gcd(s, QUERY_BLOCK)

    def rows(args):
        qb, qpos = args
        sc = jnp.einsum("qhd,khd->hqk", qb, k,
                        precision=_HI) * softmax_scale(dims)
        sc = jnp.where(qpos[None, :, None] >= pos[None, None, :], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=_HI)

    o = jax.lax.map(rows, (q.reshape(s // block, block, h, dn + dr),
                           pos.reshape(s // block, block)))
    return x + dot(o.reshape(s, h * dv), w["wo"])


def route(y, w, dims: Dims, dot):
    """(S, k) expert ids over the whole router and their weights."""
    probs = jax.nn.softmax(dot(y, w["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, dims.topk)
    if dims.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_e, top_w * dims.routed_scale


def expert_capacity(dims: Dims, rows: int) -> int:
    even = -(-rows * dims.topk // dims.router_experts)
    return min(rows, CAPACITY_FACTOR * even)


def experts(x, w, dims: Dims, dot):
    """The expert half, its residual added: the shared expert over every
    row, and each HELD expert over the rows routed to it, gathered (32
    experts over every row would be 32 times the work). The gather has
    a fixed shape, ``expert_capacity`` rows an expert a pass, and as
    many passes are made as the fullest expert needs: no row is ever
    dropped (a padded sequence's tail is one token thousands of times,
    and all of it goes to the same four experts)."""
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
    return experts(attention(x, w, dims, dot), w, dims, dot)


# -- operations and bytes the algorithm needs, from shapes alone ----------

def _attn_params(d: Dims) -> int:
    h = d.heads
    return (d.d * d.r_q + d.r_q * h * (d.d_nope + d.d_rope)
            + d.d * (d.r_kv + d.d_rope)
            + d.r_kv * h * (d.d_nope + d.d_v) + h * d.d_v * d.d)


def _expert_params(d: Dims) -> int:
    return 3 * d.d * d.expert_ff


def _always_params(d: Dims) -> int:
    """Matrix parameters every row goes through in a layer: attention,
    the router, the shared expert."""
    return _attn_params(d) + d.d * d.router_experts + 3 * d.d * d.shared_ff


def latent_bytes_per_token(d: Dims, itemsize: int = 2) -> int:
    """The cached ``[c | k_r]`` of one position, all layers."""
    return (d.r_kv + d.d_rope) * itemsize * d.layers


def decode_step_bytes(d: Dims, context_tokens: float, batch: float = 0, *,
                      tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must read for one decode step: every layer's
    always-read matrices and the head once, the latent of
    ``context_tokens`` positions once (absorbed attention reads no
    expanded key), and the held experts a row was routed to. How many
    those are is data; counted here is the CERTAIN UPPER BOUND
    ``min(held, batch * k)``, so a share built on this may read high
    where the batch's rows fell to few held experts."""
    read = min(d.held, batch * d.topk)
    per_layer = _always_params(d) + read * _expert_params(d)
    weights = (d.layers * per_layer + head_params(d)) * itemsize
    return (weights
            + context_tokens * latent_bytes_per_token(d, itemsize)) / tp


def prefill_chunk_flops(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, held_pairs: float = None) -> float:
    """Floating-point operations one chip needs for a prefill chunk of
    ``rows`` tokens: a row goes through the always-read matrices (keys
    and values expanded once a row, never again for a later chunk) and
    attends ``context_mean`` keys with heads of ``d_n + d_r`` and
    ``d_v``; ``held_pairs`` token-expert pairs a layer go through an
    expert, as the window served them (without it: the even share,
    ``rows * k * held / experts``); the head for the one row whose
    logits the chunk returns."""
    if held_pairs is None:
        held_pairs = rows * d.topk * d.held / d.router_experts
    gemm = 2.0 * d.layers * (rows * _always_params(d)
                             + held_pairs * _expert_params(d))
    attn = (2.0 * rows * context_mean * d.heads
            * (d.d_nope + d.d_rope + d.d_v) * d.layers)
    return (gemm + attn + 2.0 * head_params(d)) / tp


def prefill_chunk_bytes(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must move for that chunk: every layer's always-read
    matrices and the head once, the held experts' matrices once each
    (the CERTAIN UPPER BOUND: an expert no row was routed to need not be
    read; at hundreds of rows an expert that is no case), the latent of
    the positions before the chunk read once and the chunk's own written
    once. ``context_mean`` is the mean keys a row attends: the positions
    before the chunk plus half the chunk."""
    before = max(context_mean - (rows + 1) / 2, 0.0)
    per_layer = _always_params(d) + d.held * _expert_params(d)
    weights = (d.layers * per_layer + head_params(d)) * itemsize
    return (weights + (before + rows)
            * latent_bytes_per_token(d, itemsize)) / tp
