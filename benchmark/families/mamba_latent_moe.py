"""Mamba-2 layers, latent expert layers and attention layers, a layer
being ONE of the three (``nemotron_h``), as a model family: what the
plain reference and the roofline counts need of it, and nothing of the
program. The program's side is ``mamba_latent_moe_system.py``.

Every layer is ``x + f(rms(x, ln))`` with one ``f``, float32, by the
layer's letter in ``hybrid_override_pattern`` (``h`` the normed row):

``M`` (kind "mamba"; H heads of P, G groups of N, d_in = H P):

    [z | xBC | dt] = h w_in               (d_in), (d_in + 2 G N), (H)
    xBC = silu(conv(xBC) + conv_bias)     causal, depthwise, 4 taps,
                                          zeros before the first token
    [x | B | C] = xBC;  head j reads B and C of group j // (H / G)
    dt = softplus(dt + dt_bias);  a = exp(-exp(a_log[j]) dt)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + d_skip[j] x_t
    f = (rms_groups(y * silu(z)) * ssm_norm) w_out

  the recurrence TOKEN BY TOKEN (``lax.scan`` over positions): no
  chunks, no cache, the state float32 and never rounded.

``*`` (kind "attention"): ``q, k, v = h wq, h wk, h wv`` (``heads``
over ``kv_heads``), scores times ``head_dim^-1/2``, causal softmax,
``wo``. No rotation, no q/k norm.

``E`` (kind "experts"): ``s = sigmoid(h router)`` over ALL experts; the
``topk`` largest of ``s + router_bias`` chosen; weights ``scale * s /
sum(s)`` over the chosen; ``u = h w_latent_in``; of the chosen experts
the ones THIS CHIP HOLDS: ``routed = (sum w_e relu(u up_e)^2 down_e)
w_latent_out``; plus ``relu(h shared_up)^2 shared_down``.

Departures from the published description, each also under ``assumed``
in the configuration's file: what the absent experts would add is left
out, here as in the program (the guide's cut); the multi-token-
prediction module is left out; ``dt_bias`` and ``a_log`` are a seeded
leaf plus a constant of this file (``DT_BIAS_OFFSET``,
``A_LOG_OFFSET``), which both halves apply: ``weights.py`` draws no
leaf around an offset.
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
    "ln", "w_in", "conv", "conv_bias", "dt_bias", "a_log", "d_skip",
    "ssm_norm", "w_out", "wq", "wk", "wv", "wo", "router", "router_bias",
    "w_latent_in", "experts_up", "experts_down", "w_latent_out",
    "shared_up", "shared_down"))}

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}

# The step size and the decay, as seeded. A head's ``dt_bias`` is
# DT_BIAS_OFFSET + z and its ``a_log`` A_LOG_OFFSET + 0.5 z (z standard
# normal, the leaf): with the rows' own part of dt, ``h w_in`` at
# fan_in^-1/2, another standard normal, the step size is softplus(-4.6 +
# 1.4 z) (median 0.010, a tenth of the heads-and-rows under 0.0017 or
# over 0.06: time_step_min 0.001 to time_step_max 0.1) and A = -e^(1 +
# 0.5 z), median -2.7 (published: -16 .. -1). The median decay a step is
# exp(-2.7 * 0.010) = 0.973; a tenth of them lie over 0.996, a state
# that carries hundreds of tokens, and a tenth under 0.83.
DT_BIAS_OFFSET = -4.6
A_LOG_OFFSET = 1.0
A_LOG_SPREAD = 0.5
# The routed experts' down-projections are drawn at ROUTED_GAIN of
# fan_in^-1/2 (``mla_moe.py`` has the history). A sigmoid router weighs
# its 22 choices almost alike, 5 / 22 each, so a 22nd and 23rd choice
# that swap on rounding trade an expert of that weight in ANY precision,
# and the swaps, not the products' rounding, were what a sound run's
# widest gap read: on the chip, three seeds each (PERF.md section 6, PR
# 43), at a half: sound 0.29-0.49, int8 control 0.62-0.96; at an eighth:
# 0.015-0.10 and 0.30-0.42; at 1/32: 0.009-0.045 and 0.37-0.38. Under the
# published scale of 5 a gain of 1/32 leaves the routed sum 5/32 of what
# fan_in^-1/2 gives, the size ``mla_moe``'s eighth leaves under its scale
# of 1; int8 in every linear layer still does what it did.
ROUTED_GAIN = 0.03125
# Query rows the reference attends at once: 32 heads x 256 rows x 12,544
# keys of float32 scores are 0.41 GB.
QUERY_BLOCK = 256
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
    pattern: str
    eps: float
    tie: bool
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int            # H
    ssm_head_dim: int         # P
    ssm_groups: int           # G
    ssm_state: int            # N
    conv_taps: int
    router_experts: int       # the router's width: every expert
    held: int                 # experts whose weights are here
    first_held: int           # the first of them
    topk: int
    latent: int               # the width the routed experts work in
    expert_ff: int
    shared_ff: int
    norm_topk_prob: bool
    routed_scale: float

    @property
    def d_in(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_in + 2 * self.ssm_groups * self.ssm_state

    def count(self, kind: str) -> int:
        return sum(KINDS[c] == kind for c in self.pattern)


def dims(c: dict) -> Dims:
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != int(c["num_hidden_layers"]) or set(pattern) - set(
            KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: one of "
                         f"{sorted(KINDS)} a layer")
    if int(c.get("n_group", 1)) != 1 or int(c.get("topk_group", 1)) != 1:
        raise ValueError("mamba_latent_moe routes among all experts "
                         "(n_group 1, topk_group 1)")
    return Dims(vocab=int(c["vocab_size"]), d=int(c["hidden_size"]),
                layers=int(c["num_hidden_layers"]), pattern=pattern,
                eps=float(c["norm_eps"]),
                tie=bool(c.get("tie_word_embeddings", False)),
                heads=int(c["num_attention_heads"]),
                kv_heads=int(c["num_key_value_heads"]),
                head_dim=int(c["head_dim"]),
                ssm_heads=int(c["mamba_num_heads"]),
                ssm_head_dim=int(c["mamba_head_dim"]),
                ssm_groups=int(c["n_groups"]),
                ssm_state=int(c["ssm_state_size"]),
                conv_taps=int(c["conv_kernel"]),
                router_experts=int(c["router_outputs"]),
                held=int(c["n_routed_experts"]),
                first_held=int(c["first_held_expert"]),
                topk=int(c["num_experts_per_tok"]),
                latent=int(c["moe_latent_size"]),
                expert_ff=int(c["moe_intermediate_size"]),
                shared_ff=int(c["moe_shared_expert_intermediate_size"]),
                norm_topk_prob=bool(c["norm_topk_prob"]),
                routed_scale=float(c["routed_scaling_factor"]))


def layer_kind(dims: Dims, li: int) -> str:
    return KINDS[dims.pattern[li]]


def layer_leaves(dims: Dims, kind: str) -> dict:
    """name -> (shape, kind of leaf, scale); ``weights._leaf`` has the
    kinds. The routed experts are stacked, ``held`` of them."""
    d = dims.d
    ln = {"ln": ((d,), "g", None)}
    if kind == "mamba":
        h, d_in, cw = dims.ssm_heads, dims.d_in, dims.conv_width
        return dict(
            ln,
            w_in=((d, d_in + cw + h), "w", d ** -0.5),
            conv=((dims.conv_taps, cw), "w", dims.conv_taps ** -0.5),
            conv_bias=((cw,), "b", None),
            dt_bias=((h,), "w", 1.0),
            a_log=((h,), "w", A_LOG_SPREAD),
            d_skip=((h,), "g", None),
            ssm_norm=((d_in,), "g", None),
            w_out=((d_in, d), "w", d_in ** -0.5))
    if kind == "attention":
        q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
        return dict(
            ln,
            wq=((d, q), "w", d ** -0.5), wk=((d, kv), "w", d ** -0.5),
            wv=((d, kv), "w", d ** -0.5), wo=((q, d), "w", q ** -0.5))
    if kind == "experts":
        e, lat, f, fs = (dims.held, dims.latent, dims.expert_ff,
                         dims.shared_ff)
        return dict(
            ln,
            router=((d, dims.router_experts), "w", d ** -0.5),
            router_bias=((dims.router_experts,), "b", None),
            w_latent_in=((d, lat), "w", d ** -0.5),
            experts_up=((e, lat, f), "w", lat ** -0.5),
            experts_down=((e, f, lat), "w", ROUTED_GAIN * f ** -0.5),
            w_latent_out=((lat, d), "w", lat ** -0.5),
            shared_up=((d, fs), "w", d ** -0.5),
            shared_down=((fs, d), "w", fs ** -0.5))
    raise ValueError(f"kind {kind!r}")


# -- the layer -------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def mamba(x, w, dims: Dims, dot):
    """The Mamba-2 mixer, its residual added, the recurrence a token at
    a time."""
    s = x.shape[0]
    hh, p, g, n = (dims.ssm_heads, dims.ssm_head_dim, dims.ssm_groups,
                   dims.ssm_state)
    d_in, cw, taps = dims.d_in, dims.conv_width, dims.conv_taps
    zxbcdt = dot(rms(x, w["ln"], dims.eps), w["w_in"])
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + cw],
                  zxbcdt[:, d_in + cw:])
    seen = jnp.concatenate([jnp.zeros((taps - 1, cw), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(seen[j:j + s] * w["conv"][j] for j in range(taps))
                      + w["conv_bias"])
    xs = xbc[:, :d_in].reshape(s, hh, p)
    b = xbc[:, d_in:d_in + g * n].reshape(s, g, n)
    c = xbc[:, d_in + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"] + DT_BIAS_OFFSET)
    a = jnp.exp(-jnp.exp(w["a_log"] + A_LOG_OFFSET) * dt)       # (S, H)

    def step(state, row):
        x_t, b_t, c_t, dt_t, a_t = row
        b_h = jnp.repeat(b_t, hh // g, axis=0)                  # (H, N)
        c_h = jnp.repeat(c_t, hh // g, axis=0)
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((hh, p, n), jnp.float32),
                        (xs, b, c, dt, a))
    y = y + w["d_skip"][:, None] * xs
    v = (y.reshape(s, d_in) * jax.nn.silu(z)).reshape(s, g, d_in // g)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + dims.eps)
    return x + dot(v.reshape(s, d_in) * w["ssm_norm"], w["w_out"])


def attention(x, w, dims: Dims, dot):
    """The attention layer, its residual added; the scores are taken
    ``QUERY_BLOCK`` query rows at a time."""
    s, h, kv, hd = x.shape[0], dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(s)
    y = rms(x, w["ln"], dims.eps)
    q = dot(y, w["wq"]).reshape(s, kv, h // kv, hd)
    k = dot(y, w["wk"]).reshape(s, kv, hd)
    v = dot(y, w["wv"]).reshape(s, kv, hd)
    block = math.gcd(s, QUERY_BLOCK)

    def rows(args):
        qb, qpos = args
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k,
                        precision=_HI) * hd ** -0.5
        sc = jnp.where(qpos[None, None, :, None] >= pos[None, None, None],
                       sc, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(sc, axis=-1), v,
                          precision=_HI)

    o = jax.lax.map(rows, (q.reshape(s // block, block, kv, h // kv, hd),
                           pos.reshape(s // block, block)))
    return x + dot(o.reshape(s, h * hd), w["wo"])


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
    """The expert layer, its residual added: the shared expert over
    every row, and each HELD expert over the rows routed to it, gathered
    in the latent at a fixed shape, ``expert_capacity`` rows an expert a
    pass, in as many passes as the fullest expert needs: no row is ever
    dropped (``mla_moe.experts`` has why)."""
    e = dims.held
    y = rms(x, w["ln"], dims.eps)
    top_e, top_w = route(y, w, dims, dot)
    u = dot(y, w["w_latent_in"])
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
            up, dn, rows_e, w_e = args
            out = dot(jnp.square(jax.nn.relu(dot(u[rows_e], up))), dn)
            return routed.at[rows_e].add(out * w_e[:, None]), None

        routed, _ = jax.lax.scan(one, routed, (
            w["experts_up"], w["experts_down"], row, weight))
        return n + 1, routed

    _, routed = jax.lax.while_loop(
        lambda carry: carry[0] * cap < jnp.max(counts), one_pass,
        (jnp.zeros((), jnp.int32), jnp.zeros_like(u)))
    shared = dot(jnp.square(jax.nn.relu(dot(y, w["shared_up"]))),
                 w["shared_down"])
    return x + shared + dot(routed, w["w_latent_out"])


_LAYERS = {"mamba": mamba, "attention": attention, "experts": experts}


def layer(x, w, kind: str, dims: Dims, dot):
    """One layer over a whole sequence. x: (S, d) float32; ``w`` the
    layer's leaves, already float32; ``dot`` the product of the linear
    layers (``highest``, or the control's int8)."""
    return _LAYERS[kind](x, w, dims, dot)


# -- operations and bytes the algorithm needs, from shapes alone ----------

def _mamba_params(d: Dims) -> int:
    """The two projections of a Mamba-2 layer."""
    return d.d * (d.d_in + d.conv_width + d.ssm_heads) + d.d_in * d.d


def _attn_params(d: Dims) -> int:
    return 2 * d.d * d.head_dim * (d.heads + d.kv_heads)


def _expert_params(d: Dims) -> int:
    return 2 * d.latent * d.expert_ff


def _moe_always_params(d: Dims) -> int:
    """Matrix parameters every row of an expert layer goes through: the
    router, into and out of the latent, the shared expert."""
    return (d.d * d.router_experts + 2 * d.d * d.latent
            + 2 * d.d * d.shared_ff)


def _always_params(d: Dims) -> int:
    """Matrix parameters a row goes through in the whole model, outside
    the routed experts and the head."""
    return (d.count("mamba") * _mamba_params(d)
            + d.count("attention") * _attn_params(d)
            + d.count("experts") * _moe_always_params(d))


def kv_bytes_per_token(d: Dims, itemsize: int = 2) -> int:
    """Keys and values of one position, the attention layers'."""
    return 2 * d.kv_heads * d.head_dim * itemsize * d.count("attention")


def state_bytes_per_sequence(d: Dims, itemsize: int = 2) -> int:
    """What a sequence keeps that is no page: every Mamba-2 layer's
    state and the convolution's last inputs, in the served type."""
    return d.count("mamba") * itemsize * (
        d.ssm_heads * d.ssm_head_dim * d.ssm_state
        + (d.conv_taps - 1) * d.conv_width)


def _pairs(d: Dims, rows: int, held_pairs) -> float:
    """Held token-expert pairs of a ``rows``-row program over ALL its
    expert layers. ``held_pairs`` is the count as
    ``reducers/roofline_max.served_pairs`` hands it, the program's
    pairs over ``d.layers``; without it: the even share."""
    if held_pairs is None:
        return (d.count("experts") * rows * d.topk * d.held
                / d.router_experts)
    return held_pairs * d.layers


def ssm_chunk_flops(d: Dims, rows: int) -> float:
    """Operations of the Mamba-2 layers' block BETWEEN their two
    projections for a chunk of ``rows`` rows, all such layers: the
    recurrence as written (a state element takes two multiplies and an
    add to update and a multiply and an add to read: 5 H P N a row), the
    convolution's taps, gate and norm; whatever form computes them."""
    per_row = (5 * d.d_in * d.ssm_state + 2 * d.conv_taps * d.conv_width
               + 6 * d.d_in)
    return float(d.count("mamba") * rows * per_row)


def ssm_chunk_bytes(d: Dims, rows: int, itemsize: int = 2) -> float:
    """Bytes that block must move: the in-projection's rows read and
    the rows for the out-projection written once, the sequence's state
    and tail read and written once a chunk."""
    per_row = (2 * d.d_in + d.conv_width + d.ssm_heads) * itemsize
    return float(d.count("mamba") * rows * per_row
                 + 2 * state_bytes_per_sequence(d, itemsize))


def experts_chunk_flops(d: Dims, rows: int, held_pairs=None) -> float:
    """Operations of the grouped products for a ``rows``-row program,
    all expert layers: the held pairs as served, each through one
    expert."""
    return 2.0 * _pairs(d, rows, held_pairs) * _expert_params(d)


def experts_chunk_bytes(d: Dims, rows: int, held_pairs=None,
                        itemsize: int = 2) -> float:
    """Bytes they must move: the held experts' matrices once each (the
    certain upper bound: at tens of rows an expert none goes unread),
    a pair's row of the latent gathered and its result written."""
    return float(itemsize * (
        d.count("experts") * d.held * _expert_params(d)
        + 2 * _pairs(d, rows, held_pairs) * d.latent))


def decode_step_bytes(d: Dims, context_tokens: float, batch: float = 0, *,
                      tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must move for one decode step: the always-read
    matrices and the head once, the held experts that an EVEN routing of
    ``batch`` rows reaches (each row picks ``k`` of the router's experts,
    so a held one goes unread with ``(1 - k / router_experts) ** batch``:
    65 of 128 at 16 rows), keys and values of ``context_tokens``
    positions, and each running sequence's state read and written.
    ``min(held, batch * k)``, the certain upper bound, says every held
    expert from 6 rows on, and the step measured takes less time than
    reading them all would (10.2 ms for 11.0: PERF.md, PR 43); a skewed
    routing reaches fewer, so this count can read high for it by the
    experts it skips, never by more."""
    read = d.held * (1.0 - (1.0 - d.topk / d.router_experts) ** batch)
    weights = (_always_params(d) + d.count("experts") * read
               * _expert_params(d) + head_params(d)) * itemsize
    return (weights + context_tokens * kv_bytes_per_token(d, itemsize)
            + 2 * batch * state_bytes_per_sequence(d, itemsize)) / tp


def prefill_chunk_flops(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, held_pairs: float = None) -> float:
    """Floating-point operations one chip needs for a prefill chunk of
    ``rows`` tokens: a row goes through the always-read matrices, the
    Mamba-2 layers' recurrence (:func:`ssm_chunk_flops`) and attends
    ``context_mean`` keys in each attention layer; the held pairs go
    through an expert (``held_pairs`` as :func:`_pairs` reads it); the
    head for the one row whose logits the chunk returns."""
    gemm = 2.0 * rows * _always_params(d)
    attn = (4.0 * rows * context_mean * d.heads * d.head_dim
            * d.count("attention"))
    return (gemm + experts_chunk_flops(d, rows, held_pairs)
            + ssm_chunk_flops(d, rows) + attn
            + 2.0 * head_params(d)) / tp


def prefill_chunk_bytes(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1, itemsize: int = 2) -> float:
    """Bytes one chip must move for that chunk: the always-read
    matrices, the held experts' and the head once, keys and values of
    the positions before the chunk read once and the chunk's own
    written once, the sequence's state read and written once.
    ``context_mean`` is the mean keys a row attends: the positions
    before the chunk plus half the chunk."""
    before = max(context_mean - (rows + 1) / 2, 0.0)
    weights = (_always_params(d) + d.count("experts") * d.held
               * _expert_params(d) + head_params(d)) * itemsize
    return (weights + (before + rows) * kv_bytes_per_token(d, itemsize)
            + 2 * state_bytes_per_sequence(d, itemsize)) / tp
