"""Mamba-2 layers, latent expert layers and a few attention layers, a
layer being ONE of the three: the paged serving contract of
:mod:`triton_dist_tpu.models.latent_moe` for a model whose sequences
keep TWO kinds of state. An attention layer keeps pages of keys and
values (:class:`~triton_dist_tpu.serving.blocks.PagedKVCache`, with only
the attention layers counted); a Mamba-2 layer keeps, a SEQUENCE, a
recurrent state and the last inputs of its convolution, in the same
cache's ``seq`` arrays, and no page.

Every layer is ``x + f(rms(x))`` with one ``f``, chosen by the layer's
letter in ``cfg.layer_pattern`` (``h`` the normed row):

``M``, Mamba-2 (H heads of P = ``mamba_head_dim``, G groups of N =
``ssm_state_size``; :mod:`triton_dist_tpu.ops.mamba2`):

    [z | xBC | dt] = h w_in               (H P), (H P + 2 G N), (H)
    xBC = silu(conv(xBC) + conv_bias)     causal, depthwise, taps wide
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(a_log)
    S_t = e^(A dt_t) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    + rms_groups(y * silu(z)) * norm w_out     the RMS over each of G
                                               groups of H P / G values

``*``, attention: ``q, k, v = h w_q, h w_k, h w_v``, scores times
``head_dim^-1/2``, causal softmax, ``w_o``. NO rotation and no q/k
norm: the Mamba layers carry position. The dense family's pool and its
kernels (:func:`~triton_dist_tpu.models.paged_step.kv_attend`), every
head on every rank.

``E``, latent experts: :func:`~triton_dist_tpu.layers.ep_moe.fwd_held`
behind a sigmoid router with a selection-only bias, the held experts
ungated squared-ReLU MLPs in a latent the layer projects into and out
of (their matrices stored zero-padded to
:func:`~triton_dist_tpu.layers.ep_moe.expert_store_width`), one shared
expert on ``h`` itself.

What a sequence's state goes through (every step returns the new tree;
nothing of it lives outside the cache):

- a prefill chunk RESETS its slot's state where ``start == 0``, else
  carries what the last chunk left; rows past ``valid`` neither decay
  nor write (``dt = 0``) and stay out of the convolution's tail;
- a decode step advances the LIVE rows' states and tails; a parked row
  (``live == 0``: free, or mid-prefill) is computed and discarded;
- inside a chunk and inside a step, decay, update and readout are
  float32; the state is rounded to the pool's type, which is the
  parameters', where it is stored: at a chunk's end and after a step;
- ``verify_step_paged`` is not provided: a state that is not
  position-addressed cannot be rolled back by lengths.

Everything is replicated over ``axis`` but the head's vocabulary rows,
as in :mod:`~triton_dist_tpu.models.latent_moe`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models import dense as _dense
from triton_dist_tpu.models import paged_step
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import FwdContexts
from triton_dist_tpu.models.latent_moe import STEP_STATS
from triton_dist_tpu.obs import scope
from triton_dist_tpu.ops import mamba2 as _ssd

STATE, TAIL = "ssm_state", "conv_tail"
_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def _conv_width(cfg: ModelConfig) -> int:
    """Channels the convolution runs over: x, B and C side by side."""
    return (cfg.mamba_num_heads * cfg.mamba_head_dim
            + 2 * cfg.mamba_n_groups * cfg.ssm_state_size)


def paged_pool(cfg: ModelConfig):
    """The pool this model keeps: pages of K and V for its attention
    layers alone, and a sequence's recurrent state and convolution tail
    for its Mamba-2 layers, in the pool's type. The layer leads both
    arrays, so a layer's part of either is one contiguous block; the
    tail's slots lie before its channels (whole tiles of (slots,
    channels))."""
    from triton_dist_tpu.serving.blocks import PagedKVCache, SeqArray

    n = cfg.layer_pattern.count("M")
    return PagedKVCache, (cfg.num_key_value_heads, cfg.head_dim), {
        "layers": cfg.num_paged_layers,
        "seq_state": {
            STATE: SeqArray((n, cfg.mamba_num_heads, cfg.mamba_head_dim,
                             cfg.ssm_state_size), slot_axis=1),
            TAIL: SeqArray((n, cfg.mamba_conv_kernel - 1,
                            _conv_width(cfg)), slot_axis=2)}}


def paged_cache_specs(axis: str = "tp", quantized: bool = False):
    from triton_dist_tpu.serving.blocks import PagedKVCache

    if quantized:
        raise ValueError("models.mamba_moe keeps an unquantized pool")
    return PagedKVCache(
        k_pages=P(None, None, None, None, None),
        v_pages=P(None, None, None, None, None),
        block_table=P(None, None), lens=P(None), live=P(None),
        seq={STATE: P(None, None, None, None, None),
             TAIL: P(None, None, None, None)})


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d = cfg.hidden_size
    h, hd, kv = cfg.num_attention_heads, cfg.head_dim, cfg.num_key_value_heads
    mh, d_in = cfg.mamba_num_heads, cfg.mamba_num_heads * cfg.mamba_head_dim
    cw, taps = _conv_width(cfg), cfg.mamba_conv_kernel
    f, fs, lat = (cfg.moe_intermediate_size,
                  cfg.shared_expert_intermediate_size, cfg.moe_latent_size)
    e = cfg.held_experts
    f32 = jnp.float32

    def w(k, *shape):
        return jax.random.normal(k, shape, dtype) * shape[-2] ** -0.5

    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = []
    for li, letter in enumerate(cfg.layer_pattern):
        k = jax.random.split(keys[li], 10)
        lp = {"ln": jnp.ones((d,), dtype)}
        if letter == "M":
            lp["mamba"] = {
                "w_in": w(k[0], d, d_in + cw + mh),
                "conv": (jax.random.normal(k[1], (taps, cw), dtype)
                         * taps ** -0.5),
                "conv_bias": jnp.zeros((cw,), dtype),
                # Step sizes of e^-4.6 = 0.01 and A of -1 before the
                # rows' own part: a decay of 0.99 a step. float32, as
                # the published implementation keeps the three.
                "dt_bias": jnp.full((mh,), -4.6, f32),
                "a_log": jnp.zeros((mh,), f32),
                "d_skip": jnp.ones((mh,), f32),
                "norm": jnp.ones((d_in,), dtype),
                "w_out": w(k[2], d_in, d)}
        elif letter == "*":
            lp["attn"] = {"wq": w(k[0], d, h * hd), "wk": w(k[1], d, kv * hd),
                          "wv": w(k[2], d, kv * hd), "wo": w(k[3], h * hd, d)}
        else:
            lp["moe"] = {
                "router": w(k[0], d, cfg.num_experts),
                "router_bias": 0.01 * jax.random.normal(
                    k[1], (cfg.num_experts,), f32),
                "w_latent_in": w(k[2], d, lat),
                "w_latent_out": w(k[5], lat, d),
                "w_shared_up": w(k[6], d, fs),
                "w_shared_down": w(k[7], fs, d)}
            # Stored at the width the ragged product takes whole tiles
            # of (2,688 -> 3,072), the padding zeros.
            up_down = ep_moe.pad_expert_width(w(k[3], e, lat, f),
                                              w(k[4], e, f, lat))
            lp["moe"].update(w_up=up_down[0], w_down=up_down[1])
        layers.append(lp)
    table = lambda k: jax.random.normal(
        k, (cfg.vocab_size, d), dtype) * 0.02
    emb = table(keys[-2])
    return {"embed": emb, "layers": layers,
            "ln_f": jnp.ones((d,), dtype),
            "lm_head": emb if cfg.tie_word_embeddings else table(keys[-1])}


def param_specs(cfg: ModelConfig, axis: str = "tp") -> Dict:
    _check_cfg(cfg)
    shapes = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), cfg)["layers"])
    return {"embed": P(None, None),
            "layers": jax.tree.map(lambda x: P(*(None,) * x.ndim), shapes),
            "ln_f": P(None), "lm_head": P(axis, None)}


def _check_cfg(cfg: ModelConfig):
    if (not cfg.layer_pattern
            or len(cfg.layer_pattern) != cfg.num_hidden_layers
            or set(cfg.layer_pattern) - set(_KINDS)):
        raise ValueError(
            f"layer_pattern={cfg.layer_pattern!r}: models.mamba_moe "
            "wants one of 'M', 'E', '*' for each of the "
            f"{cfg.num_hidden_layers} layers")
    if "E" in cfg.layer_pattern and not cfg.moe_latent_size:
        raise ValueError("models.mamba_moe routes in a latent: "
                         "moe_latent_size is 0")


# -- the Engine's dense-cache contract: not this model's path ---------------

cache_specs = _dense.cache_specs


def _paged_only(*_, **__):
    raise NotImplementedError(
        "models.mamba_moe serves through the paged pool and the "
        "sequences' state beside it only: "
        "Engine(...).serving(prefill_buckets=...)")


prefill = decode_step = _paged_only


# -- the Mamba-2 mixer ------------------------------------------------------

def _split_in(mp, h, cfg: ModelConfig):
    """``h w_in`` cut into the gate ``z`` (n, H P), the convolution's
    input ``xBC`` (n, H P + 2 G N) and the raw step sizes (n, H)."""
    d_in = cfg.mamba_num_heads * cfg.mamba_head_dim
    zxbcdt = jnp.dot(h, mp["w_in"])
    cw = _conv_width(cfg)
    return (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + cw],
            zxbcdt[:, d_in + cw:])


def _ssm_inputs(mp, conved, dt, cfg: ModelConfig):
    """The convolution's sums (n, H P + 2 G N) float32 and the raw step
    sizes -> ``x (n, H, P)``, ``dt (n, H) > 0``, ``A (H,) < 0``, ``B``
    and ``C (n, G, N)``, float32."""
    n = conved.shape[0]
    mh, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, s = cfg.mamba_n_groups, cfg.ssm_state_size
    f32 = jnp.float32
    xbc = jax.nn.silu(conved + mp["conv_bias"].astype(f32))
    x = xbc[:, :mh * p].reshape(n, mh, p)
    b = xbc[:, mh * p:mh * p + g * s].reshape(n, g, s)
    c = xbc[:, mh * p + g * s:].reshape(n, g, s)
    dt = jax.nn.softplus(dt.astype(f32) + mp["dt_bias"].astype(f32))
    return x, dt, -jnp.exp(mp["a_log"].astype(f32)), b, c


def _gated_norm(mp, y, z, cfg: ModelConfig, dtype):
    """``rms_groups(y * silu(z)) * norm``: gate first, then the RMS over
    each of G groups, ready for ``w_out``: (n, H P)."""
    n, g = y.shape[0], cfg.mamba_n_groups
    v = y.reshape(n, -1) * jax.nn.silu(z.astype(jnp.float32))
    v = v.reshape(n, g, -1)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    return (v.reshape(n, -1) * mp["norm"].astype(jnp.float32)).astype(dtype)


def step_kernels(cfg: ModelConfig, rows: int, *, decode_rows: int,
                 page: int, dtype) -> tuple:
    """The blocks, of ``paged_step.STEP_KERNELS``, that a chunk program
    of ``rows`` chunk rows with ``decode_rows`` aboard runs in a Pallas
    kernel: every Mamba-2 layer's scan of the chunk rows, by
    :func:`ops.mamba2.chunk_scan_impl` (what ``ssd_prefill`` decides
    by), and every ``E`` layer's held experts' MLP, by
    :func:`ep_moe.experts_impl` at the pass every row of the program
    gives, in the latent and at the width the experts are stored at. The
    serving engine counts its chunk dispatches by it."""
    scan = _ssd.chunk_scan_impl(
        rows, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
        cfg.ssm_state_size, cfg.mamba_chunk_size)
    n_held = cfg.held_experts
    experts = "E" in cfg.layer_pattern and ep_moe.experts_impl(
        ep_moe.held_pass_rows(rows + decode_rows, cfg.num_experts_per_tok,
                              n_held, cfg.num_experts),
        n_held, cfg.moe_latent_size,
        ep_moe.expert_store_width(cfg.moe_intermediate_size), dtype)
    return (("scan",) * (scan == "kernel")
            + ("experts",) * (experts == "kernel"))


def _mamba_chunk(mp, z, xbc, dt, state, tail, cfg: ModelConfig, valid):
    """A chunk's rows of ONE sequence. ``state`` (H, P, N) float32 and
    ``tail`` (taps - 1, W) as the rows before left them. Returns ``(y
    (C, H P), state, tail)`` after the ``valid`` rows."""
    c, taps = xbc.shape[0], cfg.mamba_conv_kernel
    seen = jnp.concatenate([tail.astype(xbc.dtype), xbc])
    w = mp["conv"].astype(jnp.float32)
    conved = sum(seen[j:j + c].astype(jnp.float32) * w[j]
                 for j in range(taps))
    x, dt, a, b, cc = _ssm_inputs(mp, conved, dt, cfg)
    # A row past the chunk's last token neither decays nor writes.
    dt = jnp.where((jnp.arange(c) < valid)[:, None], dt, 0.0)
    y, state = _ssd.ssd_prefill(x, dt, a, b, cc,
                                mp["d_skip"].astype(jnp.float32), state,
                                chunk=cfg.mamba_chunk_size)
    # The last taps - 1 VALID inputs: rows valid - taps + 1 .. valid - 1.
    tail = jax.lax.dynamic_slice_in_dim(seen, valid, taps - 1, axis=0)
    return _gated_norm(mp, y, z, cfg, xbc.dtype), state, tail


def _mamba_decode(mp, z, xbc, dt, state, tail, cfg: ModelConfig):
    """One token of EVERY slot. ``state`` (S, H, P, N) float32, ``tail``
    (taps - 1, S, W). Returns ``(y (S, H P), state, tail)``, every slot
    advanced (the caller keeps the live ones')."""
    seen = jnp.concatenate([tail.astype(xbc.dtype), xbc[None]])
    w = mp["conv"].astype(jnp.float32)
    conved = jnp.sum(seen.astype(jnp.float32) * w[:, None], axis=0)
    x, dt, a, b, c = _ssm_inputs(mp, conved, dt, cfg)
    y, state = _ssd.ssd_step(state, x, dt, a, b, c,
                             mp["d_skip"].astype(jnp.float32))
    return _gated_norm(mp, y, z, cfg, xbc.dtype), state, seen[1:]


def _put(cache, mi, slot, state, tail):
    """The cache with Mamba layer ``mi``'s state and tail written at
    ``slot`` (one slot's, or with ``slot`` 0 every slot's): in place, by
    ``lax.dynamic_update_slice``, as the pages are; rounded here to the
    type they are stored in."""
    st, tl = cache.seq[STATE], cache.seq[TAIL]
    state = state.astype(st.dtype).reshape((1, -1) + st.shape[2:])
    tail = tail.astype(tl.dtype).reshape(1, tl.shape[1], -1, tl.shape[3])
    return dataclasses.replace(cache, seq={
        **cache.seq,
        STATE: jax.lax.dynamic_update_slice(st, state, (mi, slot, 0, 0, 0)),
        TAIL: jax.lax.dynamic_update_slice(tl, tail, (mi, 0, slot, 0))})


def _mix_chunk(mp, z, xbc, dt, cache, mi, cfg, *, slot, start, valid):
    """A chunk's rows through Mamba layer ``mi``: the slot's state reset
    where the chunk is its sequence's first, else carried."""
    st, tl = cache.seq[STATE], cache.seq[TAIL]
    slot = jnp.asarray(slot, jnp.int32)
    s0 = jax.lax.dynamic_slice(st, (mi, slot, 0, 0, 0),
                               (1, 1) + st.shape[2:])[0, 0]
    t0 = jax.lax.dynamic_slice(tl, (mi, 0, slot, 0),
                               (1, tl.shape[1], 1, tl.shape[3]))[0, :, 0]
    first = jnp.asarray(start, jnp.int32) == 0
    y, s1, t1 = _mamba_chunk(
        mp, z, xbc, dt, jnp.where(first, 0.0, s0.astype(jnp.float32)),
        jnp.where(first, 0, t0), cfg, valid)
    with scope("cache_write"):
        cache = _put(cache, mi, slot, s1, t1)
    return y, cache


def _mix_decode(mp, z, xbc, dt, cache, mi, cfg):
    """The decode rows through Mamba layer ``mi``: live rows advance,
    parked rows are computed on what lies there and left as they
    were."""
    states = jax.lax.dynamic_index_in_dim(cache.seq[STATE], mi, 0, False)
    tails = jax.lax.dynamic_index_in_dim(cache.seq[TAIL], mi, 0, False)
    y, s1, t1 = _mamba_decode(mp, z, xbc, dt, states.astype(jnp.float32),
                              tails, cfg)
    live = cache.live.astype(bool)
    with scope("cache_write"):
        cache = _put(
            cache, mi, jnp.zeros((), jnp.int32),
            jnp.where(live[:, None, None, None], s1.astype(states.dtype),
                      states),
            jnp.where(live[None, :, None], t1.astype(tails.dtype), tails))
    return y, cache


# -- the layers ------------------------------------------------------------

def _layers(params, rows, cache, cfg: ModelConfig, *, mode, axis, attn_impl,
            decode_attn_impl, ctxs: FwdContexts = FwdContexts()):
    """The trunk every step of this family is built from
    (:func:`paged_step.build`): ``rows`` embedded, (n, d), then every
    layer, then the final norm. An attention layer takes the K/V pool's
    halves (:func:`paged_step.kv_attend`); a Mamba layer's rows go
    through the sequences' state (``mix``), the chunk's through their
    slot's (``rows.slot``), the decode rows each through their own (the
    chunk's slot is parked in the batch: the two never meet). A kind of
    layer is ONE jitted function of its parameters and its index among
    its kind, an int32 operand (``latent_moe._layers`` has why). Returns
    ``(x (n, d), cache, stats)``: ``STEP_STATS``."""
    attend = paged_step.kv_attend(rows, attn_impl, decode_attn_impl)

    def mix(mi, mp, z, xbc, dt, cache):
        return rows.split(
            lambda cache, z, xbc, dt: _mix_chunk(
                mp, z, xbc, dt, cache, mi, cfg, slot=rows.slot,
                start=rows.start, valid=rows.valid),
            lambda cache, z, xbc, dt: _mix_decode(
                mp, z, xbc, dt, cache, mi, cfg),
            cache, z, xbc, dt)

    x = paged_step.embed_rows(params, rows.tokens())
    n = x.shape[0]
    eps = cfg.rms_norm_eps

    @jax.jit
    def layer(idx, lp, x, cache, stats):
        if "mamba" in lp:
            with scope("ssm_project"):
                z, xbc, dt = _split_in(lp["mamba"],
                                       rms_norm(x, lp["ln"], eps), cfg)
            with scope("ssm"):
                y, cache = mix(idx, lp["mamba"], z, xbc, dt, cache)
            with scope("ssm_out"):
                x = x + jnp.dot(y, lp["mamba"]["w_out"])
        elif "attn" in lp:
            with scope("attn_project"):
                h = rms_norm(x, lp["ln"], eps)
                a = lp["attn"]
                q = jnp.dot(h, a["wq"]).reshape(n, 1, -1, cfg.head_dim)
                k = jnp.dot(h, a["wk"]).reshape(n, 1, -1, cfg.head_dim)
                v = jnp.dot(h, a["wv"]).reshape(n, 1, -1, cfg.head_dim)
            o, cache = attend(idx, q, k, v, cache)
            with scope("attn_out"):
                x = x + jnp.dot(o.reshape(n, -1), a["wo"])
        else:
            with scope("router"):
                h = rms_norm(x, lp["ln"], eps)
            out, layer_stats = ep_moe.fwd_held(
                lp["moe"], h, topk=cfg.num_experts_per_tok,
                first=cfg.first_held_expert,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scale=cfg.routed_scaling_factor,
                scoring=cfg.moe_scoring, act=cfg.moe_act)
            x, stats = x + out.astype(x.dtype), stats + layer_stats
        return x, cache, stats

    stats = jnp.zeros((len(STEP_STATS),), jnp.int32)
    count = dict.fromkeys(_KINDS.values(), 0)
    for letter, lp in zip(cfg.layer_pattern, params["layers"]):
        kind = _KINDS[letter]
        x, cache, stats = layer(jnp.asarray(count[kind], jnp.int32), lp, x,
                                cache, stats)
        count[kind] += 1
    with scope("head"):
        x = rms_norm(x, params["ln_f"], eps)
    return x, cache, stats


# No ``verify_step_paged``: see the module's docstring.
prefill_chunk_paged, decode_step_paged, chunk_decode_paged, _ = (
    paged_step.build(
        _layers, slotted=True,
        xla_only="models.mamba_moe has no fused collective layer"))
