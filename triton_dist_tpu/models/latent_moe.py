"""Latent attention over a chip's share of routed experts: the paged
serving contract of :mod:`triton_dist_tpu.models.dense` for a block
whose cache is one latent a token and whose FFN is an expert layer told
which experts it holds.

The block (``h`` the normed residual row; sizes from ``ModelConfig``'s
latent fields):

    c_q = rms(h w_dq);  q = c_q w_uq          (H, d_n + d_r) a row
    [c_kv | k_r] = h w_dkv;  c = rms(c_kv)    (r_kv), (d_r): the CACHE
    rope on q's last d_r and on k_r: interleaved pairs, YaRN
        frequencies; q *= 1 + beta ln(1 + pos // original)
    [k_n | v] = c w_ukv                       (H, d_n + d_v) a key
    s = (q_n . k_n + q_r . k_r) sigma;  o = softmax(s) v;  + o wo

Two attention paths over the one pool
(:class:`~triton_dist_tpu.serving.blocks.LatentPagedCache`, ``[c | roped
k_r]`` a token a layer, a page lying ``(width, page)``):

- a prefill chunk's rows EXPAND: a block of the slot's pages at a time
  is gathered, its keys and values made by ``w_ukv``, and the rows
  attend heads of ``d_n + d_r`` and ``d_v``: the cheap form where many
  rows share the expanded keys (:func:`_attend_expanded`);
- decode and verification rows ABSORB ``w_ukv`` into the query and the
  output, ``q~ = q_n w_uk^T``, ``s = (q~ . c + q_r . k_r) sigma``,
  ``o = (softmax(s) c) w_uv``, and attend the latent itself: no key is
  ever expanded for a row that is alone with its context
  (:func:`_attend_absorbed`).

Both walk the context in blocks of whole pages with a running softmax
(no ``(rows, heads, context)`` score array exists) and stop at the last
block any row can see. The chunk rows' walk is a Pallas kernel wherever
its sizes tile (:func:`step_kernels`,
:mod:`triton_dist_tpu.ops.latent_flash_qblock`), with the XLA walk below
as its fallback and its oracle; the absorbed walk is plain XLA.

The FFN is :func:`~triton_dist_tpu.layers.ep_moe.fwd_held`: the router
over every expert, the held experts' part of the result, the shared
expert whole. Every step function returns, last, ``STEP_STATS`` as one
int32 vector summed over layers; the serving programs carry it out
beside the picked tokens (docs/observability.md, "Held experts").

Everything is replicated over ``axis`` but the head's vocabulary rows:
the deployment this stands for divides a layer's EXPERTS over chips,
and this module is one of those chips with no peer.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import ep_moe
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.rope import (apply_rope_interleaved,
                                         yarn_freqs, yarn_mscale)
from triton_dist_tpu.models import paged_step
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import FwdContexts
from triton_dist_tpu.obs import scope
from triton_dist_tpu.ops import latent_flash_qblock as _flash

# What every step function returns last, summed over layers: the
# token-expert pairs that fell to held experts, the most rows one held
# expert was given, and the passes the held experts ran.
STEP_STATS = ("held_pairs", "expert_rows_max", "expert_passes")

# Keys a block of the context walk holds, at most: the expanded path's
# float32 scores are heads x rows x this.
BLOCK_KEYS = 1280
_NEG = -1e30


def cache_width(cfg: ModelConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def paged_pool(cfg: ModelConfig):
    """The pool this model keeps: one array of ``[latent | roped key]``
    a token a layer."""
    from triton_dist_tpu.serving.blocks import LatentPagedCache

    return LatentPagedCache, (cache_width(cfg),)


def paged_cache_specs(axis: str = "tp", quantized: bool = False):
    from triton_dist_tpu.serving.blocks import LatentPagedCache

    if quantized:
        raise ValueError("the latent pool is not quantized")
    return LatentPagedCache(pages=P(None, None, None, None),
                            block_table=P(None, None), lens=P(None),
                            live=P(None))


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    dq = h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    dkv = h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    e = cfg.held_experts

    def w(k, *shape):
        return (jax.random.normal(k, shape, dtype)
                * shape[-2] ** -0.5)

    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = []
    for li in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[li], 12)
        moe = {"router": w(k[5], d, cfg.num_experts),
               "w_gate": w(k[6], e, d, f), "w_up": w(k[7], e, d, f),
               "w_down": w(k[8], e, f, d)}
        if fs:
            moe.update(w_shared_gate=w(k[9], d, fs),
                       w_shared_up=w(k[10], d, fs),
                       w_shared_down=w(k[11], fs, d))
        layers.append({
            "attn": {"w_dq": w(k[0], d, cfg.q_lora_rank),
                     "q_norm": jnp.ones((cfg.q_lora_rank,), dtype),
                     "w_uq": w(k[1], cfg.q_lora_rank, dq),
                     "w_dkv": w(k[2], d, cache_width(cfg)),
                     "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
                     "w_ukv": w(k[3], cfg.kv_lora_rank, dkv),
                     "wo": w(k[4], h * cfg.v_head_dim, d)},
            "moe": moe,
            "ln_attn": jnp.ones((d,), dtype),
            "ln_mlp": jnp.ones((d,), dtype)})
    table = lambda k: jax.random.normal(
        k, (cfg.vocab_size, d), dtype) * 0.02
    emb = table(keys[-2])
    return {"embed": emb, "layers": layers,
            "ln_f": jnp.ones((d,), dtype),
            "lm_head": emb if cfg.tie_word_embeddings else table(keys[-1])}


def param_specs(cfg: ModelConfig, axis: str = "tp") -> Dict:
    layer = jax.tree.map(
        lambda x: P(*(None,) * x.ndim),
        jax.eval_shape(lambda: init_params(
            jax.random.PRNGKey(0), cfg)["layers"][0]))
    return {"embed": P(None, None),
            "layers": [layer] * cfg.num_hidden_layers,
            "ln_f": P(None), "lm_head": P(axis, None)}


# -- the Engine's dense-cache contract: not this model's path ---------------

def cache_specs(axis: str = "tp"):
    from triton_dist_tpu.models import dense

    return dense.cache_specs(axis)


def _paged_only(*_, **__):
    raise NotImplementedError(
        "models.latent_moe serves through the paged latent pool only: "
        "Engine(...).serving(prefill_buckets=...); it keeps no dense "
        "per-request cache for Engine.serve")


prefill = decode_step = _paged_only


# -- projections -----------------------------------------------------------

def softmax_scale(cfg: ModelConfig) -> float:
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope(x, positions, cfg: ModelConfig):
    inv = yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta,
                     factor=cfg.rope_factor,
                     original=cfg.rope_original_max_position,
                     beta_fast=cfg.rope_beta_fast,
                     beta_slow=cfg.rope_beta_slow)
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return apply_rope_interleaved(x, positions, inv, scale)


def project(attn, h, cfg: ModelConfig, positions):
    """A row's queries and its cache entry. h: (n, d), positions (n,).
    Returns ``q (n, H, d_n + d_r)``, roped and scaled by position, and
    ``latent (n, r_kv + d_r)``: ``[rms(c_kv) | roped k_r]``."""
    n = h.shape[0]
    dn = cfg.qk_nope_head_dim
    eps = cfg.rms_norm_eps
    c_q = rms_norm(jnp.dot(h, attn["w_dq"]), attn["q_norm"], eps)
    q = jnp.dot(c_q, attn["w_uq"]).reshape(n, cfg.num_attention_heads, -1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], positions, cfg)],
                        axis=-1)
    if cfg.rope_query_scale_beta:
        scale = 1.0 + cfg.rope_query_scale_beta * jnp.log1p(jnp.floor(
            positions.astype(jnp.float32)
            / cfg.rope_original_max_position))
        q = (q.astype(jnp.float32) * scale[:, None, None]).astype(q.dtype)
    ckv = jnp.dot(h, attn["w_dkv"])
    r = cfg.kv_lora_rank
    latent = jnp.concatenate(
        [rms_norm(ckv[:, :r], attn["kv_norm"], eps),
         _rope(ckv[:, r:], positions, cfg)], axis=-1)
    return q, latent


def _w_ukv(attn, cfg: ModelConfig):
    """``w_ukv`` by head: (r_kv, H, d_n + d_v)."""
    return attn["w_ukv"].reshape(cfg.kv_lora_rank,
                                 cfg.num_attention_heads, -1)


# -- the context walk ------------------------------------------------------

def _blocked_table(table, page: int):
    """``(table', pages a block)`` for a walk over ``table`` (..., p_max)
    in blocks of at most ``BLOCK_KEYS`` keys: the row padded to whole
    blocks with the scratch page, whose positions lie past every
    length."""
    ppb = max(min(BLOCK_KEYS // page, table.shape[-1]), 1)
    pad = -table.shape[-1] % ppb
    return jnp.pad(table, [(0, 0)] * (table.ndim - 1) + [(0, pad)]), ppb


def _walk(n_blocks, scores_and_values, shape_m, shape_acc):
    """A running softmax over ``n_blocks`` blocks of keys (a traced
    count: the walk stops at the last block a row can see).
    ``scores_and_values(j)`` gives block ``j``'s masked float32 scores
    ``(..., keys)`` and ``pv(p)``, the block's values under weights
    ``p``. Returns the normalised output, float32."""
    def body(j, carry):
        m, l, acc = carry
        s, pv = scores_and_values(j)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        return (m_new, l * alpha + jnp.sum(p, axis=-1),
                acc * alpha[..., None] + pv(p))

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full(shape_m, _NEG, jnp.float32),
         jnp.zeros(shape_m, jnp.float32),
         jnp.zeros(shape_acc, jnp.float32)))
    return acc / l[..., None]


def _attend_expanded(attn, q, cache, li, table_row, qpos, cfg):
    """A chunk's rows over their slot's pages, keys and values expanded
    a block at a time. q: (C, H, d_n + d_r); qpos (C,) the last position
    each row sees. Returns (C, H * d_v)."""
    c, h, dqk = q.shape
    dn, dv, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    table_row, ppb = _blocked_table(table_row, cache.page)
    keys = ppb * cache.page
    sigma = softmax_scale(cfg)
    w_ukv = _w_ukv(attn, cfg)

    def block(j):
        lat = cache.gather(li, jax.lax.dynamic_slice(
            table_row, (j * ppb,), (ppb,)))            # (ppb, W, page)
        kv = jnp.einsum("jrp,rhd->jphd", lat[:, :r], w_ukv)
        kv = kv.reshape(keys, h, dn + dv)
        k_r = lat[:, r:].transpose(0, 2, 1).reshape(keys, 1, dqk - dn)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (keys, h, dqk - dn))], -1)
        s = jnp.einsum("chd,khd->hck", q, k,
                       preferred_element_type=jnp.float32) * sigma
        kpos = j * keys + jnp.arange(keys, dtype=jnp.int32)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, _NEG)
        v = kv[..., dn:]
        return s, lambda p: jnp.einsum(
            "hck,khd->hcd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)

    o = _walk(jnp.max(qpos) // keys + 1, block, (h, c), (h, c, dv))
    return o.transpose(1, 0, 2).reshape(c, h * dv).astype(q.dtype)


def _walk_tiles(cfg: ModelConfig, rows: int, page: int) -> bool:
    """Whether a chunk of ``rows`` rows walks its context in the Pallas
    kernel (``latent_flash_qblock``: the pool's pages, the head sizes
    and the row count tile for Mosaic) or in XLA
    (:func:`_attend_expanded`). A pure function of sizes: the same
    program on a chip and, interpreted, off it."""
    return _flash.legal(rows, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank, cache_width(cfg),
                        page)


def step_kernels(cfg: ModelConfig, rows: int, *, decode_rows: int,
                 page: int, dtype) -> tuple:
    """The blocks, of ``paged_step.STEP_KERNELS``, that a chunk program
    of ``rows`` chunk rows with ``decode_rows`` aboard runs in a Pallas
    kernel: the chunk rows' walk (:func:`_walk_tiles`) and the held
    experts' MLP, by :func:`ep_moe.experts_impl` at the pass
    :func:`ep_moe.held_pass_rows` gives every row of the program. The
    serving engine counts its chunk dispatches by it."""
    n_held = cfg.held_experts
    experts = ep_moe.experts_impl(
        ep_moe.held_pass_rows(rows + decode_rows, cfg.num_experts_per_tok,
                              n_held, cfg.num_experts),
        n_held, cfg.hidden_size, cfg.moe_intermediate_size, dtype)
    return (("walk",) * _walk_tiles(cfg, rows, page)
            + ("experts",) * (experts == "kernel"))


@scope("attn_chunk")
def _attend_chunk(attn, q, cache, li, table_row, qpos, cfg):
    """A chunk's rows over their slot's pages, by
    :func:`_walk_tiles`. Returns (C, H * d_v)."""
    if not _walk_tiles(cfg, q.shape[0], cache.page):
        return _attend_expanded(attn, q, cache, li, table_row, qpos, cfg)
    return _flash.latent_flash_qblock(
        q, cache.pages, table_row, qpos, _w_ukv(attn, cfg), layer=li,
        sigma=softmax_scale(cfg))


@scope("attn_decode")
def _attend_absorbed(attn, q, cache, li, qpos, cfg):
    """Rows alone with their slot's context, in the latent. q: (S, R, H,
    d_n + d_r), R rows a slot; qpos (S, R) the last position each sees.
    Returns (S * R, H * d_v)."""
    s_, r_, h, _ = q.shape
    dn, dv, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    table, ppb = _blocked_table(cache.block_table, cache.page)
    keys = ppb * cache.page
    sigma = softmax_scale(cfg)
    w_ukv = _w_ukv(attn, cfg)
    # q~ = q_n w_uk^T beside q_r: one query against [c | k_r].
    q_lat = jnp.concatenate(
        [jnp.einsum("srhd,chd->srhc", q[..., :dn], w_ukv[..., :dn]),
         q[..., dn:]], axis=-1)

    def block(j):
        lat = cache.gather(li, jax.lax.dynamic_slice_in_dim(
            table, j * ppb, ppb, axis=1))              # (S, ppb, W, page)
        s = jnp.einsum("srhw,sjwp->srhjp", q_lat, lat,
                       preferred_element_type=jnp.float32) * sigma
        s = s.reshape(s.shape[:3] + (keys,))
        kpos = j * keys + jnp.arange(keys, dtype=jnp.int32)
        s = jnp.where(kpos[None, None, None, :]
                      <= qpos[:, :, None, None], s, _NEG)
        return s, lambda p: jnp.einsum(
            "srhjp,sjcp->srhc",
            p.astype(lat.dtype).reshape(p.shape[:3] + (ppb, -1)),
            lat[:, :, :r], preferred_element_type=jnp.float32)

    o_lat = _walk(jnp.max(qpos) // keys + 1, block, (s_, r_, h),
                  (s_, r_, h, r))
    o = jnp.einsum("srhc,chd->srhd", o_lat.astype(q.dtype),
                   w_ukv[..., dn:])
    return o.reshape(s_ * r_, h * dv)


# -- the layers ------------------------------------------------------------

def _layers(params, rows, cache, cfg: ModelConfig, *, mode, axis, attn_impl,
            decode_attn_impl, ctxs: FwdContexts = FwdContexts()):
    """The trunk every step of this family is built from
    (:func:`paged_step.build`): ``rows`` embedded, (n, d), then every
    layer over the latent pool's halves (:func:`_attend`), then the
    final norm. Returns ``(x (n, d), cache, stats)``: ``STEP_STATS``.

    The layer is ONE jitted function of its index (an int32 scalar, an
    operand) and its parameters: a step program is traced and lowered at
    every start of the server (no compile cache keeps either), and six
    layers written out were three quarters of both (PERF.md, PR 39). XLA
    inlines the calls; the compiled program is the one the loop written
    out gave."""
    for impl in (attn_impl, decode_attn_impl):
        if impl != "ref":
            raise ValueError(
                f"attn_impl={impl!r}: latent attention takes 'ref' alone, "
                "the model's own paths (the chunk rows' walk is chosen by "
                "sizes: step_kernels)")
    attend = _attend(rows, cfg)
    x = paged_step.embed_rows(params, rows.tokens())
    positions = rows.positions(cache)

    @jax.jit
    def layer(li, lp, x, cache, stats):
        with scope("attn_project"):
            h = rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps)
            q, latent = project(lp["attn"], h, cfg, positions)
        o, cache = attend(li, lp["attn"], q, latent, cache)
        with scope("attn_out"):
            x = x + jnp.dot(o, lp["attn"]["wo"])
        with scope("router"):
            h = rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps)
        out, layer_stats = ep_moe.fwd_held(
            lp["moe"], h, topk=cfg.num_experts_per_tok,
            first=cfg.first_held_expert,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scale=cfg.routed_scaling_factor)
        return x + out.astype(x.dtype), cache, stats + layer_stats

    stats = jnp.zeros((len(STEP_STATS),), jnp.int32)
    for li, lp in enumerate(params["layers"]):
        x, cache, stats = layer(jnp.asarray(li, jnp.int32), lp, x, cache,
                                stats)
    with scope("head"):
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    return x, cache, stats


def _chunk_qpos(positions, start, valid):
    """The last position each chunk row sees: its own, and for bucket
    padding the last valid row's, so that the walk stops there."""
    i = jnp.arange(positions.shape[0], dtype=jnp.int32)
    last = (jnp.asarray(start, jnp.int32)
            + jnp.maximum(jnp.asarray(valid, jnp.int32) - 1, 0))
    return jnp.where(i < valid, positions, last)


def _decode_qpos(cache):
    """(S, 1): a live slot's row sees through the token appended this
    step; a parked one position 0 (scratch: finite, discarded)."""
    return (jnp.maximum(cache.lens + cache.live, 1) - 1)[:, None]


def _attend(rows: paged_step.Rows, cfg: ModelConfig):
    """The latent pool's halves: ``attend(li, attn_params, q, latent,
    cache) -> (o (n, H * d_v), cache)`` writes one latent a row, then
    the chunk's rows expand (:func:`_attend_chunk`) and the decode rows
    absorb (:func:`_attend_absorbed`), both writes before both reads as
    the K/V pool's (:func:`paged_step.kv_attend`); candidate ``j`` of a
    verification row's slot sees its paged history and the candidates
    through itself, in the latent."""
    if rows.k:
        s, k, steps = rows.s, rows.k, rows.steps

        def attend(li, attn, q, latent, cache):
            with scope("cache_write"):
                cache = cache.append_block(li, latent.reshape(s, k, -1),
                                           budget=rows.budget)
            qpos = jnp.maximum(cache.lens[:, None] + cache.live[:, None]
                               * (steps + 1), 1) - 1
            return _attend_absorbed(attn, q.reshape((s, k) + q.shape[1:]),
                                    cache, li, qpos, cfg), cache

        return attend
    pos = qpos = None
    if rows.c:
        pos = rows.chunk_pos
        qpos = _chunk_qpos(pos, rows.start, rows.valid)

    def attend(li, attn, q, latent, cache):
        with scope("cache_write"):
            _, cache = rows.split(
                lambda cache, lat: (None, cache.write_chunk(
                    li, lat, rows.table_row, pos, rows.valid, rows.wfrom)),
                lambda cache, lat: (None, cache.append_decode(li, lat)),
                cache, latent)
        return rows.split(
            lambda cache, q: (_attend_chunk(
                attn, q, cache, li, rows.table_row, qpos, cfg), cache),
            lambda cache, q: (_attend_absorbed(
                attn, q, cache, li, _decode_qpos(cache), cfg), cache),
            cache, q, per_slot=True)

    return attend


(prefill_chunk_paged, decode_step_paged, chunk_decode_paged,
 verify_step_paged) = paged_step.build(
    _layers, xla_only="models.latent_moe has no fused collective layer")
