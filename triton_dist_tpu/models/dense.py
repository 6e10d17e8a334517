"""Dense Qwen3-style LLM (reference: ``models/dense.py:117`` ``DenseLLM``
/ ``:53`` ``DenseLLMLayer``).

Functional model: ``init_params`` builds the (per-device logical) weight
pytree, ``param_specs`` gives the PartitionSpec pytree, and
``prefill``/``decode_step`` are per-shard functions to run inside
``shard_map`` over a mesh. Forward mode mirrors the reference's
``set_fwd('torch'|'triton_dist'|'triton_dist_AR')`` (``dense.py:146``):
``"xla"``, ``"fused"`` (AG+GEMM / GEMM+RS), ``"fused_ar"`` (GEMM+AR).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import tp_attn, tp_mlp
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.obs import scope
from triton_dist_tpu.ops import (
    create_ag_gemm_context, create_gemm_rs_context, create_gemm_ar_context,
)
from triton_dist_tpu.parallel.mesh import MeshContext


@dataclasses.dataclass(frozen=True)
class FwdContexts:
    """Per-layer fused-op contexts (reference ``dense.py:169-208``
    init_triton_dist_ctx: per-layer create_ag_gemm_context +
    create_gemm_rs_context)."""
    ag: object = None
    rs: object = None
    ar: object = None


def make_fwd_contexts(mesh: MeshContext, axis: str = "tp",
                      block_m: int = 256, block_n: int = 256,
                      block_k: int = 512) -> FwdContexts:
    return FwdContexts(
        ag=create_ag_gemm_context(mesh, axis, block_m, block_n, block_k),
        rs=create_gemm_rs_context(mesh, axis, block_m, block_n, block_k),
        ar=create_gemm_ar_context(mesh, axis, block_n, block_k),
    )


def cache_specs(axis: str = "tp") -> KVCache:
    """PartitionSpec pytree for :class:`KVCache` (KV heads sharded along
    ``axis``) — the Engine's shard_map in/out spec for the cache."""
    return KVCache(k=P(None, None, None, axis, None),
                   v=P(None, None, None, axis, None),
                   length=P())


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = []
    for li in range(cfg.num_hidden_layers):
        ka, km = jax.random.split(keys[li])
        layers.append({
            "attn": tp_attn.init(ka, cfg, dtype),
            "mlp": tp_mlp.init(km, cfg, dtype),
            "ln_attn": jnp.ones((cfg.hidden_size,), dtype),
            "ln_mlp": jnp.ones((cfg.hidden_size,), dtype),
        })
    emb = jax.random.normal(keys[-2], (cfg.vocab_size, cfg.hidden_size),
                            dtype) * 0.02
    lm_head = (emb if cfg.tie_word_embeddings else
               jax.random.normal(keys[-1],
                                 (cfg.vocab_size, cfg.hidden_size),
                                 dtype) * 0.02)
    return {
        "embed": emb,
        "layers": layers,
        "ln_f": jnp.ones((cfg.hidden_size,), dtype),
        "lm_head": lm_head,
    }


def param_specs(cfg: ModelConfig, axis: str = "tp") -> Dict:
    layer_spec = {
        "attn": tp_attn.param_specs(axis, cfg),
        "mlp": tp_mlp.param_specs(axis),
        "ln_attn": P(None),
        "ln_mlp": P(None),
    }
    return {
        "embed": P(None, None),
        "layers": [layer_spec] * cfg.num_hidden_layers,
        "ln_f": P(None),
        "lm_head": P(axis, None),  # vocab-sharded head
    }


def _layer_fwd_prefill(layer_params, x, cfg, *, batch, mode, axis, ctxs,
                       ffn_fn=None):
    """``ffn_fn(layer_params, h) -> h`` overrides the FFN block — the
    hook the MoE model plugs its expert block into (dense default:
    tp_mlp)."""
    h = rms_norm(x, layer_params["ln_attn"], cfg.rms_norm_eps)
    attn_out, kv = tp_attn.fwd_prefill(
        layer_params["attn"], h, cfg, batch=batch, mode=mode, axis=axis,
        ag_ctx=ctxs.ag, rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)
    x = x + attn_out
    h = rms_norm(x, layer_params["ln_mlp"], cfg.rms_norm_eps)
    if ffn_fn is None:
        x = x + tp_mlp.fwd(layer_params["mlp"], h, mode=mode, axis=axis,
                           ag_ctx=ctxs.ag, rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)
    else:
        x = x + ffn_fn(layer_params, h)
    return x, kv


def _embed_tokens(params, input_ids, *, mode, axis):
    """Embed with slice-before-gather: each tp rank embeds only its
    token slice in the token-sharded modes."""
    n = jax.lax.axis_size(axis)
    b, s = input_ids.shape
    flat = input_ids.reshape(b * s)
    if mode in ("xla", "fused"):
        me = jax.lax.axis_index(axis)
        loc = (b * s) // n
        flat = jax.lax.dynamic_slice_in_dim(flat, me * loc, loc, axis=0)
    return params["embed"][flat]


def _forward_trunk(params, input_ids, cfg: ModelConfig, *, mode, axis,
                   ctxs, cache: Optional[KVCache], ffn_fn=None):
    """Shared prefill/all-token forward: embed → layers (optionally
    recording KV) → final norm → gather to full tokens. Returns
    (x (B*S, d) full, cache)."""
    b, s = input_ids.shape
    x = _embed_tokens(params, input_ids, mode=mode, axis=axis)
    for li, layer_params in enumerate(params["layers"]):
        x, kv = _layer_fwd_prefill(
            layer_params, x, cfg, batch=b, mode=mode, axis=axis,
            ctxs=ctxs, ffn_fn=ffn_fn)
        if cache is not None:
            cache = cache.write_prefill(li, *kv)
    x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    if mode in ("xla", "fused"):
        x = jax.lax.all_gather(x, axis, axis=0, tiled=True)
    return x, cache


@scope("head")
def _lm_head(params, x, axis):
    logits_loc = jnp.dot(x, params["lm_head"].T,
                         preferred_element_type=jnp.float32)
    return jax.lax.all_gather(logits_loc, axis, axis=x.ndim - 1,
                              tiled=True)


def prefill(params, input_ids, cfg: ModelConfig, *, mode: str = "xla",
            axis: str = "tp", ctxs: FwdContexts = FwdContexts(),
            max_len: Optional[int] = None, ffn_fn=None):
    """Per-shard prefill. input_ids: (B, S) replicated. Returns
    (logits (B, vocab) for the last position, KVCache per-shard).

    Token-sharded residual stream ("sequence parallel"): requires B*S
    divisible by the axis size in xla/fused modes.
    """
    n = jax.lax.axis_size(axis)
    b, s = input_ids.shape
    kv_loc = max(cfg.num_key_value_heads // n, 1)
    max_len = max_len or s
    cache = KVCache.empty(cfg.num_hidden_layers, b, max_len, kv_loc,
                          cfg.head_dim,
                          dtype=params["embed"].dtype)
    x, cache = _forward_trunk(params, input_ids, cfg, mode=mode,
                              axis=axis, ctxs=ctxs, cache=cache,
                              ffn_fn=ffn_fn)
    cache = dataclasses.replace(cache, length=jnp.asarray(s, jnp.int32))
    last = x.reshape(b, s, cfg.hidden_size)[:, -1]
    return _lm_head(params, last, axis), cache


def forward_tokens(params, input_ids, cfg: ModelConfig, *,
                   mode: str = "xla", axis: str = "tp",
                   ctxs: FwdContexts = FwdContexts()):
    """Per-shard forward returning logits for every position —
    the training-loss forward (B, S, vocab). Same token-sharded layout
    rules as :func:`prefill`."""
    b, s = input_ids.shape
    x, _ = _forward_trunk(params, input_ids, cfg, mode=mode, axis=axis,
                          ctxs=ctxs, cache=None)
    return _lm_head(params, x, axis).reshape(b, s, cfg.vocab_size)


def decode_step(params, token_ids, cache: KVCache, cfg: ModelConfig, *,
                mode: str = "xla", axis: str = "tp",
                ctxs: FwdContexts = FwdContexts(), ffn_fn=None):
    """One decode step. token_ids: (B,) replicated. Returns
    (logits (B, vocab), updated cache). Decode always runs with a
    replicated (B, d) residual (M is tiny) — the reference's
    AR/gemm_ar decode regime (``e2e_dense.md:25,34``).

    Cache updates go through :meth:`KVCache.append_decode` — the same
    project → append → attend → output contract the paged serving path
    (:func:`decode_step_paged`) drives, so dense and paged caches stay
    interchangeable at the model layer.

    ``ffn_fn(layer_params, h) -> h`` overrides the FFN block (the MoE
    model's hook); the dense default is tp_mlp in the AR regime.
    """
    b = token_ids.shape[0]
    x = params["embed"][token_ids]
    pos = cache.length
    dec_mode = "xla" if mode == "xla" else "fused_ar"
    positions = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
    kv_len = jnp.full((b,), pos + 1, dtype=jnp.int32)

    for li, layer_params in enumerate(params["layers"]):
        h = rms_norm(x, layer_params["ln_attn"], cfg.rms_norm_eps)
        q, k_tok, v_tok = tp_attn.decode_project(
            layer_params["attn"], h, cfg, positions, axis=axis)
        cache = cache.append_decode(li, k_tok, v_tok)
        o = tp_attn.sdpa(q, cache.k[li], cache.v[li], causal=False,
                         kv_len=kv_len)
        x = x + tp_attn.decode_output(
            layer_params["attn"], o.reshape(b, -1), h, mode=dec_mode,
            axis=axis, ar_ctx=ctxs.ar)
        h = rms_norm(x, layer_params["ln_mlp"], cfg.rms_norm_eps)
        if ffn_fn is None:
            mlp_mode = "xla_ar" if dec_mode == "xla" else dec_mode
            x = x + tp_mlp.fwd(layer_params["mlp"], h, mode=mlp_mode,
                               axis=axis, ag_ctx=ctxs.ag, rs_ctx=ctxs.rs,
                               ar_ctx=ctxs.ar)
        else:
            x = x + ffn_fn(layer_params, h)

    x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    logits_loc = jnp.dot(x, params["lm_head"].T,
                         preferred_element_type=jnp.float32)
    logits = jax.lax.all_gather(logits_loc, axis, axis=1, tiled=True)
    return logits, cache.advance()


def _paged_layers(params, x, positions, cache, cfg: ModelConfig, attend,
                  *, mode, axis, ctxs, ffn_fn):
    """The layer loop every paged step shares: replicated rows ``x``
    (n, d) at per-row ``positions`` through norm → the decode-contract
    projection → ``attend`` → output projection → FFN, then the final
    norm. ``attend(li, q, k_tok, v_tok, cache) -> (o, cache)`` is what
    tells the steps apart: where the rows' K/V are written and what
    each row's query reads (``o``: anything that reshapes to (n, -1)).
    Returns ``(x (n, d), cache)``."""
    n = x.shape[0]
    dec_mode = "xla" if mode == "xla" else "fused_ar"
    for li, layer_params in enumerate(params["layers"]):
        with scope("attn_project"):
            h = rms_norm(x, layer_params["ln_attn"], cfg.rms_norm_eps)
            q, k_tok, v_tok = tp_attn.decode_project(
                layer_params["attn"], h, cfg, positions, axis=axis)
        o, cache = attend(li, q, k_tok, v_tok, cache)
        with scope("attn_out"):
            x = x + tp_attn.decode_output(
                layer_params["attn"], o.reshape(n, -1), h, mode=dec_mode,
                axis=axis, ar_ctx=ctxs.ar)
        with scope("mlp"):
            h = rms_norm(x, layer_params["ln_mlp"], cfg.rms_norm_eps)
            if ffn_fn is None:
                mlp_mode = "xla_ar" if dec_mode == "xla" else dec_mode
                x = x + tp_mlp.fwd(layer_params["mlp"], h, mode=mlp_mode,
                                   axis=axis, ag_ctx=ctxs.ag,
                                   rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)
            else:
                x = x + ffn_fn(layer_params, h)
    with scope("head"):
        return rms_norm(x, params["ln_f"], cfg.rms_norm_eps), cache


@scope("attn_chunk")
def _chunk_attend(li, q, cache, table_row, positions, start, valid,
                  attn_impl):
    """A prefill chunk's queries (C, 1, H_loc, hd) over its slot's
    pages, causal by global position — after the chunk's own K/V were
    written. Returns (C, H_loc, hd)."""
    if attn_impl == "flash":
        from triton_dist_tpu.ops.paged_flash_qblock import (
            paged_flash_qblock)

        # Bucket-padding rows clamp to the last VALID position:
        # their outputs are discarded garbage either way, but
        # unclamped they would stretch the kernel's page-walk
        # bound (max position) to the padded tail — 8x the DMA
        # traffic for exactly the short-prompt-in-a-big-bucket
        # case the kernel exists to make cheap.
        i = jnp.arange(positions.shape[0], dtype=jnp.int32)
        last_valid = (jnp.asarray(start, jnp.int32)
                      + jnp.maximum(jnp.asarray(valid, jnp.int32)
                                    - 1, 0))
        qpos = jnp.where(i < valid, positions, last_valid)
        ksc, vsc = cache.layer_scales(li)
        return paged_flash_qblock(
            q[:, 0][None], cache.k_pages, cache.v_pages,
            table_row[None], qpos[None], layer=li,
            k_scale=ksc, v_scale=vsc)[0]
    from triton_dist_tpu.ops.chunked_prefill import chunk_attend

    kd, vd = cache.dense_row(li, table_row)
    return chunk_attend(q[:, 0], kd, vd, positions)


@scope("attn_decode")
def _decode_attend(li, q, cache, attn_impl):
    """One query a slot (S, 1, H_loc, hd) over the slot's pages at its
    own length — after the step's token was appended."""
    # Active slots attend including the token appended this step;
    # parked slots clamp to 1 so a fully-masked row cannot NaN the
    # softmax (their output is discarded anyway).
    kv_len = jnp.maximum(cache.lens + cache.live, 1).astype(jnp.int32)
    if attn_impl in ("kernel", "flash"):
        from triton_dist_tpu.ops.paged_flash_decode import (
            paged_flash_decode)

        ksc, vsc = cache.layer_scales(li)
        return paged_flash_decode(
            q[:, 0], cache.k_pages, cache.v_pages, cache.block_table,
            kv_len, layer=li, axis=None, k_scale=ksc, v_scale=vsc)
    kd, vd = cache.dense_layer(li)
    return tp_attn.sdpa(q, kd, vd, causal=False, kv_len=kv_len)


@scope("embed")
def _embed_rows(params, token_ids):
    """The table's rows of ``token_ids`` (n,), replicated: (n, d)."""
    return params["embed"][token_ids]


@scope("head")
def _last_valid_row(x, valid):
    """Row ``valid - 1`` of a chunk's (C, d) rows, as (1, d)."""
    return jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(jnp.asarray(valid, jnp.int32) - 1, 0), 1, axis=0)


def verify_step_paged(params, token_ids, cache, cfg: ModelConfig, *,
                      budget=None, mode: str = "xla", axis: str = "tp",
                      ctxs: FwdContexts = FwdContexts(),
                      attn_impl: str = "ref", ffn_fn=None):
    """One SPECULATIVE-VERIFICATION step over a
    :class:`~triton_dist_tpu.serving.blocks.PagedKVCache`: K candidate
    tokens per slot through one fixed-shape dispatch.

    token_ids: (S, K) replicated — slot s's candidates are fed at
    positions ``lens[s]..lens[s]+K-1`` (K is STATIC, so the jit cache
    stays at one entry regardless of how many candidates end up
    accepted); ``budget`` (S,) int32 caps how many candidates may
    WRITE real pages per slot (over-budget rows near a request's
    token limit land in scratch — data, not shape).
    Per layer: project all S·K rows through the decode
    contract (:func:`tp_attn.decode_project` at per-row positions),
    write every candidate's K/V via :meth:`PagedKVCache.append_block`
    (parked slots land in the scratch page), then attend each
    candidate over the slot's gathered page view with the per-query
    causal mask (:func:`~triton_dist_tpu.ops.chunked_prefill.
    block_attend`) — candidate j sees exactly what a sequential decode
    of the accepted prefix would see, which is what makes accepted
    tokens token-exact with non-speculative greedy decode.

    ``attn_impl``: ``"ref"`` attends through the gather path
    (:func:`~triton_dist_tpu.ops.chunked_prefill.block_attend` over
    :meth:`PagedKVCache.dense_layer` — materializes every slot's
    dense row); ``"flash"`` streams pages through the K-query
    :func:`~triton_dist_tpu.ops.paged_flash_qblock.paged_flash_qblock`
    kernel with the same per-query causal positions riding as data —
    no dense-row materialization, work scales with resident pages.

    Returns ``(logits (S, K, vocab), cache)``. ``logits[s, j]`` is the
    next-token distribution AFTER feeding candidates 0..j. The cache's
    ``lens`` are NOT advanced — the host commits the accepted prefix
    by advancing its length mirrors (rejected suffixes simply stay
    masked garbage the next block overwrites), and rolls page
    accounting back via ``BlockManager.truncate_to``.
    """
    s, k = token_ids.shape
    x = _embed_rows(params, token_ids.reshape(s * k))     # (S·K, d)
    lens = cache.lens
    positions = (lens[:, None]
                 + jnp.arange(k, dtype=jnp.int32)[None]).reshape(s * k)

    def attend(li, q, k_tok, v_tok, cache):
        hl, hd = q.shape[2], q.shape[3]
        kvl = k_tok.shape[2]
        with scope("cache_write"):
            cache = cache.append_block(
                li, k_tok[:, 0].reshape(s, k, kvl, hd),
                v_tok[:, 0].reshape(s, k, kvl, hd), budget=budget)
        with scope("attn_decode"):
            q = q[:, 0].reshape(s, k, hl, hd)
            if attn_impl == "flash":
                from triton_dist_tpu.ops.paged_flash_qblock import (
                    paged_flash_qblock)

                # Candidate j of a live slot attends positions
                # <= lens[s]+j (its paged history + the candidate
                # prefix through itself — block_attend's kv_len-1);
                # parked slots clamp to position 0 (garbage the
                # scheduler ignores).
                qpos = jnp.maximum(
                    lens[:, None] + cache.live[:, None]
                    * (jnp.arange(k, dtype=jnp.int32)[None] + 1), 1) - 1
                ksc, vsc = cache.layer_scales(li)
                return paged_flash_qblock(
                    q, cache.k_pages, cache.v_pages, cache.block_table,
                    qpos, layer=li, k_scale=ksc, v_scale=vsc), cache
            from triton_dist_tpu.ops.chunked_prefill import block_attend

            kd, vd = cache.dense_layer(li)
            return block_attend(q, kd, vd, lens, cache.live), cache

    x, cache = _paged_layers(params, x, positions, cache, cfg, attend,
                             mode=mode, axis=axis, ctxs=ctxs,
                             ffn_fn=ffn_fn)
    return _lm_head(params, x, axis).reshape(s, k, -1), cache


def paged_pool(cfg: ModelConfig):
    """The pool this model keeps, as the serving engine allocates it:
    the cache class and what a token takes in a layer, here keys and
    values of every KV head."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    return PagedKVCache, (cfg.num_key_value_heads, cfg.head_dim)


def paged_cache_specs(axis: str = "tp", quantized: bool = False):
    """PartitionSpec pytree for the serving
    :class:`~triton_dist_tpu.serving.blocks.PagedKVCache` (KV heads
    sharded along ``axis``; page pool, table, and lengths replicated in
    every other dim) — the ServingEngine's shard_map spec.
    ``quantized=True`` adds the per-page scale arrays' specs (their KV
    dim shards with the heads whose pages they dequantize)."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    scale = P(None, None, axis) if quantized else None
    return PagedKVCache(
        k_pages=P(None, None, axis, None, None),
        v_pages=P(None, None, axis, None, None),
        block_table=P(None, None), lens=P(None), live=P(None),
        k_scale=scale, v_scale=scale)


def prefill_chunk_paged(params, chunk_toks, cache, table_row,
                        cfg: ModelConfig, *, start, wfrom, valid,
                        mode: str = "xla", axis: str = "tp",
                        ctxs: FwdContexts = FwdContexts(),
                        attn_impl: str = "ref", ffn_fn=None):
    """One FIXED-SHAPE chunk of a bucketed paged prefill (per-shard).

    The chunked half of the serving split: instead of one monolithic
    prefill dispatch per prompt length (which XLA specializes per
    length), the prompt streams through this step in bucketed chunks —
    the trace signature depends only on the chunk length ``C``, so the
    prefill jit cache is bounded by the bucket count.

    chunk_toks: (C,) int32 replicated, padded past ``valid``;
    ``table_row``: (p_max,) int32 — the slot's block-table row (data);
    ``start``: scalar — global position of the chunk's first token;
    ``wfrom``: scalar — positions below it are already resident
    (prefix-shared pages; computed but never rewritten); ``valid``:
    scalar — real tokens in this chunk. All three ride as data.

    Per layer: project the chunk through the decode contract
    (:func:`tp_attn.decode_project` at per-row positions), write K/V
    into the slot's pages (:meth:`PagedKVCache.write_chunk`), then
    attend the chunk's queries over the slot's gathered position-major
    page view with the global causal mask
    (:func:`~triton_dist_tpu.ops.chunked_prefill.chunk_attend`) — so
    earlier chunks and the shared prefix are attended exactly and
    chunk boundaries are invisible to the math. The residual stays
    replicated (the decode AR regime — no token-sharding divisibility
    constraint ties C to the mesh).

    ``attn_impl``: ``"ref"`` gathers the slot's dense row per layer
    (:meth:`PagedKVCache.dense_row` + ``chunk_attend`` — O(p_max·page)
    HBM traffic per chunk regardless of the prompt's actual length);
    ``"flash"`` streams only the RESIDENT pages through the Q-block
    :func:`~triton_dist_tpu.ops.paged_flash_qblock.paged_flash_qblock`
    kernel (positions ride as data — the trace still keys only on the
    bucket length).

    Returns ``(logits (vocab,) of the LAST VALID token, cache)`` — the
    final chunk's logits seed the first generated token; earlier
    chunks' logits are discarded.
    """
    c = chunk_toks.shape[0]
    x = _embed_rows(params, chunk_toks)
    positions = (jnp.asarray(start, jnp.int32)
                 + jnp.arange(c, dtype=jnp.int32))

    def attend(li, q, k_tok, v_tok, cache):
        with scope("cache_write"):
            cache = cache.write_chunk(li, k_tok, v_tok, table_row,
                                      positions, valid, wfrom)
        return _chunk_attend(li, q, cache, table_row, positions, start,
                             valid, attn_impl), cache

    x, cache = _paged_layers(params, x, positions, cache, cfg, attend,
                             mode=mode, axis=axis, ctxs=ctxs,
                             ffn_fn=ffn_fn)
    logits = _lm_head(params, _last_valid_row(x, valid), axis)
    return logits[0], cache


def decode_step_paged(params, token_ids, cache, cfg: ModelConfig, *,
                      mode: str = "xla", axis: str = "tp",
                      ctxs: FwdContexts = FwdContexts(),
                      attn_impl: str = "ref", ffn_fn=None):
    """One CONTINUOUS-BATCHING decode step over a
    :class:`~triton_dist_tpu.serving.blocks.PagedKVCache`.

    token_ids: (S,) replicated — one per batch slot; ``cache`` carries
    per-slot block tables, lengths, and the live mask. Every slot ropes
    and attends at its OWN length, so requests of different ages share
    one fixed-shape dispatch (the continuous-batching decode step the
    serving scheduler drives — no recompilation as requests join and
    leave). Parked slots (live == 0) still flow through the math (the
    shape is fixed) but their appends land in the manager's reserved
    scratch page, their lengths do not advance, and their logits are
    garbage the scheduler ignores.

    ``attn_impl``: ``"ref"`` gathers each layer's pages to a dense
    (S, cap, KV_loc, hd) view and reuses :func:`tp_attn.sdpa` — the
    token-exact-with-``Engine.serve`` path (and the CPU default);
    ``"kernel"`` streams pages through
    :func:`~triton_dist_tpu.ops.paged_flash_decode.paged_flash_decode`
    without materializing the dense view (the TPU path). ``"flash"``
    is an alias for ``"kernel"`` here (the one-query decode step IS
    the paged flash kernel) — it exists so the serving engine can
    spell "Pallas paged attention everywhere" with one knob value
    covering decode, chunked prefill, and speculative verification.

    ``ffn_fn(layer_params, h) -> h`` overrides the FFN block (the MoE
    model's hook), exactly as in :func:`decode_step`.
    """
    x = _embed_rows(params, token_ids)

    def attend(li, q, k_tok, v_tok, cache):
        with scope("cache_write"):
            cache = cache.append_decode(li, k_tok, v_tok)
        return _decode_attend(li, q, cache, attn_impl), cache

    x, cache = _paged_layers(params, x, cache.lens, cache, cfg, attend,
                             mode=mode, axis=axis, ctxs=ctxs,
                             ffn_fn=ffn_fn)
    return _lm_head(params, x, axis), cache.advance()


def chunk_decode_paged(params, chunk_toks, token_ids, cache, table_row,
                       cfg: ModelConfig, *, start, wfrom, valid,
                       mode: str = "xla", axis: str = "tp",
                       ctxs: FwdContexts = FwdContexts(),
                       attn_impl: str = "ref",
                       decode_attn_impl: str = "ref", ffn_fn=None):
    """One prefill chunk of one slot AND one decode step of the whole
    batch in ONE program: :func:`prefill_chunk_paged` and
    :func:`decode_step_paged` on the same pool, with every weight read
    once for both.

    The chunk's ``C`` rows and the batch's ``S`` decode rows go through
    embedding, projections, output projection, FFN and final norm as
    one row-concatenated ``(C + S, d)`` activation; only where the
    K/V are written and what each query reads differs, and there each
    half runs the code of the step it replaces: the chunk rows write
    through ``table_row`` and attend causally by global position, the
    decode rows append and attend through ``cache.block_table`` at
    ``cache.lens`` (parked rows, ``live == 0``, write the scratch page;
    the chunk's own slot is parked in the decode batch until its prompt
    is resident, so neither half reads what the other writes). The head
    runs once, over the chunk's last valid row and the decode rows.

    Arguments as the two steps': ``chunk_toks`` (C,), ``token_ids``
    (S,), scalars ``start``/``wfrom``/``valid`` and the table, lengths
    and live mask all ride as data — the trace keys on ``C`` alone.
    ``attn_impl`` is the chunk rows' ("ref" | "flash"),
    ``decode_attn_impl`` the decode rows' ("ref" | "kernel" | "flash").

    Returns ``(chunk logits (vocab,), decode logits (S, vocab),
    cache.advance())``.
    """
    c = chunk_toks.shape[0]
    x = _embed_rows(params, jnp.concatenate([chunk_toks, token_ids]))
    chunk_pos = (jnp.asarray(start, jnp.int32)
                 + jnp.arange(c, dtype=jnp.int32))

    def attend(li, q, k_tok, v_tok, cache):
        # Both writes, then both reads: each kernel takes the pool as
        # the layer's last writer left it, in place.
        with scope("cache_write"):
            cache = cache.write_chunk(li, k_tok[:c], v_tok[:c], table_row,
                                      chunk_pos, valid, wfrom)
            cache = cache.append_decode(li, k_tok[c:], v_tok[c:])
        o_chunk = _chunk_attend(li, q[:c], cache, table_row, chunk_pos,
                                start, valid, attn_impl)
        o_dec = _decode_attend(li, q[c:], cache, decode_attn_impl)
        return jnp.concatenate(
            [o_chunk.reshape(c, -1),
             o_dec.reshape(q.shape[0] - c, -1)]), cache

    x, cache = _paged_layers(
        params, x, jnp.concatenate([chunk_pos, cache.lens]), cache, cfg,
        attend, mode=mode, axis=axis, ctxs=ctxs, ffn_fn=ffn_fn)
    with scope("head"):
        logits = _lm_head(params, jnp.concatenate(
            [_last_valid_row(x[:c], valid), x[c:]]), axis)
        chunk_logits, decode_logits = logits[0], logits[1:]
    return chunk_logits, decode_logits, cache.advance()
