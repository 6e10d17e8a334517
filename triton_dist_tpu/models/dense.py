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
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import tp_attn, tp_mlp
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models import paged_step
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.models.paged_step import lm_head
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


def embed_tokens(params, input_ids, *, mode, axis):
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


def forward_trunk(params, input_ids, cfg: ModelConfig, *, mode, axis,
                   ctxs, cache: Optional[KVCache], ffn_fn=None):
    """Shared prefill/all-token forward: embed → layers (optionally
    recording KV) → final norm → gather to full tokens. Returns
    (x (B*S, d) full, cache)."""
    b, s = input_ids.shape
    x = embed_tokens(params, input_ids, mode=mode, axis=axis)
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
    x, cache = forward_trunk(params, input_ids, cfg, mode=mode,
                              axis=axis, ctxs=ctxs, cache=cache,
                              ffn_fn=ffn_fn)
    cache = dataclasses.replace(cache, length=jnp.asarray(s, jnp.int32))
    last = x.reshape(b, s, cfg.hidden_size)[:, -1]
    return lm_head(params, last, axis), cache


def forward_tokens(params, input_ids, cfg: ModelConfig, *,
                   mode: str = "xla", axis: str = "tp",
                   ctxs: FwdContexts = FwdContexts()):
    """Per-shard forward returning logits for every position —
    the training-loss forward (B, S, vocab). Same token-sharded layout
    rules as :func:`prefill`."""
    b, s = input_ids.shape
    x, _ = forward_trunk(params, input_ids, cfg, mode=mode, axis=axis,
                          ctxs=ctxs, cache=None)
    return lm_head(params, x, axis).reshape(b, s, cfg.vocab_size)


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


def paged_layers(params, rows, cache, cfg: ModelConfig, *, mode, axis,
                 attn_impl, decode_attn_impl,
                 ctxs: FwdContexts = FwdContexts(), ffn_fn=None):
    """The trunk every paged step of this family is built from
    (:func:`paged_step.build`): ``rows`` embedded, replicated (n, d: the
    decode AR regime), then every layer, norm → the decode-contract
    projection at per-row positions → the K/V pool's halves
    (:func:`paged_step.kv_attend`) → output projection → FFN, then the
    final norm. ``ffn_fn(layer_params, h) -> h`` overrides the FFN block
    (the MoE model's hook), exactly as in :func:`decode_step`. Returns
    ``(x (n, d), cache)``."""
    x = paged_step.embed_rows(params, rows.tokens())
    positions = rows.positions(cache)
    attend = paged_step.kv_attend(rows, attn_impl, decode_attn_impl)
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


(prefill_chunk_paged, decode_step_paged, chunk_decode_paged,
 verify_step_paged) = paged_step.build(paged_layers)
