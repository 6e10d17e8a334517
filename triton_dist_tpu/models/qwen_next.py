"""Qwen3-Next-style hybrid model: GDN linear-attention layers with a
full-attention layer every ``cfg.full_attn_interval``.

Reference capability: ``kernels/nvidia/gdn.py`` ships the chunked
gated-delta-rule kernel *for* Qwen3-Next; this module supplies the model
family around it (the reference's models/ tree stops at dense +
Qwen3-MoE). Same functional conventions as
:mod:`triton_dist_tpu.models.dense`: ``init_params`` / ``param_specs`` /
``forward_tokens`` / ``prefill`` / ``decode_step`` run inside
``shard_map``; mode "xla" is the lax-collective oracle, "fused" rides
ag_gemm/gemm_rs (prefill) and gemm_ar (decode).

The hybrid cache pairs the softmax layers' :class:`KVCache` with the GDN
layers' recurrent states (B, H_loc, dk, dv) — constant memory in
sequence length, the point of the architecture for long context.

MoE configs (``cfg.is_moe``, e.g. ``qwen3_next_80b_a3b``) replace the
dense FFN with a TP-MoE block: grouped SwiGLU over the local ffn shard
(fused AG-grouped-GEMM pipeline in "fused" prefill) and the GEMM+AR
regime for replicated decode rows.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import ep_moe, gdn_attn, tp_attn, tp_mlp, tp_moe
from triton_dist_tpu.models.qwen_moe import moe_ffn, moe_ffn_decode
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import FwdContexts, embed_tokens
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.models.paged_step import lm_head


@dataclasses.dataclass
class HybridCache:
    """kv: softmax layers' cache (indexed by full-attn layer ordinal);
    states: (num_gdn_layers, B, H_loc, dk, dv) recurrent states;
    conv: (num_gdn_layers, B, C_loc, K-1) short-conv tails — zero-size
    for the simplified (conv-free) cell."""
    kv: KVCache
    states: jax.Array
    conv: jax.Array

    @property
    def length(self):
        """Tokens cached so far — one counter, owned by the KV cache
        (the GDN states are position-free)."""
        return self.kv.length

    def tree_flatten(self):
        return (self.kv, self.states, self.conv), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    HybridCache, HybridCache.tree_flatten, HybridCache.tree_unflatten)


def _layer_kinds(cfg: ModelConfig):
    """Per-layer ("attn"| "gdn", ordinal within its kind)."""
    kinds = []
    n_attn = n_gdn = 0
    for li in range(cfg.num_hidden_layers):
        if cfg.layer_is_full_attn(li):
            kinds.append(("attn", n_attn))
            n_attn += 1
        else:
            kinds.append(("gdn", n_gdn))
            n_gdn += 1
    return kinds, n_attn, n_gdn


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = []
    for li in range(cfg.num_hidden_layers):
        ka, km = jax.random.split(keys[li])
        mixer = (tp_attn.init(ka, cfg, dtype)
                 if cfg.layer_is_full_attn(li)
                 else gdn_attn.init(ka, cfg, dtype))
        layers.append({
            "mixer": mixer,
            # MoE FFN when configured (Qwen3-Next-80B-A3B is MoE; the
            # r2 advisor flagged that dropping it silently served the
            # wrong architecture). Router/expert weights are shared
            # between the tp and ep layer forms.
            "mlp": (ep_moe.init(km, cfg, dtype) if cfg.is_moe
                    else tp_mlp.init(km, cfg, dtype)),
            "ln_attn": jnp.ones((cfg.hidden_size,), dtype),
            "ln_mlp": jnp.ones((cfg.hidden_size,), dtype),
        })
    emb = jax.random.normal(keys[-2], (cfg.vocab_size, cfg.hidden_size),
                            dtype) * 0.02
    lm_head = (emb if cfg.tie_word_embeddings else
               jax.random.normal(keys[-1],
                                 (cfg.vocab_size, cfg.hidden_size),
                                 dtype) * 0.02)
    return {"embed": emb, "layers": layers,
            "ln_f": jnp.ones((cfg.hidden_size,), dtype),
            "lm_head": lm_head}


def param_specs(cfg: ModelConfig, axis: str = "tp", *,
                moe_impl: str = "tp", ep_axis: str = "ep") -> Dict:
    """``moe_impl`` selects the FFN regime for MoE configs (same
    contract as ``qwen_moe.param_specs`` — the Engine introspects the
    kwarg and plumbs ``moe_impl``/``ep_ctx`` into prefill/decode)."""
    if moe_impl not in ("tp", "ep"):
        raise ValueError(f"unknown moe_impl {moe_impl!r}")
    if moe_impl == "ep" and not cfg.is_moe:
        raise ValueError("moe_impl='ep' on a non-MoE hybrid config")
    if cfg.is_moe:
        moe_specs = (tp_moe.param_specs(axis, cfg) if moe_impl == "tp"
                     else ep_moe.param_specs(ep_axis, cfg))
    layers = []
    for li in range(cfg.num_hidden_layers):
        mixer = (tp_attn.param_specs(axis, cfg)
                 if cfg.layer_is_full_attn(li)
                 else gdn_attn.param_specs(axis, cfg))
        layers.append({
            "mixer": mixer,
            "mlp": (moe_specs if cfg.is_moe
                    else tp_mlp.param_specs(axis)),
            "ln_attn": P(None),
            "ln_mlp": P(None),
        })
    return {"embed": P(None, None), "layers": layers,
            "ln_f": P(None), "lm_head": P(axis, None)}


def cache_specs(axis: str = "tp") -> "HybridCache":
    """PartitionSpec pytree for :class:`HybridCache` (KV heads, GDN
    heads, and conv channels all sharded along ``axis``) — consumed by
    the Engine's shard_map in/out specs."""
    return HybridCache(
        kv=KVCache(k=P(None, None, None, axis, None),
                   v=P(None, None, None, axis, None),
                   length=P()),
        states=P(None, None, axis, None, None),
        conv=P(None, None, axis, None))


def _conv_channels(cfg: ModelConfig) -> int:
    """Global conv channel count of the HF cell (0 = conv-free cell)."""
    if not cfg.gdn_conv_kernel:
        return 0
    return (2 * cfg.gdn_num_kh * cfg.gdn_head_dim_k
            + cfg.gdn_num_heads * cfg.gdn_head_dim_v)


def empty_cache(cfg: ModelConfig, batch: int, max_len: int, n: int,
                dtype=jnp.float32) -> HybridCache:
    _, n_attn, n_gdn = _layer_kinds(cfg)
    kv_loc = max(cfg.num_key_value_heads // n, 1)
    h_loc = max(cfg.gdn_num_heads // n, 1)
    return HybridCache(
        kv=KVCache.empty(max(n_attn, 1), batch, max_len, kv_loc,
                         cfg.head_dim, dtype=dtype),
        states=jnp.zeros((max(n_gdn, 1), batch, h_loc,
                          cfg.gdn_head_dim_k, cfg.gdn_head_dim_v),
                         jnp.float32),
        conv=jnp.zeros((max(n_gdn, 1), batch, _conv_channels(cfg) // n,
                        max(cfg.gdn_conv_kernel - 1, 0)), dtype))


def _trunk(params, input_ids, cfg, *, mode, axis, ctxs, cache,
           moe_impl="tp", ep_ctx=None, moe_block_m=None):
    b, s = input_ids.shape
    kinds, _, _ = _layer_kinds(cfg)
    x = embed_tokens(params, input_ids, mode=mode, axis=axis)
    for li, lp in enumerate(params["layers"]):
        kind, ordinal = kinds[li]
        h = rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps)
        if kind == "attn":
            mix_out, kv = tp_attn.fwd_prefill(
                lp["mixer"], h, cfg, batch=b, mode=mode, axis=axis,
                ag_ctx=ctxs.ag, rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)
            if cache is not None:
                cache.kv = cache.kv.write_prefill(ordinal, *kv)
        elif cfg.gdn_conv_kernel:
            mix_out, (state, conv) = gdn_attn.fwd_prefill_hf(
                lp["mixer"], h, cfg, batch=b, mode=mode, axis=axis,
                ag_ctx=ctxs.ag, rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)
            if cache is not None:
                cache.states = jax.lax.dynamic_update_slice(
                    cache.states, state[None], (ordinal, 0, 0, 0, 0))
                cache.conv = jax.lax.dynamic_update_slice(
                    cache.conv, conv[None].astype(cache.conv.dtype),
                    (ordinal, 0, 0, 0))
        else:
            mix_out, state = gdn_attn.fwd_prefill(
                lp["mixer"], h, cfg, batch=b, mode=mode, axis=axis,
                ag_ctx=ctxs.ag, rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)
            if cache is not None:
                cache.states = jax.lax.dynamic_update_slice(
                    cache.states, state[None],
                    (ordinal, 0, 0, 0, 0))
        x = x + mix_out
        h = rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps)
        if cfg.is_moe:
            # Same regime dispatch as qwen_moe (tp-fused / tp / ep /
            # ep-2d) — one helper, two models.
            ffn_out = moe_ffn(
                lp["mlp"], h, cfg, moe_impl=moe_impl, mode=mode,
                axis=axis, ctxs=ctxs, ep_ctx=ep_ctx,
                moe_block_m=moe_block_m)
        else:
            ffn_out = tp_mlp.fwd(lp["mlp"], h, mode=mode, axis=axis,
                                 ag_ctx=ctxs.ag, rs_ctx=ctxs.rs,
                                 ar_ctx=ctxs.ar)
        x = x + ffn_out
    x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    if mode in ("xla", "fused"):
        x = jax.lax.all_gather(x, axis, axis=0, tiled=True)
    return x, cache


def forward_tokens(params, input_ids, cfg: ModelConfig, *,
                   mode: str = "xla", axis: str = "tp",
                   ctxs: FwdContexts = FwdContexts(),
                   moe_impl: str = "tp", ep_ctx=None,
                   moe_block_m: Optional[int] = None):
    b, s = input_ids.shape
    x, _ = _trunk(params, input_ids, cfg, mode=mode, axis=axis,
                  ctxs=ctxs, cache=None, moe_impl=moe_impl,
                  ep_ctx=ep_ctx, moe_block_m=moe_block_m)
    return lm_head(params, x, axis).reshape(b, s, cfg.vocab_size)


def prefill(params, input_ids, cfg: ModelConfig, *, mode: str = "xla",
            axis: str = "tp", ctxs: FwdContexts = FwdContexts(),
            max_len: Optional[int] = None, moe_impl: str = "tp",
            ep_ctx=None, moe_block_m: Optional[int] = None):
    n = jax.lax.axis_size(axis)
    b, s = input_ids.shape
    cache = empty_cache(cfg, b, max_len or s, n,
                        dtype=params["embed"].dtype)
    x, cache = _trunk(params, input_ids, cfg, mode=mode, axis=axis,
                      ctxs=ctxs, cache=cache, moe_impl=moe_impl,
                      ep_ctx=ep_ctx, moe_block_m=moe_block_m)
    cache.kv = dataclasses.replace(cache.kv,
                                   length=jnp.asarray(s, jnp.int32))
    last = x.reshape(b, s, cfg.hidden_size)[:, -1]
    return lm_head(params, last, axis), cache


def decode_step(params, token_ids, cache: HybridCache,
                cfg: ModelConfig, *, mode: str = "xla",
                axis: str = "tp", ctxs: FwdContexts = FwdContexts(),
                moe_impl: str = "tp", ep_ctx=None):
    """One decode step; GDN layers advance their recurrent state in
    O(1), softmax layers append to the KV cache."""
    b = token_ids.shape[0]
    kinds, _, _ = _layer_kinds(cfg)
    x = params["embed"][token_ids]
    pos = cache.kv.length
    dec_mode = "xla" if mode == "xla" else "fused_ar"

    new_k, new_v = cache.kv.k, cache.kv.v
    new_states = cache.states
    new_conv = cache.conv
    for li, lp in enumerate(params["layers"]):
        kind, ordinal = kinds[li]
        h = rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps)
        if kind == "attn":
            mix_out, (lk, lv) = tp_attn.fwd_decode(
                lp["mixer"], h, cfg, new_k[ordinal], new_v[ordinal],
                pos, mode=dec_mode, axis=axis, ar_ctx=ctxs.ar)
            new_k = jax.lax.dynamic_update_slice(
                new_k, lk[None], (ordinal, 0, 0, 0, 0))
            new_v = jax.lax.dynamic_update_slice(
                new_v, lv[None], (ordinal, 0, 0, 0, 0))
        elif cfg.gdn_conv_kernel:
            mix_out, st, cv = gdn_attn.fwd_decode_hf(
                lp["mixer"], h, cfg, new_states[ordinal],
                new_conv[ordinal], mode=dec_mode, axis=axis,
                ar_ctx=ctxs.ar)
            new_states = jax.lax.dynamic_update_slice(
                new_states, st[None], (ordinal, 0, 0, 0, 0))
            new_conv = jax.lax.dynamic_update_slice(
                new_conv, cv[None].astype(new_conv.dtype),
                (ordinal, 0, 0, 0))
        else:
            mix_out, st = gdn_attn.fwd_decode(
                lp["mixer"], h, cfg, new_states[ordinal],
                mode=dec_mode, axis=axis, ar_ctx=ctxs.ar)
            new_states = jax.lax.dynamic_update_slice(
                new_states, st[None], (ordinal, 0, 0, 0, 0))
        x = x + mix_out
        h = rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps)
        if cfg.is_moe:
            # Small-batch decode FFN in the requested regime (TP
            # GEMM+AR, or EP masked-local-experts + psum).
            x = x + moe_ffn_decode(lp["mlp"], h, cfg,
                                   moe_impl=moe_impl, axis=axis,
                                   ep_ctx=ep_ctx)
        else:
            mlp_mode = "xla_ar" if dec_mode == "xla" else dec_mode
            x = x + tp_mlp.fwd(lp["mlp"], h, mode=mlp_mode, axis=axis,
                               ag_ctx=ctxs.ag, rs_ctx=ctxs.rs,
                               ar_ctx=ctxs.ar)

    x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    logits = lm_head(params, x, axis)
    cache = HybridCache(
        kv=KVCache(k=new_k, v=new_v, length=cache.kv.length + 1),
        states=new_states, conv=new_conv)
    return logits, cache
