"""Tensor-parallel attention (GQA + rope + Qwen3 q/k-norm).

Reference: ``layers/nvidia/tp_attn.py:80`` ``TP_Attn`` — QKV via ag_gemm
(AG buffer reused across the three projections), flash attention, O via
gemm_rs; gemm_ar mode for decode.

Heads are sharded along ``tp``; the residual stream is token-sharded
(sequence parallel) in "xla"/"fused" modes and replicated in "fused_ar"
decode mode.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from math import sqrt as np_sqrt

from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.layers.rope import apply_rope, rope_freqs
from triton_dist_tpu.ops import ag_gemm, gemm_rs, gemm_ar


def init(key, cfg, dtype=jnp.float32) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d = cfg.hidden_size
    hd = cfg.head_dim
    scale = d ** -0.5
    p = {
        "wq": jax.random.normal(kq, (d, cfg.num_attention_heads * hd),
                                dtype) * scale,
        "wk": jax.random.normal(kk, (d, cfg.num_key_value_heads * hd),
                                dtype) * scale,
        "wv": jax.random.normal(kv, (d, cfg.num_key_value_heads * hd),
                                dtype) * scale,
        "wo": jax.random.normal(
            ko, (cfg.num_attention_heads * hd, d), dtype
        ) * ((cfg.num_attention_heads * hd) ** -0.5),
    }
    if getattr(cfg, "qk_norm", True):
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if getattr(cfg, "attn_gate", False):
        # Qwen3-Next gated attention: q_proj emits per-head [q | gate]
        # (modeling_qwen3_next.Qwen3NextAttention); de-interleaved to a
        # separate column-parallel matrix so gate columns shard with
        # their heads.
        p["wqg"] = jax.random.normal(
            jax.random.fold_in(kq, 1),
            (d, cfg.num_attention_heads * hd), dtype) * scale
    if getattr(cfg, "attention_bias", False):
        # Seed-OSS / Qwen2-style projection biases (the reference
        # shards q_proj.bias etc. the same way, layer init path).
        p["bq"] = jnp.zeros((cfg.num_attention_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_key_value_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_key_value_heads * hd,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def param_specs(axis: str = "tp", cfg=None) -> Dict:
    """``cfg=None`` keeps the legacy Qwen3 layout (q/k norms, no
    biases); pass a config to match :func:`init`'s conditional keys."""
    s = {
        "wq": P(None, axis),
        "wk": P(None, axis),
        "wv": P(None, axis),
        "wo": P(axis, None),
    }
    if cfg is None or getattr(cfg, "qk_norm", True):
        s["q_norm"] = P(None)
        s["k_norm"] = P(None)
    if cfg is not None and getattr(cfg, "attn_gate", False):
        s["wqg"] = P(None, axis)
    if cfg is not None and getattr(cfg, "attention_bias", False):
        s["bq"] = P(axis)
        s["bk"] = P(axis)
        s["bv"] = P(axis)
        # Row-parallel o-proj: the bias adds ONCE after the reduce, so
        # it stays replicated.
        s["bo"] = P(None)
    return s


def _head_split(cfg, n: int):
    """Per-device head counts; KV-head replication for n > KV-heads is
    not implemented yet, so fail loudly rather than mis-reshape."""
    if cfg.num_attention_heads % n:
        raise ValueError(
            f"num_attention_heads={cfg.num_attention_heads} not divisible "
            f"by tp={n}")
    if cfg.num_key_value_heads % n:
        raise ValueError(
            f"num_key_value_heads={cfg.num_key_value_heads} not divisible "
            f"by tp={n} (KV-head replication unimplemented)")
    return cfg.num_attention_heads // n, cfg.num_key_value_heads // n


def _project_qkv(params, x, *, mode, axis, ag_ctx):
    """Returns (q, k, v, gate) as (tokens_full, *_loc); ``gate`` is
    None unless the layer carries the Qwen3-Next attention gate."""
    if mode == "xla":
        x_full = jax.lax.all_gather(x, axis, axis=0, tiled=True)
        q = jnp.dot(x_full, params["wq"])
        k = jnp.dot(x_full, params["wk"])
        v = jnp.dot(x_full, params["wv"])
    elif mode == "fused":
        q, x_full = ag_gemm(x, params["wq"], ag_ctx, return_ag=True)
        k = jnp.dot(x_full, params["wk"])
        v = jnp.dot(x_full, params["wv"])
    elif mode == "fused_ar":
        # Replicated tokens: plain local projections.
        x_full = x
        q = jnp.dot(x, params["wq"])
        k = jnp.dot(x, params["wk"])
        v = jnp.dot(x, params["wv"])
    else:
        raise ValueError(f"unknown TP_Attn mode {mode!r}")
    if "bq" in params:
        # Column-parallel biases: each shard owns its output columns.
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    gate = jnp.dot(x_full, params["wqg"]) if "wqg" in params else None
    return q, k, v, gate


def _o_bias(params, y):
    """Row-parallel output bias — applied AFTER the cross-shard reduce
    (a per-shard add would count it n times)."""
    return y + params["bo"] if "bo" in params else y


def _norm_rope(q, k, params, cfg, positions, rope: bool = True):
    """q: (B, S, H_loc, hd); k: (B, S, KV_loc, hd). ``rope=False``: the
    norm alone (a layer that carries no position of its own)."""
    if "q_norm" in params:       # Qwen3 per-head norm; absent for
        q = rms_norm(q, params["q_norm"], cfg.rms_norm_eps)  # Seed-OSS
        k = rms_norm(k, params["k_norm"], cfg.rms_norm_eps)
    if not rope:
        return q, k
    # Partial RoPE (Qwen3-Next rotates only the first fraction of each
    # head; the rest passes through position-free).
    rot = int(cfg.head_dim * getattr(cfg, "partial_rotary_factor", 1.0))
    if rot % 2:
        raise ValueError(
            f"rotary dim {rot} (head_dim {cfg.head_dim} × factor "
            f"{cfg.partial_rotary_factor}) must be even")
    inv_freq = rope_freqs(rot, cfg.rope_theta)
    if rot == cfg.head_dim:
        return (apply_rope(q, positions, inv_freq),
                apply_rope(k, positions, inv_freq))
    rope_part = lambda t: jnp.concatenate(
        [apply_rope(t[..., :rot], positions, inv_freq), t[..., rot:]],
        axis=-1)
    return rope_part(q), rope_part(k)


def sdpa(q, k, v, *, causal: bool, kv_len=None, use_flash=None):
    """q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd). GQA by head repeat.

    ``kv_len`` may be (B,) — one ragged length per batch row — or
    (B, Sq) — a PER-QUERY length, the speculative-verification form
    where query j of a slot attends the paged history plus its own
    candidate block prefix (lens + j + 1).

    On real TPUs with long sequences the bundled Pallas flash-attention
    kernel handles the softmax online (O(S) memory); the jnp path is the
    portable oracle (and handles ragged kv_len masking).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # The bundled kernel wants both lengths in whole 128-blocks. Causal
    # self-attention (prefill) pads up to one: keys padded at the END lie
    # in every real query's future, and the padded query rows are cut.
    blk = 128
    self_causal = causal and sq == skv
    if use_flash is None:
        from triton_dist_tpu.utils.distributed import on_tpu, use_interpret
        use_flash = (on_tpu() and not use_interpret() and kv_len is None
                     and sq >= blk and skv >= blk and hd >= 64
                     and (self_causal or (sq % blk == 0
                                          and skv % blk == 0)))
    if use_flash:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)
        pad = -sq % blk if self_causal else 0
        if pad:
            q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for t in (q, k, v))
        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal,
            sm_scale=1.0 / float(np_sqrt(hd)))
        return o.transpose(0, 2, 1, 3)[:, :sq]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if causal:
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(skv)[None, :]
        offset = skv - sq  # cache prefix
        mask = ki <= (qi + offset)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    if kv_len is not None:
        ki = jnp.arange(skv)[None, None, None, :]
        if kv_len.ndim == 2:       # per-query lengths (B, Sq)
            scores = jnp.where(ki < kv_len[:, None, :, None], scores,
                               -jnp.inf)
        else:
            scores = jnp.where(ki < kv_len[:, None, None, None],
                               scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def fwd_prefill(params, x, cfg, *, batch: int, mode: str = "xla",
                axis: str = "tp", ag_ctx=None, rs_ctx=None, ar_ctx=None,
                kv_out: bool = True):
    """x: (tokens_loc, d) token-sharded (or replicated for fused_ar).
    Returns (y in the same layout, (k_cache, v_cache) per-shard)."""
    n = jax.lax.axis_size(axis)
    hd = cfg.head_dim
    h_loc, kv_loc = _head_split(cfg, n)

    q, k, v, gate = _project_qkv(params, x, mode=mode, axis=axis,
                                 ag_ctx=ag_ctx)
    tokens = q.shape[0]
    seq = tokens // batch
    q = q.reshape(batch, seq, h_loc, hd)
    k = k.reshape(batch, seq, kv_loc, hd)
    v = v.reshape(batch, seq, kv_loc, hd)
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    q, k = _norm_rope(q, k, params, cfg, positions)

    o = sdpa(q, k, v, causal=True)
    o = o.reshape(tokens, h_loc * hd)
    if gate is not None:   # Qwen3-Next: sigmoid gate before o_proj
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)

    if mode == "xla":
        partial = jnp.dot(o, params["wo"], preferred_element_type=jnp.float32)
        y = jax.lax.psum_scatter(partial, axis, scatter_dimension=0,
                                 tiled=True).astype(x.dtype)
    elif mode == "fused":
        y = gemm_rs(o, params["wo"], rs_ctx)
    else:  # fused_ar
        y = gemm_ar(o, params["wo"], ar_ctx)
    y = _o_bias(params, y)
    return (y, (k, v)) if kv_out else y


def decode_project(params, x, cfg, positions, *, axis: str = "tp",
                   rope: bool = True):
    """Project one token per row: QKV + q/k norm + rope (``rope=False``:
    no rotation, for a layer of a model that rotates only some).

    x: (B, d) replicated; ``positions``: (B,) int32 — PER-ROW cache
    positions. Two callers, one contract: the continuous-batching
    decode step ropes each SLOT at its own length (one token per slot;
    the single-request form passes a broadcast scalar), and the
    chunked-prefill step ropes a CHUNK of consecutive tokens of one
    slot (rows = positions ``start + arange(C)``) — the projection is
    row-independent, so the same kernel serves both.
    Returns (q (B, 1, H_loc, hd), k_tok (B, 1, KV_loc, hd),
    v_tok (B, 1, KV_loc, hd)); the caller places k/v through the
    cache's ``append_decode`` / ``write_chunk`` contract before
    attending.
    """
    n = jax.lax.axis_size(axis)
    hd = cfg.head_dim
    h_loc, kv_loc = _head_split(cfg, n)
    b = x.shape[0]

    q = jnp.dot(x, params["wq"])
    k = jnp.dot(x, params["wk"])
    v = jnp.dot(x, params["wv"])
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, 1, h_loc, hd)
    k = k.reshape(b, 1, kv_loc, hd)
    v = v.reshape(b, 1, kv_loc, hd)
    pos2 = jnp.asarray(positions, jnp.int32).reshape(b, 1)
    q, k = _norm_rope(q, k, params, cfg, pos2, rope)
    return q, k, v


def decode_output(params, o, x, *, mode: str = "xla", axis: str = "tp",
                  ar_ctx=None):
    """Attention output path of a decode step: optional Qwen3-Next
    sigmoid gate (projected from the layer input ``x``), row-parallel
    o-proj, and the cross-shard reduce. o: (B, h_loc·hd); returns
    (B, d) replicated."""
    if "wqg" in params:   # Qwen3-Next: sigmoid gate before o_proj
        gate = jnp.dot(x, params["wqg"])
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    if mode in ("xla",):
        y = jax.lax.psum(
            jnp.dot(o, params["wo"], preferred_element_type=jnp.float32),
            axis).astype(x.dtype)
    else:  # fused / fused_ar decode both use gemm_ar (small M)
        y = gemm_ar(o, params["wo"], ar_ctx)
    return _o_bias(params, y)


def fwd_decode(params, x, cfg, k_cache, v_cache, cache_len, *,
               mode: str = "xla", axis: str = "tp", ar_ctx=None):
    """Single-token decode. x: (B, d) replicated; caches
    (B, max_len, KV_loc, hd); cache_len: scalar current length.
    Returns (y (B, d) replicated, updated caches).

    Composition of :func:`decode_project` → cache append →
    :func:`sdpa` → :func:`decode_output`; kept as the whole-layer
    entry point for per-layer-cache callers (qwen_next's hybrid
    decode). The Engine's dense path drives the same pieces through
    :meth:`KVCache.append_decode` instead.

    Reference: decode path of ``TP_Attn`` + ``KV_Cache``
    (``models/kv_cache.py``), gemm_ar mode (``e2e_dense.md:34``).
    """
    b = x.shape[0]
    positions = jnp.broadcast_to(cache_len, (b,)).astype(jnp.int32)
    q, k, v = decode_project(params, x, cfg, positions, axis=axis)

    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, cache_len, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, cache_len, 0, 0))

    kv_len = jnp.full((b,), cache_len + 1, dtype=jnp.int32)
    o = sdpa(q, k_cache, v_cache, causal=False, kv_len=kv_len)
    o = o.reshape(b, -1)
    y = decode_output(params, o, x, mode=mode, axis=axis, ar_ctx=ar_ctx)
    return y, (k_cache, v_cache)
