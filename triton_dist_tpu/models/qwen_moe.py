"""Qwen3-MoE model (reference: ``models/qwen_moe.py`` — Qwen3-MoE with
EP; demo model for the EP dispatch/combine stack).

Same transformer skeleton as :mod:`triton_dist_tpu.models.dense` with
the MLP replaced by a MoE block. Two parallelization regimes (mirroring
the reference's TP_MoE vs EP_MoE layers):

- ``moe_impl="tp"``: experts replicated, ffn dim sharded over tp —
  tokens stay sequence-parallel.
- ``moe_impl="ep"``: experts sharded over the axis; each rank routes its
  own token shard through the dispatch/combine all-to-all.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import tp_attn, ep_moe, tp_moe
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models import dense as _dense
from triton_dist_tpu.models import paged_step
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import FwdContexts
from triton_dist_tpu.ops.ep_a2a import EPContext, create_ep_context
from triton_dist_tpu.parallel.mesh import MeshContext


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = []
    for li in range(cfg.num_hidden_layers):
        ka, km = jax.random.split(keys[li])
        layers.append({
            "attn": tp_attn.init(ka, cfg, dtype),
            "moe": ep_moe.init(km, cfg, dtype),
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


def param_specs(cfg: ModelConfig, *, moe_impl: str = "tp",
                axis: str = "tp", ep_axis: str = "ep") -> Dict:
    moe_specs = (tp_moe.param_specs(axis, cfg) if moe_impl == "tp"
                 else ep_moe.param_specs(ep_axis, cfg))
    layer_spec = {
        "attn": tp_attn.param_specs(axis, cfg),
        "moe": moe_specs,
        "ln_attn": P(None),
        "ln_mlp": P(None),
    }
    return {"embed": P(None, None),
            "layers": [layer_spec] * cfg.num_hidden_layers,
            "ln_f": P(None),
            "lm_head": P(axis, None)}


def moe_ffn(moe, h, cfg: ModelConfig, *, moe_impl, mode, axis, ctxs,
            ep_ctx, moe_block_m=None):
    """One MoE FFN block in the requested parallel regime. ``moe`` is
    the MoE param dict (router/experts/shared); shared between
    ``qwen_moe`` and the hybrid ``qwen_next`` FFN. ``moe_block_m=None``
    takes the fused context's row tile (the Engine's ``block_m`` knob)."""
    if moe_impl == "tp":
        if mode == "fused" and ctxs.ag is not None:
            # Fully-fused pipeline: AG-fused grouped GEMM + Pallas
            # down-proj + fused RS epilogue (the reference's
            # ag_group_gemm/moe_reduce_rs layer pairing).
            return tp_moe.fwd_fused(
                moe, h, topk=cfg.num_experts_per_tok,
                num_experts=cfg.num_experts,
                mesh_ctx=ctxs.ag.mesh, axis=axis,
                block_m=(ctxs.ag.block_m if moe_block_m is None
                         else moe_block_m),
                block_n=ctxs.ag.block_n, block_k=ctxs.ag.block_k,
                norm_topk_prob=cfg.norm_topk_prob)
        return tp_moe.fwd(
            moe, h, topk=cfg.num_experts_per_tok,
            num_experts=cfg.num_experts, axis=axis,
            norm_topk_prob=cfg.norm_topk_prob)
    from triton_dist_tpu.ops.ep_a2a import EP2DContext

    if isinstance(ep_ctx, EP2DContext):
        return ep_moe.fwd_2d(moe, h, ep_ctx,
                             topk=cfg.num_experts_per_tok,
                             norm_topk_prob=cfg.norm_topk_prob)
    return ep_moe.fwd(moe, h, ep_ctx,
                      topk=cfg.num_experts_per_tok,
                      norm_topk_prob=cfg.norm_topk_prob)


def moe_ffn_decode(moe, h, cfg: ModelConfig, *, moe_impl, axis, ep_ctx,
                   transport=None, replicas=None, layer: int = 0,
                   counts=None):
    """Small-batch (decode) MoE FFN: TP experts via ``tp_moe.fwd_ar``
    (the GEMM+AR pairing), EP experts via ``ep_moe.fwd_decode`` with
    the decode ``transport`` knob (``"ar"`` masked-local + psum,
    ``"ragged"`` exact-splits round-trip, ``"ll"`` low-latency
    count-free quantized exchange, ``"ll2d"`` the hierarchical 2-hop
    ICI×DCN variant for an ``EP2DContext``, ``"auto"`` tune-cache
    winner — see :mod:`triton_dist_tpu.layers.ep_moe`). ``replicas`` is the FULL
    hot-expert replica state (:func:`ep_moe.init_replicas`); ``layer``
    selects its slice and the ll slot parity. ``counts`` (a list)
    collects this layer's per-expert routed counts."""
    from triton_dist_tpu.ops.ep_a2a import EP2DContext

    if moe_impl == "tp":
        return tp_moe.fwd_ar(moe, h, topk=cfg.num_experts_per_tok,
                             num_experts=cfg.num_experts, axis=axis,
                             norm_topk_prob=cfg.norm_topk_prob)
    if isinstance(ep_ctx, EP2DContext):
        ep_axis = (ep_ctx.outer_axis, ep_ctx.inner_axis)
    elif isinstance(ep_ctx, EPContext):
        ep_axis = ep_ctx.axis
    else:
        ep_axis = axis
    rep_layer = (ep_moe.replica_layer(replicas, layer)
                 if replicas is not None else None)
    return ep_moe.fwd_decode(moe, h, topk=cfg.num_experts_per_tok,
                             axis=ep_axis,
                             norm_topk_prob=cfg.norm_topk_prob,
                             transport=transport or "ar",
                             ep_ctx=(ep_ctx if isinstance(
                                 ep_ctx, (EPContext, EP2DContext))
                                 else None),
                             replicas=rep_layer, layer=layer,
                             counts=counts)


def _moe_block(lp, h, cfg: ModelConfig, *, moe_impl, mode, axis, ctxs,
               ep_ctx, moe_block_m=None):
    """Dense-trunk ``ffn_fn`` hook form (receives the whole layer
    param dict)."""
    return moe_ffn(lp["moe"], h, cfg, moe_impl=moe_impl, mode=mode,
                   axis=axis, ctxs=ctxs, ep_ctx=ep_ctx,
                   moe_block_m=moe_block_m)


def _moe_ffn_decode(lp, h, cfg: ModelConfig, *, moe_impl, axis, ep_ctx,
                    transport=None, replicas=None, counts=None,
                    _layer_cursor=None):
    """Dense-trunk decode hook form. ``_layer_cursor`` (a one-element
    list) tracks the layer index across the trunk's in-order ffn calls
    — the hook receives only the layer's params, but the replica slice
    and the ll slot parity are per-layer."""
    li = 0
    if _layer_cursor is not None:
        li = _layer_cursor[0]
        _layer_cursor[0] += 1
    return moe_ffn_decode(lp["moe"], h, cfg, moe_impl=moe_impl,
                          axis=axis, ep_ctx=ep_ctx, transport=transport,
                          replicas=replicas, layer=li, counts=counts)


def forward_tokens(params, input_ids, cfg: ModelConfig, *,
                   moe_impl: str = "tp", mode: str = "xla",
                   axis: str = "tp", ep_ctx: Optional[EPContext] = None,
                   ctxs: FwdContexts = FwdContexts(),
                   moe_block_m: Optional[int] = None):
    """Per-shard all-token forward → (B, S, vocab) logits.

    For ``moe_impl="ep"`` the residual stream is token-sharded along the
    *ep* axis (each rank owns its tokens); attention still runs TP over
    ``axis`` (= the same axis for a 1D mesh: tp and ep traffic share it,
    matching the reference's single-group EP demos). ``ep_ctx`` may be
    an :class:`EPContext` (flat) or ``EP2DContext`` (hierarchical
    ICI-then-DCN dispatch, ``ops/ep_a2a.ep_dispatch_2d``).

    The transformer trunk is ``dense.forward_trunk`` with the MoE
    block plugged in via its ``ffn_fn`` hook — one trunk, two models.
    """
    b, s = input_ids.shape
    ffn = functools.partial(_moe_block, cfg=cfg, moe_impl=moe_impl,
                            mode=mode, axis=axis, ctxs=ctxs,
                            ep_ctx=ep_ctx, moe_block_m=moe_block_m)
    x, _ = _dense.forward_trunk(params, input_ids, cfg, mode=mode, axis=axis,
                          ctxs=ctxs, cache=None, ffn_fn=ffn)
    return paged_step.lm_head(params, x, axis).reshape(
        b, s, cfg.vocab_size)


# --- Engine serve contract (delegates to models.dense with the MoE
# --- ffn_fn hook) -----------------------------------------------------------

cache_specs = _dense.cache_specs


def prefill(params, input_ids, cfg: ModelConfig, *, mode: str = "xla",
            axis: str = "tp", ctxs: FwdContexts = FwdContexts(),
            max_len: Optional[int] = None, moe_impl: str = "tp",
            ep_ctx: Optional[EPContext] = None,
            moe_block_m: Optional[int] = None, transport=None,
            replicas=None):
    """Per-shard prefill → (last-position logits (B, vocab), KVCache).
    Same contract as ``dense.prefill`` (the Engine's model protocol,
    reference ``Engine._init_model`` + ``DenseLLM.inference``).
    ``transport``/``replicas`` are decode-path knobs accepted here so
    one model_kwargs dict serves both dispatches; prefill always rides
    the full dispatch/combine path."""
    del transport, replicas
    ffn = functools.partial(_moe_block, cfg=cfg, moe_impl=moe_impl,
                            mode=mode, axis=axis, ctxs=ctxs,
                            ep_ctx=ep_ctx, moe_block_m=moe_block_m)
    return _dense.prefill(params, input_ids, cfg, mode=mode, axis=axis,
                          ctxs=ctxs, max_len=max_len, ffn_fn=ffn)


def decode_step(params, token_ids, cache, cfg: ModelConfig, *,
                mode: str = "xla", axis: str = "tp",
                ctxs: FwdContexts = FwdContexts(), moe_impl: str = "tp",
                ep_ctx=None, transport=None, replicas=None,
                with_expert_counts: bool = False):
    """One decode step on a replicated (B,) token batch — the dense
    decode loop with the MoE small-batch FFN plugged in.
    ``with_expert_counts=True`` appends the step's per-expert routed
    assignment counts (E,) int32, summed over layers, to the return
    tuple (the serving layer's load telemetry)."""
    counts = [] if with_expert_counts else None
    ffn = functools.partial(_moe_ffn_decode, cfg=cfg, moe_impl=moe_impl,
                            axis=axis, ep_ctx=ep_ctx,
                            transport=transport, replicas=replicas,
                            counts=counts, _layer_cursor=[0])
    out = _dense.decode_step(params, token_ids, cache, cfg, mode=mode,
                             axis=axis, ctxs=ctxs, ffn_fn=ffn)
    if not with_expert_counts:
        return out
    return out + (_sum_counts(counts, cfg),)


def _sum_counts(counts, cfg: ModelConfig):
    """Stack per-layer expert counts into one (E,) int32 vector (zeros
    when the TP regime collected nothing)."""
    if counts:
        return jnp.sum(jnp.stack(counts, axis=0), axis=0
                       ).astype(jnp.int32)
    return jnp.zeros((cfg.num_experts,), jnp.int32)


paged_pool = _dense.paged_pool
paged_cache_specs = _dense.paged_cache_specs


def _paged_layers(params, rows, cache, cfg: ModelConfig, *, mode, axis,
                  attn_impl, decode_attn_impl,
                  ctxs: FwdContexts = FwdContexts(), moe_impl: str = "tp",
                  ep_ctx=None, transport=None, replicas=None,
                  with_expert_counts: bool = False):
    """The dense trunk with the MoE FFN plugged in (the ServingEngine's
    model contract; :func:`paged_step.build` makes the steps of it).
    Decode rows ALONE take the decode dispatch's knobs: ``transport``
    routes the EP dispatch (see :func:`moe_ffn_decode`), ``replicas`` is
    the full hot-expert replica state (data, refreshed between steps),
    and ``with_expert_counts=True`` hands out last the step's (E,) int32
    expert counts. Every other step's replicated rows (a prefill chunk,
    verification, a chunk with the decode batch aboard, which the
    serving engine builds only where the decode dispatch has no
    transport of its own) take the MoE FFN in the AR decode regime, the
    masked-local + psum expert path that fits any row count exactly,
    and ignore the three knobs, so that one ``model_kwargs`` dict
    serves every dispatch."""
    alone = not (rows.c or rows.k)
    counts = [] if alone and with_expert_counts else None
    ffn = functools.partial(
        _moe_ffn_decode, cfg=cfg, moe_impl=moe_impl, axis=axis,
        ep_ctx=ep_ctx, transport=transport if alone else "ar",
        replicas=replicas if alone else None, counts=counts,
        _layer_cursor=[0])
    out = _dense.paged_layers(
        params, rows, cache, cfg, mode=mode, axis=axis, attn_impl=attn_impl,
        decode_attn_impl=decode_attn_impl, ctxs=ctxs, ffn_fn=ffn)
    return out if counts is None else out + (_sum_counts(counts, cfg),)


(prefill_chunk_paged, decode_step_paged, chunk_decode_paged,
 verify_step_paged) = paged_step.build(_paged_layers)
