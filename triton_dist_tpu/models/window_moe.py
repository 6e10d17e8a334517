"""Window and global attention mixed, over a chip's share of routed
experts (``model_type: exaone_moe``): the paged serving contract of
:mod:`triton_dist_tpu.models.dense` for a model in which three things
vary BY LAYER.

Every layer is ``a = x + attn(rms(x))``, ``x' = a + ffn(rms(a))`` (``h``
the normed row; no biases):

    q, k, v = h wq, h wk, h wv            H heads over KV heads of hd
    q, k <- rms over a head's hd values   (q_norm, k_norm)

- the ATTENTION KIND, by the layer's letter in ``cfg.attn_pattern``.
  ``L``, a window layer: ``q`` and ``k`` are rotated (plain rope at
  ``rope_theta`` over the whole head) and row ``i`` reads keys ``j``
  with ``i - sliding_window < j <= i``. ``G``, a global layer: NO
  rotation, and row ``i`` reads every key ``j <= i``;
- the POOL a layer writes and reads. A global layer keeps every
  position of a sequence, in ``PagedKVCache.k_pages`` / ``v_pages``
  through the slot's ``p_max`` table entries, as every other family's
  layers do. A window layer keeps, a sequence, a RING of pages in the
  window layers' own arrays (``PagedKVCache.win``): position ``p`` in
  entry ``(p // page) % ring``, a page behind the window written over as
  the sequence grows (:func:`paged_pool`;
  :func:`~triton_dist_tpu.models.paged_step.kv_attend` under
  ``window=``). A layer's index in its pool counts among its kind;
- the FFN. The first ``cfg.first_dense_layers`` layers: a dense SwiGLU
  of ``intermediate_size`` (:mod:`~triton_dist_tpu.layers.tp_mlp`). The
  others: :func:`~triton_dist_tpu.layers.ep_moe.fwd_held` behind a
  sigmoid router with a selection-only bias, the weights of the chosen
  renormalised and times ``routed_scaling_factor``, the held experts
  gated SwiGLUs, one shared expert on ``h`` itself, added once.

A kind of layer (attention kind x FFN kind) is ONE jitted function of
its parameters and its index in its pool, an int32 operand
(``latent_moe._layers`` has why). Every step function returns, last,
``STEP_STATS`` summed over the expert layers.

No ``verify_step_paged``: a refused candidate's entry would have written
over a key the ring still needs. Over ``axis`` the attention heads (and their
pages), the dense FFN's columns and the head's vocabulary rows are
divided as the dense family's; an expert layer is whole on every rank:
the deployment this stands for divides a layer's EXPERTS over chips,
and this module is one of those chips.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import ep_moe, tp_attn, tp_mlp
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models import dense as _dense
from triton_dist_tpu.models import paged_step
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import FwdContexts
from triton_dist_tpu.models.latent_moe import STEP_STATS
from triton_dist_tpu.obs import scope


def _check_cfg(cfg: ModelConfig):
    if (len(cfg.attn_pattern) != cfg.num_hidden_layers
            or set(cfg.attn_pattern) - set("LG")):
        raise ValueError(
            f"attn_pattern={cfg.attn_pattern!r}: models.window_moe wants "
            f"'L' or 'G' for each of the {cfg.num_hidden_layers} layers")
    if "L" in cfg.attn_pattern and cfg.sliding_window < 1:
        raise ValueError("window layers need sliding_window >= 1")
    if not cfg.is_moe and cfg.first_dense_layers < cfg.num_hidden_layers:
        raise ValueError("models.window_moe: the layers past "
                         "first_dense_layers route (num_experts is 0)")


def paged_pool(cfg: ModelConfig):
    """The pool this model keeps: pages of K and V of every KV head, in
    TWO kinds of layer. The ``G`` layers' pages are the pool's own
    (``layers``); the ``L`` layers are stated as window layers, and the
    server sizes their ring and gives them arrays of their own."""
    from triton_dist_tpu.serving.blocks import PagedKVCache, WindowLayers

    _check_cfg(cfg)
    keeps = {"layers": cfg.num_paged_layers}
    if cfg.num_window_layers:
        keeps["window"] = WindowLayers(cfg.num_window_layers,
                                       cfg.sliding_window)
    return PagedKVCache, (cfg.num_key_value_heads, cfg.head_dim), keeps


def paged_cache_specs(axis: str = "tp", quantized: bool = False,
                      ring: int = 0):
    """``ring``: the window layers' ring, as the server sized it (static
    in the cache's tree; 0 for a pattern with no window layer)."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    if quantized:
        raise ValueError("models.window_moe keeps an unquantized pool")
    pool = P(None, None, axis, None, None)
    return PagedKVCache(
        k_pages=pool, v_pages=pool, block_table=P(None, None),
        lens=P(None), live=P(None),
        win={"k": pool, "v": pool} if ring else {}, ring=ring)


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    _check_cfg(cfg)
    d = cfg.hidden_size
    f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    e = cfg.held_experts

    def w(k, *shape):
        return jax.random.normal(k, shape, dtype) * shape[-2] ** -0.5

    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = []
    for li in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[li], 9)
        lp = {"attn": tp_attn.init(k[0], cfg, dtype),
              "ln_attn": jnp.ones((d,), dtype),
              "ln_mlp": jnp.ones((d,), dtype)}
        if li < cfg.first_dense_layers:
            lp["mlp"] = tp_mlp.init(k[1], cfg, dtype)
        else:
            w_up, w_down, w_gate = ep_moe.pad_expert_width(
                w(k[3], e, d, f), w(k[4], e, f, d), w(k[2], e, d, f))
            lp["moe"] = {
                "router": w(k[5], d, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
            if fs:
                lp["moe"].update(w_shared_gate=w(k[6], d, fs),
                                 w_shared_up=w(k[7], d, fs),
                                 w_shared_down=w(k[8], fs, d))
        layers.append(lp)
    table = lambda k: jax.random.normal(
        k, (cfg.vocab_size, d), dtype) * 0.02
    emb = table(keys[-2])
    return {"embed": emb, "layers": layers,
            "ln_f": jnp.ones((d,), dtype),
            "lm_head": emb if cfg.tie_word_embeddings else table(keys[-1])}


def param_specs(cfg: ModelConfig, axis: str = "tp") -> Dict:
    """Attention and the dense FFN sharded as the dense family's (heads,
    FFN columns); an expert layer whole on every rank."""
    shapes = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), cfg)["layers"])
    layers = jax.tree.map(lambda x: P(*(None,) * x.ndim), shapes)
    for lp in layers:
        lp["attn"] = tp_attn.param_specs(axis, cfg)
        if "mlp" in lp:
            lp["mlp"] = tp_mlp.param_specs(axis)
    return {"embed": P(None, None), "layers": layers,
            "ln_f": P(None), "lm_head": P(axis, None)}


# -- the Engine's dense-cache contract: not this model's path ---------------

cache_specs = _dense.cache_specs


def _paged_only(*_, **__):
    raise NotImplementedError(
        "models.window_moe serves through the paged pool and the window "
        "layers' rings only: Engine(...).serving(prefill_buckets=...)")


prefill = decode_step = _paged_only


def step_kernels(cfg: ModelConfig, rows: int, *, decode_rows: int,
                 page: int, dtype) -> tuple:
    """The blocks, of ``paged_step.STEP_KERNELS``, that a chunk program
    of ``rows`` chunk rows with ``decode_rows`` aboard runs in a Pallas
    kernel by a rule on sizes: the held experts' MLP, by
    :func:`ep_moe.experts_impl` at the pass every row of the program
    gives, at the width the experts are stored at. (The attention
    kernels are chosen by ``attn_impl``, not by sizes.)"""
    n_held = cfg.held_experts
    experts = cfg.num_moe_layers and ep_moe.experts_impl(
        ep_moe.held_pass_rows(rows + decode_rows, cfg.num_experts_per_tok,
                              n_held, cfg.num_experts),
        n_held, cfg.hidden_size,
        ep_moe.expert_store_width(cfg.moe_intermediate_size), dtype)
    return ("experts",) * (experts == "kernel")


# -- the layers ------------------------------------------------------------

def _layers(params, rows, cache, cfg: ModelConfig, *, mode, axis, attn_impl,
            decode_attn_impl, ctxs: FwdContexts = FwdContexts()):
    """The trunk every step of this family is built from
    (:func:`paged_step.build`): ``rows`` embedded, (n, d), then every
    layer by its kinds, then the final norm. Returns ``(x (n, d), cache,
    stats)``: ``STEP_STATS``."""
    _check_cfg(cfg)
    attend = {"G": paged_step.kv_attend(rows, attn_impl, decode_attn_impl),
              "L": paged_step.kv_attend(rows, attn_impl, decode_attn_impl,
                                        window=cfg.sliding_window)}
    x = paged_step.embed_rows(params, rows.tokens())
    positions = rows.positions(cache)
    n = x.shape[0]
    eps = cfg.rms_norm_eps

    def make(letter):
        @jax.jit
        def layer(idx, lp, x, cache, stats):
            with scope("attn_project"):
                h = rms_norm(x, lp["ln_attn"], eps)
                q, k_tok, v_tok = tp_attn.decode_project(
                    lp["attn"], h, cfg, positions, axis=axis,
                    rope=letter == "L")
            o, cache = attend[letter](idx, q, k_tok, v_tok, cache)
            with scope("attn_out"):
                x = x + tp_attn.decode_output(
                    lp["attn"], o.reshape(n, -1), h, mode="xla", axis=axis)
            if "mlp" in lp:
                with scope("mlp"):
                    h = rms_norm(x, lp["ln_mlp"], eps)
                    x = x + tp_mlp.fwd(lp["mlp"], h, mode="xla_ar",
                                       axis=axis)
                return x, cache, stats
            with scope("router"):
                h = rms_norm(x, lp["ln_mlp"], eps)
            out, layer_stats = ep_moe.fwd_held(
                lp["moe"], h, topk=cfg.num_experts_per_tok,
                first=cfg.first_held_expert,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scale=cfg.routed_scaling_factor,
                scoring=cfg.moe_scoring)
            return x + out.astype(x.dtype), cache, stats + layer_stats

        return layer

    layer = {letter: make(letter) for letter in set(cfg.attn_pattern)}
    stats = jnp.zeros((len(STEP_STATS),), jnp.int32)
    count = dict.fromkeys("LG", 0)
    for letter, lp in zip(cfg.attn_pattern, params["layers"]):
        x, cache, stats = layer[letter](
            jnp.asarray(count[letter], jnp.int32), lp, x, cache, stats)
        count[letter] += 1
    with scope("head"):
        x = rms_norm(x, params["ln_f"], eps)
    return x, cache, stats


# No ``verify_step_paged``: see the module's docstring.
prefill_chunk_paged, decode_step_paged, chunk_decode_paged, _ = (
    paged_step.build(
        _layers, xla_only="models.window_moe has no fused collective "
        "layer"))
