"""A decoder whose layers run several times over the same weights: the
paged serving contract of :mod:`triton_dist_tpu.models.dense` for a
stack that is applied ``cfg.num_passes`` times, each pass of each layer
keeping pages of its own.

With ``N`` an RMSNorm with a gain of its own, ``L`` layers, ``T``
passes:

    layer l:   a  = u + N2_l(Attn_l(N1_l(u)))
               u' = a + N4_l(MLP_l(N3_l(a)))          four norms a layer
    h_0 = E[ids];  pass t = 1..T:  u = h_{t-1}, the L layers, h_t = N_f(u)
    g_t = sigmoid(w_g . h_t + b_g)                    the exit gate
    p_t = g_t prod_{s<t} (1 - g_s),  p_T the remainder
    logits = head(h_t) at the first t whose cumulated p reaches
             ``cfg.exit_threshold`` (the last pass where none does)

``Attn`` is :mod:`~triton_dist_tpu.layers.tp_attn`'s decode contract
(rotate-half rope, grouped heads, no bias, no q/k norm), ``MLP``
:mod:`~triton_dist_tpu.layers.tp_mlp`'s SwiGLU; ``N_f`` is the model's
one final norm, ``params["ln_f"]``, which therefore stands between the
passes too. Pass ``t`` of layer ``l`` attends the keys and values that
pass ``t`` of layer ``l`` wrote: pool layer ``(t - 1) L + l``, ``T L``
in all (:func:`paged_pool` states them, and ``cfg.num_paged_layers``
sizes a page by them). Every pass runs for every row whatever pass its
logits are read from: later tokens attend every pass's cache.

The layers' leaves are STACKED, ``(L, ...)``, and a step program is one
traced layer body under a ``lax.scan`` over the layers, under a scan
over the passes: the pool's layer is an int32 operand of the cache's
writers and of the two paged kernels, as in
:mod:`~triton_dist_tpu.models.mamba_moe`. The pick among passes runs on
the device, before the head, so the head reads one row a row. Every
step returns, last, ``ROW_STATS``: the pass each of its head rows took
(1-based, int32), which leaves the chip behind the picked tokens.

Tensor parallelism as the dense family's (heads and FFN columns over
``axis``, the head's vocabulary rows); ``mode="xla"`` only.
``verify_step_paged`` is not provided.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.layers import tp_attn, tp_mlp
from triton_dist_tpu.layers.norm import rms_norm
from triton_dist_tpu.models import dense as _dense
from triton_dist_tpu.models import paged_step
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import FwdContexts
from triton_dist_tpu.obs import scope

# What every step returns last, one int32 a head row: the pass whose
# ``h_t`` the row's logits were read from.
ROW_STATS = ("exit_pass",)
_NORMS = ("ln_attn_in", "ln_attn_out", "ln_mlp_in", "ln_mlp_out")


def paged_pool(cfg: ModelConfig):
    """The pool this model keeps: keys and values of every KV head, for
    every pass of every layer."""
    from triton_dist_tpu.serving.blocks import PagedKVCache

    _check_cfg(cfg)
    return PagedKVCache, (cfg.num_key_value_heads, cfg.head_dim), {
        "layers": cfg.num_paged_layers}


def paged_cache_specs(axis: str = "tp", quantized: bool = False):
    if quantized:
        raise ValueError("models.looped keeps an unquantized pool")
    return _dense.paged_cache_specs(axis)


def _check_cfg(cfg: ModelConfig):
    if cfg.num_passes < 1 or cfg.layer_pattern or not cfg.post_norm:
        raise ValueError(
            "models.looped serves four-norm blocks (post_norm) applied "
            f"num_passes >= 1 times, not num_passes={cfg.num_passes}, "
            f"post_norm={cfg.post_norm}, "
            f"layer_pattern={cfg.layer_pattern!r}")


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d, n = cfg.hidden_size, cfg.num_hidden_layers
    ka, km, ke, kh, kg = jax.random.split(key, 5)
    table = lambda k: jax.random.normal(
        k, (cfg.vocab_size, d), dtype) * 0.02
    emb = table(ke)
    return {
        "embed": emb,
        "layers": {
            "attn": jax.vmap(lambda k: tp_attn.init(k, cfg, dtype))(
                jax.random.split(ka, n)),
            "mlp": jax.vmap(lambda k: tp_mlp.init(k, cfg, dtype))(
                jax.random.split(km, n)),
            **{name: jnp.ones((n, d), dtype) for name in _NORMS}},
        "ln_f": jnp.ones((d,), dtype),
        "exit_gate": {"w": jax.random.normal(kg, (d,), dtype) * d ** -0.5,
                      "b": jnp.zeros((), dtype)},
        "lm_head": emb if cfg.tie_word_embeddings else table(kh)}


def param_specs(cfg: ModelConfig, axis: str = "tp") -> Dict:
    _check_cfg(cfg)
    stacked = lambda spec: P(None, *spec)
    return {
        "embed": P(None, None),
        "layers": {
            "attn": jax.tree.map(stacked, tp_attn.param_specs(axis, cfg)),
            "mlp": jax.tree.map(stacked, tp_mlp.param_specs(axis)),
            **{name: P(None, None) for name in _NORMS}},
        "ln_f": P(None),
        "exit_gate": {"w": P(None), "b": P()},
        "lm_head": P(axis, None)}


# -- the Engine's dense-cache contract: not this model's path ---------------

cache_specs = _dense.cache_specs


def _paged_only(*_, **__):
    raise NotImplementedError(
        "models.looped serves through the paged pool only: "
        "Engine(...).serving(prefill_buckets=...)")


prefill = decode_step = _paged_only


# -- the passes --------------------------------------------------------------

def _passes(params, rows, cache, cfg: ModelConfig, *, mode, axis, attn_impl,
            decode_attn_impl, ctxs: FwdContexts = FwdContexts()):
    """The trunk every step of this family is built from
    (:func:`paged_step.build`): ``rows`` embedded, replicated (n, d),
    through the ``num_passes`` passes over the K/V pool's halves
    (:func:`paged_step.kv_attend`, the pool's layer an int32 scalar):
    every weight is read once a PASS for the rows of both halves.
    Returns ``(h (n, d), cache, exit_pass (n,) int32)``: each row's
    ``h_t`` at the pass it left by, already normed, and that pass
    (``ROW_STATS``)."""
    attend = paged_step.kv_attend(rows, attn_impl, decode_attn_impl)
    x = paged_step.embed_rows(params, rows.tokens())
    positions = rows.positions(cache)
    n, eps = x.shape[0], cfg.rms_norm_eps
    n_layers, n_passes = cfg.num_hidden_layers, cfg.num_passes
    f32 = jnp.float32

    def layer(carry, step):
        u, cache = carry
        lp, pool_layer = step
        with scope("attn_project"):
            h = rms_norm(u, lp["ln_attn_in"], eps)
            q, k_tok, v_tok = tp_attn.decode_project(
                lp["attn"], h, cfg, positions, axis=axis)
        o, cache = attend(pool_layer, q, k_tok, v_tok, cache)
        with scope("attn_out"):
            a = u + rms_norm(
                tp_attn.decode_output(lp["attn"], o.reshape(n, -1), h,
                                      mode="xla", axis=axis),
                lp["ln_attn_out"], eps)
        with scope("mlp"):
            m = tp_mlp.fwd(lp["mlp"], rms_norm(a, lp["ln_mlp_in"], eps),
                           mode="xla_ar", axis=axis)
            return (a + rms_norm(m, lp["ln_mlp_out"], eps), cache), None

    def one_pass(carry, t):
        h, cache, out, exit_pass, cumulated, remaining = carry
        (u, cache), _ = jax.lax.scan(
            layer, (h, cache),
            (params["layers"],
             t * n_layers + jnp.arange(n_layers, dtype=jnp.int32)))
        with scope("pass_norm"):
            h = rms_norm(u, params["ln_f"], eps)
        with scope("exit_gate"):
            gate = params["exit_gate"]
            g = jax.nn.sigmoid(
                jnp.dot(h, gate["w"], preferred_element_type=f32)
                + gate["b"].astype(f32))
            last = t == n_passes - 1
            cumulated = cumulated + jnp.where(last, remaining,
                                              g * remaining)
            remaining = remaining * (1.0 - g)
            leaves = jnp.logical_and(
                exit_pass == 0,
                jnp.logical_or(cumulated >= cfg.exit_threshold, last))
            out = jnp.where(leaves[:, None], h, out)
            exit_pass = jnp.where(leaves, t + 1, exit_pass)
        return (h, cache, out, exit_pass, cumulated, remaining), None

    (_, cache, out, exit_pass, _, _), _ = jax.lax.scan(
        one_pass,
        (x, cache, jnp.zeros_like(x), jnp.zeros((n,), jnp.int32),
         jnp.zeros((n,), f32), jnp.ones((n,), f32)),
        jnp.arange(n_passes, dtype=jnp.int32))
    return out, cache, exit_pass


# No ``verify_step_paged``: see the module's docstring.
prefill_chunk_paged, decode_step_paged, chunk_decode_paged, _ = (
    paged_step.build(
        _passes, row_stats=True,
        xla_only="models.looped runs its layers under a scan, with XLA's "
                 "collectives"))
