"""The Mamba-2 / latent-expert family's program side: how a
configuration's file becomes the program's ``ModelConfig`` and the tree
``models.mamba_moe`` serves. The sizes and the seeded leaves are its
sibling's, ``mamba_latent_moe.py``.

    model_config(config)            -> triton_dist_tpu.models.ModelConfig
    make_params(config, mesh, seed) -> the tree ``Engine(params=...)`` takes
    engine_kwargs(config)           -> what ``Engine`` gets beside the
                                       file's ``engine`` keys
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from benchmark.harness import loader, weights as W
from triton_dist_tpu.layers.ep_moe import (
    pad_expert_width as _pad_expert_width)
from triton_dist_tpu.models import ModelConfig, mamba_moe

F = loader.sibling(__file__, "mamba_latent_moe")


def model_config(config: dict) -> ModelConfig:
    """The published keys through the program's own reader, then the
    chip's share: the router keeps the deployment's width, the weights
    are the held experts'."""
    import dataclasses

    cfg = ModelConfig.from_hf_config(
        dict(config, n_routed_experts=config["router_outputs"]))
    return dataclasses.replace(
        cfg, model_name=config["model_name"],
        first_held_expert=int(config["first_held_expert"]),
        num_held_experts=int(config["n_routed_experts"]))


def engine_kwargs(config: dict) -> dict:
    return {"model": mamba_moe}


def _program_layer(w: dict, kind: str) -> dict:
    """A layer's seeded leaves under the names the program's tree has.
    The step size's bias, the decay's logarithm and the skip are
    float32 in the program, as the published implementation keeps them:
    the seeded leaf, upcast, plus the family's constant, which is what
    the reference computes from the same leaf."""
    f32 = jnp.float32
    if kind == "mamba":
        return {"ln": w["ln"], "mamba": {
            "w_in": w["w_in"], "conv": w["conv"],
            "conv_bias": w["conv_bias"],
            "dt_bias": w["dt_bias"].astype(f32) + F.DT_BIAS_OFFSET,
            "a_log": w["a_log"].astype(f32) + F.A_LOG_OFFSET,
            "d_skip": w["d_skip"].astype(f32),
            "norm": w["ssm_norm"], "w_out": w["w_out"]}}
    if kind == "attention":
        return {"ln": w["ln"],
                "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")}}
    # The held experts' matrices as the program stores them: the seeded
    # leaves at the published width, zero-padded to the program's own
    # storage width (``layers.ep_moe.expert_store_width``).
    w_up, w_down = _pad_expert_width(w["experts_up"], w["experts_down"])
    return {"ln": w["ln"], "moe": {
        "router": w["router"],
        "router_bias": w["router_bias"].astype(f32),
        "w_latent_in": w["w_latent_in"], "w_up": w_up,
        "w_down": w_down, "w_latent_out": w["w_latent_out"],
        "w_shared_up": w["shared_up"], "w_shared_down": w["shared_down"]}}


def make_params(config: dict, mesh, seed: int):
    """The program's parameter tree, every leaf made on the device in
    its served type; one compiled program a KIND of layer."""
    dims = F.dims(config)
    dtype = W.DTYPES[config["dtype"]]
    specs = mamba_moe.param_specs(model_config(config), "tp")
    shard = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)
    root = W.root_key(seed)
    kinds = [F.layer_kind(dims, li) for li in range(dims.layers)]

    def maker(kind):
        def layer(root, li):
            return _program_layer(W.make_layer(
                root, li, F.layer_leaves(dims, kind), F.LEAF_IDS, dtype),
                kind)
        return jax.jit(layer, out_shardings=shard(
            specs["layers"][kinds.index(kind)]))

    make = {kind: maker(kind) for kind in dict.fromkeys(kinds)}
    made = jax.jit(
        lambda r: {"embed": W.make_table(r, "embed", dims, dtype),
                   "lm_head": W.make_table(r, "lm_head", dims, dtype),
                   "ln_f": W.make_final_norm(r, dims, dtype)},
        out_shardings=shard({k: specs[k] for k in ("embed", "lm_head",
                                                   "ln_f")}))(root)
    return dict(made, layers=[make[kind](root, li)
                              for li, kind in enumerate(kinds)])
