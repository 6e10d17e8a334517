"""The window-and-global expert family's program side: how a
configuration's file becomes the program's ``ModelConfig`` and the tree
``models.window_moe`` serves. The sizes and the seeded leaves are its
sibling's, ``swa_moe.py``.

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
from triton_dist_tpu.models import ModelConfig, window_moe

F = loader.sibling(__file__, "swa_moe")


def model_config(config: dict) -> ModelConfig:
    """The published keys through the program's own reader, then the
    chip's share: the router keeps the deployment's width, the weights
    are the held experts'. The multi-token-prediction module is stated
    as left out (the file's ``assumed.mtp``): the reader refuses the
    key otherwise."""
    import dataclasses

    cfg = ModelConfig.from_hf_config(
        dict(config, num_experts=config["router_outputs"]),
        leave_out=("mtp",))
    return dataclasses.replace(
        cfg, model_name=config["model_name"],
        first_held_expert=int(config["first_held_expert"]),
        num_held_experts=int(config["num_experts"]))


def engine_kwargs(config: dict) -> dict:
    return {"model": window_moe}


def _program_layer(w: dict, kind: str) -> dict:
    """A layer's seeded leaves under the names the program's tree has;
    the selection bias float32, as the program keeps it."""
    out = {"attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                      "k_norm")},
           "ln_attn": w["ln_attn"], "ln_mlp": w["ln_mlp"]}
    if kind.endswith("dense"):
        out["mlp"] = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
        return out
    # The held experts' matrices as the program stores them
    # (``layers.ep_moe.expert_store_width``).
    w_up, w_down, w_gate = _pad_expert_width(
        w["experts_up"], w["experts_down"], w["experts_gate"])
    out["moe"] = {"router": w["router"],
                  "router_bias": w["router_bias"].astype(jnp.float32),
                  "w_gate": w_gate, "w_up": w_up, "w_down": w_down,
                  "w_shared_gate": w["shared_gate"],
                  "w_shared_up": w["shared_up"],
                  "w_shared_down": w["shared_down"]}
    return out


def make_params(config: dict, mesh, seed: int):
    """The program's parameter tree, every leaf made on the device in
    its served type; one compiled program a KIND of layer's leaves (the
    two kinds of attention have the same)."""
    dims = F.dims(config)
    dtype = W.DTYPES[config["dtype"]]
    specs = window_moe.param_specs(model_config(config), "tp")
    shard = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)
    root = W.root_key(seed)
    kinds = [F.layer_kind(dims, li) for li in range(dims.layers)]
    ffns = [k.split("_")[1] for k in kinds]

    def maker(ffn):
        kind = kinds[ffns.index(ffn)]

        def layer(root, li):
            return _program_layer(W.make_layer(
                root, li, F.layer_leaves(dims, kind), F.LEAF_IDS, dtype),
                kind)
        return jax.jit(layer, out_shardings=shard(
            specs["layers"][ffns.index(ffn)]))

    make = {ffn: maker(ffn) for ffn in dict.fromkeys(ffns)}
    made = jax.jit(
        lambda r: {"embed": W.make_table(r, "embed", dims, dtype),
                   "lm_head": W.make_table(r, "lm_head", dims, dtype),
                   "ln_f": W.make_final_norm(r, dims, dtype)},
        out_shardings=shard({k: specs[k] for k in ("embed", "lm_head",
                                                   "ln_f")}))(root)
    return dict(made, layers=[make[ffn](root, li)
                              for li, ffn in enumerate(ffns)])
