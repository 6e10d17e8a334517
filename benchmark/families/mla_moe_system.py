"""The latent-attention expert family's program side: how a
configuration's file becomes the program's ``ModelConfig`` and the tree
``models.latent_moe`` serves. The sizes and the seeded leaves are its
sibling's, ``mla_moe.py``.

    model_config(config)            -> triton_dist_tpu.models.ModelConfig
    make_params(config, mesh, seed) -> the tree ``Engine(params=...)`` takes
    engine_kwargs(config)           -> what ``Engine`` gets beside the
                                       file's ``engine`` keys
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding

from benchmark.harness import loader, weights as W
from triton_dist_tpu.models import ModelConfig, latent_moe

F = loader.sibling(__file__, "mla_moe")


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
    return {"model": latent_moe}


def make_params(config: dict, mesh, seed: int):
    """The program's parameter tree, every leaf made on the device in
    its served type; one compiled program for all layers."""
    dims = F.dims(config)
    dtype = W.DTYPES[config["dtype"]]
    specs = latent_moe.param_specs(model_config(config), "tp")
    shard = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)
    root = W.root_key(seed)

    def layer(root, li):
        w = W.make_layer(root, li, F.layer_leaves(dims), F.LEAF_IDS, dtype)
        return {"attn": {k: w[k] for k in ("w_dq", "q_norm", "w_uq",
                                           "w_dkv", "kv_norm", "w_ukv",
                                           "wo")},
                "moe": {"router": w["router"],
                        "w_gate": w["experts_gate"],
                        "w_up": w["experts_up"],
                        "w_down": w["experts_down"],
                        "w_shared_gate": w["shared_gate"],
                        "w_shared_up": w["shared_up"],
                        "w_shared_down": w["shared_down"]},
                "ln_attn": w["ln_attn"], "ln_mlp": w["ln_mlp"]}

    layer_jit = jax.jit(layer, out_shardings=shard(specs["layers"][0]))
    made = jax.jit(
        lambda r: {"embed": W.make_table(r, "embed", dims, dtype),
                   "lm_head": W.make_table(r, "lm_head", dims, dtype),
                   "ln_f": W.make_final_norm(r, dims, dtype)},
        out_shardings=shard({k: specs[k] for k in ("embed", "lm_head",
                                                   "ln_f")}))(root)
    return dict(made, layers=[layer_jit(root, li)
                              for li in range(dims.layers)])
