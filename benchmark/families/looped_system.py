"""The looped decoder's program side: how a configuration's file becomes
the program's ``ModelConfig`` and the tree ``models.looped`` serves. The
sizes and the seeded leaves are its sibling's, ``looped.py``.

    model_config(config)            -> triton_dist_tpu.models.ModelConfig
    make_params(config, mesh, seed) -> the tree ``Engine(params=...)`` takes
    engine_kwargs(config)           -> what ``Engine`` gets beside the
                                       file's ``engine`` keys
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from benchmark.harness import loader, weights as W
from triton_dist_tpu.models import ModelConfig, looped

F = loader.sibling(__file__, "looped")


def model_config(config: dict) -> ModelConfig:
    """The published keys through the program's own reader."""
    return dataclasses.replace(ModelConfig.from_hf_config(config),
                               model_name=config["model_name"])


def engine_kwargs(config: dict) -> dict:
    return {"model": looped}


def make_params(config: dict, mesh, seed: int):
    """The program's parameter tree, every leaf made on the device in
    its served type and under the sharding the program states. The
    layers' leaves are made STACKED, ``(layers, ...)``, as the program
    takes them: one compiled program makes a layer at a time into its
    place, and no second copy of the stack ever exists."""
    dims = F.dims(config)
    dtype = W.DTYPES[config["dtype"]]
    specs = looped.param_specs(model_config(config), "tp")
    shard = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)

    def layer(root, li):
        w = W.make_layer(root, li, F.layer_leaves(dims, "block"),
                         F.LEAF_IDS, dtype)
        # The two norms after a sublayer: the seeded leaf times the
        # family's constant (a power of two: exact in the served type),
        # which is what the reference computes from the same leaf.
        post = jnp.asarray(F.POST_NORM_GAIN, dtype)
        return {"attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
                "ln_attn_in": w["ln_attn_in"], "ln_mlp_in": w["ln_mlp_in"],
                "ln_attn_out": w["ln_attn_out"] * post,
                "ln_mlp_out": w["ln_mlp_out"] * post}

    def everything(root):
        gate = W.make_layer(root, dims.layers,
                            F.layer_leaves(dims, "gate"), F.LEAF_IDS, dtype)
        return {
            "embed": W.make_table(root, "embed", dims, dtype),
            "lm_head": W.make_table(
                root, "embed" if dims.tie else "lm_head", dims, dtype),
            "ln_f": W.make_final_norm(root, dims, dtype),
            "exit_gate": {"w": gate["exit_row"][:, 0],
                          "b": gate["exit_bias"][0]},
            "layers": jax.lax.map(
                lambda li: layer(root, li),
                jnp.arange(dims.layers, dtype=jnp.int32))}

    return jax.jit(everything, out_shardings=shard(specs))(
        W.root_key(seed))
