"""The system under test, built from a configuration file. The ONLY
module of the harness that imports the program: ``Engine`` ->
``ServingEngine`` through their public API, weights from ``weights.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from . import weights as W
from .serve_loop import Refused

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(config: dict):
    from triton_dist_tpu.models import ModelConfig

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "max_position_embeddings",
            "tie_word_embeddings", "attention_bias", "qk_norm")
    return ModelConfig(model_name=config["model_name"],
                       **{k: config[k] for k in keys})


def make_mesh(config: dict):
    import triton_dist_tpu as tdt

    tp = int(config["tp"])
    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(f"configuration needs {tp} devices, JAX has "
                           f"{len(devs)}")
    return (tdt.make_mesh(tp=tp) if len(devs) == tp
            else tdt.make_mesh(tp=tp, devices=devs[:tp]))


def make_params(config: dict, mesh, seed: int):
    """The program's parameter tree, every leaf made on the device in
    its served type and under the sharding the program states; one
    compiled program for all layers."""
    from triton_dist_tpu.models import dense

    dims = W.Dims.from_config(config)
    dtype = DTYPES[config["dtype"]]
    specs = dense.param_specs(model_config(config), "tp")
    shard = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)
    root = W.root_key(seed)

    def layer(root, li):
        w = W.make_layer(root, li, dims, dtype)
        attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                  "k_norm", "bq", "bk", "bv") if k in w}
        if dims.attention_bias:
            # The program's bias variant carries an output-projection
            # bias; the published model has none: zero.
            attn["bo"] = jnp.zeros((dims.d,), dtype)
        return {"attn": attn,
                "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
                "ln_attn": w["ln_attn"], "ln_mlp": w["ln_mlp"]}

    layer_jit = jax.jit(layer, out_shardings=shard(specs["layers"][0]))
    layers = [layer_jit(root, li) for li in range(dims.layers)]
    embed = jax.jit(lambda r: W.make_table(r, "embed", dims, dtype),
                    out_shardings=shard(specs["embed"]))(root)
    head = embed if dims.tie else jax.jit(
        lambda r: W.make_table(r, "lm_head", dims, dtype),
        out_shardings=shard(specs["lm_head"]))(root)
    ln_f = jax.jit(lambda r: W.make_final_norm(r, dims, dtype),
                   out_shardings=shard(specs["ln_f"]))(root)
    return {"embed": embed, "layers": layers, "ln_f": ln_f,
            "lm_head": head}


class Served:
    """``ServingEngine`` behind the interface ``serve_loop`` drives."""

    def __init__(self, config: dict, seed: int):
        from triton_dist_tpu.models import Engine

        self.config = config
        self.mesh = make_mesh(config)
        params = make_params(config, self.mesh, seed)
        jax.block_until_ready(params)
        eng = dict(config["engine"])
        self.engine = Engine(model_config(config), self.mesh,
                             dtype=DTYPES[config["dtype"]], params=params,
                             fallback=None, **eng)
        srv = dict(config["serving"],
                   prefill_buckets=tuple(config["serving"]["prefill_buckets"]))
        self.srv = self.engine.serving(**srv)
        self._mode = eng.get("mode", "xla")

    def submit(self, planned, on_token):
        from triton_dist_tpu.serving.scheduler import QueueFullError

        try:
            return self.srv.submit(
                planned.prompt, max_new_tokens=planned.max_new_tokens,
                request_id=f"r{planned.rid}",
                stream_cb=lambda tok, h: on_token(tok))
        except QueueFullError as e:
            raise Refused(str(e)) from e

    def step(self) -> int:
        return self.srv.step()

    def busy(self) -> bool:
        return not self.srv.sched.idle

    def status(self, handle) -> str:
        return handle.status

    def slot(self, handle):
        return handle.slot

    def program_counts(self) -> dict:
        """Compiled programs the server holds, and that nothing gave
        way underneath (the engine still in the mode it was built in)."""
        return {"decode_programs": self.srv.decode_cache_size(),
                "prefill_programs": self.srv.prefill_cache_size(),
                "mode": self.engine.mode,
                "mode_kept": self.engine.mode == self._mode}

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest device of the mesh; 0 where
        the backend reports none (the CPU)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.mesh.devices.flat]
        return int(max(peaks))

    def close(self):
        """Drop engine, server and weights, so that what follows has
        the device to itself."""
        self.srv = self.engine = None


def warm_up_plan(config: dict, seed: int):
    """Requests that run every program the cell's traffic can reach:
    one prompt per slot, long enough for one chunk of every bucket (the
    last one padded), a few tokens each."""
    from .loadgen import Planned

    srv = config["serving"]
    n_prompt = int(sum(srv["prefill_buckets"])) - 3
    rng = np.random.default_rng([int(seed), 77])
    return [Planned(-1 - i, 0.0,
                    rng.integers(0, config["vocab_size"],
                                 size=n_prompt).tolist(), 4)
            for i in range(int(srv["num_slots"]))]
