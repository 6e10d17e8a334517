"""The device's half of the ``tdt.`` vocabulary (``obs.DEVICE_SCOPES``,
``obs.scope``): the chunk program the serving engine jits, with its
decode rows aboard, compiled at a tiny size on the CPU, carries every
block its model family runs as a segment of its operations' ``op_name``
and no ``tdt.`` segment outside the vocabulary. That path is what the
profiler writes into a capture beside each operation
(docs/observability.md, "In a profiler capture";
``benchmark/harness/reducers/device_scopes.py`` reads it there)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu as tdt
from triton_dist_tpu import obs
from triton_dist_tpu.models import (Engine, ModelConfig, latent_moe,
                                    looped, mamba_moe, window_moe)

# What each family's chunk program runs; ``embed``, the attention
# blocks, ``head`` and ``pick`` are common to all.
COMMON = {"embed", "attn_project", "cache_write", "attn_chunk",
          "attn_decode", "attn_out", "head", "pick"}
EXPERTS = {"router", "experts", "shared_expert"}
RUNS = {"dense": COMMON | {"mlp"},
        "latent_moe": COMMON | EXPERTS,
        "looped": COMMON | {"mlp", "pass_norm", "exit_gate"},
        "mamba_moe": COMMON | EXPERTS | {"ssm_project", "ssm", "ssm_out",
                                         "expert_latent"},
        # Both kinds of attention layer, the leading dense layer and
        # the expert layers.
        "window_moe": COMMON | EXPERTS | {"mlp", "attn_chunk_window",
                                          "attn_decode_window"}}


def _chunk_program_text(family: str) -> str:
    """The optimised HLO of the one chunk program (8 rows + 2 decode
    rows) of a tiny serving engine of ``family``."""
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    if family == "dense":
        eng = Engine(ModelConfig.tiny(), mesh, mode="xla", max_len=32,
                     seed=0)
    elif family == "latent_moe":
        eng = Engine(ModelConfig.tiny_latent_moe(), mesh, model=latent_moe,
                     mode="xla", dtype=jnp.float32, max_len=32, seed=0)
    elif family == "looped":
        eng = Engine(ModelConfig.tiny_looped(), mesh, model=looped,
                     mode="xla", dtype=jnp.float32, max_len=32, seed=0)
    elif family == "window_moe":
        eng = Engine(ModelConfig.tiny_window_moe(), mesh, model=window_moe,
                     mode="xla", dtype=jnp.float32, max_len=32, seed=0)
    else:
        eng = Engine(ModelConfig.tiny_mamba_moe(), mesh, model=mamba_moe,
                     mode="xla", dtype=jnp.float32, max_len=32, seed=0)
    srv = eng.serving(num_slots=2, page=8, prefill_buckets=(8,))
    assert srv.chunker.decode_rows == 2
    p_max = srv.cache.block_table.shape[1]
    # A pool whose sequences keep state is told the chunk's slot.
    slot = (np.int32(0),) * bool(srv.cache.seq)
    return srv.chunker._chunk.lower(
        eng.params, jnp.zeros((8,), jnp.int32), srv.cache,
        jnp.zeros((p_max,), jnp.int32), np.int32(0), np.int32(0),
        np.int32(8), *slot, jnp.zeros((2,), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("family", sorted(RUNS))
def test_the_chunk_program_names_every_block_it_runs(family):
    text = _chunk_program_text(family)
    segments = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        segments.update(s for s in re.split(r"[/;]", path)
                        if s.startswith("tdt."))
    assert segments == {"tdt." + b for b in RUNS[family]}
    assert RUNS[family] <= set(obs.DEVICE_SCOPES)


def test_the_families_together_run_the_whole_vocabulary():
    assert set().union(*RUNS.values()) == set(obs.DEVICE_SCOPES)


@pytest.mark.parametrize("block", ["", "attention", "tdt.mlp", "tick"])
def test_scope_refuses_a_block_outside_the_vocabulary(block):
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        obs.scope(block)


def test_scope_is_a_name_and_nothing_else():
    """The jaxpr under a scope is the jaxpr without it: no operand, no
    equation added; only the name stack differs."""
    def f(x):
        return jnp.tanh(x) * 2

    def g(x):
        with obs.scope("mlp"):
            return jnp.tanh(x) * 2

    a, b = jax.make_jaxpr(f)(1.0), jax.make_jaxpr(g)(1.0)
    assert str(a) == str(b)
    assert "tdt.mlp" in str(b.eqns[0].source_info.name_stack)
    assert "tdt." not in str(a.eqns[0].source_info.name_stack)
