"""What the chip bring-up repaired, checked on the CPU mesh: the compile
cache helper, initialisation under the target sharding, and the
platform predicate."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import triton_dist_tpu as tdt
from triton_dist_tpu.models import Engine, ModelConfig, dense
from triton_dist_tpu.utils import distributed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """enable_compile_cache() writes process-wide jax config: put it
    back, or every later test would compile into the checkout."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_env_wins(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert distributed.enable_compile_cache() == "/some/dir"
    # JAX reads the variable itself; no directory is set in code.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert distributed.enable_compile_cache() == want
    assert distributed.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # ... and in another process: nothing of pid, time or tmp in it.
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run(
        [sys.executable, "-c",
         "from triton_dist_tpu.utils.distributed import "
         "enable_compile_cache as e; print(e())"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert r.stdout.strip() == want, r.stderr


def test_platform_is_jax_own_string(monkeypatch):
    assert distributed.platform() == jax.devices()[0].platform

    class _Dev:
        platform = "anything-jax-says"

    distributed.platform.cache_clear()
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    try:
        assert distributed.platform() == "anything-jax-says"
        assert not distributed.on_tpu()
    finally:
        distributed.platform.cache_clear()


def test_engine_initialises_under_target_sharding():
    """No weight ever exists unsharded: the initialiser's outputs carry
    ``param_specs`` and its compiled program holds no array of a sharded
    leaf's full shape. Asserted on the program, not on memory."""
    # vocab 128: no shard of the head shares a sharded leaf's full shape
    cfg = ModelConfig.tiny(vocab_size=128)
    mesh = tdt.make_mesh(tp=4, devices=jax.devices()[:4])
    eng = Engine(cfg, mesh, mode="xla", seed=3)
    compiled = eng.sharded_init().lower(
        jax.random.PRNGKey(3), cfg, jnp.float32).compile()

    specs = dense.param_specs(cfg, "tp")
    jax.tree.map(
        lambda sh, spec: sh.is_equivalent_to(NamedSharding(mesh, spec), 2)
        or pytest.fail(f"{sh} is not {spec}"),
        compiled.output_shardings, specs,
        is_leaf=lambda s: isinstance(s, P))
    hlo = compiled.as_text()
    d, ff = cfg.hidden_size, cfg.intermediate_size
    assert f"f32[{d},{ff // 4}]" in hlo          # a w_gate shard
    for full in (f"f32[{d},{ff}]", f"f32[{ff},{d}]"):
        assert full not in hlo, f"unsharded {full} in the init program"

    # Same values as the eager initialiser, placed as the specs say.
    want = dense.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    got = eng.params["layers"][1]["mlp"]["w_down"]
    assert got.sharding.is_equivalent_to(
        NamedSharding(mesh, P("tp", None)), 2)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want["layers"][1]["mlp"]["w_down"]))


def test_kv_pool_allocated_under_target_sharding():
    from triton_dist_tpu.serving.blocks import PagedKVCache

    mesh = tdt.make_mesh(tp=4, devices=jax.devices()[:4])
    cache, shardings = PagedKVCache.empty_sharded(
        mesh, dense.paged_cache_specs, "tp", 2, 5, 8, 8, 8,
        num_slots=2, p_max=2, dtype=jnp.float32)
    assert cache.k_pages.shape == (2, 5, 8, 8, 8)      # global KV heads
    assert cache.k_pages.sharding == shardings.k_pages
    assert {s.data.shape for s in cache.k_pages.addressable_shards} == {
        (2, 5, 2, 8, 8)}
    assert cache.k_scale is None and shardings.k_scale is None
