"""End-to-end dense model tests (reference: ``test_tp_e2e.py --check``
pattern — triton_dist forward vs torch-eager oracle,
``docs/getting-started/e2e/e2e_dense.md:115-124``).

Here the oracle is the same model in mode="xla" (pure lax collectives);
mode="fused" must match, and a 1-device dense run must match both.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import ModelConfig, Engine
from triton_dist_tpu.utils.testing import assert_allclose

CFG = ModelConfig.tiny()
B, S = 2, 32


# Four ranks, and blocks of one rank's share of the rows and columns:
# every ring step is one tile. The 8-rank rings and the multi-tile grids
# of ag_gemm / gemm_rs / gemm_ar are held at the ops (test_fused_gemm.py,
# test_overlap.py, test_stress.py). Both engines serve the whole module:
# ``prefill`` and ``serve`` make their cache anew.
def _engine(mesh, mode):
    return Engine(CFG, mesh, mode=mode, max_len=64, seed=3,
                  block_m=B * S // 4, block_n=16, block_k=32)


@pytest.fixture(scope="module")
def e_xla(tp4_mesh):
    return _engine(tp4_mesh, "xla")


@pytest.fixture(scope="module")
def e_fused(tp4_mesh):
    return _engine(tp4_mesh, "fused")


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                              CFG.vocab_size)


def test_prefill_fused_matches_xla(e_xla, e_fused, ids):
    logits_xla, cache_xla = e_xla.prefill(ids)
    logits_fused, cache_fused = e_fused.prefill(ids)
    assert_allclose(logits_fused, logits_xla, rtol=2e-3, atol=2e-3)
    assert_allclose(cache_fused.k, cache_xla.k, rtol=2e-3, atol=2e-3)


def test_decode_fused_matches_xla(e_xla, e_fused, ids):
    # Two tokens: the first from the prefill's logits, the second from a
    # decode step on the cache the prefill left.
    toks_xla = np.asarray(e_xla.serve(ids, gen_len=2))
    toks_fused = np.asarray(e_fused.serve(ids, gen_len=2))
    np.testing.assert_array_equal(toks_fused, toks_xla)
    assert toks_xla.shape == (B, 2)


def test_cache_length_advances(e_xla, ids):
    logits, cache = e_xla.prefill(ids)
    assert int(np.asarray(cache.length)) == S
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    _, cache2 = e_xla.decode(tok, cache)
    assert int(np.asarray(cache2.length)) == S + 1


def test_serve_sampling(e_xla, ids):
    """Sampling decode: deterministic per seed, different across seeds,
    and temperature→0 converges to greedy. top_k=1 IS greedy."""
    eng = e_xla
    greedy = np.asarray(eng.serve(ids, gen_len=4))

    s1 = np.asarray(eng.serve(ids, gen_len=4, temperature=0.8, seed=1))
    s1b = np.asarray(eng.serve(ids, gen_len=4, temperature=0.8, seed=1))
    np.testing.assert_array_equal(s1, s1b)       # same seed → same tokens

    s2 = np.asarray(eng.serve(ids, gen_len=4, temperature=5.0, seed=2))
    assert s1.shape == s2.shape == greedy.shape

    k1 = np.asarray(eng.serve(ids, gen_len=4, temperature=0.8,
                              top_k=1, seed=9))
    np.testing.assert_array_equal(k1, greedy)    # top-1 == argmax


def test_engine_rejects_moe_impl_on_dense_model(tp8_mesh):
    """Engine(moe_impl=...) with a non-MoE model raises a clear error
    instead of a TypeError inside param_specs (ADVICE r4)."""
    import pytest
    from triton_dist_tpu.models import Engine, ModelConfig

    with pytest.raises(ValueError, match="not a MoE model"):
        Engine(ModelConfig.tiny(), tp8_mesh, moe_impl="ep")


def test_dense_attention_bias_seed_oss_shape(tp4_mesh):
    """Seed-OSS-class dense models (attention biases, NO per-head q/k
    norm — reference serves ByteDance-Seed/Seed-OSS-36B-Instruct
    through the same DenseLLM, models/__init__.py:42): fused modes must
    match the XLA path with biases active."""
    import dataclasses

    from triton_dist_tpu.models import ModelConfig, Engine

    cfg = dataclasses.replace(ModelConfig.tiny(), attention_bias=True,
                              qk_norm=False,
                              model_name="seed-oss-tiny")
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0,
                             cfg.vocab_size)

    # Nonzero biases so the test actually exercises them.
    from triton_dist_tpu.models import dense as dense_mod
    params = dense_mod.init_params(jax.random.PRNGKey(1), cfg)
    for lyr in params["layers"]:
        assert "bq" in lyr["attn"] and "q_norm" not in lyr["attn"]
        lyr["attn"]["bq"] = jnp.full_like(lyr["attn"]["bq"], 0.05)
        lyr["attn"]["bo"] = jnp.full_like(lyr["attn"]["bo"], -0.03)

    # Biases must be load-bearing: the per-shard forward with nonzero
    # bq/bo differs from the zero-bias forward at the LOGITS level.
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu.utils.testing import spmd
    specs = dense_mod.param_specs(cfg)
    params0 = dense_mod.init_params(jax.random.PRNGKey(1), cfg)
    f = spmd(tp4_mesh,
             lambda p, i: dense_mod.prefill(p, i, cfg, max_len=16)[0],
             (specs, P(None, None)), P(None, None))
    lg_b = np.asarray(f(params, ids))
    lg_0 = np.asarray(f(params0, ids))
    assert np.abs(lg_b - lg_0).max() > 1e-4

    outs = {}
    for mode in ("xla", "fused"):
        eng = Engine(cfg, tp4_mesh, mode=mode, params=params)
        outs[mode] = np.asarray(eng.serve(ids, gen_len=2))
    np.testing.assert_array_equal(outs["xla"], outs["fused"])


def test_hf_loader_maps_bias_checkpoint():
    """State-dict mapping for a bias-carrying, norm-free checkpoint."""
    import numpy as _np
    from triton_dist_tpu.models.hf_loader import params_from_hf_state_dict
    from triton_dist_tpu.models import ModelConfig
    import dataclasses

    cfg = dataclasses.replace(
        ModelConfig.tiny(vocab_size=32, hidden_size=16,
                         intermediate_size=32, num_hidden_layers=1,
                         num_attention_heads=2, num_key_value_heads=2,
                         head_dim=8),
        attention_bias=True, qk_norm=False)
    d, hq, hkv = 16, 16, 16
    state = {}
    p = "model.layers.0."
    rng = _np.random.default_rng(0)
    for k, shape in [
            (p + "self_attn.q_proj.weight", (hq, d)),
            (p + "self_attn.k_proj.weight", (hkv, d)),
            (p + "self_attn.v_proj.weight", (hkv, d)),
            (p + "self_attn.o_proj.weight", (d, hq)),
            (p + "self_attn.q_proj.bias", (hq,)),
            (p + "self_attn.k_proj.bias", (hkv,)),
            (p + "self_attn.v_proj.bias", (hkv,)),
            (p + "mlp.gate_proj.weight", (32, d)),
            (p + "mlp.up_proj.weight", (32, d)),
            (p + "mlp.down_proj.weight", (d, 32)),
            (p + "input_layernorm.weight", (d,)),
            (p + "post_attention_layernorm.weight", (d,)),
            ("model.embed_tokens.weight", (32, d)),
            ("model.norm.weight", (d,)),
            ("lm_head.weight", (32, d)),
    ]:
        state[k] = rng.standard_normal(shape).astype(_np.float32)
    params = params_from_hf_state_dict(state, cfg)
    attn = params["layers"][0]["attn"]
    assert "bq" in attn and "bo" in attn and "q_norm" not in attn
    np.testing.assert_allclose(
        np.asarray(attn["bq"], np.float32),
        state[p + "self_attn.q_proj.bias"], rtol=1e-2, atol=1e-2)
    # o_proj.bias absent -> zeros fallback.
    assert np.all(np.asarray(attn["bo"], np.float32) == 0.0)
