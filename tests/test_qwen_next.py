"""Hybrid GDN/full-attention model (Qwen3-Next family).

The GDN kernel's model-level contract: fused mode matches the XLA
oracle, and the recurrent-state handoff from chunked prefill into O(1)
decode reproduces the all-tokens forward (the same prefill/decode
equivalence the dense tests establish for the KV cache).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models import Engine, qwen_next
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.dense import make_fwd_contexts
from triton_dist_tpu.utils.testing import spmd, assert_allclose

# One GDN and one attention layer: every layer kind, and the handoff
# between the two, once. Four ranks; the blocks are one rank's share of
# the rows and columns, so every ring step is one tile (the 8-rank rings
# and the multi-tile grids are the op tests': test_fused_gemm.py,
# test_gdn.py, test_overlap.py).
CFG = ModelConfig.tiny_next(num_hidden_layers=2)
B, S = 2, 16
BLOCKS = dict(block_m=B * S // 4, block_n=16, block_k=32)


def _engine(mesh, mode):
    return Engine(CFG, mesh, mode=mode, max_len=64, seed=3, model=qwen_next,
                  **BLOCKS)


@pytest.fixture(scope="module")
def xla_engine(tp4_mesh):
    """The oracle of the module. ``serve`` and ``prefill`` make their
    cache anew, so a test sees nothing of the one before."""
    return _engine(tp4_mesh, "xla")


def _ids(seed=1, s=S):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, s), 0,
                              CFG.vocab_size)


def _forward(mesh, ctx, cfg, params, ids, mode="xla"):
    ctxs = make_fwd_contexts(ctx, "tp", **BLOCKS)
    return spmd(
        mesh,
        lambda p, i: qwen_next.forward_tokens(p, i, cfg, mode=mode,
                                              ctxs=ctxs),
        (qwen_next.param_specs(cfg), P(None, None)),
        P(None, None, None))(params, ids)


def test_layer_schedule():
    cfg = ModelConfig.tiny_next()
    kinds, n_attn, n_gdn = qwen_next._layer_kinds(cfg)
    # interval=2 over 4 layers → gdn, attn, gdn, attn.
    assert [k for k, _ in kinds] == ["gdn", "attn", "gdn", "attn"]
    assert (n_attn, n_gdn) == (2, 2)
    assert cfg.is_hybrid
    assert [k for k, _ in qwen_next._layer_kinds(CFG)[0]] == ["gdn", "attn"]


def test_forward_fused_matches_xla(tp4_mesh, tp4_ctx):
    params = qwen_next.init_params(jax.random.PRNGKey(0), CFG)
    ids = _ids()
    logits_xla = _forward(tp4_mesh, tp4_ctx, CFG, params, ids)
    assert logits_xla.shape == (B, S, CFG.vocab_size)
    assert_allclose(_forward(tp4_mesh, tp4_ctx, CFG, params, ids, "fused"),
                    logits_xla, rtol=2e-3, atol=2e-3)


def _assert_chain_matches_forward(eng, cfg, mesh, ctx, ids, gen=4):
    """Greedy tokens of (prefill → decode chain) against the all-tokens
    forward, teacher-forced on the same tokens."""
    s = ids.shape[1]
    chain = np.asarray(eng.serve(ids, gen_len=gen))        # (B, gen)
    full = jnp.concatenate([ids, jnp.asarray(chain)], axis=1)
    fwd = _forward(mesh, ctx, cfg, jax.tree.map(np.asarray, eng.params),
                   full)
    want = np.asarray(jnp.argmax(fwd, -1))[:, s - 1:s - 1 + gen]
    np.testing.assert_array_equal(chain, want)


def test_prefill_decode_matches_forward(xla_engine, tp4_mesh, tp4_ctx):
    """Greedy continuation from (prefill → decode chain) must equal the
    all-tokens forward teacher-forced on the same tokens — proving the
    GDN recurrent state and the KV cache carry exactly the prefix
    information."""
    _assert_chain_matches_forward(xla_engine, CFG, tp4_mesh, tp4_ctx,
                                  _ids(seed=2))


def test_decode_fused_matches_xla(xla_engine, tp4_mesh):
    # Two tokens: the first comes from the prefill's logits, the second
    # from a decode step on the state and the cache the prefill left.
    ids = _ids(seed=3)
    toks_xla = np.asarray(xla_engine.serve(ids, gen_len=2))
    toks_fused = np.asarray(
        _engine(tp4_mesh, "fused").serve(ids, gen_len=2))
    np.testing.assert_array_equal(toks_fused, toks_xla)
    assert toks_xla.shape == (B, 2)


MOE_CFG = ModelConfig.tiny_next(num_hidden_layers=2, num_experts=4,
                                num_experts_per_tok=2,
                                moe_intermediate_size=32)


def test_moe_ffn_forward_fused_matches_xla(tp4_mesh, tp4_ctx):
    """MoE hybrid configs must actually run the MoE FFN (r2 advisor:
    cfg.is_moe was silently ignored) and the fused pipeline must match
    the XLA oracle."""
    params = qwen_next.init_params(jax.random.PRNGKey(7), MOE_CFG)
    # MoE param set, not a dense MLP: router + per-expert weights.
    assert "router" in params["layers"][0]["mlp"]
    assert params["layers"][0]["mlp"]["w_gate"].shape[0] == 4
    ids = _ids(seed=8)
    logits_xla = _forward(tp4_mesh, tp4_ctx, MOE_CFG, params, ids)
    assert logits_xla.shape == (B, S, MOE_CFG.vocab_size)
    assert_allclose(
        _forward(tp4_mesh, tp4_ctx, MOE_CFG, params, ids, "fused"),
        logits_xla, rtol=2e-3, atol=2e-3)


def test_moe_prefill_decode_matches_forward(tp4_mesh, tp4_ctx):
    """The MoE FFN decode path (replicated rows + AR) must agree with
    the token-sharded prefill path token-for-token."""
    eng = Engine(MOE_CFG, tp4_mesh, mode="xla", max_len=64, seed=9,
                 model=qwen_next, **BLOCKS)
    _assert_chain_matches_forward(eng, MOE_CFG, tp4_mesh, tp4_ctx,
                                  _ids(seed=10))


def test_state_is_constant_memory(xla_engine):
    """The GDN cache does not grow with sequence length (the point of
    the hybrid architecture for long context)."""
    eng = xla_engine
    _, c16 = eng.prefill(_ids(seed=4, s=16))
    _, c32 = eng.prefill(_ids(seed=5, s=32))
    assert c16.states.shape == c32.states.shape


def test_hybrid_training_step(tp8_mesh):
    """Grads flow through the whole hybrid stack — chunked delta rule
    (triangular solve), conv, gates — and one SGD step lowers the loss.
    The hybrid family is trainable, not inference-only (long-context
    training is the architecture's point)."""
    import dataclasses

    cfg = dataclasses.replace(
        ModelConfig.tiny_next(), gdn_num_key_heads=8, gdn_conv_kernel=4,
        attn_gate=True, partial_rotary_factor=0.5)
    params = qwen_next.init_params(jax.random.PRNGKey(0), cfg)
    specs = qwen_next.param_specs(cfg)
    ids = _ids(seed=5, s=16)

    def loss_fn(p, i):
        logits = qwen_next.forward_tokens(p, i, cfg)
        tgt = jnp.roll(i, -1, axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return jnp.mean(nll)

    def train_step(p, i):
        loss, grads = jax.value_and_grad(loss_fn)(p, i)

        def has_tp(spec):
            return any(e == "tp" or (isinstance(e, tuple) and "tp" in e)
                       for e in tuple(spec))

        # Every shard computes the FULL loss from the replicated
        # logits, so backward counts each parameter's contribution
        # axis_size times in aggregate: complete replicated-spec leaves
        # with a psum (their per-shard grad saw only this rank's token
        # slice), then scale EVERYTHING by 1/n to recover the true
        # gradient (verified against a single-device oracle).
        n = jax.lax.axis_size("tp")
        grads = jax.tree.map(
            lambda g, s: (g if has_tp(s)
                          else jax.lax.psum(g, "tp")) / n,
            grads, specs)
        new_p = jax.tree.map(lambda w, g: w - 1e-2 * g, p, grads)
        return loss, new_p

    step = spmd(tp8_mesh, train_step, (specs, P(None, None)),
                (P(), specs))
    loss0, p1 = step(params, ids)
    assert np.isfinite(float(loss0))
    flat, _ = jax.tree_util.tree_flatten(p1)
    assert all(bool(jnp.isfinite(x).all()) for x in flat)
    loss1, _ = step(jax.tree.map(np.asarray, p1), ids)
    assert float(loss1) < float(loss0), (float(loss0), float(loss1))
