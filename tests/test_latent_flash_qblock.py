"""``ops.latent_flash_qblock`` under the Pallas interpreter, against the
XLA walk it replaces (``models.latent_moe._attend_expanded``, its
oracle) on the same pool: heads of 64 + 64 and 128 over a latent of 128,
pages of 128 positions, queries from the model's own projection (roped,
scaled by position), the pool random.

bf16 on both sides: the two differ by the blocks their running softmax
walks in (512 keys against 1,280), which rounds a probability to bf16
against another running maximum: one bf16 step of the result, 0.0078 at
its size (std 0.2-0.5, largest ~2), is what was read; 0.02 is held.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import ModelConfig, latent_moe
from triton_dist_tpu.ops import latent_flash_qblock as K
from triton_dist_tpu.serving.blocks import LatentPagedCache

PAGE = 128


def _cfg(heads=2):
    return ModelConfig.tiny_latent_moe(
        num_attention_heads=heads, num_key_value_heads=heads,
        q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=128, rope_factor=128.0,
        rope_original_max_position=8192, rope_beta_fast=32.0)


def _case(cfg, rows, start, valid, p_max, dtype=jnp.bfloat16, seed=0):
    """``(attn, q, cache, table row, qpos)``: a chunk of ``rows`` rows
    from position ``start`` of a slot whose row holds ``p_max`` pages,
    scattered over a pool of a few more."""
    rng = np.random.default_rng([seed, rows, start])
    attn = latent_moe.init_params(jax.random.PRNGKey(seed), cfg,
                                  dtype)["layers"][1]["attn"]
    pages = p_max + 3
    pool = jnp.asarray(rng.normal(size=(
        2, pages, latent_moe.cache_width(cfg), PAGE)), dtype)
    row = jnp.asarray(rng.permutation(np.arange(1, pages))[:p_max],
                      jnp.int32)
    cache = LatentPagedCache(
        pages=pool, block_table=row[None], lens=jnp.zeros((1,), jnp.int32),
        live=jnp.zeros((1,), jnp.int32))
    pos = start + jnp.arange(rows, dtype=jnp.int32)
    q, _ = latent_moe.project(
        attn, jnp.asarray(rng.normal(size=(rows, cfg.hidden_size)), dtype),
        cfg, pos)
    return attn, q, cache, row, latent_moe._chunk_qpos(pos, start, valid)


def _kernel(attn, q, cache, row, qpos, cfg, sizes=None):
    return np.asarray(K._latent_qblock_call(
        q, cache.pages, row, qpos, latent_moe._w_ukv(attn, cfg),
        jnp.asarray([1], jnp.int32), sigma=latent_moe.softmax_scale(cfg),
        sizes=sizes), np.float32)


def _oracle(attn, q, cache, row, qpos, cfg):
    return np.asarray(latent_moe._attend_expanded(
        attn, q, cache, 1, row, qpos, cfg), np.float32)


@pytest.mark.parametrize("rows,start,valid,p_max,heads,sizes", [
    # (a) a chunk that starts the prompt: every step on its diagonal.
    (256, 0, 256, 4, 2, None),
    # (b) mid-prompt, on a page boundary and off it.
    (256, 384, 256, 6, 2, None),
    (256, 200, 256, 4, 4, None),
    # (c) bucket padding: the rows past ``valid`` see what the last valid
    # row sees, and the walk stops there.
    (256, 200, 100, 4, 2, None),
    (512, 640, 300, 10, 2, None),
    # (d) a table row that is no whole number of steps (9 pages, 4 a
    # step), walked to its last page.
    (256, 896, 256, 9, 2, None),
    # (e) a context past position 8,192, where the query's scale is not
    # 1; the steps before the chunk take the unmasked path.
    (256, 8320, 256, 67, 2, None),
    # The chunk cut into row blocks and sub-tiles of other sizes (rows a
    # block, heads a group, rows a sub-tile, pages a step).
    (512, 1024, 512, 14, 2, (256, 1, 128, 2)),
    (512, 200, 512, 6, 4, (256, 2, 256, 1)),
], ids=["from-0", "page-boundary", "off-boundary", "padded",
        "padded-512", "ragged-row", "past-8192", "row-blocks",
        "row-blocks-4-heads"])
def test_equals_the_xla_walk(rows, start, valid, p_max, heads, sizes):
    cfg = _cfg(heads)
    assert K.legal(rows, 128, 128, 128, 192, PAGE)
    case = _case(cfg, rows, start, valid, p_max)
    if start > 8192:
        assert cfg.rope_query_scale_beta and start > (
            cfg.rope_original_max_position)
    want = _oracle(*case, cfg)
    got = _kernel(*case, cfg, sizes=sizes)
    assert got.shape == want.shape == (rows, heads * 128)
    assert want.std() > 0.15
    np.testing.assert_allclose(got, want, rtol=0, atol=0.02)


def test_equals_the_xla_walk_in_float32():
    """float32 pool and queries (the CPU tests of the model): the two
    walks differ by their order of summation alone."""
    cfg = _cfg(2)
    case = _case(cfg, 256, 300, 256, 5, dtype=jnp.float32)
    np.testing.assert_allclose(_kernel(*case, cfg), _oracle(*case, cfg),
                               rtol=0, atol=2e-5)


def test_a_row_block_reads_no_page_past_its_last_visible_position():
    """Two row blocks of 256 rows from position 200: the first sees
    positions through 455, pages 0-3 of the row. With every later page
    of the row NaN its rows are finite and what they were (a fetched NaN
    would reach them through a product with probability 0); the second
    block, which does see those pages, is not."""
    cfg = _cfg(2)
    sizes = (256, 2, 128, 2)
    attn, q, cache, row, qpos = _case(cfg, 512, 200, 512, 8)
    clean = _kernel(attn, q, cache, row, qpos, cfg, sizes=sizes)
    pool = np.array(cache.pages.astype(jnp.float32))
    pool[:, np.asarray(row[4:])] = np.nan
    poisoned = LatentPagedCache(
        pages=jnp.asarray(pool, jnp.bfloat16), block_table=cache.block_table,
        lens=cache.lens, live=cache.live)
    got = _kernel(attn, q, poisoned, row, qpos, cfg, sizes=sizes)
    assert np.isfinite(got[:256]).all()
    np.testing.assert_array_equal(got[:256], clean[:256])
    assert np.isnan(got[256:]).any()


def test_sizes_are_chosen_from_shapes_alone():
    # mistral-small-4-1chip's chunk programs: one row block a chunk.
    assert K.legal(2048, 128, 128, 256, 320, 128)
    assert K.block_sizes(2048, 32, 128, 128, 2) == (2048, 4, 256, 4)
    assert K.block_sizes(512, 32, 128, 128, 2) == (512, 4, 256, 4)
    # Twice the rows do not fit the budget: two row blocks.
    assert K.block_sizes(4096, 32, 128, 128, 2)[0] == 2048
    assert K.block_sizes(384, 3, 128, 128, 2) == (384, 1, 128, 4)
    # The tiny preset of tests/test_latent_moe.py: pages of 8, heads of
    # 8 + 8 and 16.
    assert not K.legal(16, 16, 16, 16, 24, 8)
    assert not K.legal(2048, 128, 128, 256, 320, 64)      # half a lane
    assert not K.legal(2000, 128, 128, 256, 320, 128)     # ragged rows
    with pytest.raises(ValueError, match="cannot tile"):
        K.latent_flash_qblock(
            jnp.zeros((16, 4, 16)), jnp.zeros((2, 3, 24, 8)),
            jnp.zeros((2,), jnp.int32), jnp.zeros((16,), jnp.int32),
            jnp.zeros((16, 4, 24)), layer=0, sigma=1.0)
