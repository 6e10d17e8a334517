"""Seeded weights, one leaf at a time, in the type they are served in.

The benchmark makes the weights, not the program: ``system.py`` hands
them to the engine, and ``reference.py`` makes the same leaves again from
the same seed, a layer at a time, so that the reference takes nothing the
program has made. A leaf's values depend only on (seed, layer, leaf
name, shape): one jitted call makes a layer, compiled once for all
layers because the layer index is an argument.

Layout (plain ``x @ w``): ``wq`` (d, H*hd), ``wk``/``wv`` (d, KV*hd),
``wo`` (H*hd, d), ``w_gate``/``w_up`` (d, ff), ``w_down`` (ff, d); query
head ``h`` reads KV head ``h // (H // KV)``. Tables are (vocab, d), made
in ``TABLE_BLOCKS`` row blocks.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

TABLE_BLOCKS = 16
_LEAF_IDS = {n: i for i, n in enumerate((
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln_attn",
    "ln_mlp", "q_norm", "k_norm", "bq", "bk", "bv", "ln_f"))}
_TABLE_IDS = {"embed": 0, "lm_head": 1}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, read from a configuration file
    (Hugging Face key names)."""
    vocab: int
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    rope_theta: float
    qk_norm: bool
    attention_bias: bool
    tie: bool

    @classmethod
    def from_config(cls, c):
        return cls(vocab=int(c["vocab_size"]), d=int(c["hidden_size"]),
                   ff=int(c["intermediate_size"]),
                   layers=int(c["num_hidden_layers"]),
                   heads=int(c["num_attention_heads"]),
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c["head_dim"]),
                   eps=float(c["rms_norm_eps"]),
                   rope_theta=float(c["rope_theta"]),
                   qk_norm=bool(c["qk_norm"]),
                   attention_bias=bool(c["attention_bias"]),
                   tie=bool(c.get("tie_word_embeddings", False)))


def root_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layer_leaves(dims: Dims):
    """name -> (shape, kind, scale); kinds: 'w' normal * scale,
    'g' 1 + 0.1 * normal (a norm's gain), 'b' 0.02 * normal (a bias)."""
    d, ff, hd = dims.d, dims.ff, dims.head_dim
    q, kv = dims.heads * hd, dims.kv_heads * hd
    out = {
        "wq": ((d, q), "w", d ** -0.5), "wk": ((d, kv), "w", d ** -0.5),
        "wv": ((d, kv), "w", d ** -0.5), "wo": ((q, d), "w", q ** -0.5),
        "w_gate": ((d, ff), "w", d ** -0.5),
        "w_up": ((d, ff), "w", d ** -0.5),
        "w_down": ((ff, d), "w", ff ** -0.5),
        "ln_attn": ((d,), "g", None), "ln_mlp": ((d,), "g", None),
    }
    if dims.qk_norm:
        out["q_norm"] = ((hd,), "g", None)
        out["k_norm"] = ((hd,), "g", None)
    if dims.attention_bias:
        out["bq"] = ((q,), "b", None)
        out["bk"] = ((kv,), "b", None)
        out["bv"] = ((kv,), "b", None)
    return out


def _leaf(key, shape, kind, scale, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        z = z * scale
    elif kind == "g":
        z = 1.0 + 0.1 * z
    else:
        z = 0.02 * z
    return z.astype(dtype)


def make_layer(root, li, dims: Dims, dtype):
    """All leaves of layer ``li`` (traced or not)."""
    lkey = jax.random.fold_in(root, li)
    return {name: _leaf(jax.random.fold_in(lkey, _LEAF_IDS[name]),
                        shape, kind, scale, dtype)
            for name, (shape, kind, scale) in layer_leaves(dims).items()}


def make_final_norm(root, dims: Dims, dtype):
    key = jax.random.fold_in(jax.random.fold_in(root, 2_000_000),
                             _LEAF_IDS["ln_f"])
    return _leaf(key, (dims.d,), "g", None, dtype)


def table_blocks(vocab: int) -> int:
    return TABLE_BLOCKS if vocab % TABLE_BLOCKS == 0 else 1


def make_table_block(root, which: str, block, dims: Dims, dtype):
    """Rows ``[block * r, (block + 1) * r)`` of the embedding or the
    head, ``r = vocab / table_blocks``."""
    rows = dims.vocab // table_blocks(dims.vocab)
    key = jax.random.fold_in(
        jax.random.fold_in(root, 1_000_000 + _TABLE_IDS[which]), block)
    return _leaf(key, (rows, dims.d), "w", 0.02, dtype)


def make_table(root, which: str, dims: Dims, dtype):
    n = table_blocks(dims.vocab)
    return jnp.concatenate(
        [make_table_block(root, which, b, dims, dtype) for b in range(n)],
        axis=0)
