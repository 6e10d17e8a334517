"""The plain reference and the comparison that decides ``correct``.

The dense decoder's forward in ``jax.numpy``, float32, matmul precision
``highest``: no cache, no kernels, no batching, nothing imported from the
program. Weights are made again from the seed by ``weights.py``, one
layer at a time (a float32 layer of Seed-OSS is 2.2 GB, the whole model
would not fit), in the served type and then upcast: the model IS the
bf16 leaves.

What is compared, after the window has closed: for a seeded sample of
the requests the window finished (the longest among them), the reference
runs once over ``prompt + served tokens`` and reads, at every served
position, how far the served token's logit lies below the reference's
best logit there. The widest such gap is the number held to the limit;
it is valid because every request decodes greedily. The control computes
the same positions with every linear layer in int8 (weights per output
channel, activations per row), the step below bf16, and reads the gap of
the token that int8 puts first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

PAD = 256          # sequences are padded to a multiple: few programs


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, positions, theta):
    """x: (S, heads, hd); rotate-half form, as the published models."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dot(x, w):
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _dot_int8(x, w):
    """``x @ w`` with both operands rounded to int8: activations by row,
    weights by output column, the product exact."""
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127
    xq, wq = jnp.round(x / sx), jnp.round(w / sw)
    return _dot(xq, wq) * sx * sw


def _layer(x, w, dims: W.Dims, dot):
    """One decoder layer over a whole sequence. x: (S, d) float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    h, kv, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(s)
    y = _rms(x, w["ln_attn"], dims.eps)
    q, k, v = dot(y, w["wq"]), dot(y, w["wk"]), dot(y, w["wv"])
    if dims.attention_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = (q.reshape(s, h, hd), k.reshape(s, kv, hd),
               v.reshape(s, kv, hd))
    if dims.qk_norm:
        q = _rms(q, w["q_norm"], dims.eps)
        k = _rms(k, w["k_norm"], dims.eps)
    q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    q = q.reshape(s, kv, h // kv, hd)
    sc = jnp.einsum("qcgd,kcd->cgqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                   sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("cgqk,kcd->qcgd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, h * hd)
    x = x + dot(o, w["wo"])
    y = _rms(x, w["ln_mlp"], dims.eps)
    return x + dot(jax.nn.silu(dot(y, w["w_gate"])) * dot(y, w["w_up"]),
                   w["w_down"])


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "int8"))
def _layer_step(x, root, li, *, dims, dtype, int8):
    """One layer over a batch of sequences (B, S, d): its weights made
    once, the sequences one after another (a sequence's float32 scores
    alone are over a gigabyte at 80 heads and 2048 positions)."""
    w = W.make_layer(root, li, dims, dtype)
    dot = _dot_int8 if int8 else _dot
    return jax.lax.map(lambda one: _layer(one, w, dims, dot), x)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _embed(ids, root, *, dims, dtype):
    return W.make_table(root, "embed", dims, dtype)[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "int8"))
def _head_block(x, root, block, *, dims, dtype, int8):
    which = "embed" if dims.tie else "lm_head"
    y = _rms(x, W.make_final_norm(root, dims, dtype).astype(jnp.float32),
             dims.eps)
    t = W.make_table_block(root, which, block, dims, dtype)
    return (_dot_int8 if int8 else _dot)(y, t.astype(jnp.float32).T)


def _round_up(n, to):
    return -(-int(n) // to) * to


def logits_at(seed, dims: W.Dims, dtype, sequences, wanted, *, int8=False,
              pad_to=None, rows_to=None):
    """Reference logits. ``sequences[i]`` is a token list; ``wanted[i]``
    the positions whose next-token logits are returned, as one float32
    array (len(wanted[i]), vocab) per sequence. Every sequence is padded
    to ``pad_to`` positions and ``rows_to`` wanted rows (defaults: the
    longest, rounded up), so that a cell that always passes its mix's
    largest sizes runs the same three programs in every run."""
    root = W.root_key(seed)
    n = len(sequences)
    s_pad = _round_up(max(pad_to or 0, max(map(len, sequences))), PAD)
    r_pad = _round_up(max(rows_to or 0, max(map(len, wanted))), 64)
    ids = np.zeros((n, s_pad), np.int32)
    pos = np.zeros((n, r_pad), np.int32)
    for i, (seq, want) in enumerate(zip(sequences, wanted)):
        ids[i, :len(seq)] = seq   # causal: the padding is in the future
        pos[i, :len(want)] = want
    x = _embed(jnp.asarray(ids), root, dims=dims, dtype=dtype)
    for li in range(dims.layers):
        x = _layer_step(x, root, li, dims=dims, dtype=dtype, int8=int8)
    rows = jnp.take_along_axis(x, jnp.asarray(pos)[:, :, None], axis=1)
    rows = rows.reshape(n * r_pad, dims.d)
    logits = np.concatenate(
        [np.asarray(_head_block(rows, root, b, dims=dims, dtype=dtype,
                                int8=int8))
         for b in range(W.table_blocks(dims.vocab))], axis=1)
    logits = logits.reshape(n, r_pad, dims.vocab)
    return [logits[i, :len(want)] for i, want in enumerate(wanted)]


def served_positions(prompt, tokens):
    """The sequence the reference reads and the positions whose logits
    chose each served token: token j was picked after
    ``prompt + tokens[:j]``."""
    seq = list(prompt) + list(tokens[:-1])
    first = len(prompt) - 1
    return seq, list(range(first, first + len(tokens)))


def gaps(rows, tokens):
    """For each position, how far the token's logit lies below the
    row's best. rows: (n, vocab) reference logits."""
    rows = np.asarray(rows, np.float32)
    best = rows.max(axis=1)
    return best - rows[np.arange(len(tokens)), np.asarray(tokens)]


def pick_sample(finished, n, seed):
    """``n`` of the finished requests: the longest (prompt plus served
    tokens) always; then, in an order drawn from the seed, one from each
    decode slot not yet among them, so that a fault confined to a slot
    is met in every run; then more until there are ``n``."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i].prompt)
                  + len(finished[i].tokens))
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    order = [i for i in rng.permutation(len(finished)) if i != longest]
    chosen, slots = [longest], {finished[longest].slot}
    for i in order:
        if len(chosen) < n and finished[i].slot not in slots:
            chosen.append(i)
            slots.add(finished[i].slot)
    chosen += [i for i in order if i not in chosen][:max(n - len(chosen), 0)]
    return [finished[i] for i in chosen[:max(n, 1)]]


def check_served(seed, dims, dtype, sample, limit, *, control=False,
                 log=print, pad_to=None, rows_to=None, batch=None):
    """Compare the sampled requests' served tokens with the reference.
    Returns (correct, numbers): ``numbers`` holds each number compared
    beside its limit, and with ``control`` the int8 control's too."""
    if not sample:
        log("correct: no finished request to compare: not correct")
        return False, {"served_tokens": 0}
    seqs, wanted = map(list, zip(*(served_positions(r.prompt, r.tokens)
                                   for r in sample)))
    # A fixed batch: fewer finished requests than asked for are made up
    # by repeats, which are computed and not compared.
    fill = max((batch or 0) - len(seqs), 0)
    sizes = dict(pad_to=pad_to, rows_to=rows_to)
    ref = logits_at(seed, dims, dtype, seqs + seqs[:1] * fill,
                    wanted + wanted[:1] * fill, **sizes)[:len(seqs)]
    widest, n_tok, n_same, spread = 0.0, 0, 0, []
    for r, rows in zip(sample, ref):
        g = gaps(rows, r.tokens)
        widest = max(widest, float(g.max()))
        n_tok += len(g)
        n_same += int((g == 0).sum())
        spread.append(float(rows.std()))
    numbers = {"served_tokens": n_tok, "requests": len(sample),
               "widest_gap": widest, "limit": limit,
               "share_equal_to_best": n_same / n_tok,
               "logit_std": float(np.mean(spread))}
    finite = math.isfinite(widest)
    correct = finite and widest <= limit
    log(f"correct: widest gap of a served token below the reference's "
        f"best logit {widest:.6g} (limit {limit:.6g}) over {n_tok} served "
        f"tokens of {len(sample)} requests; {n_same} equal the best; "
        f"logit std {numbers['logit_std']:.4g}: "
        f"{'within' if correct else 'OVER'}")
    if control:
        low = logits_at(seed, dims, dtype, seqs + seqs[:1] * fill,
                        wanted + wanted[:1] * fill, int8=True,
                        **sizes)[:len(seqs)]
        cw = max(float(gaps(rows, lo.argmax(axis=1)).max())
                 for rows, lo in zip(ref, low))
        numbers["control_widest_gap"] = cw
        log(f"control: int8 in the reference's place, widest gap {cw:.6g} "
            f"(limit {limit:.6g}): "
            f"{'FAILS, as it must' if cw > limit else 'PASSES: no control'}")
    return correct, numbers
