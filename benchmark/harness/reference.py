"""The plain reference and the comparison that decides ``correct``.

A decoder's forward in ``jax.numpy``, float32, matmul precision
``highest``: no cache, no kernels, no batching, nothing imported from the
program. The layer itself is the configuration's family's
(``families/<family>.py``: ``layer``, ``layer_leaves``, ``layer_kind``);
embedding, final norm, head and the comparison are here, the same for
every family. Weights are made again from the seed by ``weights.py``,
one layer at a time (a float32 layer of Seed-OSS is 2.2 GB, the whole
model would not fit), in the served type and then upcast: the model IS
the bf16 leaves.

Between the embedding and the head the layers run in the order the
family states. A family that states none has every layer ``0 ..
dims.layers - 1`` applied once, in that order. One whose layers run in
another order, more than once over the same weights, or with something
between them defines ``trunk(x, apply, final_norm, dims)``:

1. ``x`` is the embedded batch, (B, S, d) float32. What ``trunk``
   returns goes to the wanted rows' gather and to the head, which
   applies the final norm and the head's table as for every family: a
   model whose last pass ends in that norm returns what stood BEFORE it.
2. ``apply(x, li, kind)`` is this module's ``_layer_step`` with the
   seed's key, the family, ``dims``, the served type and the control's
   ``int8`` bound. The leaves of index ``li`` are made by
   ``weights.make_layer`` inside that one call, never stacked and never
   kept: an index applied twice gives the SAME leaves, and a model of
   any depth costs the reference one layer's weights at a time. ``li``
   need not be below ``dims.layers``: an index past the stack, under a
   ``kind`` of its own in the family's ``layer_leaves`` and ``layer``,
   holds what belongs to no layer (a gate's row).
3. ``apply`` returns what the family's ``layer`` returns for that
   ``kind``, a sequence at a time under ``lax.map``: (B,) + that shape,
   which need not be (S, d) (a gate returns (S, 1) scores).
4. ``final_norm(x)`` is ``rms`` with ``weights.make_final_norm``'s gain
   and ``dims.eps`` over the last axis: the gain the head's norm has, so
   that a family can norm between passes with the model's one final norm.
5. The int8 control reaches every application through ``apply``:
   ``--control`` needs no word from the family.
6. Without ``trunk`` the programs are the ones there always were:
   ``_embed``, ``_layer_step`` and ``_head_block`` keep their text, so
   the compile cache and the readings behind every limit stand.

``trunk`` runs once a call of ``logits_at``, in Python and untraced;
each ``apply`` is one dispatch of a program compiled once a ``kind``.
Keep the calls of ``apply`` out of any ``jit`` of the family's own: a
traced ``apply`` would unroll every application into one program. Of
``dims`` this module reads ``vocab``, ``d``, ``eps`` and ``tie``, and
``layers`` only where the family has no ``trunk``.

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


def rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _dot(x, w):
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _dot_int8(x, w):
    """``x @ w`` with both operands rounded to int8: activations by row,
    weights by output column, the product exact."""
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127
    return _dot(jnp.round(x / sx), jnp.round(w / sw)) * sx * sw


@functools.partial(jax.jit, static_argnames=("family", "kind", "dims",
                                             "dtype", "int8"))
def _layer_step(x, root, li, *, family, kind, dims, dtype, int8):
    """One layer over a batch of sequences (B, S, d): its weights made
    once, the sequences one after another (a sequence's float32 scores
    alone are over a gigabyte at 80 heads and 2048 positions). One
    program a kind of layer: the index is an argument."""
    w = W.make_layer(root, li, family.layer_leaves(dims, kind),
                     family.LEAF_IDS, dtype)
    dot = _dot_int8 if int8 else _dot

    def one(seq):
        # The upcast stays inside the loop's body. On the v5e that is
        # the form whose layers agree with float64 on the host (hidden
        # state within 4e-5 after 8 layers); with the leaves upcast once
        # outside the loop, or passed in as float32, the same products
        # come out with errors of bfloat16's size (0.14) and a sound
        # run's gap reads 0.05-0.09 for 0.02-0.06 (PERF.md, PR 27).
        w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        return family.layer(seq, w32, kind, dims, dot)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _embed(ids, root, *, dims, dtype):
    return W.make_table(root, "embed", dims, dtype)[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "int8"))
def _head_block(x, root, block, *, dims, dtype, int8):
    which = "embed" if dims.tie else "lm_head"
    y = rms(x, W.make_final_norm(root, dims, dtype).astype(jnp.float32),
            dims.eps)
    t = W.make_table_block(root, which, block, dims, dtype)
    return (_dot_int8 if int8 else _dot)(y, t.astype(jnp.float32).T)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _final_norm(x, root, *, dims, dtype):
    """The head's norm alone, for a family's ``trunk``."""
    return rms(x, W.make_final_norm(root, dims, dtype).astype(jnp.float32),
               dims.eps)


def _round_up(n, to):
    return -(-int(n) // to) * to


def logits_at(seed, family, dims, dtype, sequences, wanted, *, int8=False,
              pad_to=None, rows_to=None):
    """Reference logits of ``family``'s model at ``dims`` (of which this
    module reads ``vocab``, ``d``, ``eps``, ``tie``, and ``layers`` only
    where the family states no ``trunk``: the module's docstring has the
    contract). ``sequences[i]`` is a token list; ``wanted[i]``
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

    def apply(x, li, kind):
        return _layer_step(x, root, li, family=family, kind=kind, dims=dims,
                           dtype=dtype, int8=int8)

    trunk = getattr(family, "trunk", None)
    if trunk is None:
        for li in range(dims.layers):
            x = apply(x, li, family.layer_kind(dims, li))
    else:
        x = trunk(x, apply, functools.partial(_final_norm, root=root,
                                              dims=dims, dtype=dtype), dims)
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


def check_served(seed, family, dims, dtype, sample, limit, *, control=False,
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
    ref = logits_at(seed, family, dims, dtype, seqs + seqs[:1] * fill,
                    wanted + wanted[:1] * fill, **sizes)[:len(seqs)]
    widest, n_tok, n_same, spread = 0.0, 0, 0, []
    for r, rows in zip(sample, ref):
        g = gaps(rows, r.tokens)
        # A gap that is not a number must reach the comparison below:
        # ``max`` keeps its first argument beside a NaN.
        widest = float(np.max([widest, g.max()]))
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
        low = logits_at(seed, family, dims, dtype, seqs + seqs[:1] * fill,
                        wanted + wanted[:1] * fill, int8=True,
                        **sizes)[:len(seqs)]
        cw = float(np.max([gaps(rows, lo.argmax(axis=1)).max()
                           for rows, lo in zip(ref, low)]))
        numbers["control_widest_gap"] = cw
        log(f"control: int8 in the reference's place, widest gap {cw:.6g} "
            f"(limit {limit:.6g}): "
            f"{'FAILS, as it must' if cw > limit else 'PASSES: no control'}")
    return correct, numbers
