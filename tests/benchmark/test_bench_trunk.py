"""A family's own trunk (``harness/reference.py``: ``trunk(x, apply,
final_norm, dims)``), its six properties one by one.

- A tiny family whose layers run several times over the same weights
  (``data/families/tiny_looped.py``: four norms a block, the final norm
  between the passes, an exit gate's row past the stack, the pick among
  passes) gives, through the hook, the logits of a straight-line NumPy
  float64 forward of the equations, written here and sharing no code
  with the family; three faults of a trunk each fail that comparison.
- An index applied twice is the layer composed with itself on the same
  leaves; ``apply`` returns whatever the family's ``layer`` does;
  ``final_norm`` is ``rms`` with the head's gain; the int8 control
  reaches every application.
- A family that states no trunk runs the loop there always was: its
  logits bit for bit, and the three reference programs' lowered text at
  the tests' sizes and at the cells' own, by sha256.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import (loader, loadgen, reference as R, run,
                               serve_loop as L, weights as W)

DATA = run.REHEARSE_DATA
ROOTS = [DATA, loader.DATA_ROOT]
SEED = 2**31 + 4711
LOOPED = loader.load_family("tiny_looped", ROOTS)
F32 = jnp.float32


def _config(name):
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        return json.load(f)


def _looped_dims(threshold=1.0):
    return LOOPED.dims(dict(_config("tiny-looped"),
                            early_exit_threshold=threshold))


def _sequences(n=3, length=40):
    rng = np.random.default_rng(7)
    seqs = [list(map(int, rng.integers(0, 256, length - 7 * i)))
            for i in range(n)]
    return seqs, [list(range(len(s))) for s in seqs]


# -- the equations, in NumPy float64 ------------------------------------------
# The leaves' names, shapes, kinds and folds are written out again: a
# family that made other leaves would not agree with this forward.

_BLOCK = {"wq": (0, (32, 32), "w", 32 ** -0.5),
          "wk": (1, (32, 32), "w", 32 ** -0.5),
          "wv": (2, (32, 32), "w", 32 ** -0.5),
          "wo": (3, (32, 32), "w", 32 ** -0.5),
          "w_gate": (4, (32, 64), "w", 32 ** -0.5),
          "w_up": (5, (32, 64), "w", 32 ** -0.5),
          "w_down": (6, (64, 32), "w", 64 ** -0.5),
          "n1": (7, (32,), "g", None), "n2": (8, (32,), "g", None),
          "n3": (9, (32,), "g", None), "n4": (10, (32,), "g", None)}
_GATE = {"row": (11, (32, 1), "w", 32 ** -0.5), "bias": (12, (1,), "b", None)}
LAYERS, PASSES, HEADS, HD, EPS, THETA = 2, 3, 4, 8, 1e-6, 10000.0


def _leaves64(root, li, table):
    made = W.make_layer(root, li, {k: v[1:] for k, v in table.items()},
                        {k: v[0] for k, v in table.items()}, F32)
    return {k: np.asarray(v, np.float64) for k, v in made.items()}


def _norm64(x, g):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + EPS) * g


def _rope64(x):
    s = x.shape[0]
    inv = 1.0 / THETA ** (np.arange(0, HD, 2) / HD)
    ang = np.arange(s)[:, None] * inv
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :HD // 2], x[..., HD // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block64(u, w):
    s = u.shape[0]
    y = _norm64(u, w["n1"])
    q, k, v = ((y @ w[n]).reshape(s, HEADS, HD) for n in ("wq", "wk", "wv"))
    q, k = _rope64(q), _rope64(k)
    sc = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(HD)
    sc = np.where(np.tril(np.ones((s, s), bool))[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", p, v).reshape(s, HEADS * HD) @ w["wo"]
    a = u + _norm64(o, w["n2"])
    y = _norm64(a, w["n3"])
    g = y @ w["w_gate"]
    m = (g / (1.0 + np.exp(-g)) * (y @ w["w_up"])) @ w["w_down"]
    return a + _norm64(m, w["n4"])


def numpy_forward(seed, seq, threshold):
    """Logits at every position of ``seq``, each position's exit pass,
    and how near its cumulated probability came to the threshold."""
    root = W.root_key(seed)
    dims = _looped_dims()
    blocks = [_leaves64(root, li, _BLOCK) for li in range(LAYERS)]
    gate = _leaves64(root, LAYERS, _GATE)
    g_f = np.asarray(W.make_final_norm(root, dims, F32), np.float64)
    table = lambda which: np.asarray(W.make_table(root, which, dims, F32),
                                     np.float64)
    h = table("embed")[np.asarray(seq)]
    hs, ps, remaining = [], [], np.ones(len(seq))
    for t in range(PASSES):
        u = h
        for w in blocks:
            u = _block64(u, w)
        h = _norm64(u, g_f)
        g = 1.0 / (1.0 + np.exp(-(h @ gate["row"] + gate["bias"])[:, 0]))
        ps.append(remaining if t == PASSES - 1 else g * remaining)
        remaining = remaining * (1.0 - g)
        hs.append(h)
    cum = np.cumsum(np.stack(ps, 1), axis=1)
    reached = cum >= threshold
    step = np.where(reached.any(1), reached.argmax(1), PASSES - 1)
    h_exit = np.stack(hs, 1)[np.arange(len(seq)), step]
    margin = np.abs(cum[:, :-1] - threshold).min()
    return h_exit @ table("lm_head").T, step, margin


def _worst(got, want):
    """The widest difference of any logit, in the logits' spreads."""
    return max(float(np.abs(g - w).max() / w.std())
               for g, w in zip(got, want))


@pytest.fixture(scope="module")
def want():
    seqs, _ = _sequences()
    return {thr: [numpy_forward(SEED, s, thr) for s in seqs]
            for thr in (1.0, 0.55)}


@pytest.mark.parametrize("threshold", [1.0, 0.55])
def test_a_looped_family_gives_the_logits_of_the_equations(threshold, want):
    seqs, wanted = _sequences()
    got = R.logits_at(SEED, LOOPED, _looped_dims(threshold), F32, seqs,
                      wanted)
    steps = np.concatenate([w[1] for w in want[threshold]])
    if threshold == 1.0:
        assert set(steps) == {PASSES - 1}
    else:
        # Positions leave at every pass, and none so near the threshold
        # that float32 and float64 could part there.
        assert set(steps) == set(range(PASSES))
        assert min(w[2] for w in want[threshold]) > 1e-4
    assert [g.shape for g in got] == [(len(s), 256) for s in seqs]
    assert _worst(got, [w[0] for w in want[threshold]]) < 1e-4


def _passes(x, apply, final_norm, dims, *, order=None, leaves_of=None,
            norm=None):
    """``tiny_looped.trunk`` at threshold 1.0 (every position takes the
    last pass), with a fault where an argument is given."""
    order = order or [range(dims.layers)] * dims.passes
    norm = norm or final_norm
    for t, layers in enumerate(order):
        for li in layers:
            x = apply(x, leaves_of(t, li) if leaves_of else li, "block")
        if t < len(order) - 1:
            x = norm(x)
    return x


FAULTS = {
    "sound": {},
    "a wrong order of applications": {"order": [(0, 0, 0), (1, 1, 1)]},
    "a second set of leaves for a second pass": {
        "leaves_of": lambda t, li: li + 10 * t},
    "a norm between passes with another gain": {
        "norm": lambda x: R.rms(x, 1.0, EPS)},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_trunk_fails_the_comparison(fault, want, monkeypatch):
    monkeypatch.setattr(LOOPED, "trunk", lambda *a: _passes(
        *a, **FAULTS[fault]))
    seqs, wanted = _sequences()
    got = R.logits_at(SEED, LOOPED, _looped_dims(), F32, seqs, wanted)
    worst = _worst(got, [w[0] for w in want[1.0]])
    if fault == "sound":
        assert worst < 1e-4
    else:
        assert worst > 1e-2


# -- the properties of ``apply`` and ``final_norm`` ---------------------------

@pytest.fixture()
def hook(monkeypatch):
    """``logits_at`` through a trunk that hands the test its arguments:
    ``hook(body)`` runs ``body(x, apply, final_norm, dims)`` where a
    family's trunk would run, and returns what ``body`` returned."""
    def call(body, dtype=F32, **kw):
        kept = []

        def trunk(x, apply, final_norm, dims):
            kept.append(body(x, apply, final_norm, dims))
            return x

        monkeypatch.setattr(LOOPED, "trunk", trunk)
        seqs, wanted = _sequences()
        R.logits_at(SEED, LOOPED, _looped_dims(), dtype, seqs, wanted, **kw)
        return kept[0]
    return call


def _layer_on(x, li, kind, dims, dot=R._dot):
    """The family's layer over a batch on the leaves of index ``li``,
    made here."""
    w = W.make_layer(W.root_key(SEED), li, LOOPED.layer_leaves(dims, kind),
                     LOOPED.LEAF_IDS, F32)
    return jnp.stack([LOOPED.layer(seq, w, kind, dims, dot) for seq in x])


def test_an_index_applied_twice_is_the_layer_composed_with_itself(hook):
    x, twice, other = hook(lambda x, apply, norm, dims: (
        x, apply(apply(x, 0, "block"), 0, "block"),
        apply(apply(x, 0, "block"), 1, "block")))
    dims = _looped_dims()
    want = _layer_on(_layer_on(x, 0, "block", dims), 0, "block", dims)
    assert x.shape == (3, 256, 32) and x.dtype == F32
    np.testing.assert_allclose(twice, want, rtol=0, atol=1e-5)
    assert float(jnp.abs(other - want).max()) > 0.1


def test_apply_returns_what_the_layer_returns_past_the_stack_too(hook):
    x, scores = hook(lambda x, apply, norm, dims: (
        x, apply(x, dims.layers, "gate")))
    assert scores.shape == (3, 256, 1)
    np.testing.assert_allclose(
        scores, _layer_on(x, LAYERS, "gate", _looped_dims()), rtol=0,
        atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_final_norm_is_rms_with_the_heads_gain(dtype, hook):
    dims, dt = _looped_dims(), W.DTYPES[dtype]
    x, normed = hook(lambda x, apply, norm, dims: (x, norm(x)), dtype=dt)
    seqs, wanted = _sequences()
    gain = W.make_final_norm(W.root_key(SEED), dims, dt).astype(F32)
    np.testing.assert_allclose(normed, R.rms(x, gain, dims.eps), rtol=0,
                               atol=2e-6)
    assert float(jnp.abs(normed - R.rms(x, 1.0, dims.eps)).max()) > 0.1
    # ... which is the norm the head applies: the logits of a trunk that
    # returns x are the head's table over it.
    table = W.make_table(W.root_key(SEED), "lm_head", dims, dt).astype(F32)
    got = R.logits_at(SEED, LOOPED, dims, dt, seqs[:1], wanted[:1])[0]
    np.testing.assert_allclose(got, R._dot(normed[0, :len(seqs[0])], table.T),
                               rtol=0, atol=1e-5)


def test_the_int8_control_reaches_every_application_of_a_trunk(hook):
    seen = {}
    for int8 in (False, True):
        seen[int8] = hook(lambda x, apply, norm, dims: (
            x, apply(x, 1, "block"), apply(x, dims.layers, "gate")),
            int8=int8)
    dims = _looped_dims()
    x = seen[True][0]
    for got, li, kind in ((seen[True][1], 1, "block"),
                          (seen[True][2], LAYERS, "gate")):
        np.testing.assert_allclose(
            got, _layer_on(x, li, kind, dims, R._dot_int8), rtol=0, atol=1e-5)
    assert float(jnp.abs(seen[True][1] - seen[False][1]).max()) > 1e-3
    # (the gate reads the embedding's rows here, 0.02 in size)
    assert float(jnp.abs(seen[True][2] - seen[False][2]).max()) > 1e-4


def test_the_control_reads_its_gap_through_a_trunk():
    """Requests "served" by the reference itself, greedily: the sound
    gap is nought, and the int8 control's is read at the same positions."""
    dims = _looped_dims(0.55)
    rng = np.random.default_rng(11)
    recs = []
    for rid in range(3):
        r = L.Record(rid, list(map(int, rng.integers(0, 256, 24))), 6,
                     due_at=0.0)
        r.tokens, r.status, r.slot = [], "done", rid
        recs.append(r)
    for _ in range(6):
        rows = R.logits_at(SEED, LOOPED, dims, F32,
                           [r.prompt + r.tokens for r in recs],
                           [[len(r.prompt + r.tokens) - 1] for r in recs])
        for r, row in zip(recs, rows):
            r.tokens.append(int(row[0].argmax()))
    lines = []
    ok, numbers = R.check_served(SEED, LOOPED, dims, F32, recs, 1e-4,
                                 control=True, log=lines.append)
    assert ok and numbers["widest_gap"] < 1e-5
    assert numbers["served_tokens"] == 18
    assert np.isfinite(numbers["control_widest_gap"])
    assert any(ln.startswith("control: int8") for ln in lines)
    # Over every position of the three sequences int8 puts another
    # token first somewhere, and the gap it reads there is int8's size,
    # not float32's.
    seqs, wanted = [r.prompt + r.tokens for r in recs], [list(range(29))] * 3
    sound = R.logits_at(SEED, LOOPED, dims, F32, seqs, wanted)
    low = R.logits_at(SEED, LOOPED, dims, F32, seqs, wanted, int8=True)
    gap = max(R.gaps(s, lo.argmax(axis=1)).max() for s, lo in zip(sound, low))
    assert _worst(low, sound) > 1e-2
    assert gap > 1e-3


# -- without a trunk: the loop as it stood ------------------------------------

def _logits_at_as_it_stood(seed, family, dims, dtype, sequences, wanted, *,
                           int8=False):
    """``reference.logits_at`` of the parent of the hook (PR 46's tree),
    kept as the yardstick."""
    root = W.root_key(seed)
    n = len(sequences)
    s_pad = R._round_up(max(map(len, sequences)), R.PAD)
    r_pad = R._round_up(max(map(len, wanted)), 64)
    ids = np.zeros((n, s_pad), np.int32)
    pos = np.zeros((n, r_pad), np.int32)
    for i, (seq, want) in enumerate(zip(sequences, wanted)):
        ids[i, :len(seq)] = seq
        pos[i, :len(want)] = want
    x = R._embed(jnp.asarray(ids), root, dims=dims, dtype=dtype)
    for li in range(dims.layers):
        x = R._layer_step(x, root, li, family=family,
                          kind=family.layer_kind(dims, li), dims=dims,
                          dtype=dtype, int8=int8)
    rows = jnp.take_along_axis(x, jnp.asarray(pos)[:, :, None], axis=1)
    rows = rows.reshape(n * r_pad, dims.d)
    logits = np.concatenate(
        [np.asarray(R._head_block(rows, root, b, dims=dims, dtype=dtype,
                                  int8=int8))
         for b in range(W.table_blocks(dims.vocab))], axis=1)
    logits = logits.reshape(n, r_pad, dims.vocab)
    return [logits[i, :len(want)] for i, want in enumerate(wanted)]


def _family_of(config):
    return loader.load_family(config["family"], ROOTS)


@pytest.mark.parametrize("int8", [False, True], ids=["sound", "int8"])
@pytest.mark.parametrize("name", ["tiny", "tiny-bf16", "tiny-mla",
                                  "tiny-mamba", "tiny-moe"])
def test_a_family_without_a_trunk_runs_the_loop_as_it_stood(name, int8):
    config = _config(name)
    family = _family_of(config)
    assert not hasattr(family, "trunk")
    dims, dtype = family.dims(config), W.DTYPES[config["dtype"]]
    seqs, wanted = _sequences(2, 24)
    wanted = [w[-5:] for w in wanted]
    got = R.logits_at(SEED, family, dims, dtype, seqs, wanted, int8=int8)
    want = _logits_at_as_it_stood(SEED, family, dims, dtype, seqs, wanted,
                                  int8=int8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# sha256 (first 16 hex digits) of the StableHLO text of the reference's
# programs, lowered on the CPU from PR 46's tree, the parent of the hook:
# ``_embed``, ``_head_block`` and ``_layer_step`` once a kind of layer, at
# the shapes ``check_served`` gives them (the tests' data at one sequence
# of 256 positions; a cell at its ``check_requests`` sequences of its
# mix's longest request). A sum that moves means that a cell's reference
# compiles anew and that the readings behind its limit are another
# program's: a ``benchmark`` PR's business, with the limits read again.
LOWERED = {
    "tiny": {
        "embed": "47f39243eb32f853",
        "head": "04278175bd99fc6f",
        "layer.block": "5b1f0d6604c4157c"},
    "tiny-mla": {
        "embed": "0231559b62c2dab8",
        "head": "448b5e6b3580fcf2",
        "layer.block": "ff0e70c5a4444426"},
    "tiny-mamba": {
        "embed": "0231559b62c2dab8",
        "head": "d7a305b774e2c244",
        "layer.mamba": "36eedce46e0ed7f4",
        "layer.experts": "1aa0411192e66019",
        "layer.attention": "6416e3283ab0b154"},
    "seed-oss-36b-1chip.docs": {
        "embed": "0d9bcf2df2ad91d9",
        "head": "b3d5577faef164c8",
        "layer.block": "30fa3ff4192f3bdb"},
    "mistral-small-4-1chip.longdocs": {
        "embed": "e2d1e9354994669b",
        "head": "9e39ea4137faa1a2",
        "layer.block": "6272877a79c143d8"},
    "nemotron-3-super-1chip.longdocs": {
        "embed": "e2d1e9354994669b",
        "head": "e0e8bc6157f10699",
        "layer.mamba": "5d6d6d1e73ab8e71",
        "layer.experts": "73abe12e965bc9b1",
        "layer.attention": "80358fb2bbe0dab1"},
}


def lowered_sums(name):
    """name: a configuration of the tests' data, or a committed cell."""
    if "." in name:
        cell = loader.load_cell(name)
        config, family, mix = cell.config, cell.family, cell.traffic
        most_out = loadgen.largest(mix["output_tokens"])
        n, rows = int(mix["check_requests"]), R._round_up(most_out, 64)
        s_pad = R._round_up(
            loadgen.largest(mix["prompt_tokens"]) + most_out, R.PAD)
    else:
        config = _config(name)
        family, n, s_pad, rows = _family_of(config), 1, 256, 64
    dims, dtype = family.dims(config), W.DTYPES[config["dtype"]]
    root = W.root_key(SEED)
    text = {
        "embed": R._embed.lower(
            jax.ShapeDtypeStruct((n, s_pad), jnp.int32), root, dims=dims,
            dtype=dtype),
        "head": R._head_block.lower(
            jax.ShapeDtypeStruct((n * rows, dims.d), F32), root, 0,
            dims=dims, dtype=dtype, int8=False)}
    x = jax.ShapeDtypeStruct((n, s_pad, dims.d), F32)
    for kind in dict.fromkeys(family.layer_kind(dims, li)
                              for li in range(dims.layers)):
        text["layer." + kind] = R._layer_step.lower(
            x, root, 0, family=family, kind=kind, dims=dims, dtype=dtype,
            int8=False)
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
            for k, v in text.items()}


@pytest.mark.parametrize("name", sorted(LOWERED))
def test_the_references_programs_keep_their_text(name):
    assert lowered_sums(name) == LOWERED[name]


if __name__ == "__main__":
    # The table above, from whatever tree this file is run in.
    print(json.dumps({name: lowered_sums(name) for name in LOWERED},
                     indent=1))
