"""The looped family (``benchmark/families/looped.py``: layers applied
several times over the same weights) and its configuration and cell,
``ouro-2.6b-1chip.fewshot``:

- the committed cell resolves to its files, nothing is cut and the file
  holds every number of the guide's catalog entry;
- the family's counts at the published widths are the numbers written
  out here by hand: FOUR passes and 192 caches;
- the family's ``trunk`` gives the logits of a straight-line NumPy
  float64 forward of the equations, written here and sharing no code
  with it, at a threshold at which every position takes the last pass
  and at one at which positions leave at every pass;
- a rehearsal of the family at a tiny size (``data/configs/
  tiny-ouro.json``: 2 layers x 3 passes) is ``correct``, traced too,
  with a token altered where the server picks it is not, and the int8
  control reads over the limit.
"""

import copy
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import loader, reference as R, run, weights as W

DATA = run.REHEARSE_DATA
ROOT = loader.REPO_ROOT
ROOTS = [DATA, loader.DATA_ROOT]
CELL = "ouro-2.6b-1chip.fewshot"
TINY = "tiny-ouro.docs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = tuple(n + ".ouro" for n in (
    "prefill_chunk_ms", "chunk_roofline_share", "decode_step_ms",
    "decode_hbm_share", "chunk_attn_ms", "chunk_mlp_ms",
    "decode_rows_attn_ms", "head_ms", "pass_overhead_ms", "idle_in_tick_ms",
    "decode_batch", "decode_fused_share", "exit_pass_mean"))
SEED = 2**31 + 4801
F32 = jnp.float32


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def _tiny_config(threshold=1.0):
    with open(os.path.join(DATA, "configs", "tiny-ouro.json")) as f:
        return dict(json.load(f), early_exit_threshold=threshold)


# -- the committed cell -------------------------------------------------------

def test_the_cell_resolves_to_its_files(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ouro-2.6b-1chip", "fewshot", 1)
    assert cell.family.__file__ == os.path.join(
        loader.DATA_ROOT, "families", "looped.py")
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m, _ in cell.per_layer]
    assert names[0] == "compile_s" and set(names[1:]) == set(METRICS)
    # No reducer of its own: the ones the accepted cells read with.
    assert {spec["reducer"] for _, spec in cell.per_layer} == {
        "compile_seconds", "program_ms", "roofline_max", "roofline_share",
        "scope_ms", "idle_by_span", "span_stat"}
    for m, _ in cell.per_layer[1:]:
        assert (m["moves"], m["workloads"]) == ("tokens_per_s", [CELL])
    specs = {m["name"]: s["params"] for m, s in cell.per_layer[1:]}
    assert specs["pass_overhead_ms.ouro"]["scope"] == "pass_norm"
    assert specs["exit_pass_mean.ouro"] == {
        "span": "decode", "stat": "exit_pass", "reduce": "mean"}
    assert specs["chunk_roofline_share.ouro"]["rows"] == 512
    mix = cell.traffic
    assert (mix["loop"], mix["clients"], mix["check_requests"]) == (
        "closed", 12, 6)
    assert mix["prompt_tokens"] == {"law": "uniform", "min": 320,
                                    "max": 704}
    # ISSUE 48's rule (a) took the named 32 to its last step.
    assert mix["output_tokens"] == {"law": "constant", "value": 16}
    assert "APPLIED" in mix["why"] and "NOT NEEDED" in mix["why"]
    srv, eng = cell.config["serving"], cell.config["engine"]
    assert (srv["num_slots"], srv["page"], srv["prefill_buckets"],
            srv["attn_impl"]) == (6, 128, [128, 512], "flash")
    assert mix["clients"] == 2 * srv["num_slots"]
    assert eng == {"mode": "xla", "max_len": 768}
    # The longest request and the warm-up's prompts fit a slot's row.
    assert 704 + 16 <= eng["max_len"] >= sum(srv["prefill_buckets"]) + 1
    assert cell.config["tp"] == 1 and cell.config["dtype"] == "bfloat16"
    bench = loader.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "12 clients" in entry["why"]
    conf = next(c for c in bench["configs"]
                if c["name"] == cell.config_name)
    assert conf["reduced"] == [] and conf["source"] == cell.config["source"]
    assert len(conf["why"]) <= 200


def test_nothing_is_cut_and_the_file_holds_the_catalogs_entry(cell):
    c = cell.config
    assert c["reduced"] == {}
    assert (c["hidden_size"], c["intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_hidden_layers"], c["vocab_size"], c["total_ut_steps"],
            c["early_exit_threshold"], c["rope_theta"],
            c["rms_norm_eps"]) == (2048, 5632, 128, 16, 16, 48, 49152, 4,
                                   1, 1000000, 1e-6)
    assert {"four_norm_block", "norm_between_passes", "exit_gate",
            "cache_a_pass", "attention", "seeded_laws"} <= set(c["assumed"])
    limit = c["correct"]["widest_gap_limit"]
    assert 0 < limit < 1 and "control" in c["correct"]["readings"]
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert c["source"] == row["source_url"]
    assert {k for k, v in row["config"].items()
            if c.get(k, "absent") != v} == set()


def test_the_counts_at_the_published_widths(cell):
    import math

    F, d = cell.family, cell.family.dims(cell.config)
    assert (d.layers, d.passes, d.exit_threshold) == (48, 4, 1.0)
    size = lambda kind: sum(math.prod(shape) for shape, _, _ in
                            F.layer_leaves(d, kind).values())
    attn, mlp = 4 * 2048 * 2048, 3 * 2048 * 5632
    assert (attn, mlp) == (16_777_216, 34_603_008)
    assert F.layer_params(d) == attn + mlp == 51_380_224
    assert size("block") == attn + mlp + 4 * 2048
    assert size("gate") == 2049
    assert set(F.LEAF_IDS) == set(F.layer_leaves(d, "block")) | set(
        F.layer_leaves(d, "gate"))
    head = 49152 * 2048
    whole = 48 * size("block") + 2 * head + 2048 + size("gate")
    assert round(whole / 1e6) == 2668               # 5.34 GB in bf16
    # A cache a pass: 192 x 2 x 16 x 128 bf16 values a token, 1.5 MiB.
    assert F.kv_bytes_per_token(d) == 192 * 2 * 16 * 128 * 2 == 3 * 2**19
    layers = 48 * (attn + mlp)
    # A decode step over 3,000 cached positions reads the layers four
    # times, the head once, every pass's caches once.
    assert F.decode_step_bytes(d, 3000, 6) == (
        (4 * layers + head) * 2 + 3000 * 3 * 2**19)
    assert 4 * layers * 2 / 819e9 * 1e3 == pytest.approx(24.1, abs=0.05)
    # A 512-row chunk whose rows see 400 keys in the mean.
    flops = (2 * 512 * 4 * layers + 4 * 512 * 400 * 16 * 128 * 192
             + 2 * head)
    assert F.prefill_chunk_flops(d, 512, 400) == flops
    assert flops / 197e12 * 1e3 == pytest.approx(52.9, abs=0.1)
    nbytes = (4 * layers + head) * 2 + (400 - 256.5 + 512) * 3 * 2**19
    assert F.prefill_chunk_bytes(d, 512, 400) == nbytes
    # The ridge: a 128-row chunk is bound by the weights' bytes, a
    # 512-row one by its products.
    by = lambda rows: (F.prefill_chunk_bytes(d, rows, rows / 2) / 819e9,
                       F.prefill_chunk_flops(d, rows, rows / 2) / 197e12)
    assert by(128)[0] > by(128)[1] and by(512)[0] < by(512)[1]


# -- the equations, in NumPy float64 ------------------------------------------
# The leaves' names, shapes, kinds and folds are written out again: a
# family that made other leaves would not agree with this forward.

D, FF, HEADS, KV, HD, LAYERS, PASSES = 64, 128, 2, 2, 32, 2, 3
EPS, THETA, POST = 1e-6, 10000.0, 0.125     # POST: the seeded law of N2, N4
_BLOCK = {"wq": (0, (D, HEADS * HD), "w", D ** -0.5),
          "wk": (1, (D, KV * HD), "w", D ** -0.5),
          "wv": (2, (D, KV * HD), "w", D ** -0.5),
          "wo": (3, (HEADS * HD, D), "w", (HEADS * HD) ** -0.5),
          "w_gate": (4, (D, FF), "w", D ** -0.5),
          "w_up": (5, (D, FF), "w", D ** -0.5),
          "w_down": (6, (FF, D), "w", FF ** -0.5),
          "n1": (7, (D,), "g", None), "n2": (8, (D,), "g", None),
          "n3": (9, (D,), "g", None), "n4": (10, (D,), "g", None)}
_GATE = {"row": (11, (D, 1), "w", D ** -0.5), "bias": (12, (1,), "b", None)}


def _leaves64(root, li, table):
    made = W.make_layer(root, li, {k: v[1:] for k, v in table.items()},
                        {k: v[0] for k, v in table.items()}, F32)
    return {k: np.asarray(v, np.float64) for k, v in made.items()}


def _norm64(x, g):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + EPS) * g


def _rope64(x):
    inv = 1.0 / THETA ** (np.arange(0, HD, 2) / HD)
    ang = np.arange(x.shape[0])[:, None] * inv
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :HD // 2], x[..., HD // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block64(u, w):
    s = u.shape[0]
    y = _norm64(u, w["n1"])
    q = _rope64((y @ w["wq"]).reshape(s, HEADS, HD))
    k = _rope64((y @ w["wk"]).reshape(s, KV, HD))
    v = (y @ w["wv"]).reshape(s, KV, HD)
    o = np.zeros((s, HEADS, HD))
    for h in range(HEADS):
        c = h // (HEADS // KV)
        sc = q[:, h] @ k[:, c].T / np.sqrt(HD)
        sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        o[:, h] = p / p.sum(-1, keepdims=True) @ v[:, c]
    a = u + _norm64(o.reshape(s, HEADS * HD) @ w["wo"], w["n2"] * POST)
    y = _norm64(a, w["n3"])
    g = y @ w["w_gate"]
    m = (g / (1.0 + np.exp(-g)) * (y @ w["w_up"])) @ w["w_down"]
    return a + _norm64(m, w["n4"] * POST)


def numpy_forward(seed, seq, threshold, dims):
    """Logits at every position of ``seq``, each position's exit pass,
    and how near its cumulated probability came to the threshold."""
    root = W.root_key(seed)
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


@pytest.mark.parametrize("threshold", [1.0, 0.6])
def test_the_trunk_gives_the_logits_of_the_equations(threshold):
    """Tolerance 1e-4 of the logits' spread: float32 at ``highest``
    against float64 reads 1e-6 to 1e-5 here; a product in bfloat16
    reads 1e-2."""
    F = loader.load_family("looped", ROOTS)
    config = _tiny_config(threshold)
    dims = F.dims(config)
    assert (dims.d, dims.ff, dims.heads, dims.kv_heads, dims.head_dim,
            dims.layers, dims.passes, dims.eps, dims.rope_theta) == (
                D, FF, HEADS, KV, HD, LAYERS, PASSES, EPS, THETA)
    rng = np.random.default_rng(7)
    seqs = [list(map(int, rng.integers(0, 256, n))) for n in (40, 33)]
    got = R.logits_at(SEED, F, dims, F32, seqs,
                      [list(range(len(s))) for s in seqs])
    want = [numpy_forward(SEED, s, threshold, dims) for s in seqs]
    steps = np.concatenate([w[1] for w in want])
    if threshold == 1.0:
        assert set(steps) == {PASSES - 1}
    else:
        assert set(steps) == set(range(PASSES))
        assert min(w[2] for w in want) > 1e-4
    assert max(float(np.abs(g - w[0]).max() / w[0].std())
               for g, w in zip(got, want)) < 1e-4


# -- the family rehearsed at a tiny size --------------------------------------

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tests' benchmark with the tiny configuration of this family,
    one cell and this PR's per-layer entries APPENDED (the metric files
    are the committed ones)."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    before = copy.deepcopy(bench)
    bench["configs"].append({
        "name": "tiny-ouro", "source": "tests only",
        "file": "tests/benchmark/data/configs/tiny-ouro.json",
        "reduced": [], "why": "tests only"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny-ouro", "traffic": "tiny-docs",
        "chips": 1, "why": "tests only"})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append(TINY)
    for m in real["per_layer"]:
        if m["name"] in METRICS:
            bench["per_layer"].append(dict(m, workloads=[TINY]))
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path_factory.mktemp("looped") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def _run(bench, seed, capsys, trace=0):
    assert run.main(["--rehearse", "--workload", TINY, "--seed", str(seed),
                     "--seconds", "1.0", "--trace", str(trace),
                     "--benchmark-file", bench]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_tiny_family_builds_the_programs_config(tiny):
    cell = loader.load_cell(TINY, tiny, ROOTS)
    build = loader.sibling(cell.family.__file__, "looped_system")
    assert build.F is cell.family
    cfg = build.model_config(cell.config)
    assert (cfg.num_passes, cfg.exit_threshold, cfg.post_norm,
            cfg.qk_norm, cfg.num_paged_layers, cfg.model_name) == (
                3, 1.0, True, False, 6, "tiny-ouro")
    assert build.engine_kwargs(cell.config)["model"].__name__.endswith(
        "looped")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_family_is_served_and_is_correct(tiny, capsys, trace,
                                                  tmp_path, monkeypatch):
    if trace:
        # The capture in a directory of this test's own: two traced
        # rehearsals in two workers would empty each other's.
        monkeypatch.setattr(run, "REPO_ROOT", str(tmp_path))
    res, lines = _run(tiny, 2**31 + 81 + trace, capsys, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4
    text = "\n".join(lines)
    assert "'mode': 'xla', 'mode_kept': True" in text
    assert "compiled inside the window: 0" in text
    if trace:
        assert set(res["metrics"]) == {"compile_s", *METRICS}
    else:
        assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_the_tiny_family_with_a_token_altered_is_not_correct(
        tiny, monkeypatch, capsys):
    from triton_dist_tpu.serving.server import ServingEngine

    sound = ServingEngine._pick
    calls = [0]

    def off_by_one(self, logits_row, req, step):
        calls[0] += 1
        tok = sound(self, logits_row, req, step)
        return (tok + 1) % len(logits_row) if step == 2 else tok

    monkeypatch.setattr(ServingEngine, "_pick", off_by_one)
    res, lines = _run(tiny, 2**31 + 83, capsys)
    assert calls[0] > 20
    assert res["correct"] is False and res["failed"] == 0
    assert any("OVER" in ln for ln in lines)


def test_the_int8_control_reads_over_the_limit(tiny):
    """Every linear layer of the reference in int8, the gate's row among
    them, reads over the tiny configuration's limit: through
    ``reference.check_served`` on a made-up sample (the control's number
    depends on the reference's two readings alone, not on what was
    served)."""
    cell = loader.load_cell(TINY, tiny, ROOTS)
    dims = cell.family.dims(cell.config)
    rng = np.random.default_rng(85)
    sample = [types.SimpleNamespace(
        prompt=rng.integers(0, dims.vocab, size=24).tolist(),
        tokens=rng.integers(0, dims.vocab, size=40).tolist())
        for _ in range(3)]
    limit = cell.config["correct"]["widest_gap_limit"]
    lines = []
    _, check = R.check_served(SEED, cell.family, dims, F32, sample, limit,
                              control=True, log=lines.append)
    assert check["served_tokens"] == 120
    assert check["control_widest_gap"] > 10 * limit
    assert any("FAILS, as it must" in ln for ln in lines)
