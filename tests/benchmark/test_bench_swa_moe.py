"""The window-and-global expert family
(``benchmark/families/swa_moe.py``) and its configuration and cell,
``k-exaone-236b-1chip.longdocs``:

- the committed cell resolves to its files, its cut is the guide's
  (a chip's share, stated), and every width is the published one;
- the family's counts at the published widths are the numbers written
  out here by hand, the window kernel's among them;
- the reference's mask is the inequality, checked against a forward
  written out in NumPy at a tiny size;
- PR 45's rule: no metric of the cell reads the decode-only program;
- a rehearsal of the family at a tiny size (``data/configs/
  tiny-swa.json``) is ``correct``, traced too (in a directory of its
  own), with a token altered where the server picks it is not, and the
  int8 control reads over the limit.
"""

import copy
import json
import math
import os
import re

import pytest

from benchmark.harness import loader, run

DATA = run.REHEARSE_DATA
ROOT = loader.REPO_ROOT
CELL = "k-exaone-236b-1chip.longdocs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = tuple(n + ".exaone" for n in (
    "prefill_chunk_ms", "chunk_roofline_share", "chunk_attn_ms",
    "chunk_attn_window_ms", "attn_window_roofline_share",
    "decode_rows_attn_ms", "chunk_experts_ms", "experts_roofline_share",
    "chunk_shared_expert_ms", "chunk_mlp_ms", "head_ms",
    "expert_load_imbalance", "window_pages_a_slot", "idle_in_tick_ms",
    "decode_batch", "decode_fused_share"))
REDUCED = ("num_experts", "num_hidden_layers", "layer_types",
           "mlp_layer_types", "sliding_windows", "vocab_size")


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def test_the_cell_resolves_to_its_files(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "k-exaone-236b-1chip", "longdocs", 1)
    assert cell.family.__file__ == os.path.join(
        loader.DATA_ROOT, "families", "swa_moe.py")
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m, _ in cell.per_layer]
    assert names[0] == "compile_s" and names[1:] == list(METRICS)
    assert {spec["reducer"] for _, spec in cell.per_layer} == {
        "compile_seconds", "program_ms", "roofline_max", "scope_ms",
        "scope_roofline_max", "idle_by_span", "span_stat"}
    for m, _ in cell.per_layer[1:]:
        assert (m["moves"], m["workloads"]) == ("tokens_per_s", [CELL])
    # The accepted mix as it is, the other expert configurations' too:
    # the three differ in model code alone.
    for other in ("mistral-small-4-1chip.longdocs",
                  "nemotron-3-super-1chip.longdocs"):
        assert cell.traffic == loader.load_cell(other).traffic
    srv, eng = cell.config["serving"], cell.config["engine"]
    assert (srv["page"], srv["prefill_buckets"], srv["attn_impl"]) == (
        128, [512, 2048], "flash")
    assert srv["num_slots"] in (8, 6, 16)       # ISSUE 52's fallback rule
    assert eng == {"mode": "xla", "max_len": 16512}
    assert cell.config["tp"] == 1 and cell.config["dtype"] == "bfloat16"
    bench = loader.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and bench["workloads"][-1] == entry
    assert bench["configs"][-1]["name"] == cell.config_name
    assert next(m for m in bench["end_to_end"] if m["name"]
                == "tokens_per_s")["workloads"][-1] == CELL


def test_every_width_is_as_published_and_the_cut_is_a_stated_share(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["sliding_window"], c["routed_scaling_factor"],
            c["router_outputs"], c["n_group"], c["topk_group"]) == (
                6144, 64, 8, 128, 18432, 2048, 8, 128, 2.5, 128, 1, 1)
    cuts = {k: (v["published"], v["here"]) for k, v in c["reduced"].items()}
    assert set(cuts) == set(REDUCED)
    assert (cuts["num_experts"], cuts["vocab_size"]) == ((128, 16),
                                                         (153600, 19200))
    # Whole periods from layer 0 on, the published 3 : 1, the dense
    # layer the first; the fallback leaves one period after it.
    depth = c["num_hidden_layers"]
    assert cuts["num_hidden_layers"] == (48, depth) and depth in (8, 5)
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        published, here = cuts[key]
        assert len(published) == 48 and here == published[:depth] == c[key]
    assert c["layer_types"].count("full_attention") == depth // 4
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * (depth - 1)
    dep = c["deployment"]
    assert dep["layer_divided_over_chips"] == 8
    assert dep["experts_held"] == "0-15" and c["first_held_expert"] == 0
    # The guide's floors: a period, 4 layers after the dense one, 8
    # experts, an eighth of the words.
    assert depth - 1 >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= 153600
    assumed = c["assumed"]
    assert {"residual", "attention", "router", "shared_expert", "mtp",
            "window_cache", "weights"} <= set(assumed)
    for key, n in (("residual", 1), ("attention", 2), ("router", 3),
                   ("shared_expert", 4), ("mtp", 5)):
        assert assumed[key].startswith(f"({n})")
    assert "LEFT OUT" in assumed["mtp"]
    assert "window layers ONLY" in cell.family.__doc__
    assert "DEPARTURES" in cell.family.__doc__
    entry = next(e for e in loader.load_benchmark(
        os.path.join(ROOT, "BENCHMARK.json"))["configs"]
        if e["name"] == cell.config_name)
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert not any(loader.is_width(k) for k in entry["reduced"])
    loader.check_reduced(c, entry["reduced"])
    limit = c["correct"]["widest_gap_limit"]
    assert 0 < limit < 1 and "control" in c["correct"]["readings"]


def test_the_file_holds_every_number_of_the_catalogs_entry(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    assert differs == set(cell.config["reduced"])


def test_the_counts_at_the_published_widths(cell):
    F, d = cell.family, cell.family.dims(cell.config)
    if d.layers != 8:
        pytest.skip("the counts below are the eight-layer cut's")
    assert (d.count("window"), d.count("global"), d.count("dense"),
            d.count("sparse")) == (6, 2, 1, 7)
    size = lambda kind: sum(math.prod(shape) for shape, _, _ in
                            F.layer_leaves(d, kind).values())
    attn = 6144 * (8192 + 1024 + 1024) + 8192 * 6144      # 113.25 M
    expert = 3 * 6144 * 2048                               # 37.75 M
    norms = 2 * 6144 + 2 * 128
    assert attn == F._attn_params(d) == 113_246_208
    assert expert == F._expert_params(d) == 37_748_736
    # ISSUE 52: layer 0 453.0 M, a sparse layer 755.8 M here.
    assert size("window_dense") == attn + 3 * 6144 * 18432 + norms
    assert size("global_sparse") == size("window_sparse") == (
        attn + 6144 * 128 + 128 + 17 * expert + norms)
    assert round(size("window_dense") / 1e6, 1) == 453.0
    assert round(size("window_sparse") / 1e6, 1) == 755.8
    assert set(F.LEAF_IDS) == set(F.layer_leaves(d, "window_dense")) | set(
        F.layer_leaves(d, "global_sparse"))
    always = 8 * attn + 3 * 6144 * 18432 + 7 * (6144 * 128 + expert)
    assert always == F._always_params(d) == 1_515_454_464
    head = 19200 * 6144
    weights = (always + 7 * 16 * expert + 2 * head) * 2
    assert round(weights / 1e9, 2) == 11.96                # GB, ISSUE 52
    assert F.kv_bytes_per_token(d) == 2 * 8 * 128 * 2 == 4096
    # A slot's two pools and what one table would take (ISSUE 52).
    ring = -(-(128 + 2048) // 128) + 1
    a_slot = 2 * 16512 * 4096 + 6 * ring * 128 * 4096
    assert ring == 18 and round(a_slot / 1e6, 1) == 191.9
    assert round(8 * 16512 * 4096 / 1e6, 1) == 541.1
    # A token's work: the GEMMs with the even share of one held pair.
    even = 2048 * 8 * 16 / 128
    assert even == 2048
    gemm = 2 * always + 2 * 7 * expert
    assert round(gemm / 1e9, 2) == 3.56
    flops = (2048 * gemm + 4.0 * 2048 * (2 * 8192 + 6 * 128) * 64 * 128
             + 2 * head)
    assert F.prefill_chunk_flops(d, 2048, 8192) == pytest.approx(flops)
    assert F.prefill_chunk_flops(d, 2048, 8192,
                                 held_pairs=7 * even / 8) == (
        pytest.approx(flops))
    # 3.56 in the GEMMs, 0.54 in the two global layers' scores at 8k
    # keys a row, 0.03 in the six window layers'.
    assert flops / 2048 / 1e9 == pytest.approx(4.12, abs=0.01)
    # A chunk 64 positions deep: a window layer's rows see what is there.
    assert F.prefill_chunk_flops(d, 128, 64.5) == pytest.approx(
        128 * (2 * always + 2 * 7 * (128 * 8 * 16 / 128) / 128 * expert)
        + 4.0 * 128 * 8 * 64.5 * 64 * 128 + 2 * head)
    nbytes = ((always + 7 * 16 * expert + head) * 2 + 4096 * (
        2 * (8192 - 1024.5 + 2048) + 6 * (127 + 2048)))
    assert F.prefill_chunk_bytes(d, 2048, 8192) == pytest.approx(nbytes)
    # The two blocks: a window layer's chunk reads its queries, 2,175
    # positions of keys and values, and writes its rows; the held
    # experts' 1.21 GB a layer beside the pairs' rows.
    assert F.attn_window_chunk_flops(d, 2048) == (
        6 * 4 * 2048 * 128 * 64 * 128)
    assert F.attn_window_chunk_bytes(d, 2048) == 6 * (
        2 * 2048 * 8192 * 2 + 2175 * 4096)
    assert (F.attn_window_chunk_bytes(d, 2048) / 819e9 * 1e3
            == pytest.approx(0.557, abs=0.001))
    assert F.experts_chunk_bytes(d, 2048) == 2 * (
        7 * 16 * expert + 2 * 7 * even * 6144)
    assert F.experts_chunk_flops(d, 2048, held_pairs=1000) == (
        2 * 8_000 * expert)
    # A decode step of 8 rows over 64,000 cached positions: the global
    # layers read them all, a window layer 128 a row.
    reached = 16 * (1 - (1 - 8 / 128) ** 8)
    assert F.decode_step_bytes(d, 64_000, 8) == pytest.approx(
        (always + 7 * reached * expert + head) * 2
        + 4096 * (2 * 64_000 + 6 * 8 * 128))
    assert F.decode_step_bytes(d, 0, 0) == (always + head) * 2
    assert F.expert_capacity(d, 12544) == 4 * 784


def test_the_references_mask_is_the_inequality(cell):
    """One window layer and one global layer of the reference against a
    forward written out in NumPy, float64, a row at a time: row ``i``
    reads ``max(0, i - w + 1) .. i`` on the window layer (rotated) and
    ``0 .. i`` on the global one (not)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import reference, weights as W

    F = cell.family
    with open(os.path.join(DATA, "configs", "tiny-swa.json")) as f:
        d = F.dims(json.load(f))
    x = np.random.default_rng(0).normal(size=(24, d.d))
    for kind in ("window_sparse", "global_sparse"):
        w = {k: np.asarray(v, np.float64) for k, v in W.make_layer(
            W.root_key(7), 2, F.layer_leaves(d, kind), F.LEAF_IDS,
            jnp.float32).items()}
        got = np.asarray(F.attention(
            jnp.asarray(x, jnp.float32),
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, d,
            reference._dot, window=kind.startswith("window")))
        rms = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True)
                                       + d.eps) * g
        y = rms(x, w["ln_attn"])
        q = rms((y @ w["wq"]).reshape(24, d.heads, d.head_dim), w["q_norm"])
        k = rms((y @ w["wk"]).reshape(24, d.kv_heads, d.head_dim),
                w["k_norm"])
        v = (y @ w["wv"]).reshape(24, d.kv_heads, d.head_dim)
        if kind.startswith("window"):
            half = d.head_dim // 2
            inv = d.rope_theta ** (-np.arange(half) / half)
            ang = np.arange(24)[:, None, None] * inv
            rot = lambda t: np.concatenate(
                [t[..., :half] * np.cos(ang) - t[..., half:] * np.sin(ang),
                 t[..., half:] * np.cos(ang) + t[..., :half] * np.sin(ang)],
                -1)
            q, k = rot(q), rot(k)
        out = np.zeros((24, d.heads, d.head_dim))
        for i in range(24):
            lo = max(0, i - d.window + 1) if kind.startswith("window") else 0
            for h in range(d.heads):
                g = h // (d.heads // d.kv_heads)
                s = k[lo:i + 1, g] @ q[i, h] / math.sqrt(d.head_dim)
                p = np.exp(s - s.max())
                out[i, h] = (p / p.sum()) @ v[lo:i + 1, g]
        want = x + out.reshape(24, -1) @ w["wo"]
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_no_metric_reads_the_decode_only_program(cell):
    """PR 45's rule, which both other ``longdocs`` cells are held to:
    the steady loop under this mix never runs ``jit__decode``, so every
    program-named metric reads the slowest chunk program."""
    patterns = {m["name"]: spec.get("params", {}).get("pattern")
                for m, spec in cell.per_layer}
    assert not {n for n, p in patterns.items()
                if p and re.search(p, "jit__decode(3)")}
    chunk = {n for n, p in patterns.items()
             if p and re.search(p, "jit__chunk(7)")}
    assert chunk == {n + ".exaone" for n in (
        "prefill_chunk_ms", "chunk_roofline_share", "chunk_attn_ms",
        "chunk_attn_window_ms", "attn_window_roofline_share",
        "decode_rows_attn_ms", "chunk_experts_ms",
        "experts_roofline_share", "chunk_shared_expert_ms", "chunk_mlp_ms",
        "head_ms")}
    specs = {m["name"]: spec["params"] for m, spec in cell.per_layer[1:]}
    assert all(specs[n]["variant"] == "slowest" for n in chunk)
    assert specs["attn_window_roofline_share.exaone"] == {
        "pattern": "^jit__chunk", "variant": "slowest",
        "scope": "attn_chunk_window", "rows": 2048,
        "bytes": "attn_window_chunk_bytes",
        "flops": "attn_window_chunk_flops"}
    assert specs["window_pages_a_slot.exaone"] == {
        "span": "prefill_chunk", "stat": "window_pages", "reduce": "mean"}
    mix, srv = cell.traffic, cell.config["serving"]
    assert mix["clients"] >= 2 * srv["num_slots"]


def test_the_ring_reaches_a_capture_and_the_reducer_reads_it(tmp_path):
    """The tiny model served under the profiler: every
    ``tdt.prefill_chunk`` says in the capture how many pages its slot
    holds in a window layer (the ring: 7 for a window of 8, pages of 4
    and buckets up to 16), and the committed file reads their mean from
    the rows ``trace_reduce`` keeps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import triton_dist_tpu as tdt
    from benchmark.harness import trace_reduce as T
    from benchmark.harness.reducers import RunContext, read_metric
    from triton_dist_tpu.models import Engine

    with open(os.path.join(DATA, "configs", "tiny-swa.json")) as f:
        config = json.load(f)
    F = loader.load_family("swa_moe", [loader.DATA_ROOT])
    build = loader.sibling(F.__file__, "swa_moe_system")
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    eng = Engine(build.model_config(config), mesh, mode="xla",
                 dtype=jnp.float32, max_len=96,
                 params=build.make_params(config, mesh, 7),
                 **build.engine_kwargs(config))
    srv = eng.serving(num_slots=2, page=4, prefill_buckets=(4, 16),
                      telemetry="spans")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (37, 9, 50)]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        srv.generate(prompts, max_new_tokens=2)
    finally:
        jax.profiler.stop_trace()
    rows = T.read_xplane(T.find_xplane(str(tmp_path)))
    chunks = [r["stats"] for r in rows if r["name"] == "tdt.prefill_chunk"]
    assert len(chunks) >= 6 and {s["window_pages"] for s in chunks} == {7}
    with open(loader.find_data("layer_metrics", "window_pages_a_slot.exaone",
                               [loader.DATA_ROOT])) as f:
        spec = json.load(f)
    assert read_metric(spec, RunContext(
        cell=None, family=None, dims=None, peaks=None, window=None,
        traced=(0.0, 1.0), rows=rows, compile_s=0.0,
        log=lambda m: None)) == 7.0


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
        "name": "tiny-swa", "source": "tests only",
        "file": "tests/benchmark/data/configs/tiny-swa.json",
        "reduced": ["num_experts"], "why": "tests only"})
    bench["workloads"].append({
        "name": "tiny-swa.docs", "config": "tiny-swa",
        "traffic": "tiny-docs", "chips": 1, "why": "tests only"})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append("tiny-swa.docs")
    for m in real["per_layer"]:
        if m["name"] in METRICS:
            bench["per_layer"].append(dict(m, workloads=["tiny-swa.docs"]))
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path_factory.mktemp("swa") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def _run(bench, seed, capsys, trace=0, more=()):
    assert run.main(["--rehearse", "--workload", "tiny-swa.docs",
                     "--seed", str(seed), "--seconds", "1.0", "--trace",
                     str(trace), "--benchmark-file", bench, *more]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_tiny_family_is_found_and_builds_the_programs_config(tiny):
    cell = loader.load_cell("tiny-swa.docs", tiny, [DATA, loader.DATA_ROOT])
    dims = cell.family.dims(cell.config)
    assert (dims.router_experts, dims.held, dims.first_held, dims.topk,
            dims.window) == (8, 2, 0, 2, 8)
    assert {"vocab", "d", "layers", "eps", "tie"} <= set(vars(dims))
    assert hash(dims) == hash(cell.family.dims(cell.config))
    kinds = [cell.family.layer_kind(dims, i) for i in range(dims.layers)]
    assert kinds == ["window_dense", "window_sparse", "window_sparse",
                     "global_sparse"] + ["window_sparse"] * 3 + [
                         "global_sparse"]
    leaves = cell.family.layer_leaves(dims, "window_sparse")
    assert leaves["experts_up"][0] == (2, 64, 32)
    assert leaves["router"][0] == (64, 8)
    assert leaves["experts_down"][2] == pytest.approx(
        cell.family.ROUTED_GAIN * 32 ** -0.5)
    build = loader.sibling(cell.family.__file__, "swa_moe_system")
    assert build.F is cell.family
    cfg = build.model_config(cell.config)
    assert cfg.attn_pattern == "LLLGLLLG" and cfg.num_experts == 8
    assert (cfg.first_held_expert, cfg.held_experts,
            cfg.first_dense_layers, cfg.sliding_window) == (0, 2, 1, 8)
    assert build.engine_kwargs(cell.config)["model"].__name__.endswith(
        "window_moe")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_family_is_served_and_is_correct(tiny, capsys, trace,
                                                  tmp_path, monkeypatch):
    if trace:
        # The capture in a directory of this test's own: two traced
        # rehearsals in two workers would empty each other's.
        monkeypatch.setattr(run, "REPO_ROOT", str(tmp_path))
    res, lines = _run(tiny, 2**31 + 71 + trace, capsys, trace)
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
    res, lines = _run(tiny, 2**31 + 73, capsys)
    assert calls[0] > 20
    assert res["correct"] is False and res["failed"] == 0
    assert any("OVER" in ln for ln in lines)


def test_the_int8_control_reads_over_the_limit(tiny):
    """Every linear layer of the reference in int8 in the program's
    place reads over the tiny configuration's limit (through
    ``reference.check_served`` on a made-up sample, as the other
    families' tests: no timing decides what is compared)."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import reference

    cell = loader.load_cell("tiny-swa.docs", tiny, [DATA, loader.DATA_ROOT])
    dims = cell.family.dims(cell.config)
    rng = np.random.default_rng(75)
    sample = [types.SimpleNamespace(
        prompt=rng.integers(0, dims.vocab, size=24).tolist(),
        tokens=rng.integers(0, dims.vocab, size=64).tolist())
        for _ in range(3)]
    limit = cell.config["correct"]["widest_gap_limit"]
    lines = []
    _, check = reference.check_served(
        2**31 + 75, cell.family, dims, jnp.float32, sample, limit,
        control=True, log=lines.append)
    assert check["served_tokens"] == 192
    assert check["control_widest_gap"] > 10 * limit
    assert any("FAILS, as it must" in ln for ln in lines)
