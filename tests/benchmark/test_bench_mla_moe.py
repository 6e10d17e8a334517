"""The latent-attention expert family (``benchmark/families/mla_moe.py``)
and its configuration and cell, ``mistral-small-4-1chip.longdocs``:

- the committed cell resolves to its files, its cut is the guide's
  (a chip's share, stated), and every width is the published one;
- the family's counts at the published widths are the numbers written
  out here by hand;
- the reducer this cell brings (``roofline_max``) on hand-made rows;
- a rehearsal of the family at a tiny size (``data/configs/
  tiny-mla.json``) is ``correct``, traced too, and with a token altered
  where the server picks it is not.
"""

import copy
import json
import os

import pytest

from benchmark.harness import loader, run, serve_loop as L
from benchmark.harness.reducers import RunContext, read_metric

DATA = run.REHEARSE_DATA
ROOT = loader.REPO_ROOT
CELL = "mistral-small-4-1chip.longdocs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("prefill_chunk_ms.longdocs", "chunk_roofline_share.longdocs",
           "idle_in_tick_ms.longdocs", "decode_batch.longdocs",
           "decode_fused_share.longdocs", "expert_load_imbalance.longdocs",
           "chunk_attn_ms.longdocs", "decode_rows_attn_ms.longdocs",
           "chunk_experts_ms.longdocs", "chunk_shared_expert_ms.longdocs")


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def test_the_cell_resolves_to_its_files(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "mistral-small-4-1chip", "longdocs", 1)
    assert cell.family.__file__ == os.path.join(
        loader.DATA_ROOT, "families", "mla_moe.py")
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m, _ in cell.per_layer] == [
        "compile_s", *METRICS]
    assert {spec["reducer"] for _, spec in cell.per_layer} == {
        "compile_seconds", "program_ms", "roofline_max", "scope_ms",
        "idle_by_span", "span_stat"}
    mix = cell.traffic
    assert (mix["loop"], mix["clients"], mix["check_requests"]) == (
        "closed", 32, 4)
    assert mix["output_tokens"] == {"law": "constant", "value": 64}
    low, high = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    # The cell as ISSUE 37 names it, or as its one rule moved it: both
    # ends lowered by the same whole number of 1,024s.
    assert mix["prompt_tokens"]["law"] == "uniform"
    assert (16384 - high) == (8192 - low) and (16384 - high) % 1024 == 0
    assert low >= 4096
    srv, eng = cell.config["serving"], cell.config["engine"]
    assert (srv["num_slots"], srv["page"], srv["prefill_buckets"]) == (
        16, 128, [512, 2048])
    # The smallest multiple of the page that holds the longest request.
    assert eng["max_len"] == 16512 and eng["max_len"] % 128 == 0
    assert eng["max_len"] - 128 < 16384 + 64 <= eng["max_len"]
    assert cell.config["tp"] == 1 and cell.config["dtype"] == "bfloat16"


def test_every_width_is_as_published_and_the_cut_is_a_stated_share(cell):
    c = cell.config
    assert (c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"]) == (
        4096, 1024, 256)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["num_attention_heads"]) == (64, 64, 128, 32)
    assert (c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["n_shared_experts"], c["router_outputs"]) == (2048, 4, 1, 128)
    assert (c["n_routed_experts"], c["num_hidden_layers"],
            c["vocab_size"]) == (32, 6, 32768)
    cuts = {k: (v["published"], v["here"]) for k, v in c["reduced"].items()}
    assert cuts == {"n_routed_experts": (128, 32),
                    "num_hidden_layers": (36, 6),
                    "vocab_size": (131072, 32768)}
    dep = c["deployment"]
    assert dep["layer_divided_over_chips"] == 4
    assert dep["experts_held"] == "0-31" and c["first_held_expert"] == 0
    # The guide's floors: 4 layers, 8 experts, an eighth of the words.
    assert c["num_hidden_layers"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= 131072
    assert {"router", "softmax_scale", "rope_pairs", "yarn", "query_scale",
            "shared_expert", "vision_tower"} <= set(c["assumed"])
    entry = next(e for e in loader.load_benchmark(
        os.path.join(ROOT, "BENCHMARK.json"))["configs"]
        if e["name"] == cell.config_name)
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert not any(loader.is_width(k) for k in entry["reduced"])


def test_the_file_holds_every_number_of_the_catalogs_entry(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Mistral-Small-4-119B-2603")
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    assert differs == set(cell.config["reduced"])


def test_the_counts_at_the_published_widths(cell):
    F, d = cell.family, cell.family.dims(cell.config)
    # w_dq, w_uq, w_dkv, w_ukv, wo
    attn = (4096 * 1024 + 1024 * 32 * 128 + 4096 * 320 + 256 * 32 * 192
            + 32 * 128 * 4096)
    assert attn == 28_049_408 == F._attn_params(d)
    expert = 3 * 4096 * 2048
    always = attn + 4096 * 128 + expert          # router, shared expert
    assert (expert, always) == (25_165_824, 53_739_520)
    assert always == F._always_params(d) and expert == F._expert_params(d)
    layer = always + 32 * expert
    assert layer == 859_045_888                  # ISSUE 37: 859.0 M
    head = 32768 * 4096
    assert F.latent_bytes_per_token(d) == 320 * 2 * 6 == 3840
    # A decode step of 16 rows over 100,000 cached positions: at most
    # min(32, 16 * 4) = 32 experts a layer.
    assert F.decode_step_bytes(d, 100_000, 16) == (
        (6 * layer + head) * 2 + 100_000 * 3840)
    assert F.decode_step_bytes(d, 0, 2) == (
        (6 * (always + 8 * expert) + head) * 2)
    # A 2048-row chunk whose rows see 6000 keys in the mean, 2048 pairs
    # a layer falling to held experts.
    flops = (2 * 6 * (2048 * always + 2048 * expert)
             + 2 * 2048 * 6000 * 32 * (64 + 64 + 128) * 6 + 2 * head)
    assert F.prefill_chunk_flops(d, 2048, 6000, held_pairs=2048) == flops
    assert F.prefill_chunk_flops(d, 2048, 6000) == flops   # the even share
    assert flops / 197e12 * 1e3 == pytest.approx(15.98, abs=0.01)
    nbytes = (6 * layer + head) * 2 + (6000 - 1024.5 + 2048) * 3840
    assert F.prefill_chunk_bytes(d, 2048, 6000) == nbytes
    assert nbytes / 819e9 * 1e3 == pytest.approx(12.95, abs=0.01)
    assert F.softmax_scale(d) == pytest.approx(128 ** -0.5 * 1.48520 ** 2,
                                               rel=1e-5)
    assert F.rope_scale(d) == 1.0
    assert F.expert_capacity(d, 16640) == 4 * 520
    # The blend: the fastest pairs keep f, the slowest take f / 128.
    f = F.yarn_inv_freq(d)
    assert float(f[0]) == 1.0
    assert float(f[-1]) == pytest.approx(10000 ** (-62 / 64) / 128, rel=1e-5)
    import jax.numpy as jnp
    scale = F.query_scale(d, jnp.asarray([0, 8191, 8192, 16383, 16384]))
    assert scale.tolist() == pytest.approx(
        [1, 1, 1.0693147, 1.0693147, 1.1098612])


def _window(prompts):
    recs = []
    for rid, n in enumerate(prompts):
        r = L.Record(rid, [1] * n, 4, due_at=0.9)
        r.tokens, r.status = [5] * 4, "done"
        recs.append(r)
    return L.Window(recs, [], 0.9, 2.0, [0.0], 2.0)


def _rows(ms, events):
    rows = [{"plane": "/device:TPU:0", "line": "XLA Modules",
             "name": "jit__chunk(7)", "start_ns": 1e6 * i,
             "dur_ns": ms * 1e6} for i in range(3)]
    rows += [{"plane": "/host:CPU", "line": "python",
              "name": "tdt.expert_load", "start_ns": 0.0, "dur_ns": 0.0,
              "stats": s} for s in events]
    return rows


def test_roofline_max_takes_the_larger_bound_and_the_served_pairs(cell):
    F, d = cell.family, cell.family.dims(cell.config)
    spec = next(s for m, s in cell.per_layer
                if m["name"] == "chunk_roofline_share.longdocs")
    assert spec["params"]["rows"] == 2048
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    logged = []

    def ctx(rows, prompts):
        return RunContext(cell=cell, family=F, dims=d, peaks=peaks,
                          window=_window(prompts), traced=(0.9, 2.0),
                          rows=rows, compile_s=0.0, log=logged.append)

    # Two prompts of 4096: full chunks at 0 and 2048, mean context 2048.5.
    events = [{"rows": 2064, "held_pairs": 6 * 3000},
              {"rows": 2064, "held_pairs": 6 * 1000},
              {"rows": 16, "held_pairs": 6 * 9}]        # a decode step's
    got = read_metric(spec, ctx(_rows(40.0, events), [4096, 4096]))
    by_flops = F.prefill_chunk_flops(d, 2048, 2048.5,
                                     held_pairs=2000) / 197e12
    by_bytes = F.prefill_chunk_bytes(d, 2048, 2048.5) / 819e9
    assert by_bytes > by_flops            # short contexts: the weights
    assert got == pytest.approx(100 * by_bytes / 40e-3)
    assert "held pairs a layer 2000" in logged[-1]
    # Long contexts: attention's operations pass the weights' bytes.
    got = read_metric(spec, ctx(_rows(80.0, events), [16384] * 3))
    by_flops = F.prefill_chunk_flops(d, 2048, 7 * 1024 + 1024.5,
                                     held_pairs=2000) / 197e12
    assert by_flops > F.prefill_chunk_bytes(d, 2048, 8192.5) / 819e9
    assert got == pytest.approx(100 * by_flops / 80e-3)
    # A program without the events: the family's even share.
    got = read_metric(spec, ctx(_rows(80.0, []), [16384] * 3))
    assert got == pytest.approx(
        100 * F.prefill_chunk_flops(d, 2048, 8192.5) / 197e12 / 80e-3)
    # No prompt as long as a chunk: nothing to read.
    assert read_metric(spec, ctx(_rows(80.0, events), [100])) is None


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
        "name": "tiny-mla", "source": "tests only",
        "file": "tests/benchmark/data/configs/tiny-mla.json",
        "reduced": ["n_routed_experts"], "why": "tests only"})
    bench["workloads"].append({
        "name": "tiny-mla.docs", "config": "tiny-mla",
        "traffic": "tiny-docs", "chips": 1, "why": "tests only"})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append("tiny-mla.docs")
    for m in real["per_layer"]:
        if m["name"] in METRICS:
            bench["per_layer"].append(dict(m, workloads=["tiny-mla.docs"]))
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path_factory.mktemp("mla") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def _run(bench, seed, capsys, trace=0):
    assert run.main(["--rehearse", "--workload", "tiny-mla.docs", "--seed",
                     str(seed), "--seconds", "1.0", "--trace", str(trace),
                     "--benchmark-file", bench]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_tiny_family_is_found_and_builds_the_programs_config(tiny):
    cell = loader.load_cell("tiny-mla.docs", tiny, [DATA, loader.DATA_ROOT])
    dims = cell.family.dims(cell.config)
    assert (dims.router_experts, dims.held, dims.first_held,
            dims.topk) == (16, 4, 0, 4)
    assert {"vocab", "d", "layers", "eps", "tie"} <= set(vars(dims))
    assert hash(dims) == hash(cell.family.dims(cell.config))
    leaves = cell.family.layer_leaves(dims, cell.family.layer_kind(dims, 0))
    assert leaves["experts_up"][0] == (4, 64, 32)
    assert leaves["router"][0] == (64, 16)
    assert leaves["w_dkv"][0] == (64, 16 + 8)
    assert set(leaves) == set(cell.family.LEAF_IDS)
    build = loader.sibling(cell.family.__file__, "mla_moe_system")
    assert build.F is cell.family
    cfg = build.model_config(cell.config)
    assert cfg.is_latent and cfg.num_experts == 16
    assert (cfg.first_held_expert, cfg.held_experts) == (0, 4)
    assert build.engine_kwargs(cell.config)["model"].__name__.endswith(
        "latent_moe")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_family_is_served_and_is_correct(tiny, capsys, trace):
    res, lines = _run(tiny, 2**31 + 41 + trace, capsys, trace)
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
    res, lines = _run(tiny, 2**31 + 43, capsys)
    assert calls[0] > 20
    assert res["correct"] is False and res["failed"] == 0
    assert any("OVER" in ln for ln in lines)
