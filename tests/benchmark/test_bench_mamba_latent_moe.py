"""The Mamba-2 / latent-expert family
(``benchmark/families/mamba_latent_moe.py``) and its configuration and
cell, ``nemotron-3-super-1chip.longdocs``:

- the committed cell resolves to its files, its cut is the guide's
  (a chip's share, stated), and every width is the published one;
- the family's counts at the published widths are the numbers written
  out here by hand;
- the reducer this cell brings (``scope_roofline_max``) on hand-made
  rows;
- a rehearsal of the family at a tiny size (``data/configs/
  tiny-mamba.json``) is ``correct``, traced too (in a directory of its
  own), with a token altered where the server picks it is not, and the
  int8 control reads over the limit.
"""

import copy
import json
import os

import pytest

from benchmark.harness import loader, run, serve_loop as L
from benchmark.harness.reducers import RunContext, device_scopes as D
from benchmark.harness.reducers import read_metric

DATA = run.REHEARSE_DATA
ROOT = loader.REPO_ROOT
CELL = "nemotron-3-super-1chip.longdocs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = tuple(n + ".nemotron" for n in (
    "prefill_chunk_ms", "chunk_roofline_share", "chunk_ssm_ms",
    "ssm_roofline_share", "experts_roofline_share", "chunk_experts_ms",
    "chunk_shared_expert_ms", "chunk_attn_ms", "decode_rows_attn_ms",
    "head_ms", "idle_in_tick_ms", "idle_schedule_ms", "idle_enqueue_ms",
    "idle_fetch_ms", "idle_sample_ms", "idle_submit_ms", "queue_wait_ms",
    "decode_batch", "decode_fused_share", "expert_load_imbalance"))
PATTERN = "MEMEMEM*EME"


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


def test_the_cell_resolves_to_its_files(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "nemotron-3-super-1chip", "longdocs", 1)
    assert cell.family.__file__ == os.path.join(
        loader.DATA_ROOT, "families", "mamba_latent_moe.py")
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m, _ in cell.per_layer]
    assert names[0] == "compile_s" and set(names[1:]) >= set(METRICS)
    assert {spec["reducer"] for _, spec in cell.per_layer} >= {
        "compile_seconds", "program_ms", "roofline_max", "scope_ms",
        "scope_roofline_max", "idle_by_span", "span_stat"}
    for m, _ in cell.per_layer[1:]:
        assert (m["moves"], m["workloads"]) == ("tokens_per_s", [CELL])
    # The accepted mix as it is, the other expert configuration's too.
    other = loader.load_cell("mistral-small-4-1chip.longdocs")
    assert cell.traffic == other.traffic
    mix = cell.traffic
    assert (mix["loop"], mix["clients"], mix["check_requests"]) == (
        "closed", 32, 4)
    srv, eng = cell.config["serving"], cell.config["engine"]
    assert (srv["num_slots"], srv["page"], srv["prefill_buckets"],
            srv["attn_impl"]) == (16, 128, [512, 2048], "flash")
    assert eng == other.config["engine"] == {"mode": "xla",
                                             "max_len": 16512}
    assert cell.config["tp"] == 1 and cell.config["dtype"] == "bfloat16"
    entry = next(w for w in loader.load_benchmark(os.path.join(
        ROOT, "BENCHMARK.json"))["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200
    assert "88 rows" in entry["why"] and "4x" in entry["why"]


def test_every_width_is_as_published_and_the_cut_is_a_stated_share(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (4096, 32, 2, 128)
    assert (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
            c["ssm_state_size"], c["conv_kernel"], c["chunk_size"],
            c["expand"]) == (128, 64, 8, 128, 4, 128, 2)
    assert (c["moe_intermediate_size"], c["moe_latent_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"],
            c["router_outputs"], c["n_group"], c["topk_group"]) == (
                2688, 1024, 5376, 22, 5, 512, 1, 1)
    assert (c["n_routed_experts"], c["num_hidden_layers"],
            c["hybrid_override_pattern"], c["vocab_size"]) == (
                128, 11, PATTERN, 32768)
    cuts = {k: (v["published"], v["here"]) for k, v in c["reduced"].items()}
    published = cuts.pop("hybrid_override_pattern")
    assert cuts == {"n_routed_experts": (512, 128),
                    "num_hidden_layers": (88, 11),
                    "vocab_size": (131072, 32768)}
    # One whole period: the published layers 0-10, 5 : 5 : 1 for the
    # published 40 : 40 : 8.
    assert published[1] == PATTERN == published[0][:11]
    assert [published[0].count(x) for x in "ME*"] == [40, 40, 8]
    assert [PATTERN.count(x) for x in "ME*"] == [5, 5, 1]
    dep = c["deployment"]
    assert dep["layer_divided_over_chips"] == 4
    assert dep["experts_held"] == "0-127" and c["first_held_expert"] == 0
    # The guide's floors: 4 layers, 8 experts, an eighth of the words.
    assert c["num_hidden_layers"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= 131072
    assert {"attention", "mamba", "gated_norm", "latent_experts", "router",
            "shared_expert", "mtp", "state_cache", "weights"} <= set(
                c["assumed"])
    assert "median decay" in c["assumed"]["weights"].lower()
    assert "bfloat16" in c["assumed"]["state_cache"]
    entry = next(e for e in loader.load_benchmark(
        os.path.join(ROOT, "BENCHMARK.json"))["configs"]
        if e["name"] == cell.config_name)
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert not any(loader.is_width(k) for k in entry["reduced"])
    limit = c["correct"]["widest_gap_limit"]
    assert 0 < limit < 1 and "control" in c["correct"]["readings"]


def test_the_file_holds_every_number_of_the_catalogs_entry(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    assert differs == set(cell.config["reduced"])


def test_the_counts_at_the_published_widths(cell):
    import math

    F, d = cell.family, cell.family.dims(cell.config)
    assert (d.d_in, d.conv_width) == (8192, 10240)
    size = lambda kind: sum(math.prod(shape) for shape, _, _ in
                            F.layer_leaves(d, kind).values())
    # ISSUE 43: 109.64 M, 35.66 M, 54.53 M + 128 x 5.505 M.
    assert size("mamba") == 109_640_064
    assert size("attention") == 35_655_680
    assert size("experts") == 54_530_560 + 128 * 5_505_024 == 759_173_632
    assert set(F.LEAF_IDS) == set().union(*(
        F.layer_leaves(d, k) for k in ("mamba", "attention", "experts")))
    mamba = 4096 * (8192 + 10240 + 128) + 8192 * 4096
    attn = 2 * 4096 * 128 * (32 + 2)
    expert = 2 * 1024 * 2688
    moe = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert (mamba, attn, expert, moe) == (
        F._mamba_params(d), F._attn_params(d), F._expert_params(d),
        F._moe_always_params(d))
    always = 5 * mamba + attn + 5 * moe
    assert always == F._always_params(d) == 856_162_304
    head = 32768 * 4096
    assert F.kv_bytes_per_token(d) == 2 * 2 * 128 * 2 == 1024
    state = 5 * 2 * (128 * 64 * 128 + 3 * 10240)
    assert F.state_bytes_per_sequence(d) == state == 10_792_960   # 10.8 MB
    # A decode step of 16 rows over 100,000 cached positions: an even
    # routing of 16 x 22 picks over 512 experts reaches 64.6 of the 128
    # held; no row reaches none, many reach all.
    reached = lambda rows: 128 * (1 - (1 - 22 / 512) ** rows)
    assert reached(16) == pytest.approx(64.6, abs=0.05)
    assert F.decode_step_bytes(d, 100_000, 16) == pytest.approx(
        (always + 5 * reached(16) * expert + head) * 2 + 100_000 * 1024
        + 2 * 16 * state)
    assert F.decode_step_bytes(d, 0, 2) == pytest.approx(
        (always + 5 * reached(2) * expert + head) * 2 + 2 * 2 * state)
    assert F.decode_step_bytes(d, 0, 0) == (always + head) * 2
    assert F.decode_step_bytes(d, 0, 4096) == pytest.approx(
        (always + 5 * 128 * expert + head) * 2 + 2 * 4096 * state)
    # A 2048-row chunk whose rows see 6000 keys in the mean; 11,264 held
    # pairs a layer = the even share, which roofline_max would hand over
    # as the program's 5 x 11,264 over 11 layers.
    even = 2048 * 22 * 128 / 512
    assert even == 11_264
    ssm = 5 * 2048 * (5 * 8192 * 128 + 2 * 4 * 10240 + 6 * 8192)
    assert F.ssm_chunk_flops(d, 2048) == ssm
    flops = (2 * 2048 * always + 2 * 5 * even * expert + ssm
             + 4 * 2048 * 6000 * 32 * 128 + 2 * head)
    assert F.prefill_chunk_flops(d, 2048, 6000) == flops
    assert F.prefill_chunk_flops(d, 2048, 6000,
                                 held_pairs=5 * even / 11) == flops
    assert flops / 2048 / 1e9 == pytest.approx(2.14, abs=0.01)  # a token
    assert flops / 197e12 * 1e3 == pytest.approx(22.25, abs=0.01)
    nbytes = ((always + 5 * 128 * expert + head) * 2
              + (6000 - 1024.5 + 2048) * 1024 + 2 * state)
    assert F.prefill_chunk_bytes(d, 2048, 6000) == nbytes
    assert nbytes / 819e9 * 1e3 == pytest.approx(11.06, abs=0.01)
    # The two blocks: the scan's rows in and out and the state twice;
    # the held experts' 1.41 GB a layer beside the pairs' rows.
    assert F.ssm_chunk_bytes(d, 2048) == (
        5 * 2048 * (2 * 8192 + 10240 + 128) * 2 + 2 * state)
    assert F.experts_chunk_bytes(d, 2048) == 2 * (
        5 * 128 * expert + 2 * 5 * even * 1024)
    assert 128 * expert * 2 / 819e9 * 1e3 == pytest.approx(1.72, abs=0.01)
    assert F.experts_chunk_flops(d, 2048, held_pairs=1000) == (
        2 * 11_000 * expert)
    assert F.expert_capacity(d, 12544) == 4 * 539


def _window(prompts):
    recs = []
    for rid, n in enumerate(prompts):
        r = L.Record(rid, [1] * n, 4, due_at=0.9)
        r.tokens, r.status = [5] * 4, "done"
        recs.append(r)
    return L.Window(recs, [], 0.9, 2.0, [0.0], 2.0)


def _capture(block_ms, scopes=True):
    """Three runs of one chunk program, 40 ms each: ``block_ms`` under
    each named block, the rest under ``tdt.head``."""
    rows = []
    for i in range(3):
        t0 = 1e8 * i
        rows.append({"line": "XLA Modules", "name": "jit__chunk(7)",
                     "start_ns": t0, "dur_ns": 40e6})
        at = t0
        for block, ms in list(block_ms.items()) + [
                ("head", 40.0 - sum(block_ms.values()))]:
            rows.append({"line": "XLA Ops", "name": "%fusion.1",
                         "start_ns": at, "dur_ns": ms * 1e6,
                         "scope": f"jit(f)/tdt.{block}/dot" if scopes
                         else ""})
            at += ms * 1e6
    return tuple(rows)


def test_scope_roofline_max_takes_the_larger_bound_of_a_block(
        cell, monkeypatch):
    F, d = cell.family, cell.family.dims(cell.config)
    specs = {m["name"]: s for m, s in cell.per_layer}
    ssm, experts = (specs[n + "_roofline_share.nemotron"]
                    for n in ("ssm", "experts"))
    assert (ssm["reducer"], experts["reducer"]) == ("scope_roofline_max",) * 2
    assert (ssm["params"]["scope"], experts["params"]["scope"]) == (
        "ssm", "experts")
    assert experts["params"]["pairs"] and not ssm["params"].get("pairs")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    logged = []
    events = [{"rows": 2064, "held_pairs": 5 * 12_000},
              {"rows": 2064, "held_pairs": 5 * 10_000},
              {"rows": 16, "held_pairs": 5 * 90}]         # a decode step's
    host = [{"plane": "/host:CPU", "line": "python",
             "name": "tdt.expert_load", "start_ns": 0.0, "dur_ns": 0.0,
             "stats": s} for s in events]

    def read(spec, capture, host_rows=host):
        monkeypatch.setattr(D, "rows_of", lambda ctx: capture)
        return read_metric(spec, RunContext(
            cell=cell, family=F, dims=d, peaks=peaks,
            window=_window([4096]), traced=(0.9, 2.0), rows=host_rows,
            compile_s=0.0, log=logged.append))

    capture = _capture({"ssm": 20.0, "experts": 16.0})
    # The scan between its projections: bound by its bytes.
    by_bytes = F.ssm_chunk_bytes(d, 2048) / 819e9
    assert by_bytes > F.ssm_chunk_flops(d, 2048) / 197e12
    assert read(ssm, capture) == pytest.approx(100 * by_bytes / 20e-3)
    assert by_bytes * 1e3 == pytest.approx(0.695, abs=0.001)
    # The grouped products: the weights' bytes, with the pairs as served
    # (11,000 a layer of the five; 5,000 a layer of the eleven).
    pairs = 55_000 / 11
    by_bytes = F.experts_chunk_bytes(d, 2048, held_pairs=pairs) / 819e9
    assert by_bytes > F.experts_chunk_flops(d, 2048,
                                            held_pairs=pairs) / 197e12
    assert read(experts, capture) == pytest.approx(100 * by_bytes / 16e-3)
    assert "held_pairs" in logged[-1] and "5000" in logged[-1]
    # No such events: the even share.
    assert read(experts, capture, []) == pytest.approx(
        100 * F.experts_chunk_bytes(d, 2048) / 819e9 / 16e-3)
    # A share cannot pass 100 % while the block takes what its bytes
    # allow or longer.
    assert read(ssm, _capture({"ssm": by_bytes * 1e3 + 1.0})) < 100
    # A program from before the block, or before any scope: nothing to
    # read, and nothing raised.
    assert read(ssm, _capture({"experts": 16.0})) is None
    assert read(ssm, _capture({"ssm": 20.0}, scopes=False)) is None


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
        "name": "tiny-mamba", "source": "tests only",
        "file": "tests/benchmark/data/configs/tiny-mamba.json",
        "reduced": ["n_routed_experts"], "why": "tests only"})
    bench["workloads"].append({
        "name": "tiny-mamba.docs", "config": "tiny-mamba",
        "traffic": "tiny-docs", "chips": 1, "why": "tests only"})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append("tiny-mamba.docs")
    for m in real["per_layer"]:
        if m["name"] in METRICS:
            bench["per_layer"].append(dict(m, workloads=["tiny-mamba.docs"]))
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path_factory.mktemp("mamba") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def _run(bench, seed, capsys, trace=0, more=()):
    assert run.main(["--rehearse", "--workload", "tiny-mamba.docs",
                     "--seed", str(seed), "--seconds", "1.0", "--trace",
                     str(trace), "--benchmark-file", bench, *more]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_tiny_family_is_found_and_builds_the_programs_config(tiny):
    cell = loader.load_cell("tiny-mamba.docs", tiny,
                            [DATA, loader.DATA_ROOT])
    dims = cell.family.dims(cell.config)
    assert (dims.router_experts, dims.held, dims.first_held, dims.topk,
            dims.pattern) == (16, 4, 0, 4, "MEM*EM")
    assert {"vocab", "d", "layers", "eps", "tie"} <= set(vars(dims))
    assert hash(dims) == hash(cell.family.dims(cell.config))
    kinds = [cell.family.layer_kind(dims, i) for i in range(dims.layers)]
    assert [dims.count(k) for k in ("mamba", "experts", "attention")] == [
        kinds.count(k) for k in ("mamba", "experts", "attention")] == [
            3, 2, 1]
    leaves = cell.family.layer_leaves(dims, "experts")
    assert leaves["experts_up"][0] == (4, 32, 48)
    assert leaves["router"][0] == (64, 16)
    assert cell.family.layer_leaves(dims, "mamba")["w_in"][0] == (
        64, 128 + 192 + 8)
    build = loader.sibling(cell.family.__file__, "mamba_latent_moe_system")
    assert build.F is cell.family
    cfg = build.model_config(cell.config)
    assert cfg.layer_pattern == "MEM*EM" and cfg.num_experts == 16
    assert (cfg.first_held_expert, cfg.held_experts) == (0, 4)
    assert build.engine_kwargs(cell.config)["model"].__name__.endswith(
        "mamba_moe")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_family_is_served_and_is_correct(tiny, capsys, trace,
                                                  tmp_path, monkeypatch):
    if trace:
        # The capture in a directory of this test's own: two traced
        # rehearsals in two workers would empty each other's.
        monkeypatch.setattr(run, "REPO_ROOT", str(tmp_path))
    res, lines = _run(tiny, 2**31 + 61 + trace, capsys, trace)
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
    res, lines = _run(tiny, 2**31 + 63, capsys)
    assert calls[0] > 20
    assert res["correct"] is False and res["failed"] == 0
    assert any("OVER" in ln for ln in lines)


def test_the_int8_control_reads_over_the_limit(tiny):
    """Every linear layer of the reference in int8 in the program's
    place reads over the tiny configuration's limit. Through
    ``reference.check_served`` on a made-up sample of 192 positions (the
    control's number depends on the reference's two readings alone, not
    on what was served), so that no timing decides which requests are
    compared: the 12 tokens a 1 s rehearsal compares leave int8's
    choice equal to the reference's every third time."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import reference

    cell = loader.load_cell("tiny-mamba.docs", tiny,
                            [DATA, loader.DATA_ROOT])
    dims = cell.family.dims(cell.config)
    rng = np.random.default_rng(65)
    sample = [types.SimpleNamespace(
        prompt=rng.integers(0, dims.vocab, size=24).tolist(),
        tokens=rng.integers(0, dims.vocab, size=64).tolist())
        for _ in range(3)]
    limit = cell.config["correct"]["widest_gap_limit"]
    lines = []
    _, check = reference.check_served(
        2**31 + 65, cell.family, dims, jnp.float32, sample, limit,
        control=True, log=lines.append)
    assert check["served_tokens"] == 192
    assert check["control_widest_gap"] > 10 * limit
    assert any("FAILS, as it must" in ln for ln in lines)
