"""The loader: a cell resolves to its three kinds of file by name, the
names and units the driver refuses are refused here, and the committed
``BENCHMARK.json`` keeps to the contract's limits."""

import json
import os

import pytest

from benchmark.harness import loader

ROOT = loader.REPO_ROOT
BENCH = os.path.join(ROOT, "BENCHMARK.json")


# The tests' own benchmark of tiny cells, beside the committed one.
DATA = os.path.join(ROOT, "tests", "benchmark", "data")


def test_cell_resolves_to_its_files_by_name():
    cell = loader.load_cell("tiny.chat", os.path.join(DATA, "BENCHMARK.json"),
                            [DATA, loader.DATA_ROOT])
    assert cell.chips == 1 and cell.config["hidden_size"] == 32
    assert cell.traffic["loop"] == "open"
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p90_ms", "itl_p50_ms", "setup_s"}
    # compile_s from the committed files, the other from the tests' own.
    assert {m["name"]: spec["reducer"] for m, spec in cell.per_layer} == {
        "compile_s": "compile_seconds", "decode_step_ms.tiny": "program_ms"}

    docs = loader.load_cell("seed-oss-36b-1chip.docs")
    assert docs.config["num_attention_heads"] == 80
    assert docs.traffic["loop"] == "closed" and docs.traffic["clients"] == 16
    assert {m["name"] for m in docs.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m, _ in docs.per_layer} == {
        "compile_s", "prefill_chunk_ms.docs", "prefill_mxu_share",
        "decode_step_ms.docs", "decode_hbm_share.docs"}
    # The correctness limit is the configuration's, beside its readings;
    # a traffic mix carries none.
    assert docs.config["correct"]["widest_gap_limit"] == 0.14
    assert "readings" in docs.config["correct"]
    assert not any("limit" in k or k == "check" for k in docs.traffic)


def test_unknown_cell_and_missing_file_are_errors(tmp_path):
    with pytest.raises(loader.BenchmarkError, match="no workload"):
        loader.load_cell("nobody.home")
    with pytest.raises(loader.BenchmarkError, match="no configs/"):
        loader.load_cell("seed-oss-36b-1chip.docs", roots=[str(tmp_path)])


@pytest.mark.parametrize("bad", [
    "two words", "a,b", "a/b", "", ".hidden", "-dash", "x" * 65, "µs",
    "tab\tname", None])
def test_names_the_driver_refuses(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_name(bad)


@pytest.mark.parametrize("good", ["ttft_p90_ms", "seed-oss-36b-1chip.docs",
                                  "_x", "9lives", "x" * 64])
def test_names_the_driver_takes(good):
    assert loader.check_name(good) == good


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17,
                                 "a,b"])
def test_units_the_driver_refuses(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_unit(bad)


@pytest.mark.parametrize("good", ["tokens/s", "%", "ms", "s", "GB/s"])
def test_units_the_driver_takes(good):
    assert loader.check_unit(good) == good


# -- the committed BENCHMARK.json against the contract --------------------

KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _is_width(key):
    """A size the contract never lets a configuration cut."""
    return (key.endswith(("_dim", "_rank", "_size", "_factor"))
            or "experts_per_tok" in key)


@pytest.fixture(scope="module")
def bench():
    assert os.path.getsize(BENCH) <= 64 * 1024
    return loader.load_benchmark(BENCH)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)), p
        assert not p.startswith("/") and ".." not in p.split("/")
    cmd = bench["command"]
    assert len(cmd) <= 32 and all(1 <= len(w) <= 200 for w in cmd)
    files = [w for w in cmd if os.path.exists(os.path.join(ROOT, w))]
    assert files and all(any(f.startswith(p + "/") for p in bench["paths"])
                         for f in files)


@pytest.mark.parametrize("group", sorted(KEYS))
def test_entries_have_just_the_keys_shown(bench, group):
    assert 1 <= len(bench[group])
    for e in bench[group]:
        extra = set(e) - KEYS[group]
        assert extra <= ({"workloads"} if group in ("end_to_end",
                                                    "per_layer") else set())
        assert KEYS[group] <= set(e), (group, e["name"])
        for k in ("why", "layer", "source"):
            if k in e and group != "end_to_end" and k != "source":
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]


def test_configurations_are_files_under_paths_and_cut_no_width(bench):
    seen = set()
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in seen
        seen.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            loader.check_name(key)
            assert not _is_width(key), key
            assert key in body["reduced"], (c["name"], key)
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_bounds_and_what_every_cell_reports(bench):
    names = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in names
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = [w["name"] for w in bench["workloads"]]
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(cells) <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 4)
    for name in cells:
        cell = loader.load_cell(name)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for entry, _ in cell.per_layer:
            assert entry["moves"] in e2e, (name, entry["name"])
    for m in bench["per_layer"] + bench["end_to_end"]:
        for w in m.get("workloads", []):
            assert w in cells, (m["name"], w)


def test_files_under_paths_are_named_from_a_names_characters(bench):
    import re

    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel
