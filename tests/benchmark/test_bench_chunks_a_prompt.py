"""``chunks_a_prompt.ouro`` / ``.longdocs`` (PR 49): the programs a prompt
took, stat ``chunks`` of ``tdt.prefill_fetch``, read by ``span_stat``
from the two committed files: on hand-made rows, on a capture of the
tiny looped server on the CPU, and named in a tiny traced rehearsal.

Neither has an entry in the committed ``BENCHMARK.json`` yet:
``test_bench_looped.py`` and ``test_bench_mla_moe.py`` hold their cells'
per-layer lists by equality, and a PR that is no ``benchmark`` PR edits
no file the benchmark has. The entries a ``benchmark`` PR adds are
``ENTRIES`` below, as they were run on the chip through
``run.py --benchmark-file``.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu as tdt
from benchmark.harness import loader, run, trace_reduce as T
from benchmark.harness.reducers import RunContext, read_metric

DATA = run.REHEARSE_DATA
CELLS = {"chunks_a_prompt.ouro": "ouro-2.6b-1chip.fewshot",
         "chunks_a_prompt.longdocs": "mistral-small-4-1chip.longdocs"}
ENTRIES = [{"name": name, "unit": "programs", "better": "lower",
            "source": "program_counter", "layer": "serving tick",
            "moves": "tokens_per_s", "workloads": [cell]}
           for name, cell in CELLS.items()]
TINY = "tiny-ouro.docs"


def _spec(name):
    with open(loader.find_data("layer_metrics", name,
                               [loader.DATA_ROOT])) as f:
        return json.load(f)


def _ctx(rows, logged=None):
    return RunContext(cell=None, family=None, dims=None, peaks=None,
                      window=None, traced=(0.0, 1.0), rows=rows,
                      compile_s=0.0,
                      log=(logged.append if logged is not None
                           else lambda m: None))


def _span(kind, start, **stats):
    return {"plane": "/host:CPU", "line": "python", "name": "tdt." + kind,
            "start_ns": float(start), "dur_ns": 10.0, "stats": stats}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_file_names_the_stat_and_the_entry_is_one_the_loader_takes(
        name, tmp_path):
    assert _spec(name) == {"reducer": "span_stat", "params": {
        "span": "prefill_fetch", "stat": "chunks", "reduce": "mean"}}
    with open(os.path.join(loader.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = copy.deepcopy(bench["per_layer"])
    assert name not in {m["name"] for m in before}
    bench["per_layer"].extend(ENTRIES)
    assert bench["per_layer"][:len(before)] == before
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = loader.load_cell(CELLS[name], str(path))
    entry, spec = cell.per_layer[-1]
    assert (entry["name"], spec) == (name, _spec(name))
    assert entry["moves"] in {m["name"] for m in cell.end_to_end}
    assert entry["layer"] in {m["layer"] for m in before}
    # The other cell's metric is not this cell's.
    assert sum(m["name"] in CELLS for m, _ in cell.per_layer) == 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_mean_of_the_programs_a_prompt_took(name):
    rows = [_span("tick", 0, tick=0),
            _span("prefill_chunk", 1, bucket=512, valid=400, padded_up=1),
            _span("prefill_fetch", 2, slot=0, chunks=1),
            _span("prefill_fetch", 3, slot=1, chunks=3),
            _span("prefill_fetch", 4, slot=2, chunks=2, passes=4)]
    logged = []
    assert read_metric(_spec(name), _ctx(rows, logged)) == 2.0
    assert "3 events, 1 to 3" in logged[0]
    # A program from before the spans leaves the metric out; one whose
    # ``prefill_fetch`` carries no such stat fails the traced run, as a
    # renamed stat must.
    assert read_metric(_spec(name), _ctx([])) is None
    with pytest.raises(T.TraceError, match="carries the stat 'chunks'"):
        read_metric(_spec(name), _ctx(rows[:2] + [
            _span("prefill_fetch", 2, slot=0)]))


def test_the_stat_reaches_a_capture_and_the_reducer_reads_it(tmp_path):
    """The tiny looped model served over buckets (8, 32) under the
    profiler: prompts of 29, 19, 45 and 7 tokens take 1, 1, 3 and 1
    programs (``plan_chunks``), ``tdt.prefill_fetch`` says so in the
    capture, and the committed file reads their mean from the rows
    ``trace_reduce`` keeps."""
    with open(os.path.join(DATA, "configs", "tiny-ouro.json")) as f:
        config = json.load(f)
    F = loader.load_family("looped", [loader.DATA_ROOT])
    build = loader.sibling(F.__file__, "looped_system")
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    from triton_dist_tpu.models import Engine

    eng = Engine(build.model_config(config), mesh, mode="xla",
                 dtype=jnp.float32, max_len=64,
                 params=build.make_params(config, mesh, 7),
                 **build.engine_kwargs(config))
    srv = eng.serving(num_slots=2, page=8, prefill_buckets=(8, 32),
                      telemetry="spans")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=n).tolist()
               for n in (29, 19, 45, 7)]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        srv.generate(prompts, max_new_tokens=2)
    finally:
        jax.profiler.stop_trace()
    rows = T.read_xplane(T.find_xplane(str(tmp_path)))
    fetched = [r["stats"] for r in rows if r["name"] == "tdt.prefill_fetch"]
    assert sorted(s["chunks"] for s in fetched) == [1, 1, 1, 3]
    chunks = [r["stats"] for r in rows if r["name"] == "tdt.prefill_chunk"]
    assert sorted((s["bucket"], s["padded_up"]) for s in chunks) == [
        (8, 0), (8, 0), (8, 0), (32, 0), (32, 1), (32, 1)]
    for name in CELLS:
        assert read_metric(_spec(name), _ctx(rows)) == 1.5
    st = srv.stats()
    assert (st["chunk_dispatches_padded_up"], st["prefill_chunks"]) == (2, 6)


def test_a_tiny_traced_rehearsal_names_both(tmp_path, capsys, monkeypatch):
    """The tests' benchmark with the tiny looped cell and both entries
    appended, ``--trace 1``: the result's line names both metrics (off
    the chip every per-layer value prints as null)."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
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
    bench["per_layer"].extend(dict(m, workloads=[TINY]) for m in ENTRIES)
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    # The capture in a directory of this test's own.
    monkeypatch.setattr(run, "REPO_ROOT", str(tmp_path))
    assert run.main(["--rehearse", "--workload", TINY, "--seed",
                     str(2**31 + 4901), "--seconds", "1.0", "--trace", "1",
                     "--benchmark-file", str(path)]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(CELLS) <= set(res["metrics"])
    assert all(res["metrics"][n] == {"value": None, "unit": "programs"}
               for n in CELLS)
