"""The program's own spans read from a capture: the row arithmetic on a
small hand-made trace, then on ``data/span_rows.jsonl`` (eight ticks cut
from a traced run of ``seed-oss-36b-1chip.docs`` on the v5e, PR 25, seed
2147493014: the programs, the device's operations merged into busy
intervals where less than 1 us apart, ``bench.*`` rows and ``tdt.*``
rows with their stats), and the metric files that name the reducers."""

import json
import os

import pytest

from benchmark.harness import loader, trace_reduce as T
from benchmark.harness.reducers import (RunContext, idle_by_span,
                                        program_spans as P, read_metric,
                                        span_stat)

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "span_rows.jsonl")
STAGED = os.path.join(loader.DATA_ROOT, "layer_metrics", "staged",
                      "BENCHMARK.json")
CELL = "seed-oss-36b-1chip.docs"
DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ("idle_in_tick_ms.docs", "idle_schedule_ms.docs",
       "idle_enqueue_ms.docs", "idle_fetch_ms.docs", "idle_sample_ms.docs",
       "idle_submit_ms.docs", "queue_wait_ms.docs", "decode_batch.docs")


def _op(start, dur):
    return {"plane": DEV, "line": T.OPS_LINE, "name": "fusion",
            "start_ns": float(start), "dur_ns": float(dur)}


def _span(kind, start, dur, **stats):
    return {"plane": HOST, "line": "python", "name": "tdt." + kind,
            "start_ns": float(start), "dur_ns": float(dur), "stats": stats}


@pytest.fixture()
def rows():
    """Two ticks. The device is busy [0, 10), [20, 30), [34, 60) and
    [80, 90) us: gaps of 10, 4 and 20 us. Tick 0 spans [5, 38), the
    caller holds [38, 62) with a submit in it, tick 1 spans [62, 95)."""
    us = 1_000
    return [
        _op(0, 10 * us), _op(20 * us, 10 * us), _op(34 * us, 26 * us),
        _op(80 * us, 10 * us),
        _span("tick", 5 * us, 33 * us, tick=0),
        _span("schedule", 5 * us, 7 * us, tick=0),          # [5, 12)
        _span("admit", 6 * us, 0, tick=0, waited_ms=3.0, slot=1),
        _span("admit", 7 * us, 0, tick=0, waited_ms=9.0, slot=2),
        _span("decode", 12 * us, 24 * us, tick=0, batch=2),  # [12, 36)
        _span("decode_enqueue", 12 * us, 6 * us, tick=0),    # [12, 18)
        _span("decode_wait", 19 * us, 12 * us, tick=0),      # [19, 31)
        _span("decode_fetch", 31 * us, 4 * us, tick=0),      # [31, 35)
        _span("emit", 36 * us, 1 * us, tick=0, slot=1),
        _span("submit", 64 * us - 20 * us, 6 * us),          # [44, 50)
        _span("tick", 62 * us, 33 * us, tick=1),
        _span("schedule", 62 * us, 10 * us, tick=1),         # [62, 72)
        _span("decode", 74 * us, 20 * us, tick=1, batch=5),
        _span("decode_enqueue", 74 * us, 8 * us, tick=1),    # [74, 82)
        _span("tick", 96 * us, 1 * us, tick=2),              # an idle tick
    ]


def _ctx(rows, logged=None):
    return RunContext(cell=None, dims=None, peaks=None, window=None,
                      traced=(0.0, 1.0), rows=rows, compile_s=0.0,
                      log=(logged.append if logged is not None
                           else lambda m: None))


def test_a_gap_is_split_by_time_among_the_spans_that_cover_it(rows):
    gaps = P.idle_intervals(rows)
    assert gaps == [[10_000, 20_000], [30_000, 34_000], [60_000, 80_000]]
    parts = P.named(rows[4:], ["schedule", "decode_enqueue", "decode_wait",
                               "decode_fetch"], "test")
    ms = {k: P.overlap_ns(gaps, P.intervals(v)) for k, v in parts.items()}
    # [10, 20): schedule 2, enqueue 6, wait 1, the decode span itself 1.
    # [30, 34): wait 1, fetch 3. [60, 80): schedule 10, enqueue 6.
    assert ms == {"schedule": 12_000, "decode_enqueue": 12_000,
                  "decode_wait": 2_000, "decode_fetch": 3_000}
    # Two of the three ticks handed the device a program.
    assert P.dispatching_ticks(rows) == 2
    logged = []
    per_tick = idle_by_span.reduce(
        {"span": ["decode_enqueue", "decode_wait"]}, _ctx(rows, logged))
    assert per_tick == pytest.approx(14_000 * 1e-6 / 2)
    assert "over 2 ticks" in logged[0] and "'decode_wait': 0.002" in logged[0]


def test_the_attribution_closes(rows):
    a = P.attribution(P.idle_intervals(rows),
                      [r for r in rows if "stats" in r])
    assert a["idle"] == 34_000
    # Tick 0 covers [10, 20) and [30, 34); tick 1 [62, 80); the caller
    # has [60, 62); the submit lies where the device is busy.
    assert (a["in_tick"], a["in_submit"], a["outside"]) == (32_000, 0, 2_000)
    assert a["in_tick"] + a["in_submit"] + a["outside"] == a["idle"]
    # Of the tick's: [18, 19) inside decode between two children, and
    # [72, 74) between schedule and decode.
    assert a["no_leaf"] == 3_000


@pytest.mark.parametrize("spec", [
    {"reducer": "idle_by_span", "params": {"span": "decode_prep"}},
    {"reducer": "idle_by_span", "params": {"span": ["schedule", "sample"]}},
    {"reducer": "span_stat", "params": {"span": "sample", "stat": "slot",
                                        "reduce": "mean"}}])
def test_a_span_the_capture_lacks_fails_the_read(rows, spec):
    # A renamed span must fail the traced run, not drop its metrics.
    with pytest.raises(T.TraceError, match="no tdt.(decode_prep|sample)"):
        read_metric(spec, _ctx(rows))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_annotations_reports_nothing(rows, name,
                                                       monkeypatch,
                                                       tmp_path):
    # The parent of PR 25 under these metric files: the metric is left
    # out, the traced run does not fail.
    monkeypatch.setattr(P, "TRACE_DIR", str(tmp_path / "none"))
    with open(os.path.join(loader.DATA_ROOT, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert read_metric(spec, _ctx([r for r in rows
                                   if "stats" not in r])) is None


def test_span_stat_reduces_one_stat(rows):
    ctx = _ctx(rows)
    assert span_stat.reduce({"span": "decode", "stat": "batch",
                             "reduce": "mean"}, ctx) == 3.5
    assert span_stat.reduce({"span": "admit", "stat": "waited_ms",
                             "reduce": "median"}, ctx) == 6.0
    with pytest.raises(T.TraceError, match="carries the stat 'batch'"):
        span_stat.reduce({"span": "admit", "stat": "batch",
                          "reduce": "mean"}, ctx)


def test_the_capture_is_read_where_the_rows_hold_no_span(monkeypatch,
                                                         tmp_path):
    """``read_xplane`` drops the program's host rows, so the reducers
    make their own pass over the capture under ``.bench_trace``."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("tdt.tick", tick=7):
            with jax.profiler.TraceAnnotation("tdt.decode", tick=7, batch=3,
                                              request_id="r1"):
                pass
        with jax.profiler.TraceAnnotation("bench.step"):
            pass
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(P, "TRACE_DIR", str(tmp_path))
    spans = P.spans_of(_ctx([_op(0, 5)]))
    assert [s["name"] for s in spans] == ["tdt.tick", "tdt.decode"]
    assert spans[1]["stats"] == {"tick": 7, "batch": 3, "request_id": "r1"}
    assert spans[0]["start_ns"] <= spans[1]["start_ns"]
    assert (spans[1]["start_ns"] + spans[1]["dur_ns"]
            <= spans[0]["start_ns"] + spans[0]["dur_ns"])


# -- the metric files and the entries staged for them ----------------------

def test_staged_entries_are_the_committed_benchmark_plus_eight():
    """``BENCHMARK.json`` cannot take the entries in this PR
    (``test_bench_loader`` holds the docs cell to its five metrics, and
    no file of the benchmark may be edited); they wait, ready to run
    through ``--benchmark-file``, in ``layer_metrics/staged``."""
    with open(os.path.join(loader.REPO_ROOT, "BENCHMARK.json")) as f:
        committed = json.load(f)
    with open(STAGED) as f:
        staged = json.load(f)
    n = len(committed["per_layer"])
    assert {k: v for k, v in staged.items() if k != "per_layer"} == {
        k: v for k, v in committed.items() if k != "per_layer"}
    assert staged["per_layer"][:n] == committed["per_layer"]
    new = staged["per_layer"][n:]
    assert [m["name"] for m in new] == list(NEW)
    for m in new:
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "serving tick", "tokens_per_s", [CELL])
        assert m["source"] == ("program_counter"
                               if m["name"] == "decode_batch.docs"
                               else "program_span")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


# -- eight ticks recorded on the chip ---------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return T.load_rows(RECORDED)


def test_recorded_attribution_closes_and_names_the_leaf(recorded):
    spans = [r for r in recorded if r["name"].startswith(P.SPAN_PREFIX)]
    assert P.dispatching_ticks(spans) == 8
    gaps = P.idle_intervals(recorded)
    a = P.attribution(gaps, spans)
    # 39.7 ms of idle in 482 ms: 19 gaps between the busy intervals.
    assert len(gaps) == 19 and a["idle"] == 39717754.0
    assert a["idle"] == pytest.approx(
        sum(s for _, s in T.idle_gaps(recorded)) * 1e9)
    # What lies in a tick, in a submit and in the caller's hands is all
    # of it; the leaves cover a tick's part to within a tenth.
    assert a["in_tick"] + a["in_submit"] + a["outside"] == pytest.approx(
        a["idle"], rel=1e-9)
    assert (a["in_submit"], a["outside"]) == (86600.0, 1325110.0)
    assert 0 < a["no_leaf"] < 0.1 * a["in_tick"]
    by_kind = {}         # the leaves; the two events lie inside leaves
    for s in spans:
        if s["name"] not in P.PARENTS + ("tdt.admit", "tdt.first_token"):
            by_kind.setdefault(s["name"], []).append(s)
    idle = {k: P.overlap_ns(gaps, P.intervals(v)) for k, v in by_kind.items()}
    assert sum(idle.values()) == pytest.approx(
        a["in_tick"] + a["in_submit"] - a["no_leaf"])
    # In these eight ticks most gaps end where a chunk program starts:
    # the way to the device (input build, dispatch) holds the most, then
    # the logits copy, then the wait after the device has finished.
    assert sorted(idle, key=idle.get)[-3:] == [
        "tdt.decode_wait", "tdt.decode_fetch", "tdt.prefill_chunk"]
    assert idle["tdt.decode_wait"] == 7024394.0


@pytest.mark.parametrize("name, value", [
    ("idle_in_tick_ms.docs", 38.306044 / 8),
    ("idle_schedule_ms.docs", (0.43636 + 0.059589 + 11.253139) / 8),
    ("idle_enqueue_ms.docs", (1.483799 + 7.024394) / 8),
    ("idle_fetch_ms.docs", (8.91978 + 1.517002) / 8),
    ("idle_sample_ms.docs", (3.62648 + 0.895185) / 8),
    ("idle_submit_ms.docs", 0.0866 / 8),
    ("queue_wait_ms.docs", (1905.025994 + 2013.538288) / 2),
    ("decode_batch.docs", 56 / 8)])
def test_each_staged_metric_loads_and_reads_the_recorded_rows(
        recorded, name, value):
    cell = loader.load_cell(CELL, STAGED)
    entry, spec = next((m, s) for m, s in cell.per_layer
                       if m["name"] == name)
    assert entry["moves"] in {m["name"] for m in cell.end_to_end}
    assert read_metric(spec, _ctx(recorded)) == pytest.approx(value,
                                                              rel=1e-6)
    assert len(cell.per_layer) == 13
