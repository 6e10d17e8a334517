"""Trace reduction on a small recorded trace kept as data
(``data/trace_rows.jsonl``: rows cut from a traced run of Qwen3-8B,
24 layers, under an open-loop chat mix on the v5e, PR 24), and the
reducers that read it."""

import os
import statistics

import pytest

from benchmark.harness import serve_loop as L, trace_reduce as T
from benchmark.harness.reducers import (RunContext, program_ms,
                                        read_metric, roofline_share)
from benchmark.harness.weights import Dims

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_rows.jsonl")
DEV = "/device:TPU:0"


def _op(name, start, dur, line=T.OPS_LINE, plane=DEV):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


@pytest.fixture()
def rows():
    """Two programs on the device, 10 us apart, inside one host step."""
    return [
        _op("fusion.1", 0, 4_000), _op("fusion.2", 3_000, 3_000),   # overlap
        _op("fusion.1", 16_000, 4_000),
        _op("jit_a(1)", 0, 6_000, T.MODULES_LINE),
        _op("jit_a(1)", 16_000, 4_000, T.MODULES_LINE),
        _op("jit_b(2)", 30_000, 9_000, T.MODULES_LINE),
        _op("fusion.3", 30_000, 9_000),
        _op("bench.step", 5_000, 12_000, "python", "/host:CPU"),
        _op("bench.submit", 20_500, 9_000, "python", "/host:CPU"),
    ]


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_is_the_union_and_idle_follows(rows):
    # [0, 6000) + [16000, 20000) + [30000, 39000)
    assert T.busy_seconds(rows) == pytest.approx(19_000e-9)
    window = 39_000e-9
    assert 1 - T.busy_seconds(rows) / window == pytest.approx(20 / 39)


def test_busy_averages_over_device_planes(rows):
    rows.append(_op("fusion.9", 0, 1_000, plane="/device:TPU:1"))
    assert T.busy_seconds(rows) == pytest.approx((19_000 + 1_000) / 2 * 1e-9)


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(T.TraceError, match="no device plane"):
        T.busy_seconds([_op("bench.step", 0, 5, "python", "/host:CPU")])
    with pytest.raises(T.TraceError, match="shows no operation"):
        T.busy_seconds([_op("x", 0, 0)])


def test_per_program_medians_and_the_error_on_zero_matches(rows):
    assert T.program_median_ms(rows, r"^jit_a") == pytest.approx(0.005)
    assert T.program_median_ms(rows, r"^jit_b") == pytest.approx(0.009)
    with pytest.raises(T.TraceError, match="no program matches"):
        T.program_median_ms(rows, r"^jit__decode")


def test_top_ops_and_gaps_laid_to_host_spans(rows):
    ops = dict(T.top_device_ops(rows))
    assert ops["fusion.3"] == pytest.approx(9e-6)
    assert ops["fusion.1"] == pytest.approx(8e-6)
    gaps = dict(T.idle_gaps(rows))
    assert gaps == {"bench.step": pytest.approx(10e-6),
                    "bench.submit": pytest.approx(10e-6)}


def _ctx(rows, window=None, traced=(0.9, 2.0)):
    dims = Dims(vocab=1000, d=64, ff=128, layers=2, heads=4, kv_heads=2,
                head_dim=16, eps=1e-6, rope_theta=1e4, qk_norm=True,
                attention_bias=False, tie=False)
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    return RunContext(cell=_Cell(), dims=dims, peaks=peaks, window=window,
                      traced=traced, rows=rows, compile_s=3.5,
                      log=lambda m: None)


def test_program_ms_picks_the_slowest_variant(rows):
    rows += [_op("jit_c(7)", 50_000, 1_000, T.MODULES_LINE),
             _op("jit_c(8)", 52_000, 5_000, T.MODULES_LINE),
             _op("jit_c(8)", 58_000, 7_000, T.MODULES_LINE)]
    ctx = _ctx(rows)
    assert program_ms.reduce({"pattern": "^jit_c", "variant": "slowest"},
                             ctx) == pytest.approx(0.006)
    assert program_ms.reduce({"pattern": "^jit_c"}, ctx) == \
        pytest.approx(0.005)          # all runs together
    with pytest.raises(ValueError):
        program_ms.reduce({"pattern": "^jit_c", "variant": "fastest"}, ctx)


@pytest.mark.parametrize("spec", [
    {"reducer": "program_ms", "params": {"pattern": "^jit_zz"}},
    {"reducer": "roofline_share", "params": {"bound": "bytes",
                                             "pattern": "^jit_none"}},
    {"reducer": "roofline_share", "params": {"bound": "flops", "rows": 128,
                                             "pattern": "^jit_none"}}])
def test_a_pattern_that_matches_no_program_fails_the_read(rows, spec):
    # A renamed program must fail the traced run, not drop its metrics.
    with pytest.raises(T.TraceError, match="no program matches"):
        read_metric(spec, _ctx(rows, _window()))


def _window():
    """Two requests decoding together from t=1.0; ticks of 20 ms."""
    recs = []
    for rid, n_prompt in ((0, 100), (1, 300)):
        r = L.Record(rid, [1] * n_prompt, 4, due_at=0.9)
        r.token_times = [1.0, 1.02, 1.04, 1.06]
        r.tokens, r.status, r.finished_at = [5] * 4, "done", 1.06
        recs.append(r)
    ticks = [L.Tick(0.95, 1.0, 0, True)] + [
        L.Tick(1.0 + 0.02 * i, 1.0 + 0.02 * i + 0.018, 2, False)
        for i in range(3)]
    return L.Window(recs, ticks, 0.9, 2.0, [0.0, 0.0], 2.0)


class _Cell:
    config = {"tp": 1}


def test_reducers_read_contexts_and_shares(rows):
    ctx = _ctx(rows, _window())
    dims = ctx.dims
    assert read_metric({"reducer": "compile_seconds"}, ctx) == 3.5
    # Contexts at the three decode ticks: (100+1)+(300+1), +2 each tick.
    assert roofline_share.mean_decode_context(ctx) == pytest.approx(404.0)
    assert roofline_share.mean_chunk_context(ctx, 128) == pytest.approx(
        statistics.mean([64.5, 192.5]))
    from benchmark.harness import opcount
    want = (opcount.decode_step_bytes(dims, 404.0) / 1e9 * 1e3) / 0.005
    got = read_metric({"reducer": "roofline_share",
                       "params": {"bound": "bytes", "pattern": "^jit_a"}},
                      ctx)
    assert got == pytest.approx(100 * want)
    flops = read_metric({"reducer": "roofline_share",
                         "params": {"bound": "flops", "rows": 128,
                                    "pattern": "^jit_b"}}, ctx)
    ctx_mean = statistics.mean([64.5, 192.5])
    assert flops == pytest.approx(
        100 * opcount.prefill_chunk_flops(dims, 128, ctx_mean) / 1e12
        * 1e3 / 0.009)
    # No decode tick in the traced part: nothing to read, by design.
    assert read_metric({"reducer": "roofline_share",
                        "params": {"bound": "bytes", "pattern": "^jit_a"}},
                       _ctx(rows, _window(), traced=(5.0, 6.0))) is None


# -- the recorded trace ----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return T.load_rows(RECORDED)


def test_recorded_trace_has_the_lines_the_reduction_reads(recorded):
    assert T.device_planes(recorded) == [DEV]
    lines = {(r["plane"], r["line"]) for r in recorded}
    assert (DEV, T.OPS_LINE) in lines and (DEV, T.MODULES_LINE) in lines
    assert any(r["name"] == "bench.step" for r in recorded)


def test_recorded_busy_union_and_idle_share(recorded):
    # Operations of the first decode step only were kept: 28.0 ms of a
    # 32.9 ms tick.
    busy = T.busy_seconds(recorded)
    assert busy == pytest.approx(0.028017, abs=2e-5)
    ops = [r for r in recorded if r["line"] == T.OPS_LINE]
    assert busy < sum(r["dur_ns"] for r in ops) * 1e-9 + 1e-12
    window = 32.9e-3
    assert 1 - busy / window == pytest.approx(0.148, abs=0.002)


def test_recorded_per_program_medians(recorded):
    # The patterns are the ones the committed metric files carry.
    import json
    lm = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark",
                      "layer_metrics")
    with open(os.path.join(lm, "decode_step_ms.docs.json")) as f:
        dec = json.load(f)["params"]
    with open(os.path.join(lm, "prefill_chunk_ms.docs.json")) as f:
        chunk = json.load(f)["params"]
    assert program_ms.reduce(dec, _ctx(recorded)) == pytest.approx(
        28.02, abs=0.02)
    assert [len(v) for v in T.program_runs_ms(
        recorded, dec["pattern"]).values()] == [29]
    assert program_ms.reduce(chunk, _ctx(recorded)) == pytest.approx(
        30.82, abs=0.02)
    with pytest.raises(T.TraceError, match="no program matches"):
        T.program_median_ms(recorded, "^jit__prefill_monolithic")


def test_recorded_ops_and_gaps(recorded):
    name, secs = T.top_device_ops(recorded)[0]
    assert name.startswith("%copy.") and "bf16[24,129,8,128,128]" in name
    assert secs == pytest.approx(2.457e-3, abs=1e-5)
    gaps = dict(T.idle_gaps(recorded))
    assert set(gaps) <= {"bench.step", "outside any bench span"}


# -- a traced run whose trace lacks a program -------------------------------

def test_a_traced_run_that_finds_no_program_exits_non_zero(
        recorded, monkeypatch, capsys):
    import types

    from benchmark.harness import run

    entry = {"name": "decode_step_ms.gone", "unit": "ms"}
    cell = types.SimpleNamespace(name="c", per_layer=(
        (entry, {"reducer": "program_ms",
                 "params": {"pattern": "^jit__renamed"}}),))
    args = types.SimpleNamespace(dump_trace=None)
    result = {"metrics": {}, "device": {}}
    monkeypatch.setattr(run._t, "timed", True)
    with pytest.raises(T.TraceError, match="no program matches"):
        run._per_layer(args, cell, result, _ctx(recorded, _window(),
                                                traced=(0.0, 1.0)))

    # ... and the command turns that into exit code 1 and no result line.
    def fails(*a, **k):
        raise T.TraceError("no program matches '^jit__renamed'")

    monkeypatch.setattr(run, "run_one", fails)
    assert run.main(["--rehearse", "--workload", "tiny.chat", "--seed", "1",
                     "--seconds", "0.5", "--trace", "1"]) == 1
    cap = capsys.readouterr()
    assert "traced run failed" in cap.err
    assert not any(ln.startswith("{") for ln in cap.out.splitlines())
