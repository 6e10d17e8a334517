"""The device's ``tdt.<block>`` scopes read from a capture
(``reducers/device_scopes.py``, ``scope_ms.py``): the row arithmetic on
hand-made rows, then on ``data/scope_rows.jsonl`` (the first three runs
of the 2048+16-row chunk program of a traced run of
``mistral-small-4-1chip.longdocs`` on the v5e, PR 40, seed 2147810002:
the programs and their operations of 1 us and more, names cut to 40
characters, each operation with the ``tf_op`` of its event metadata), the
capture's own bytes read by field number, and the metric files that name
the reducer."""

import json
import os

import pytest

from benchmark.harness import loader, trace_reduce as T
from benchmark.harness.reducers import (RunContext, device_scopes as D,
                                        read_metric, scope_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "scope_rows.jsonl")
CELL = "seed-oss-36b-1chip.docs"
LONG = "mistral-small-4-1chip.longdocs"
NEW = {"chunk_attn_ms.docs": ("attn_chunk", "kernels"),
       "decode_rows_attn_ms.docs": ("attn_decode", "kernels"),
       "chunk_mlp_ms.docs": ("mlp", "model step"),
       "head_ms.docs": ("head", "model step")}
# Written, measured (PERF.md, PR 40) and tested on the recorded rows;
# their entries came with PR 45.
LONG_FILES = {"chunk_attn_ms.longdocs": ("attn_chunk", "kernels"),
              "decode_rows_attn_ms.longdocs": ("attn_decode", "kernels"),
              "chunk_experts_ms.longdocs": ("experts", "expert layer"),
              "chunk_shared_expert_ms.longdocs": ("shared_expert",
                                                  "expert layer")}
US = 1_000


def _program(name, start, dur):
    return {"line": T.MODULES_LINE, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def _op(name, start, dur, scope=""):
    return {"line": T.OPS_LINE, "name": name, "start_ns": float(start),
            "dur_ns": float(dur), "scope": scope}


def _run(t0, mlp_us, *, scoped=True):
    """One run of ``jit__chunk(1)`` from ``t0``: a projection, a ``while``
    of 30 us with two body operations nested in it, an ``mlp`` fusion of
    ``mlp_us``, a ragged product XLA wrote without metadata, an
    operation under no scope (4 us), the head. 100 us + ``mlp_us``."""
    path = (lambda b, op: f"jit(_chunk)/jit(main)/tdt.{b}/{op}"
            if scoped else f"jit(_chunk)/jit(main)/{op}")
    at = [t0]

    def nxt(name, dur, scope):
        at[0] += dur * US
        return _op(name, at[0] - dur * US, dur * US, scope)

    rows = [_program("jit__chunk(1)", t0, (100 + mlp_us) * US),
            nxt("%fusion.1", 10, path("attn_project", "dot_general")),
            nxt("%while.2", 30, path("attn_decode", "while")),
            _op("%fusion.3", at[0] - 28 * US, 12 * US,
                path("attn_decode", "while/body/dot_general")),
            _op("%fusion.4", at[0] - 14 * US, 10 * US,
                path("attn_decode", "while/body/reduce_max")),
            nxt("%fusion.5", mlp_us, path("mlp", "dot_general") + ";"
                + path("attn_out", "add")),
            nxt("%ragged-dot-none.6 = f32[64,32] custom-call(...)", 40,
                "jit(_chunk)/jit(main)/ragged-dot-none"),
            nxt("%copy.7", 4, ""),
            nxt("%fusion.8", 16, path("head", "dot_general"))]
    return rows


@pytest.fixture()
def rows():
    """Three runs of the slow program (mlp 20, 50, 26 us) and two of a
    faster program of the same name but for its number."""
    fast = [_program("jit__chunk(2)", 900 * US, 30 * US),
            _op("%fusion.1", 900 * US, 30 * US,
                "jit(_chunk)/jit(main)/tdt.mlp/dot_general"),
            _program("jit__chunk(2)", 950 * US, 30 * US),
            _op("%fusion.1", 950 * US, 30 * US,
                "jit(_chunk)/jit(main)/tdt.mlp/dot_general")]
    return (_run(0, 20) + _run(300 * US, 50) + fast + _run(600 * US, 26))


def _ctx(logged=None):
    return RunContext(cell=None, family=None, dims=None, peaks=None,
                      window=None, traced=(0.0, 1.0), rows=[],
                      compile_s=0.0, log=(logged.append if logged
                                          is not None else lambda m: None))


def test_a_while_and_its_body_count_once_and_the_median_is_over_runs(rows):
    got, share, runs = D.blocks_ms(rows, "^jit__chunk", "slowest")
    assert runs == 3
    # The while's 30 us hold its body's 22: the union is 30.
    assert got["attn_decode"] == pytest.approx(0.030)
    # 20, 50 and 26 us: the median, not the mean.
    assert got["mlp"] == pytest.approx(0.026)
    # An operation fused from two blocks counts for both.
    assert got["attn_out"] == got["mlp"]
    # XLA's ragged product carries no scope: read by its name.
    assert got["experts"] == pytest.approx(0.040)
    assert got[D.NO_SCOPE] == pytest.approx(0.004)
    assert got["busy"] == pytest.approx(0.126)
    assert share == pytest.approx(1 - 12 / (120 + 150 + 126))
    # Without the variant every matching program's runs count together.
    every, _, n = D.blocks_ms(rows, "^jit__chunk")
    assert n == 5 and every["mlp"] == pytest.approx(0.030)
    assert every["head"] == pytest.approx(0.016)    # 0 in two of five


def test_scope_ms_reads_one_block_and_logs_them_all(rows):
    logged = []
    ms = scope_ms.block_ms(rows, "^jit__chunk", "slowest", "attn_decode",
                           log=logged.append)
    assert ms == pytest.approx(0.030)
    assert "3 runs" in logged[0] and "mlp 0.0260" in logged[0]
    assert f"{D.NO_SCOPE} 0.0040" in logged[0]


def test_a_program_without_scopes_reports_nothing():
    # The parent of PR 40 under these metric files: the metric is left
    # out, the traced run does not fail; the ragged product's name alone
    # makes no capture a scoped one.
    bare = _run(0, 20, scoped=False) + _run(300 * US, 30, scoped=False)
    assert D.blocks_ms(bare, "^jit__chunk", "slowest") is None
    assert scope_ms.block_ms(bare, "^jit__chunk", "slowest", "mlp") is None


def test_a_scope_the_program_lacks_fails_the_read(rows):
    with pytest.raises(T.TraceError, match="under tdt.attn_chunk"):
        scope_ms.block_ms(rows, "^jit__chunk", "slowest", "attn_chunk")
    with pytest.raises(T.TraceError, match="no program matches"):
        scope_ms.block_ms(rows, "^jit__verify", None, "mlp")


def test_a_program_mostly_unscoped_fails_the_read(rows):
    # The names fell off all but the head: 16 of 120 us.
    for r in rows:
        if r["line"] == T.OPS_LINE and "tdt.head" not in r["scope"]:
            r["scope"] = ""
            r["name"] = r["name"].replace("ragged-dot", "custom-call")
    with pytest.raises(T.TraceError, match="lies under a tdt. scope"):
        scope_ms.block_ms(rows, "^jit__chunk", "slowest", "head")


@pytest.mark.parametrize("workload, name", [
    *((CELL, n) for n in sorted(NEW)),
    *((LONG, n) for n in sorted(LONG_FILES))])
def test_the_loader_finds_the_metric_with_its_cell(workload, name):
    cell = loader.load_cell(workload)
    entry, spec = next((m, s) for m, s in cell.per_layer
                       if m["name"] == name)
    scope, layer = {**NEW, **LONG_FILES}[name]
    assert spec == {"reducer": "scope_ms", "params": {
        "pattern": "^jit__chunk", "variant": "slowest", "scope": scope}}
    assert (entry["source"], entry["moves"], entry["unit"],
            entry["better"], entry["layer"], entry["workloads"]) == (
        "device_trace", "tokens_per_s", "ms", "lower", layer, [workload])
    # The same program as the metric that times it from outside.
    outside = next(s for m, s in cell.per_layer
                   if m["name"] == "prefill_chunk_ms." + name.split(".")[1])
    assert {k: spec["params"][k] for k in ("pattern", "variant")} == (
        outside["params"])


# -- three runs recorded on the chip ----------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return T.load_rows(RECORDED)


def test_recorded_blocks_cover_the_program(recorded):
    got, share, runs = D.blocks_ms(recorded, "^jit__chunk", "slowest")
    assert runs == 3 and share == pytest.approx(0.9805, abs=1e-4)
    # Early in the window: long contexts, so the walk reads 10.6 ms where
    # the traced part's 68 runs read 5.2 (PERF.md, PR 40); the experts do
    # not depend on the context and read what all the runs read.
    assert got["experts"] == pytest.approx(39.613, abs=1e-3)
    assert got["attn_chunk"] == pytest.approx(10.573, abs=1e-3)
    assert got["shared_expert"] == pytest.approx(3.928, abs=1e-3)
    assert got["attn_decode"] == pytest.approx(1.626, abs=1e-3)
    assert got["busy"] == pytest.approx(62.092, abs=1e-3)
    assert set(got) - {"busy", D.NO_SCOPE} == {
        "embed", "attn_project", "cache_write", "attn_chunk",
        "attn_decode", "attn_out", "router", "experts", "shared_expert",
        "head", "pick"}
    # The blocks lie one after another on the one core: they add up to
    # the busy time less what no scope covers.
    named = sum(v for k, v in got.items() if k not in ("busy", D.NO_SCOPE))
    assert named == pytest.approx(got["busy"] - got[D.NO_SCOPE], rel=0.01)


def test_recorded_whiles_hold_their_bodies(recorded):
    """The decode rows' six walks are ``while`` operations whose events
    carry no ``tf_op`` themselves; the operations of their bodies lie
    inside them on the same line and carry ``tdt.attn_decode``. The time
    under no scope is the union's remainder, not the whiles' lengths."""
    ops = [r for r in recorded if r["line"] == T.OPS_LINE]
    whiles = [r for r in ops if r["name"].startswith("%while")]
    assert len(whiles) == 18 and not any(r["scope"] for r in whiles)
    first = whiles[0]
    end = first["start_ns"] + first["dur_ns"]
    inside = [r for r in ops if first["start_ns"] < r["start_ns"] < end]
    assert len(inside) > 20 and all(
        "tdt.attn_decode/while/body" in r["scope"] for r in inside)
    got, _, _ = D.blocks_ms(recorded, "^jit__chunk", "slowest")
    a_run = sum(r["dur_ns"] for r in whiles) * 1e-6 / 3
    assert a_run > 1.5 > got[D.NO_SCOPE]
    assert got["attn_decode"] == pytest.approx(a_run, rel=0.1)


def test_recorded_ragged_products_are_the_experts(recorded):
    products = [r for r in recorded if r["name"].startswith("%ragged-dot")]
    assert len(products) == 3 * 6 * 4       # metadata + gate, up, down
    assert {r["scope"] for r in products} == {
        "jit(_chunk)/jit(layer)/ragged-dot-none:",
        "jit(_chunk)/jit(layer)/ragged-dot-metadata:"}
    assert all(D.blocks_of(r) == {"experts"} for r in products)
    assert sum(r["dur_ns"] for r in products) * 1e-6 / 3 == pytest.approx(
        30.8, abs=0.3)


@pytest.mark.parametrize("name", sorted(LONG_FILES))
def test_the_longdocs_metric_files_read_the_recorded_rows(
        recorded, monkeypatch, name):
    with open(loader.find_data("layer_metrics", name,
                               [loader.DATA_ROOT])) as f:
        spec = json.load(f)
    assert spec["params"]["scope"] == LONG_FILES[name][0]
    monkeypatch.setattr(D, "rows_of", lambda ctx: recorded)
    logged = []
    got, _, _ = D.blocks_ms(recorded, "^jit__chunk", "slowest")
    assert read_metric(spec, _ctx(logged)) == got[LONG_FILES[name][0]]
    assert "3 runs" in logged[0] and "experts 39.61" in logged[0]


# -- the capture's bytes -----------------------------------------------------

def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x >> 7 else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str as a
    length-delimited field."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    """An ``XSpace`` of a host plane and one device plane by the field
    numbers of ``xplane.proto``: stat names 1 ``tf_op`` and 2 (a string
    that a ``ref_value`` points at), four event names, a line of one
    program and a line of three operations."""
    ref = "jit(f)/tdt.head/dot_general:"
    stat_names = b"".join(
        _field(5, _entry(i, _field(1, i) + _field(2, n)))
        for i, n in ((1, "tf_op"), (2, ref), (3, "flops")))
    events = {
        10: _field(2, "jit_f(7)"),
        11: _field(2, "%fusion.1 = f32[8]{0} fusion(...)") + _field(
            5, _field(1, 3) + _field(4, 99)) + _field(
            5, _field(1, 1) + _field(5, "jit(f)/tdt.mlp/tanh:")),
        12: _field(2, "%fusion.2 = f32[8]{0} fusion(...)") + _field(
            5, _field(1, 1) + _field(7, 2)),
        13: _field(2, "%copy.3 = f32[8]{0} copy(...)")}
    metadata = b"".join(
        _field(4, _entry(i, _field(1, i) + m)) for i, m in events.items())

    def event(meta, offset_ps, dur_ps):
        return _field(4, _field(1, meta) + _field(2, offset_ps)
                      + _field(3, dur_ps))

    modules = _field(3, _field(2, "XLA Modules") + _field(3, 1_000)
                     + event(10, 0, 9_000_000))
    ops = _field(3, _field(2, "XLA Ops") + _field(3, 1_000)
                 + event(11, 0, 4_000_000) + event(12, 4_000_000, 2_500_000)
                 + event(13, 7_000_000, 1_000_000))
    skipped = _field(3, _field(2, "Async XLA Ops") + event(13, 0, 5))
    device = (_field(2, "/device:TPU:0") + stat_names + metadata + modules
              + ops + skipped)
    host = _field(2, "/host:CPU") + _field(3, _field(2, "python"))
    return _field(1, host) + _field(1, device)


def test_the_capture_is_read_by_field_number(tmp_path):
    """``read_capture`` against ``jax.profiler.ProfileData`` on the same
    bytes: the same names, starts and lengths, and beside them what
    ``ProfileData`` does not give, the ``tf_op`` of the event's metadata,
    as a string or as a reference to one."""
    from jax.profiler import ProfileData

    data = _xspace()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(data)
    rows = D.read_capture(str(path))
    plane = ProfileData.from_serialized_xspace(data).find_plane_with_name(
        "/device:TPU:0")
    theirs = [(line.name, ev.name, ev.start_ns, ev.duration_ns)
              for line in plane.lines for ev in line.events
              if line.name in (T.OPS_LINE, T.MODULES_LINE)]
    assert [(r["line"], r["name"], r["start_ns"], r["dur_ns"])
            for r in rows] == theirs
    assert len(rows) == 4 and rows[0]["name"] == "jit_f(7)"
    assert "scope" not in rows[0]
    assert [r["scope"] for r in rows[1:]] == [
        "jit(f)/tdt.mlp/tanh:", "jit(f)/tdt.head/dot_general:", ""]
    got, share, runs = D.blocks_ms(rows, "^jit_f")
    assert (runs, got["mlp"], got["head"], got[D.NO_SCOPE]) == (
        1, 0.004, 0.0025, 0.001)
    assert share == pytest.approx(6.5 / 7.5)
    host_only = tmp_path / "h.xplane.pb"
    host_only.write_bytes(_field(1, _field(2, "/host:CPU")))
    with pytest.raises(T.TraceError, match="no device plane"):
        D.read_capture(str(host_only))
