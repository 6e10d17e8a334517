"""Whole runs of tiny cells on the CPU, in this process, through the
benchmark's one command with ``--rehearse`` (which skips the look for a
chip and prints every timing as null):

- a cell, a configuration, a traffic mix and a per-layer metric added as
  new files plus one entry each, with no file of the harness touched,
  run traced and report the added metric; an untraced run pins the last
  line to the contract's keys;
- the timed path broken underneath (a token altered where the server
  picks it) comes out ``correct: false``;
- the control (the reference in int8 in the program's place) comes out
  over the limit that the sound bf16 run keeps;
- a configuration with ``tp`` 4 builds its mesh and serves on the CPU's
  virtual devices.
"""

import copy
import json
import os
import shutil

import pytest

from benchmark.harness import loader, run

DATA = run.REHEARSE_DATA


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.fixture()
def added(tmp_path):
    """A data root of NEW files only, and a benchmark file with one
    entry added for each: configuration, mix, metric, cell."""
    for sub in ("configs", "traffic", "layer_metrics"):
        (tmp_path / sub).mkdir()
    with open(os.path.join(DATA, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(model_name="tiny-deeper", num_hidden_layers=3)
    (tmp_path / "configs" / "tiny-deeper.json").write_text(json.dumps(cfg))
    with open(os.path.join(DATA, "traffic", "tiny-docs.json")) as f:
        mix = json.load(f)
    mix.update(clients=3, check_requests=6)
    (tmp_path / "traffic" / "tiny-batch.json").write_text(json.dumps(mix))
    (tmp_path / "layer_metrics" / "chunk_ms.batch.json").write_text(
        json.dumps({"reducer": "program_ms",
                    "params": {"pattern": "^jit__chunk"}}))
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = copy.deepcopy(bench)
    bench["configs"].append({"name": "tiny-deeper", "source": "tests only",
                             "file": "configs/tiny-deeper.json",
                             "reduced": [], "why": "tests only"})
    bench["workloads"].append({"name": "tiny-deeper.batch",
                               "config": "tiny-deeper",
                               "traffic": "tiny-batch", "chips": 1,
                               "why": "tests only"})
    bench["per_layer"].append({
        "name": "chunk_ms.batch", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model step",
        "moves": "tokens_per_s", "workloads": ["tiny-deeper.batch"]})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append("tiny-deeper.batch")
    # Entries were added; none that was there changed.
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path), [str(tmp_path), DATA, loader.DATA_ROOT]


def _args(workload, seed, seconds, trace, bench=None, roots=()):
    argv = ["--rehearse", "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    if bench:
        argv += ["--benchmark-file", bench]
    for r in roots:
        argv += ["--data-root", r]
    return argv


def _pin_the_untraced_line(res, lines, metric, unit):
    """The contract's keys, every timing null off the chip."""
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert res["metrics"][metric] == {"value": None, "unit": unit}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    text = "\n".join(lines)
    assert "generator lateness: median null ms" in text
    assert "set-up: null s" in text and "reference: null s" in text
    assert "compiled inside the window: 0" in text
    assert "widest gap" in text and "limit" in text


def _slots(lines):
    """(covered, held) of the run's ``check sample:`` line."""
    ln = next(ln for ln in lines if ln.startswith("check sample:"))
    covered, held = ln.split("from decode slots ")[1].split(" of ")
    return json.loads(covered), json.loads(held.split("]")[0] + "]")


def test_a_cell_added_as_files_runs_and_reports_the_added_metric(
        added, capsys):
    bench, roots = added
    cell = loader.load_cell("tiny-deeper.batch", bench, roots)
    assert cell.config["num_hidden_layers"] == 3
    assert [m["name"] for m, _ in cell.per_layer] == ["compile_s",
                                                      "chunk_ms.batch"]

    # The traced form: the cell's per-layer metrics, the new one too.
    assert run.main(_args("tiny-deeper.batch", 2**31 + 9, 1.0, 1, bench,
                          roots)) == 0
    res, lines = _last_line(capsys)
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["correct"] is True and res["attempted"] >= 3
    assert set(res["metrics"]) == {"compile_s", "chunk_ms.batch"}
    assert all(v["value"] is None for v in res["metrics"].values())
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert not os.path.exists(os.path.join(run.REPO_ROOT, ".bench_trace"))
    # The compared sample meets every decode slot that served a request.
    covered, held = _slots(lines)
    assert covered == held and held


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from triton_dist_tpu.serving.server import ServingEngine

    sound = ServingEngine._pick
    calls = [0]

    def off_by_one(self, logits_row, req, step):
        calls[0] += 1
        tok = sound(self, logits_row, req, step)
        return (tok + 1) % len(logits_row) if calls[0] % 7 == 0 else tok

    monkeypatch.setattr(ServingEngine, "_pick", off_by_one)
    assert run.main(_args("tiny.chat", 31, 0.5, 0)) == 0
    res, lines = _last_line(capsys)
    assert calls[0] > 20
    assert res["correct"] is False and res["failed"] == 0
    assert any("OVER" in ln for ln in lines)


def test_the_int8_control_fails_where_the_bf16_run_passes(capsys):
    # Seed 51: at this size (32 wide, 256 words, logit std 0.12)
    # the two readings overlap from seed to seed, which is why a cell's
    # limit is set from readings at its own size. All 40 requests are
    # compared, so the readings do not depend on the host's timing.
    assert run.main(_args("tiny-bf16.chat", 51, 1.0, 0)
                    + ["--control", "1"]) == 0
    res, lines = _last_line(capsys)
    assert res["correct"] is True
    summary = next(ln for ln in lines if ln.startswith("control summary"))
    checks = [json.loads(ln[ln.index("{"):]) for ln in lines
              if ln.startswith("control-seed")]
    assert len(checks) == 1, summary
    for c in checks:
        assert c["served_tokens"] >= 200
        assert c["widest_gap"] <= c["limit"] < c["control_widest_gap"], c


def test_a_tp4_configuration_builds_its_mesh_and_serves(capsys):
    cell = loader.load_cell("tiny-tp4.docs",
                            os.path.join(DATA, "BENCHMARK.json"),
                            [DATA, loader.DATA_ROOT])
    assert cell.chips == 4 and cell.config["tp"] == 4
    assert run.main(_args("tiny-tp4.docs", 5, 0.5, 0)) == 0
    res, lines = _last_line(capsys)
    assert res["attempted"] >= 4
    _pin_the_untraced_line(res, lines, "tokens_per_s", "tokens/s")
    assert any("'mode': 'xla', 'mode_kept': True" in ln for ln in lines)
    covered, held = _slots(lines)
    assert covered == held == [0, 1]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result(
        capsys):
    assert run.main(["--workload", "seed-oss-36b-1chip.docs", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 1
    cap = capsys.readouterr()
    assert "no TPU" in cap.err and "{" not in cap.out
