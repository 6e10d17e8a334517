"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix; the per-layer metrics it
reports follow from ``BENCHMARK.json`` (a metric's ``workloads`` key, or,
without one, every cell that reports the end-to-end metric it ``moves``).
Each name maps to one data file under the data roots:

    configs/<config>.json   traffic/<mix>.json   layer_metrics/<metric>.json

so a later PR adds files and entries and edits no file that is there.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
DATA_ROOT = os.path.join(REPO_ROOT, "benchmark")


class BenchmarkError(ValueError):
    """A name, unit or file the driver would refuse, or a missing piece."""


def check_name(name, what="name"):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(f"{what} {name!r}: at most 64 of letters, "
                             "digits, '_', '.', '-', not starting with "
                             "'.' or '-'")
    return name


def check_unit(unit):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchmarkError(f"unit {unit!r}: 1 to 16 of letters, digits, "
                             "'_', '/', '%', '.', '-'")
    return unit


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with everything a run needs, already read."""
    name: str
    chips: int
    config_name: str
    config: dict          # configs/<config>.json
    traffic_name: str
    traffic: dict         # traffic/<mix>.json
    end_to_end: tuple     # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple      # (entry, layer_metrics/<name>.json) pairs
    run_seconds: int


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def find_data(kind, name, roots):
    """``<root>/<kind>/<name>.json`` in the first root that has it."""
    check_name(name, kind)
    for root in roots:
        path = os.path.join(root, kind, name + ".json")
        if os.path.isfile(path):
            return path
    raise BenchmarkError(f"no {kind}/{name}.json under {list(roots)}")


def load_benchmark(path):
    bench = _read_json(path)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in bench[group]:
            check_name(entry["name"], group)
            if entry["name"] in seen:
                raise BenchmarkError(f"{group}: {entry['name']!r} twice")
            seen.add(entry["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_unit(m["unit"])
        if m["better"] not in ("lower", "higher"):
            raise BenchmarkError(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            raise BenchmarkError(f"{m['name']}: source={m['source']!r}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            raise BenchmarkError(f"{m['name']} moves {m['moves']!r}, which "
                                 "is no end-to-end metric")
    return bench


def _reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload, bench_path=None, roots=None):
    """The cell ``workload`` of ``bench_path`` with its three kinds of
    file read from ``roots`` (default: the repo's own)."""
    bench_path = bench_path or os.path.join(REPO_ROOT, "BENCHMARK.json")
    roots = list(roots or [DATA_ROOT])
    bench = load_benchmark(bench_path)
    entry = next((w for w in bench["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {workload!r} in {bench_path}; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    if entry["chips"] not in (1, 4):
        raise BenchmarkError(f"{workload}: chips={entry['chips']}")
    check_name(entry["config"], "config")
    check_name(entry["traffic"], "traffic")
    if entry["config"] not in {c["name"] for c in bench["configs"]}:
        raise BenchmarkError(f"{workload}: configuration "
                             f"{entry['config']!r} is not in configs")
    config = _read_json(find_data("configs", entry["config"], roots))
    traffic = _read_json(find_data("traffic", entry["traffic"], roots))
    if config.get("chips", entry["chips"]) != entry["chips"]:
        raise BenchmarkError(
            f"{workload}: the cell asks for {entry['chips']} chips, its "
            f"configuration file for {config['chips']}")
    e2e = tuple(m for m in bench["end_to_end"]
                if _reports(m, workload))
    mine = {m["name"] for m in e2e}
    per_layer = tuple(
        (m, _read_json(find_data("layer_metrics", m["name"], roots)))
        for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m
            else m["moves"] in mine))
    return Cell(name=workload, chips=entry["chips"],
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))
