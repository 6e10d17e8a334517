"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix; the per-layer metrics it
reports follow from ``BENCHMARK.json`` (a metric's ``workloads`` key, or,
without one, every cell that reports the end-to-end metric it ``moves``).
Each name maps to one data file under the data roots:

    configs/<config>.json   traffic/<mix>.json   layer_metrics/<metric>.json

and the configuration's file names its model family, two modules found
the same way:

    families/<family>.py          what the reference and the counts need
    families/<family>_system.py   what builds the program's side

so a later PR adds files and entries and edits no file that is there.
The names a family's first half may give are listed in
``families/dense.py``'s docstring, ``trunk`` among them: its layers'
own order of application, where that is not each layer once
(``harness/reference.py`` has its contract). Of the two halves only
``*_system.py`` may import the program
(``tests/benchmark/test_bench_families.py`` holds every ``families``
directory to that).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

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
    family: object        # families/<family>.py, loaded
    end_to_end: tuple     # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple      # (entry, layer_metrics/<name>.json) pairs
    run_seconds: int


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def find_data(kind, name, roots, ext=".json"):
    """``<root>/<kind>/<name><ext>`` in the first root that has it."""
    check_name(name, kind)
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise BenchmarkError(f"no {kind}/{name}{ext} under {list(roots)}")


def module_at(path):
    """The module in the file at ``path``, loaded once: a family's two
    halves and every jitted function keyed on the module see one
    object."""
    path = os.path.realpath(path)
    name = "benchmark_" + re.sub(r"\W", "_", os.path.relpath(
        os.path.splitext(path)[0], REPO_ROOT))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def sibling(file, name):
    """``<name>.py`` beside ``file``: how the harness reaches a
    family's ``_system`` half, and that half the one that holds the
    sizes and the leaves."""
    return module_at(os.path.join(os.path.dirname(file), name + ".py"))


def load_family(name, roots):
    """``families/<name>.py`` of the first root that has it."""
    return module_at(find_data("families", name, roots, ext=".py"))


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


def is_width(key):
    """A size no configuration may cut: hidden, intermediate, latent,
    state, projection and head sizes, expansion factors, experts a
    token. The vocabulary's rows held here are no width: a chip's slice
    of them is one of the cuts a deployment's share allows."""
    return (key.endswith(("_dim", "_rank", "_factor"))
            or (key.endswith("_size") and key != "vocab_size")
            or "experts_per_tok" in key)


def check_reduced(config, listed):
    """A configuration's file against the ``reduced`` its entry lists:
    each key is no width and states ``published``, ``here`` (what the
    file runs) and ``why``; a count of experts or of vocabulary rows is
    one chip's share, so ``deployment.layer_divided_over_chips`` says
    over how many chips a layer is divided."""
    body = config.get("reduced", {})
    if set(listed) != set(body):
        raise BenchmarkError(f"reduced: the entry lists {sorted(listed)}, "
                             f"the file {sorted(body)}")
    for key, cut in body.items():
        check_name(key, "reduced")
        if is_width(key):
            raise BenchmarkError(f"reduced: {key!r} is a width")
        if not {"published", "here", "why"} <= set(cut):
            raise BenchmarkError(f"reduced.{key}: needs published, here "
                                 "and why")
        if config.get(key) != cut["here"]:
            raise BenchmarkError(f"reduced.{key}: the file runs "
                                 f"{config.get(key)!r}, not {cut['here']!r}")
        if "experts" in key or key == "vocab_size":
            dep = config.get("deployment")
            chips = dep.get("layer_divided_over_chips") if isinstance(
                dep, dict) else None
            if not isinstance(chips, int) or chips < 2:
                raise BenchmarkError(
                    f"reduced.{key}: a share needs deployment."
                    "layer_divided_over_chips, a whole number over 1")


def _reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload, bench_path=None, roots=None):
    """The cell ``workload`` of ``bench_path`` with its kinds of
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
    listed = next((c["reduced"] for c in bench["configs"]
                   if c["name"] == entry["config"]), None)
    if listed is None:
        raise BenchmarkError(f"{workload}: configuration "
                             f"{entry['config']!r} is not in configs")
    config = _read_json(find_data("configs", entry["config"], roots))
    check_reduced(config, listed)
    traffic = _read_json(find_data("traffic", entry["traffic"], roots))
    if config.get("chips", entry["chips"]) != entry["chips"]:
        raise BenchmarkError(
            f"{workload}: the cell asks for {entry['chips']} chips, its "
            f"configuration file for {config['chips']}")
    if "family" not in config:
        raise BenchmarkError(f"configuration {entry['config']!r} names "
                             "no family")
    family = load_family(config["family"], roots)
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
                family=family, end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))
