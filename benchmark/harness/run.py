#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/harness/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's configuration on the chips it asks for (its family's
modules found by name, weights from the seed, on the device), warms
exactly the programs its traffic reaches, measures for ``--seconds``,
then frees the system and compares a seeded sample of what the window
served with the family's plain reference. The last line
of stdout is the result as one JSON object, its last key ``compared``
holding each number compared beside its limit (standard error ends with
the same); everything else (generator lateness, request counts, set-up
split) is on the lines before it. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

Builder's tools, not used by the driver: ``--control N`` (N seeds in
one process, each with the int8 control beside the sound reading),
``--rehearse`` (the tests: a tiny cell from ``tests/benchmark/data`` on
the CPU, every timing printed as null), ``--benchmark-file`` and
``--data-root`` (a cell from other files than the committed ones),
``--dump-trace`` (a traced run's rows, cut small enough to keep as test
data).
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

# A traced run: this long untraced, this long under the profiler, stop.
TRACE_LEAD_S = 6.0
TRACE_WINDOW_S = 6.0

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

REHEARSE_DATA = os.path.join(REPO_ROOT, "tests", "benchmark", "data")

# libtpu pins a host buffer for transfers inside the first look at the
# device: 4 GiB unless told otherwise, which on a host without transparent
# hugepages took 5-19 s of every run's set-up and moved with what the
# process before had left to free (PERF.md, section 6, PR 45). The cells
# move token ids between host and chip, which this size holds many times
# over. A value given from outside stands.
PREMAPPED_BUFFER_BYTES = 256 << 20
_COMPILE = {"seconds": 0.0, "compiles": 0, "listening": False}


def log(msg: str) -> None:
    print(msg, flush=True)


class _Clock:
    """Formats a time for the log: off the chip (``--rehearse``) every
    timing prints as null, so that no CPU time stands under a name a
    device run uses."""
    timed = True

    def __call__(self, x, spec=".3f") -> str:
        return format(x, spec) if self.timed else "null"


_t = _Clock()


def _on_duration(name: str, secs: float, **_) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += secs
        _COMPILE["compiles"] += 1
    elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
        _COMPILE["seconds"] += secs


def _compile_cache_dir(jax) -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at the fixed ``<checkout>/.jax_cache``; never a moving path."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def runtime_env(environ=os.environ) -> None:
    """What the TPU runtime reads when JAX loads it; call before that."""
    environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE",
                       str(PREMAPPED_BUFFER_BYTES))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, default=0, metavar="N")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark-file", default=None)
    ap.add_argument("--data-root", action="append", default=None)
    ap.add_argument("--dump-trace", default=None,
                    help="write the traced rows' summary under this dir")
    return ap.parse_args(argv)


class _Annotated:
    """The system with the harness's own host spans around each call, so
    that the trace's idle gaps can be laid to what the host was doing."""

    def __init__(self, system):
        import jax

        self._s, self._ann = system, jax.profiler.TraceAnnotation

    def submit(self, planned, on_token):
        with self._ann("bench.submit"):
            return self._s.submit(planned, on_token)

    def step(self):
        with self._ann("bench.step"):
            return self._s.step()

    def busy(self):
        return self._s.busy()

    def status(self, handle):
        return self._s.status(handle)

    def slot(self, handle):
        return self._s.slot(handle)


def _drive(cell, system, plan, seconds, hooks=(), drain=True):
    from benchmark.harness import serve_loop as L

    mix = cell.traffic
    if mix["loop"] == "open":
        return L.run_open(system, plan.arrivals(seconds), seconds,
                          drain_s=L.DRAIN_S if drain else 0.0, hooks=hooks)
    if mix["loop"] == "closed":
        return L.run_closed(system, plan, int(mix["clients"]), seconds,
                            hooks=hooks)
    raise ValueError(f"traffic loop {mix['loop']!r}")


def _window_report(win, e2e):
    from benchmark.harness import metrics as M, serve_loop as L

    c = L.counts(win)
    late = sorted(win.late_s) or [0.0]
    dec = [t for t in win.ticks if t.decoded > 0]
    log(f"window: {win.end - win.start:.3f} s measured, loop returned "
        f"after {_t(win.stopped - win.start)} s; requests {c['attempted']} "
        f"sent, by state {c['by_status']}; ticks {len(win.ticks)} "
        f"({len(dec)} with a decode, mean batch "
        f"{statistics.mean([t.decoded for t in dec]) if dec else 0:.2f})")
    # A run that reads low shows here whether one tick stalled (a closed
    # loop's first ticks admit and prefill every client at once).
    ms = [(t.start - win.start, (t.end - t.start) * 1e3) for t in win.ticks]
    at, worst = max([x for x in ms if x[0] > 1.0] or [(0.0, 0.0)],
                    key=lambda x: x[1])
    log(f"ticks: median {_t(statistics.median([m for _, m in ms] or [0]))} "
        f"ms; longest after the window's first second {_t(worst)} ms, at "
        f"{_t(at)} s")
    log(f"generator lateness: median {_t(M.percentile(late, 50) * 1e3)} ms, "
        f"p99 {_t(M.percentile(late, 99) * 1e3)} ms, "
        f"max {_t(late[-1] * 1e3)} ms")
    if _t.timed:
        by_batch = {}
        for t in dec:
            if not t.prefill:
                by_batch.setdefault(t.decoded, []).append(
                    (t.end - t.start) * 1e3)
        log("decode-only tick ms by batch [ticks, median]: " + json.dumps(
            {b: [len(v), round(statistics.median(v), 3)]
             for b, v in sorted(by_batch.items())}))
    first = M.ttfts_ms(win.records)
    if first and _t.timed:
        log("ttft ms: " + json.dumps({
            "mean": statistics.mean(first),
            **{f"p{q}": M.percentile(first, q)
               for q in (50, 75, 90, 95, 100)}}))
    log(f"samples: {e2e['_samples']}")
    return c


def run_one(args, cell, seed, t_begin, *, control=False):
    """One whole run of ``cell`` with ``seed``, its set-up counted from
    ``t_begin``; returns the result object (the last line) or raises."""
    import jax

    from benchmark.harness import (loadgen, metrics as M, opcount,
                                   reference, serve_loop as L, system as S,
                                   trace_reduce as T, weights as W)
    from benchmark.harness.reducers import RunContext

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    timed = _t.timed = device["platform"] == "tpu"
    peaks = opcount.peaks_for(device["kind"]) if timed else None
    seconds = float(args.seconds if args.seconds is not None
                    else cell.run_seconds)
    config, mix = cell.config, cell.traffic
    family = cell.family
    dims = family.dims(config)
    dtype = W.DTYPES[config["dtype"]]
    c0 = dict(_COMPILE)

    t0 = time.perf_counter()
    served = S.Served(cell, seed)
    t_built = time.perf_counter()
    warm = L.run_until_idle(served,
                            S.warm_up_plan(config, dims.vocab, seed))
    if any(r.status != "done" for r in warm):
        raise RuntimeError(f"warm-up requests ended "
                           f"{[r.status for r in warm]}")
    t_warm = time.perf_counter()
    compile_s = _COMPILE["seconds"] - c0["seconds"]
    plan = loadgen.Plan(mix, dims.vocab, seed)
    setup_s = time.perf_counter() - t_begin
    log(f"set-up: {_t(setup_s)} s (to imports and device "
        f"{_t(t0 - t_begin)}, weights and engine {_t(t_built - t0)}, "
        f"warm-up {_t(t_warm - t_built)}; of it compile or cache fetch "
        f"{_t(compile_s)}, {_COMPILE['compiles'] - c0['compiles']} "
        f"programs compiled or fetched)")

    traced, trace_dir, system, hooks = None, None, served, []
    if args.trace:
        lead = min(TRACE_LEAD_S, seconds / 2)
        length = min(TRACE_WINDOW_S, seconds - lead)
        seconds = lead + length
        trace_dir = os.path.join(REPO_ROOT, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        system, traced = _Annotated(served), [None, None]

        def start():
            jax.profiler.start_trace(trace_dir)
            traced[0] = time.perf_counter()

        def stop():
            if traced[0] is not None and traced[1] is None:
                traced[1] = time.perf_counter()
                jax.profiler.stop_trace()

        hooks = [(lead, start), (lead + length, stop)]

    n_before = _COMPILE["compiles"]
    win = _drive(cell, system, plan, seconds, hooks,
                 drain=not args.trace)
    in_window = _COMPILE["compiles"] - n_before
    e2e = M.end_to_end(win.records, win.start, win.end)
    c = _window_report(win, e2e)
    progs = served.program_counts()
    peak = served.memory_peak_bytes()
    log(f"programs: {progs}; compiled inside the window: {in_window}")
    log(f"device: {device}, memory_peak_bytes {peak}")
    device["memory_peak_bytes"] = peak

    n_check = int(mix["check_requests"])
    finished = [r for r in win.records if r.status == "done"]
    sample = reference.pick_sample(finished, n_check, seed)
    log(f"check sample: {len(sample)} of {len(finished)} finished "
        f"requests, from decode slots "
        f"{sorted({r.slot for r in sample} - {None})} of "
        f"{sorted({r.slot for r in finished} - {None})} that held one")
    served.close()
    del served, system
    gc.collect()
    t_ref = time.perf_counter()
    limit = float(config["correct"]["widest_gap_limit"])
    most_out = loadgen.largest(mix["output_tokens"])
    ok, numbers = reference.check_served(
        seed, family, dims, dtype, sample, limit, control=control, log=log,
        pad_to=loadgen.largest(mix["prompt_tokens"]) + most_out,
        rows_to=most_out, batch=n_check)
    log(f"reference: {_t(time.perf_counter() - t_ref)} s after the window")
    numbers["mode_kept"] = progs["mode_kept"]
    correct = bool(ok and progs["mode_kept"])
    if not progs["mode_kept"]:
        log(f"correct: the engine fell back to mode {progs['mode']!r}")

    values = dict(e2e, setup_s=setup_s)
    result = {"correct": correct, "attempted": c["attempted"],
              "failed": c["failed"], "metrics": {}, "device": device,
              "check": numbers}
    if not args.trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": _num(values[m["name"]]), "unit": m["unit"]}
    else:
        try:
            _per_layer(args, cell, result, RunContext(
                cell=cell, family=family, dims=dims, peaks=peaks,
                window=win, traced=tuple(traced),
                rows=T.read_xplane(T.find_xplane(trace_dir)),
                compile_s=compile_s, log=log))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def _per_layer(args, cell, result, ctx):
    """Fill a traced run's result: the cell's per-layer metrics, the
    device's busy seconds and the breakdown, all from ``ctx.rows``."""
    from benchmark.harness import trace_reduce as T
    from benchmark.harness.reducers import read_metric

    rows, device = ctx.rows, result["device"]
    if args.dump_trace:
        os.makedirs(args.dump_trace, exist_ok=True)
        with open(os.path.join(args.dump_trace,
                               f"{cell.name}.summary.json"), "w") as f:
            json.dump(T.summary(rows), f, indent=1)
        T.dump_rows(T.cut(rows), os.path.join(
            args.dump_trace, f"{cell.name}.cut.jsonl"))
    device["window_s"] = _num(ctx.traced[1] - ctx.traced[0])
    device["busy_s"] = None
    if not _t.timed:
        for entry, _ in cell.per_layer:
            result["metrics"][entry["name"]] = {"value": None,
                                                "unit": entry["unit"]}
        return
    log("programs on the device [runs, seconds]: "
        + json.dumps(T.program_totals(rows)))
    device["busy_s"] = _num(T.busy_seconds(rows))
    for entry, spec in cell.per_layer:
        value = read_metric(spec, ctx)
        if value is None:
            log(f"per-layer metric {entry['name']}: nothing to read, "
                "left out")
            continue
        result["metrics"][entry["name"]] = {"value": _num(value),
                                            "unit": entry["unit"]}
    result["breakdown"] = {"device_ops": T.top_device_ops(rows),
                           "idle_gaps": T.idle_gaps(rows)}


def _num(x):
    """A number as measured, or null off the chip: a CPU time is never
    written under a metric's name."""
    if not _t.timed or x is None:
        return None
    x = float(x)
    if not math.isfinite(x):
        raise RuntimeError("a metric came out not finite: the window "
                           "held no sample for it")
    return x


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
    runtime_env()
    try:
        import jax

        # ``system`` is the harness's one importer of the program.
        from benchmark.harness import loader, system  # noqa: F401
        from benchmark.harness.trace_reduce import TraceError
    except ImportError as e:
        print(f"run.py: the benchmark needs the repository around it: {e}",
              file=sys.stderr)
        return 1

    bench_file = args.benchmark_file or (
        os.path.join(REHEARSE_DATA, "BENCHMARK.json") if args.rehearse
        else os.path.join(REPO_ROOT, "BENCHMARK.json"))
    roots = args.data_root or (
        [REHEARSE_DATA, loader.DATA_ROOT] if args.rehearse
        else [loader.DATA_ROOT])
    cell = loader.load_cell(args.workload, bench_file, roots)

    t_imported = time.perf_counter()
    devs = jax.devices()
    _t.timed = devs[0].platform == "tpu"
    log(f"start: imports {_t(t_imported - _T_PROCESS)} s, first look at "
        f"the device {_t(time.perf_counter() - t_imported)} s (premapped "
        f"host buffer {os.environ['TPU_PREMAPPED_BUFFER_SIZE']} bytes)")
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"run.py: no TPU: JAX found platform="
                  f"{devs[0].platform!r}; the benchmark has no CPU mode",
                  file=sys.stderr)
            return 1
        log(f"compile cache: {_compile_cache_dir(jax)}")
    if len(devs) < cell.chips:
        print(f"run.py: cell {cell.name} asks for {cell.chips} chips, JAX "
              f"has {len(devs)}", file=sys.stderr)
        return 1
    if not _COMPILE["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _COMPILE["listening"] = True
    log(f"cell {cell.name}: configuration {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}, trace {args.trace}")

    if args.control:
        readings = []
        for k in range(args.control):
            res = run_one(args, cell, args.seed + k,
                          time.perf_counter() if k else _T_PROCESS,
                          control=True)
            readings.append(res["check"])
            log(f"control-seed {args.seed + k}: correct {res['correct']} "
                + json.dumps(res["check"]))
        sound = [r["widest_gap"] for r in readings]
        ctrl = [r["control_widest_gap"] for r in readings]
        log(f"control summary: sound widest {max(sound):.6g} "
            f"(all {sound}); control smallest {min(ctrl):.6g} (all {ctrl})")
    else:
        try:
            res = run_one(args, cell, args.seed, _T_PROCESS)
        except TraceError as e:
            print(f"run.py: the traced run failed: {e}", file=sys.stderr)
            return 1
    # Each number compared beside its limit: the result's last key, and
    # the last lines on standard error.
    check = res.pop("check")
    res["compared"] = {
        "widest_gap": {"value": check.get("widest_gap"),
                       "limit": check.get("limit")},
        "mode_kept": {"value": int(check["mode_kept"]), "limit": 1}}
    print(json.dumps(res), flush=True)
    for name, c in res["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']} "
              f"(served tokens {check.get('served_tokens')})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
