"""Test scaffolding.

The reference's testing contract (SURVEY.md §4): every fused op has a
pure-framework reference implementation and an allclose gate, tests run
on one host with N local devices, a conftest-style spawner abstracts
world bring-up. Here "N local devices" is the forced-host-platform CPU
mesh and the spawner is :func:`spmd` (no processes needed — shard_map is
the SPMD region).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401


def spmd(mesh: Mesh, fn, in_specs, out_specs, jit: bool = True):
    """Wrap a per-shard fn into a jitted SPMD callable over ``mesh``.

    The analogue of launching a reference test under torchrun
    (``scripts/launch.sh``): inside ``fn`` the code sees per-device
    shards and named axes.

    The wrapper blocks until the result is ready: the interpret-mode
    Pallas engine deadlocks if an unrelated JAX computation is
    dispatched while a multi-kernel program is in flight (its vector-
    clock io_callbacks dispatch nested jnp ops that starve the CPU
    client's thread pool), so tests must never overlap an SPMD run
    with oracle computation.
    """
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    compiled = jax.jit(mapped) if jit else mapped

    def call(*args, **kwargs):
        return jax.block_until_ready(compiled(*args, **kwargs))

    call.lower = getattr(compiled, "lower", None)
    return call


def assert_allclose(actual: Any, desired: Any, rtol: float = 1e-5,
                    atol: float = 1e-5, msg: str = ""):
    actual = jax.device_get(actual)
    desired = jax.device_get(desired)
    np.testing.assert_allclose(actual, desired, rtol=rtol, atol=atol,
                               err_msg=msg)


# -- a compiled step program: no copy of the paged pool ----------------------

_IN_PLACE = {"parameter", "dynamic-update-slice", "tuple", "bitcast",
             "get-tuple-element"}


def pool_copies(hlo: str, pool_shape, dtype: str = "bf16"):
    """Instructions of an optimised HLO module's entry computation that
    copy the KV pool or one layer of it: anything that produces an array
    of ``pool_shape`` or ``pool_shape[1:]`` other than the parameter
    itself, a Mosaic call, or an update in place (a
    ``dynamic-update-slice``, alone or as all a fusion does to the
    pool), and any such array in a layout that is not row-major. A
    ``while`` that carries the pool through unchanged (a walk over
    pages that only reads it: in its body the pool is a tuple element
    and nothing else) is no copy. Returns the offending lines, cut
    short."""
    import re

    def dims(shape):
        return dtype + "[" + ",".join(str(d) for d in shape) + "]"

    pool, layer = dims(pool_shape), dims(pool_shape[1:])
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif name is not None and " = " in line:
            comps[name].append(line)

    def parse(line):
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        return m.groups() if m else ("", "")

    def relaid(result):
        laid = re.findall(re.escape(pool) + r"\{([\d,]+)", result)
        laid += re.findall(re.escape(layer) + r"\{([\d,]+)", result)
        return any(lay.split(",") != sorted(lay.split(","), reverse=True)
                   for lay in laid)

    bad = []
    for line in comps["ENTRY"]:
        result, op = parse(line)
        if pool not in result and layer not in result:
            continue
        ok = op in _IN_PLACE and layer + "{" not in result.replace(pool, "")
        if op == "while":
            called = re.search(r"body=%([\w.\-]+)", line).group(1)
            ok = all(parse(inner)[1] in _IN_PLACE - {"dynamic-update-slice"}
                     for inner in comps[called]
                     if pool in parse(inner)[0] or layer in parse(inner)[0])
        if op == "fusion" and layer + "{" not in result.replace(pool, ""):
            called = re.search(r"calls=%([\w.\-]+)", line).group(1)
            ok = all(parse(inner)[1] in _IN_PLACE
                     for inner in comps[called] if pool in parse(inner)[0])
        if not ok or relaid(result):
            bad.append(line.strip()[:200])
    return bad
