"""Pipeline-parallel schedule benchmark — reference ``benchmark/
bench_pp.py`` analogue: times the microbatched GPipe schedule and
reports per-rank utilization vs the (M+S-1)/(M*S) ideal.

Run: python benchmark/bench_pp.py --stages 8 --microbatches 16
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=16)
    ap.add_argument("--mb-rows", type=int, default=8)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.stages}")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import triton_dist_tpu as tdt
    from triton_dist_tpu.layers.pp_comm import gpipe_forward
    from triton_dist_tpu.utils.distributed import (enable_compile_cache,
                                                   on_tpu)

    enable_compile_cache()

    S, M = args.stages, args.microbatches
    mesh = tdt.make_mesh(pp=S, devices=jax.devices()[:S])
    mctx = tdt.MeshContext.from_mesh(mesh)
    w = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (S, args.d, args.d))
        * args.d ** -0.5,
        NamedSharding(mesh, P("pp", None, None)))
    x_mb = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1),
                          (M, args.mb_rows, args.d)),
        NamedSharding(mesh, P(None, None, None)))

    f = jax.jit(jax.shard_map(
        lambda ws, xs: gpipe_forward(
            lambda h: jnp.tanh(h @ ws[0]), xs, axis="pp",
            ctx=mctx, impl=args.impl),
        mesh=mesh, in_specs=(P("pp", None, None), P(None, None, None)),
        out_specs=P(None, None, None), check_vma=False))

    np.asarray(f(w, x_mb))  # compile + warm
    reps = 3 if on_tpu() else 1   # interpreter wall times: one is enough
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(f(w, x_mb))
        best = min(best, time.perf_counter() - t0)

    # Per-device FLOPs utilization vs the schedule's theoretical bound.
    cost = f.lower(w, x_mb).compile().cost_analysis() or {}
    flops = cost.get("flops", 0.0)
    seq_flops = 2.0 * M * args.mb_rows * args.d * args.d * S
    ticks = M + S - 1
    ideal = seq_flops * ticks / (M * S)
    print(json.dumps({
        "metric": "gpipe_step_seconds", "value": round(best, 6),
        "unit": "s", "vs_baseline": None,
        "platform": jax.devices()[0].platform,
        "detail": {"stages": S, "microbatches": M, "impl": args.impl,
                   # backend cost_analysis scope varies; report both
                   # raw numbers rather than a ratio that mixes scopes.
                   "cost_analysis_flops": flops,
                   "schedule_ideal_per_rank_flops": ideal,
                   "sequential_total_flops": seq_flops}}))


if __name__ == "__main__":
    main()
