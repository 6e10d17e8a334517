"""Per-layer TP benchmark — the reference's ``benchmark/bench_tp_attn.py``
/ ``bench_tp_mlp.py`` analogue.

Times the fused TP layer paths against the XLA-collective forms at a
chosen shape, on whatever backend JAX initialises (``JAX_PLATFORMS=cpu``
selects the 8-device CPU mesh in interpret mode — a smoke of the
plumbing, not a device timing). Prints one JSON line per measurement.

Run: python benchmark/bench_tp_layer.py --layer mlp --m 2048
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _slope(fn, lo=4, hi=16, reps=3):
    # Interpret-mode CPU is an emulator: timings there are smoke-only.
    import numpy as np

    best = {}
    for iters in (lo, hi):
        def run():
            out = None
            for _ in range(iters):
                out = fn()
            return np.asarray(out)
        run()  # warm
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run()
            b = min(b, time.perf_counter() - t0)
        best[iters] = b
    return (best[hi] - best[lo]) / (hi - lo)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layer", default="mlp", choices=["mlp", "attn"])
    ap.add_argument("--tp", type=int, default=8)
    ap.add_argument("--m", type=int, default=256,
                    help="tokens (global rows)")
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--ff", type=int, default=512)
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.tp}")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import triton_dist_tpu as tdt
    from triton_dist_tpu.models import ModelConfig, dense
    from triton_dist_tpu.utils.distributed import (enable_compile_cache,
                                                   on_tpu, platform)

    enable_compile_cache()

    mesh = tdt.make_mesh(tp=args.tp, devices=jax.devices()[:args.tp])
    mctx = tdt.MeshContext.from_mesh(mesh)
    cfg = ModelConfig.tiny(hidden_size=args.d, intermediate_size=args.ff)
    blocks = dict(block_m=min(64, args.m // args.tp),
                  block_n=min(64, args.ff // args.tp),
                  block_k=min(128, args.d))
    ctxs = dense.make_fwd_contexts(mctx, "tp", **blocks)

    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (args.m, args.d)),
        NamedSharding(mesh, P("tp", None)))
    modes = ("xla", "fused")
    if args.layer == "mlp":
        from triton_dist_tpu.layers import tp_mlp

        specs = tp_mlp.param_specs("tp")
        params = jax.tree.map(
            lambda w, sp: jax.device_put(w, NamedSharding(mesh, sp)),
            tp_mlp.init(jax.random.PRNGKey(0), cfg), specs)

        def make(mode):
            return jax.jit(jax.shard_map(
                lambda ps, xs: tp_mlp.fwd(ps, xs, mode=mode, axis="tp",
                                          ag_ctx=ctxs.ag, rs_ctx=ctxs.rs,
                                          ar_ctx=ctxs.ar),
                mesh=mesh, in_specs=(specs, P("tp", None)),
                out_specs=P("tp", None), check_vma=False))
    else:
        from triton_dist_tpu.layers import tp_attn

        specs = tp_attn.param_specs("tp")
        params = jax.tree.map(
            lambda w, sp: jax.device_put(w, NamedSharding(mesh, sp)),
            tp_attn.init(jax.random.PRNGKey(0), cfg), specs)

        def make(mode):
            return jax.jit(jax.shard_map(
                lambda ps, xs: tp_attn.fwd_prefill(
                    ps, xs, cfg, batch=1, mode=mode, axis="tp",
                    ag_ctx=ctxs.ag, rs_ctx=ctxs.rs, ar_ctx=ctxs.ar)[0],
                mesh=mesh, in_specs=(specs, P("tp", None)),
                out_specs=P("tp", None), check_vma=False))
    fns = {m: (lambda f=make(m): f(params, x)) for m in modes}

    lo, hi, reps = (4, 16, args.reps or 3) if on_tpu() else \
        (1, 2, args.reps or 1)   # CPU interpret: smoke numbers only
    times = {m: _slope(fns[m], lo=lo, hi=hi, reps=reps) for m in modes}
    for m in modes:
        print(json.dumps({
            "metric": f"tp_{args.layer}_{m}_seconds_per_iter",
            "value": round(times[m], 6), "unit": "s",
            "vs_baseline": (round(times["xla"] / max(times[m], 1e-12), 4)
                            if m != "xla" else 1.0),
            "platform": platform(),
            "shape": {"m": args.m, "d": args.d, "ff": args.ff,
                      "tp": args.tp}}))


if __name__ == "__main__":
    main()
