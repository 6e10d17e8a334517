"""Offline tuning CLI — the reference's ``tools/tune/tune_gemm.py``
analogue: sweep the fused-GEMM config spaces on the ATTACHED backend
and persist winners into the tune cache, so serving jobs hit tuned
configs on first use.

Timing cannot happen inside a jit/shard_map trace (a tracer has no
wall clock), so this CLI drives :func:`triton_dist_tpu.autotuner.
tune_spmd`: one jitted SPMD step per candidate config, compiled and
timed eagerly, winner persisted under the same cache key the op's
``*_tuned`` wrapper reads in-trace.

Run:  python -m triton_dist_tpu.tools.tune_cli \
    --op ag_gemm --m 2048 --k 4096 --n 4096
(``JAX_PLATFORMS=cpu`` runs the sweep on the 8-virtual-device CPU mesh —
a smoke of the plumbing; its timings are interpreter wall times.)
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default="ag_gemm",
                    choices=["ag_gemm", "gemm_rs", "gemm_ar"])
    ap.add_argument("--m", type=int, default=2048)
    ap.add_argument("--k", type=int, default=4096)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--tp", type=int, default=None,
                    help="mesh size (default: all attached devices)")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    # Only affects the CPU backend (JAX_PLATFORMS=cpu).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import triton_dist_tpu as tdt
    from triton_dist_tpu import ops, tune
    from triton_dist_tpu.autotuner import tune_spmd
    from triton_dist_tpu.utils.distributed import enable_compile_cache

    enable_compile_cache()

    ndev = args.tp or len(jax.devices())
    mesh = tdt.make_mesh(tp=ndev, devices=jax.devices()[:ndev])
    mctx = tdt.MeshContext.from_mesh(mesh)
    dt = jnp.dtype(args.dtype)
    ka, kb = jax.random.PRNGKey(0), jax.random.PRNGKey(1)

    # Per-op geometry: shardings, config space, step factory. Cache
    # keys mirror each *_tuned wrapper's key_fn so in-trace lookups hit
    # what this sweep stores.
    if args.op == "ag_gemm":
        sa, sb, so = P("tp", None), P(None, "tp"), P(None, "tp")
        configs = [
            {"block_m": 256, "block_n": 512, "block_k": 1024},
            {"block_m": 512, "block_n": 512, "block_k": 2048},
            {"block_m": 512, "block_n": 1024, "block_k": 1024},
            {"block_m": 256, "block_n": 256, "block_k": 512},
            {"block_m": 64, "block_n": 64, "block_k": 64},
        ]

        def make_step(cfg):
            ctx = ops.create_ag_gemm_context(mctx, "tp", **cfg)
            return jax.jit(jax.shard_map(
                lambda xs, ws: ops.ag_gemm(xs, ws, ctx,
                                           force_kernel=(ndev == 1)),
                mesh=mesh, in_specs=(sa, sb), out_specs=so,
                check_vma=False))
    elif args.op == "gemm_rs":
        sa, sb, so = P(None, "tp"), P("tp", None), P("tp", None)
        configs = [
            {"block_m": 1024, "block_n": 128, "block_k": 4096},
            {"block_m": 512, "block_n": 128, "block_k": 4096},
            {"block_m": 512, "block_n": 128, "block_k": 2048},
            {"block_m": 256, "block_n": 256, "block_k": 1024},
            {"block_m": 64, "block_n": 32, "block_k": 32},
        ]

        def make_step(cfg):
            ctx = ops.create_gemm_rs_context(mctx, "tp", **cfg)
            return jax.jit(jax.shard_map(
                lambda xs, ws: ops.gemm_rs(xs, ws, ctx,
                                           force_kernel=(ndev == 1)),
                mesh=mesh, in_specs=(sa, sb), out_specs=so,
                check_vma=False))
    else:
        sa, sb, so = P(None, "tp"), P("tp", None), P(None, None)
        configs = [
            {"variant": "ll", "block_n": 512, "block_k": 1024},
            {"variant": "ll", "block_n": 1024, "block_k": 1024},
            {"variant": "ll", "block_n": 512, "block_k": 2048},
            {"variant": "one_shot", "block_n": 512, "block_k": 1024},
            {"variant": "ll", "block_n": 32, "block_k": 32},
        ]

        def make_step(cfg):
            cfg = dict(cfg)
            variant = cfg.pop("variant", "ll")
            ctx = ops.create_gemm_ar_context(mctx, "tp", variant=variant,
                                             **cfg)
            return jax.jit(jax.shard_map(
                lambda xs, ws: ops.gemm_ar(xs, ws, ctx,
                                           force_kernel=(ndev == 1)),
                mesh=mesh, in_specs=(sa, sb), out_specs=so,
                check_vma=False))

    a = jax.device_put(jax.random.normal(ka, (args.m, args.k), dt),
                       NamedSharding(mesh, sa))
    b = jax.device_put(jax.random.normal(kb, (args.k, args.n), dt),
                       NamedSharding(mesh, sb))
    # The in-trace *_tuned wrappers key on PER-SHARD shapes (what they
    # see inside shard_map); mirror that here or the cache never hits.
    if args.op == "ag_gemm":       # A row-sharded, B col-sharded
        key_attrs = {"m": args.m // ndev, "k": args.k,
                     "n": args.n // ndev}
    else:                          # A col-sharded (K), B row-sharded
        key_attrs = {"m": args.m, "k": args.k // ndev, "n": args.n}
    key_attrs.update({"dtype": str(a.dtype), "world": ndev})
    best = tune_spmd(args.op, configs, make_step, (a, b), key_attrs)
    if best is None:
        raise SystemExit(f"no {args.op} config compiled at "
                         f"m={args.m} k={args.k} n={args.n}")
    print(f"tuned {args.op} m={args.m} k={args.k} n={args.n} "
          f"world={ndev}: winner {best}; cache at {tune.cache_path()}")


if __name__ == "__main__":
    main()
