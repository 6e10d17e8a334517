"""Batch-serving demo for the dense Qwen3 engine.

Reference analogue: ``test_e2e_inference.py`` / the megakernel
``model_server.py`` chat demo. Runs greedy generation over a token
batch and reports per-token latency; add ``--megakernel`` to run every
decode step as one persistent Pallas kernel per device.

Runs on whatever backend JAX initialises; the tiny presets below are
sized for the CPU interpreter.

Run (CPU mesh): JAX_PLATFORMS=cpu python examples/serve_dense.py
Run (TPU host): python examples/serve_dense.py --tp 4
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--mode", default="fused",
                    choices=["xla", "fused", "fused_ar"])
    ap.add_argument("--megakernel", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="megakernel paged-KV cache (page pool + block "
                         "table) instead of the dense cache")
    ap.add_argument("--model", default="dense",
                    choices=["dense", "qwen_moe"])
    ap.add_argument("--moe-impl", default="tp", choices=["tp", "ep"],
                    help="qwen_moe only: TP experts (ffn-sharded) or EP "
                         "experts (dispatch/combine all-to-all)")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={args.tp}")
    import jax
    import jax.numpy as jnp
    import numpy as np

    import triton_dist_tpu as tdt
    from triton_dist_tpu.models import ModelConfig, Engine
    from triton_dist_tpu.utils.distributed import (enable_compile_cache,
                                                   platform)

    enable_compile_cache()

    # vocab kept small so the megakernel arena stays under the CPU
    # interpret-mode per-buffer limit (docs/testing.md).
    if args.model == "qwen_moe":
        cfg = ModelConfig.tiny_moe(vocab_size=64, num_experts=8)
    else:
        cfg = ModelConfig.tiny(vocab_size=64)
    mesh = tdt.make_mesh(tp=args.tp)
    ids = jax.random.randint(jax.random.PRNGKey(0),
                             (args.batch, args.prompt_len), 0,
                             cfg.vocab_size)

    if args.megakernel:
        from jax.sharding import Mesh
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine

        mesh1d = Mesh(np.array(jax.devices()[:args.tp]), ("tp",))
        max_len = -(-(args.prompt_len + args.gen_len) // 16) * 16
        eng = MegaKernelEngine(cfg, mesh1d, batch=args.batch,
                               max_len=max_len, tile_w=16, t_tile=16,
                               paged=args.paged)
        t0 = time.perf_counter()
        seed = eng.prefill_chain(ids)
        toks = np.asarray(eng.generate(seed, steps=args.gen_len,
                                       start_pos=args.prompt_len - 1))
        dt = time.perf_counter() - t0
    else:
        extra, mode = {}, args.mode
        if args.model == "qwen_moe":
            from triton_dist_tpu.models import qwen_moe

            # MoE serve runs the XLA collectives; the fused MoE blocks
            # are exercised by forward_tokens/tests at these tiny shapes.
            extra = {"model": qwen_moe, "moe_impl": args.moe_impl}
            if args.mode != "xla":
                print(f"note: --model qwen_moe serves in mode=xla "
                      f"(requested --mode {args.mode} applies to the "
                      "dense model only)")
            mode = "xla"
        eng = Engine(cfg, mesh, mode=mode,
                     max_len=args.prompt_len + args.gen_len,
                     block_m=8, block_n=8, block_k=32, **extra)
        t0 = time.perf_counter()
        toks = np.asarray(eng.serve(ids, gen_len=args.gen_len))
        dt = time.perf_counter() - t0

    print("generated tokens:\n", toks)
    print(f"{toks.size} tokens in {dt:.2f}s on platform={platform()} "
          f"({dt / max(toks.shape[1], 1) * 1e3:.1f} ms/step incl. "
          "compile)")


if __name__ == "__main__":
    main()
