"""Length and sha256 of the StableHLO that a benchmark configuration's
step programs lower to, from the tree given first:

    python scripts/lowered_sums.py <tree> [config[:program] ...]

One line a program: ``<config> <program> <length> <sha256>``, the chunk
program of every prefill bucket with the decode batch aboard
(``chunk-<rows>+<slots>``) and the server's decode program
(``decode``), at the configuration's own sizes (shapes only: nothing is
allocated), for one device of a described ``v5e:2x2`` topology, the
Pallas kernels lowered through Mosaic as on the chip. ``<tree>`` is a
checkout or a ``git archive`` of one; without a configuration, every one
under its ``benchmark/configs``; ``config:program`` lowers that program
alone. Runs on the CPU, chip or none, in seconds.

Two trees whose sums are equal hand the compiler the same programs, so
a change that claims to move no program is checked here, without a chip
(docs/testing.md, "Whether a change moves a program"). The text leaves
out source locations (a Mosaic kernel's serialized module would carry
its callers' files and LINES, which move with any edit above them) and
has the tree's path replaced, so that two copies of one tree agree
wherever they lie. ``DUMP=<dir>`` writes each text to
``<dir>/<config>.<program>.txt`` for ``diff``.
"""

import hashlib
import json
import os
import sys
import types


def main(argv):
    if len(argv) < 2:
        raise SystemExit(__doc__)
    tree = os.path.abspath(argv[1])
    sys.path.insert(0, tree)
    os.chdir(tree)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    jax.config.update("jax_traceback_in_locations_limit", 0)

    import triton_dist_tpu as tdt
    from benchmark.harness import loader
    from triton_dist_tpu.models import dense
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.serving.blocks import pool_shardings
    from triton_dist_tpu.serving.chunked import (ChunkedPrefill,
                                                 greedy_tokens,
                                                 picked_with_stats)
    from triton_dist_tpu.utils import distributed

    if loader.REPO_ROOT != tree or not tdt.__file__.startswith(tree):
        raise SystemExit(f"lowered_sums: {tree} is not the tree that was "
                         f"imported ({tdt.__file__})")
    distributed.platform = lambda: "tpu"      # lower as on the chip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = tdt.make_mesh(tp=1, devices=topo.devices[:1])

    def on_mesh(shapes, specs):
        def one(x, s):
            if not isinstance(s, NamedSharding):
                s = NamedSharding(mesh, s)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        return jax.tree.map(one, shapes, specs,
                            is_leaf=lambda s: isinstance(s, P))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, P()))

    def report(name, program, lowered):
        text = lowered.as_text().replace(tree, "<tree>")
        if os.environ.get("DUMP"):
            with open(os.path.join(os.environ["DUMP"],
                                   f"{name}.{program}.txt"), "w") as f:
                f.write(text)
        print(name, program, len(text),
              hashlib.sha256(text.encode()).hexdigest(), flush=True)

    asked = argv[2:] or sorted(
        f[:-5] for f in os.listdir("benchmark/configs")
        if f.endswith(".json"))
    for arg in asked:
        name, _, only = arg.partition(":")
        with open(f"benchmark/configs/{name}.json") as f:
            config = json.load(f)
        family = loader.load_family(config["family"], [loader.DATA_ROOT])
        build = loader.sibling(family.__file__,
                               config["family"] + "_system")
        cfg = build.model_config(config)
        model = build.engine_kwargs(config).get("model", dense)
        srv, mode = config["serving"], config["engine"]["mode"]
        slots, page = srv["num_slots"], srv["page"]
        p_max = config["engine"]["max_len"] // page
        attn = srv["attn_impl"]
        dtype = jnp.bfloat16

        specs = model.param_specs(cfg, "tp")
        params = on_mesh(jax.eval_shape(lambda: model.init_params(
            jax.random.PRNGKey(0), cfg, dtype)), specs)
        engine = types.SimpleNamespace(
            cfg=cfg, mesh=mesh, axis="tp", mode=mode, model=model,
            model_kwargs={}, _specs=specs,
            ctxs=dense.make_fwd_contexts(MeshContext.from_mesh(mesh), "tp",
                                         256, 256, 512))
        pool_cls, per_token, *keeps = model.paged_pool(cfg)
        keeps = dict(keeps[0]) if keeps else {}
        layers = keeps.pop("layers", cfg.num_hidden_layers)
        ring = {}
        if "window" in keeps:      # sized as the server sizes them
            keeps["window"] = keeps["window"].sized(
                page, max(srv["prefill_buckets"]), slots)
            ring = {"ring": keeps["window"].ring}
        kv_spec = model.paged_cache_specs("tp", **ring)
        kv_sh = pool_shardings(mesh, kv_spec)
        cache = on_mesh(jax.eval_shape(lambda: pool_cls.empty(
            layers, 1 + slots * p_max, page, *per_token, num_slots=slots,
            p_max=p_max, dtype=dtype, **keeps)), kv_sh)

        chunker = ChunkedPrefill(
            engine, kv_sh, srv["prefill_buckets"],
            attn_impl=attn if attn in ("ref", "flash") else "ref",
            decode_rows=slots, decode_attn=attn)
        slot = (ints(),) * bool(getattr(cache, "seq", None))
        for bucket in srv["prefill_buckets"]:
            program = f"chunk-{bucket}+{slots}"
            if only in ("", program):
                report(name, program, chunker._chunk.lower(
                    params, ints(bucket), cache,
                    ints(cache.block_table.shape[1]), ints(), ints(),
                    ints(), *slot, ints(slots)))

        # The server's decode program (``ServingEngine._decode``).
        stats = bool(getattr(model, "STEP_STATS", ())
                     or getattr(model, "ROW_STATS", ()))

        def _decode(p, toks, c):
            out = model.decode_step_paged(
                p, toks, c, cfg, mode=mode, axis="tp", ctxs=engine.ctxs,
                attn_impl=attn)
            if stats:
                return (picked_with_stats(greedy_tokens(out[0]), out[-1]),
                        *out[:-1])
            return (greedy_tokens(out[0]), *out)

        if only in ("", "decode"):
            report(name, "decode", jax.jit(
                jax.shard_map(_decode, mesh=mesh,
                              in_specs=(specs, P(None), kv_spec),
                              out_specs=(P(None), P(None, None), kv_spec),
                              check_vma=False),
                donate_argnums=(2,)).lower(params, ints(slots), cache))


if __name__ == "__main__":
    main(sys.argv)
