"""Streaming serving loop — the reference's megakernel ``model_server.py``
/ chat-demo analogue (``mega_triton_kernel/test/models``), now on the
continuous-batching :class:`~triton_dist_tpu.serving.ServingEngine`.

Reads one prompt of space-separated token ids per line on stdin and
STREAMS the generated ids as they decode (one token per flush — no
more waiting for the full ``--gen-len``). Malformed prompt lines (non-
integer tokens) terminate with a nonzero exit and a diagnostic instead
of a traceback. With ``--hf-dir`` it loads a real local HF checkpoint
(config.json + safetensors) through ``models.hf_loader`` and serves
THAT model (dense or MoE); otherwise a tiny randomly-initialized dense
model. ``--megakernel`` swaps in the persistent-kernel runtime — the
same ServingEngine drives it through the prefill-lane decode batch.

Run: printf '1 2 3\n9 8 7\n' | python examples/chat_server.py --gen-len 8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--slots", type=int, default=2,
                    help="decode-batch width of the serving engine "
                         "(layer path)")
    ap.add_argument("--page", type=int, default=None,
                    help="KV page size (layer path; must divide "
                         "--max-len)")
    ap.add_argument("--hf-dir", default=None,
                    help="local HF checkpoint directory")
    ap.add_argument("--moe-ep", action="store_true",
                    help="serve the tiny MoE model with experts "
                         "sharded over the mesh (EP decode dispatch)")
    ap.add_argument("--transport", default=None,
                    choices=["ar", "ragged", "ll", "ll2d", "auto"],
                    help="EP decode dispatch transport (--moe-ep / MoE "
                         "checkpoints; see docs/serving.md)")
    ap.add_argument("--ep-nodes", type=int, default=1,
                    help="--moe-ep: split the --tp devices into this "
                         "many nodes — a (nodes, tp/nodes) (dp, tp) "
                         "hierarchy whose decode dispatch rides the "
                         "2-hop ll2d transport (docs/serving.md, "
                         "EP-decode hierarchy)")
    ap.add_argument("--replica-slots", type=int, default=0,
                    help="hot-expert replica slots per MoE layer "
                         "(EP decode, transport=ll)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode serving: the "
                         "first half of the tp devices becomes the "
                         "prefill worker, the second half the decode "
                         "worker (one colocated role at --tp 1); "
                         "completed prefills migrate KV pages to the "
                         "decode pool (see docs/serving.md)")
    ap.add_argument("--buckets", default="8,32",
                    help="--disagg/chunked prefill: comma-separated "
                         "chunk-length buckets (the prefill jit cache "
                         "is bounded by their count)")
    ap.add_argument("--attn-impl", default="ref",
                    choices=["ref", "kernel", "flash"],
                    help="layer-path paged attention implementation: "
                         "'ref' gathers dense rows (CPU default, "
                         "token-exact oracle); 'kernel' streams decode "
                         "through the paged flash kernel; 'flash' also "
                         "routes chunked prefill + speculative "
                         "verification through the paged Q-block "
                         "kernel (see docs/serving.md)")
    ap.add_argument("--kv-quant", default="bf16",
                    choices=["bf16", "int8", "fp8"],
                    help="KV pool storage (both lanes): int8/fp8 "
                         "stores pages quantized with per-page scales "
                         "(2-4x capacity, bounded divergence; with "
                         "--megakernel the persistent lane's arena "
                         "pools quantize too; see docs/serving.md)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (both lanes): n-gram "
                         "self-draft + one K-token verification "
                         "dispatch, token-exact greedy outputs (with "
                         "--megakernel: the Q-block verification "
                         "task)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="--spec: candidates per verification "
                         "dispatch (static K; jit cache stays flat)")
    ap.add_argument("--kv-tiers", action="store_true",
                    help="layer path: arm the tiered KV memory "
                         "hierarchy — cold committed prefix pages "
                         "demote into a host-RAM tier (scored "
                         "eviction) and prefetch back on reuse, and "
                         "park/resume become serving verbs (see "
                         "docs/serving.md, 'KV memory hierarchy')")
    ap.add_argument("--tier-host-pages", type=int, default=64,
                    help="--kv-tiers: host-tier capacity in pool "
                         "pages")
    ap.add_argument("--park-after-idle", type=int, default=0,
                    metavar="TICKS",
                    help="--kv-tiers: once a running request has "
                         "decoded for N consecutive ticks, park it "
                         "(KV offloaded, slot released) and resume "
                         "it on the next tick — the deterministic "
                         "park/resume drill (token streams stay "
                         "bit-identical to an uninterrupted serve; "
                         "scripts/tier_smoke.sh gates on it)")
    ap.add_argument("--fleet", type=int, default=0, metavar="R",
                    help="layer path: serve through a FleetRouter "
                         "over R replicated serving fleets (prefix-"
                         "affinity routing, health feedback, fleet "
                         "failover — docs/serving.md, 'Fleet "
                         "serving'); prefix_reuse is forced on. "
                         "Combine with --kv-tiers for the parked-tier "
                         "cross-fleet failover path")
    ap.add_argument("--kill-fleet-after", type=int, default=0,
                    metavar="N",
                    help="--fleet: once N tokens have been generated, "
                         "kill one live fleet MID-SERVE (reachable — "
                         "running sessions fail over cross-fleet) and "
                         "keep serving; token streams stay "
                         "bit-identical to an unkilled run "
                         "(scripts/fleet_smoke.sh gates on it)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot the full serving state (paged "
                         "pools + scales, allocator, queue, counters; "
                         "--megakernel: the arena by schema) here on "
                         "SIGTERM, and RESUME from an existing "
                         "snapshot on startup — restored requests "
                         "finish token-exact mid-stream "
                         "(docs/serving.md, checkpoint/restore)")
    ap.add_argument("--checkpoint-after", type=int, default=0,
                    help="drill flag for the SIGTERM path: checkpoint "
                         "and exit through the same code path after N "
                         "tokens generated this process (deterministic "
                         "— scripts/chaos_smoke.sh uses it)")
    ap.add_argument("--telemetry", default=None,
                    choices=["off", "counters", "spans"],
                    help="serving telemetry level (docs/observability"
                         ".md): counters = latency histograms only "
                         "(default); spans = full per-request span "
                         "timeline. --trace-out implies spans unless "
                         "overridden")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="dump the merged Perfetto trace (host spans "
                         "+ megakernel slot records), the xprof capture "
                         "(which holds the host spans as tdt.* "
                         "annotations beside the device's operations) "
                         "and a metrics.json snapshot into DIR "
                         "on exit and on SIGTERM, and print the "
                         "one-line 'obs:' latency summary")
    ap.add_argument("--slo", action="store_true",
                    help="layer path: arm the multi-tenant SLO "
                         "scheduling layer — per-tenant bounded "
                         "queues, deadline classes, weighted fair "
                         "share, and priority preemption "
                         "(docs/serving.md, 'Multi-tenant SLO "
                         "scheduling'). Prompts carry a tenant via "
                         "an '@NAME ' line prefix or --tenants")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="--slo: label stdin prompts with tenants "
                         "t0..t{N-1} round-robin (lines with an "
                         "explicit '@NAME ' prefix keep their own)")
    ap.add_argument("--tenant-quota", action="append", default=[],
                    metavar="NAME=TOKENS",
                    help="--slo: give NAME a decode-token quota "
                         "bucket refilling at TOKENS/s (repeatable; "
                         "an exhausted tenant queues, it is never "
                         "failed)")
    ap.add_argument("--megakernel", action="store_true")
    ap.add_argument("--mk-model", default="dense",
                    choices=["dense", "moe", "hybrid"],
                    help="--megakernel only: which family the one-"
                         "kernel runtime serves")
    ap.add_argument("--mk-chunked", action="store_true",
                    help="--megakernel: admit prompts through the "
                         "bucketed WRITE_KV_CHUNK/ATTN_CHUNK prefill-"
                         "chunk tasks (chunk lengths from --buckets) "
                         "instead of the one-token-per-tick prefill "
                         "lane (see docs/megakernel.md, 'Chunked "
                         "prefill')")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.tp}")
    import jax
    import numpy as np

    import triton_dist_tpu as tdt
    from triton_dist_tpu.models import Engine, ModelConfig, qwen_moe
    from triton_dist_tpu.serving import QueueFullError, ServingEngine
    from triton_dist_tpu.utils.distributed import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp

    if args.hf_dir and args.megakernel:
        sys.exit("--megakernel serves the built-in tiny model only; "
                 "drop one of --hf-dir/--megakernel")
    if args.disagg and (args.megakernel or args.moe_ep
                        or args.transport or args.replica_slots):
        sys.exit("--disagg splits the layer path's dense/HF serving; "
                 "it does not combine with --megakernel or the EP "
                 "decode knobs")
    if args.megakernel and (args.transport or args.replica_slots):
        sys.exit("--transport/--replica-slots route the layer path's "
                 "EP decode dispatch; the megakernel serves experts "
                 "in-kernel (use --moe-ep without --megakernel)")
    if args.megakernel and args.mk_model == "hybrid" and (
            args.kv_quant != "bf16" or args.spec or args.mk_chunked):
        sys.exit("--kv-quant/--spec/--mk-chunked cover the attention "
                 "families; the hybrid GDN recurrent state is neither "
                 "paged nor rewindable (see docs/serving.md)")
    if args.mk_chunked and not args.megakernel:
        sys.exit("--mk-chunked routes the megakernel's prefill-chunk "
                 "tasks; the layer path gets chunked prefill from "
                 "--disagg or ServingEngine(prefill_buckets=...)")
    if args.megakernel and args.attn_impl != "ref":
        sys.exit("--attn-impl routes the layer path's paged "
                 "attention; the megakernel's attention task has its "
                 "own in-arena lane (see docs/serving.md)")
    if args.checkpoint_after and not args.checkpoint_dir:
        sys.exit("--checkpoint-after needs --checkpoint-dir (it is the "
                 "deterministic drill for that snapshot path)")
    if args.megakernel and args.kv_tiers:
        sys.exit("--kv-tiers routes the layer path's paged pool; the "
                 "megakernel's KV lives in its in-kernel arena "
                 "(see docs/serving.md)")
    if args.park_after_idle and not args.kv_tiers:
        sys.exit("--park-after-idle needs --kv-tiers (parking "
                 "offloads into the tier store)")
    if args.kill_fleet_after and args.fleet < 2:
        sys.exit("--kill-fleet-after needs --fleet >= 2 (killing the "
                 "last live fleet has nowhere to fail over to)")
    if args.fleet and (args.megakernel or args.disagg or args.moe_ep
                       or args.transport or args.replica_slots):
        sys.exit("--fleet fronts replicated layer-path ServingEngines;"
                 " it does not combine with --megakernel/--disagg or "
                 "the EP decode knobs")
    if args.fleet and (args.checkpoint_dir or args.trace_out
                       or args.park_after_idle):
        sys.exit("--fleet does not combine with --checkpoint-dir/"
                 "--trace-out/--park-after-idle (those drive one "
                 "engine; the router has scale_to/kill_fleet drills "
                 "instead)")
    if args.slo and args.megakernel:
        sys.exit("--slo arbitrates the layer path's decode slots; the "
                 "megakernel's persistent lane schedules its own "
                 "(see docs/serving.md)")
    if (args.tenants or args.tenant_quota) and not args.slo:
        sys.exit("--tenants/--tenant-quota need --slo (they configure "
                 "the SLO scheduling layer)")
    slo_specs = []
    for q in args.tenant_quota:
        name, sep, tok = q.partition("=")
        if not sep or not name:
            sys.exit(f"--tenant-quota {q!r}: expected NAME=TOKENS")
        try:
            slo_specs.append({"name": name,
                              "decode_quota": float(tok)})
        except ValueError:
            sys.exit(f"--tenant-quota {q!r}: TOKENS must be a number")
    # Layer-path serving knobs shared by every engine construction
    # below: attention impl, quantized KV pools, speculative decode.
    telemetry = args.telemetry or ("spans" if args.trace_out
                                   else "counters")
    serve_kw = dict(kv_dtype=args.kv_quant,
                    attn_impl=args.attn_impl,
                    spec_k=args.spec_k if args.spec else 0,
                    telemetry=telemetry,
                    kv_tiers=({"host_pages": args.tier_host_pages}
                              if args.kv_tiers else None),
                    slo=({"specs": slo_specs} if args.slo else None))
    def build_disagg(cfg, params, model_kw):
        """Two engines over split tp halves (or one colocated role at
        tp=1) sharing ONE weight pytree, wrapped in the disaggregated
        serving engine — chunked prefill + KV page migration."""
        from triton_dist_tpu.serving import DisaggServingEngine

        buckets = tuple(int(b) for b in args.buckets.split(","))
        devs = jax.devices()
        if args.tp >= 2:
            half = args.tp // 2
            pf_mesh = tdt.make_mesh(tp=half, devices=devs[:half])
            dec_mesh = tdt.make_mesh(tp=args.tp - half,
                                     devices=devs[half:args.tp])
        else:
            pf_mesh = dec_mesh = tdt.make_mesh(tp=1, devices=devs[:1])
        kw = dict(mode="xla", max_len=args.max_len, params=params,
                  **model_kw)
        pf_eng = Engine(cfg, pf_mesh, **kw)
        dec_eng = (pf_eng if pf_mesh is dec_mesh
                   else Engine(cfg, dec_mesh, **kw))
        return DisaggServingEngine(
            dec_eng, prefill_engine=pf_eng, num_slots=args.slots,
            page=args.page, prefill_buckets=buckets, **serve_kw)

    if args.fleet and args.hf_dir:
        sys.exit("--fleet serves the built-in tiny dense model "
                 "(replicated fleets share one weight pytree); drop "
                 "one of --fleet/--hf-dir")
    if args.fleet:
        from triton_dist_tpu.serving import FleetRouter

        cfg = ModelConfig.tiny(vocab_size=128)
        mesh = tdt.make_mesh(tp=args.tp, devices=jax.devices()[:args.tp])
        eng = Engine(cfg, mesh, mode="xla", max_len=args.max_len)

        # Every fleet shares the one Engine (weights + prefill jit)
        # but owns its pools, scheduler, and tier store — the
        # replicated-fleet shape. prefix_reuse forced on: the chained
        # content keys are the affinity signal.
        def fleet_factory():
            return ServingEngine(eng, num_slots=args.slots,
                                 page=args.page, prefix_reuse=True,
                                 **serve_kw)

        srv = FleetRouter(fleet_factory, fleets=args.fleet)
    elif args.hf_dir:
        from triton_dist_tpu.models.hf_loader import load_hf_checkpoint

        cfg, params = load_hf_checkpoint(args.hf_dir, dtype=jnp.float32)
        if not cfg.is_moe and (args.moe_ep or args.transport
                               or args.replica_slots):
            sys.exit(f"{args.hf_dir} is not a MoE checkpoint; "
                     "--moe-ep/--transport/--replica-slots need one")
        model_kw = ({"model": qwen_moe} if cfg.is_moe else {})
        if args.disagg:
            srv = build_disagg(cfg, params, model_kw)
        else:
            mesh = tdt.make_mesh(tp=args.tp,
                                 devices=jax.devices()[:args.tp])
            if cfg.is_moe and (args.moe_ep or args.transport
                               or args.replica_slots):
                model_kw.update(moe_impl="ep",
                                ep_transport=args.transport)
            eng = Engine(cfg, mesh, mode="xla", max_len=args.max_len,
                         params=params, **model_kw)
            srv = ServingEngine(eng, num_slots=args.slots,
                                page=args.page,
                                replica_slots=args.replica_slots,
                                **serve_kw)
    elif args.moe_ep or args.transport or args.replica_slots:
        # --transport / --replica-slots imply the EP-MoE tiny model:
        # silently serving the dense model would drop the knobs.
        cfg = ModelConfig.tiny_moe(vocab_size=128, num_experts=8)
        ep_kw = {}
        if args.ep_nodes > 1:
            # Forced (nodes, chips) hierarchy on the host mesh: dp
            # plays the DCN axis, tp the ICI axis — the decode
            # dispatch resolves to the 2-hop ll2d transport.
            if args.tp % args.ep_nodes:
                sys.exit(f"--ep-nodes {args.ep_nodes} must divide "
                         f"--tp {args.tp}")
            mesh = tdt.make_mesh(dp=args.ep_nodes,
                                 tp=args.tp // args.ep_nodes,
                                 devices=jax.devices()[:args.tp])
            ep_kw["ep_axis"] = ("dp", "tp")
        else:
            mesh = tdt.make_mesh(tp=args.tp,
                                 devices=jax.devices()[:args.tp])
        eng = Engine(cfg, mesh, mode="xla", max_len=args.max_len,
                     model=qwen_moe, moe_impl="ep",
                     ep_transport=args.transport, **ep_kw)
        srv = ServingEngine(eng, num_slots=args.slots, page=args.page,
                            replica_slots=args.replica_slots,
                            **serve_kw)
    elif args.megakernel:
        from jax.sharding import Mesh
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine

        if args.mk_model == "moe":
            cfg = ModelConfig.tiny_moe(vocab_size=128, num_experts=8)
        elif args.mk_model == "hybrid":
            cfg = ModelConfig.tiny_next(vocab_size=128,
                                        num_key_value_heads=4,
                                        full_attn_interval=2)
        else:
            cfg = ModelConfig.tiny(vocab_size=128)
        mesh1d = Mesh(np.array(jax.devices()[:args.tp]), ("tp",))
        # One engine for the whole session; the ServingEngine streams
        # prompts through its prefill lane, so slot count = batch.
        # Quantized KV, speculation, and checkpointing all ride the
        # PAGED arena (per-page scales / block-table verification /
        # schema snapshots); the plain run keeps the original dense
        # cache.
        mk_paged = bool(args.kv_quant != "bf16" or args.spec
                        or args.checkpoint_dir or args.mk_chunked)
        mk_buckets = (tuple(int(b) for b in args.buckets.split(","))
                      if args.mk_chunked else None)
        mk_kw = {}
        if mk_paged:
            page = 16
            if args.max_len % page:
                sys.exit(f"--megakernel with serving knobs pages the "
                         f"arena at {page} tokens; --max-len must be "
                         f"a multiple of {page}")
            mk_kw = dict(paged=True, page=page,
                         num_pages=args.tp * (args.max_len // page) + 1,
                         kv_dtype=args.kv_quant,
                         spec_k=args.spec_k if args.spec else 0,
                         prefill_buckets=mk_buckets)
            if args.spec:
                # The scoreboard claims hot verification chains first.
                mk_kw["schedule"] = "dynamic"
        mk = MegaKernelEngine(cfg, mesh1d, batch=args.tp,
                              max_len=args.max_len, tile_w=16,
                              t_tile=16,
                              profile=bool(args.trace_out), **mk_kw)
        srv = ServingEngine(mk, telemetry=telemetry,
                            kv_dtype=args.kv_quant,
                            spec_k=args.spec_k if args.spec else 0,
                            prefill_buckets=mk_buckets)
    elif args.disagg:
        from triton_dist_tpu.models import dense

        cfg = ModelConfig.tiny(vocab_size=128)
        params = dense.init_params(jax.random.PRNGKey(0), cfg)
        srv = build_disagg(cfg, params, {})
    else:
        cfg = ModelConfig.tiny(vocab_size=128)
        mesh = tdt.make_mesh(tp=args.tp, devices=jax.devices()[:args.tp])
        eng = Engine(cfg, mesh, mode="xla", max_len=args.max_len)
        srv = ServingEngine(eng, num_slots=args.slots, page=args.page,
                            **serve_kw)

    # Telemetry dump wiring (--trace-out): ONE trace session covers
    # the whole serve; on exit (and on SIGTERM, alongside the
    # checkpoint path below) the merged Perfetto trace + a
    # metrics.json snapshot land in the session directory and a
    # one-line latency summary prints.
    tracing = {"ctx": None, "sess": None, "dumped": False}
    if args.trace_out:
        ctx = srv.trace("chat", out_dir=args.trace_out)
        tracing["sess"] = ctx.__enter__()
        tracing["ctx"] = ctx

    def _obs_line(st):
        lat = st.get("latency") or {}

        def pct(series, q):
            v = (lat.get(series) or {}).get(q)
            return "n/a" if v is None else f"{v:.1f}ms"

        return (f"obs: ttft_p50={pct('ttft_ms', 'p50')} "
                f"ttft_p99={pct('ttft_ms', 'p99')} "
                f"itl_p50={pct('itl_ms', 'p50')} "
                f"itl_p99={pct('itl_ms', 'p99')} "
                f"telemetry={st.get('telemetry')}")

    def _dump_obs():
        if tracing["dumped"]:
            return
        tracing["dumped"] = True
        st = srv.stats()
        if tracing["ctx"] is not None:
            tracing["ctx"].__exit__(None, None, None)
            sess = tracing["sess"]
            merged = sess.export()
            metrics = sess.export_metrics(st)
            print(f"trace: merged={merged} metrics={metrics}",
                  flush=True)
        # The obs: line is opt-in (--trace-out / --telemetry): default
        # runs keep their pre-existing stdout contract.
        if ((args.trace_out or args.telemetry)
                and st.get("latency") is not None):
            print(_obs_line(st), flush=True)

    # Checkpoint/restore wiring (layer path): a SIGTERM mid-serve
    # snapshots the full serving state between ticks; a restart with
    # the same flags resumes every in-flight request token-exact.
    ckpt_path = None
    stop = {"flag": False, "serving": False}

    def _snapshot_and_exit():
        from triton_dist_tpu.serving.server import save_checkpoint

        save_checkpoint(srv.checkpoint(), ckpt_path)
        inflight = len(srv.sched.queue) + len(srv.sched.slots)
        print(f"\ncheckpointed {inflight} in-flight "
              f"request(s) to {ckpt_path}", flush=True)
        _dump_obs()
        sys.exit(0)

    if args.checkpoint_dir or args.trace_out:
        import signal

        def _on_term(signum, frame):
            # Mid-serve: only set the flag — the snapshot/dump happens
            # at the next tick boundary where the state is consistent.
            # Idle (blocked on stdin): the engine IS at a boundary, so
            # act right here — otherwise Python's EINTR retry resumes
            # the readline and the signal is swallowed.
            stop["flag"] = True
            if not stop["serving"]:
                if ckpt_path:
                    _snapshot_and_exit()
                _dump_obs()
                sys.exit(0)

        if args.checkpoint_dir:
            os.makedirs(args.checkpoint_dir, exist_ok=True)
            ckpt_path = os.path.join(args.checkpoint_dir,
                                     "serving.ckpt")
        signal.signal(signal.SIGTERM, _on_term)

    def _checkpoint_tick():
        if not (ckpt_path or stop["flag"]):
            return
        done_here = (srv.stats_counters["tokens_generated"]
                     - tokens_at_start)
        if ckpt_path and (stop["flag"] or (
                args.checkpoint_after
                and done_here >= args.checkpoint_after)):
            _snapshot_and_exit()
        elif stop["flag"]:
            # --trace-out without a checkpoint dir: SIGTERM still
            # drains the telemetry at the tick boundary.
            _dump_obs()
            sys.exit(0)

    # --park-after-idle drill: a running request that has decoded for
    # N consecutive ticks parks (KV offloaded wholesale, slot free)
    # and resumes on the next tick — once per request, so the stream
    # always finishes. Token output is bit-identical to an
    # uninterrupted serve (the tier_smoke gate).
    park_state = {"age": {}, "done": set()}

    def _park_tick():
        if not args.park_after_idle:
            return
        for h in list(srv.sched.running()):
            rid = h.request.request_id
            if (h.status != "running" or not h.tokens
                    or rid in park_state["done"]):
                continue
            age = park_state["age"].get(rid, 0) + 1
            park_state["age"][rid] = age
            if age >= args.park_after_idle:
                try:
                    srv.park(h)
                except Exception as e:  # noqa: BLE001 — drill only
                    print(f"[park skipped: {e}]", file=sys.stderr,
                          flush=True)
                    park_state["done"].add(rid)
                    continue
                park_state["done"].add(rid)
                srv.resume(h)

    # --kill-fleet-after drill: once N tokens have streamed, one live
    # fleet dies MID-SERVE (reachable: running sessions park into its
    # tier and hop to a survivor — or re-prefill without tiers). Fires
    # once; streams stay bit-identical to an unkilled run.
    fleet_kill = {"done": False}

    def _fleet_tick():
        if not args.kill_fleet_after or fleet_kill["done"]:
            return
        done_tokens = sum(f.engine.stats_counters["tokens_generated"]
                          for f in srv.fleets)
        if done_tokens < args.kill_fleet_after:
            return
        live = srv._live_fleets()
        if len(live) < 2:
            fleet_kill["done"] = True
            return
        # Prefer a fleet with live work so the kill actually
        # exercises the cross-fleet failover path.
        victim = next((f for f in live if f.engine.sched.slots),
                      live[-1])
        srv.kill_fleet(victim.id, reachable=True)
        fleet_kill["done"] = True
        print(f"[fleet {victim.id} killed mid-serve: failed over]",
              file=sys.stderr, flush=True)

    def run_serving():
        stop["serving"] = True
        try:
            srv.run(on_tick=lambda: (_park_tick(), _checkpoint_tick(),
                                     _fleet_tick()))
        finally:
            stop["serving"] = False

    restored_handles = []
    if ckpt_path and os.path.exists(ckpt_path):
        from triton_dist_tpu.serving.server import load_checkpoint

        restored_handles = srv.restore(load_checkpoint(ckpt_path))
        os.remove(ckpt_path)   # consumed; SIGTERM writes a fresh one
        print(f"restored {len(restored_handles)} in-flight "
              f"request(s) from {ckpt_path}", flush=True)
    tokens_at_start = (srv.stats_counters["tokens_generated"]
                       if hasattr(srv, "stats_counters") else 0)
    if restored_handles:
        run_serving()
        for h in restored_handles:
            # FULL token list (pre-kill + post-restore) — the
            # token-exactness gate diffs this against a clean run.
            print(f"[restored {h.request.request_id}] "
                  + " ".join(str(t) for t in h.tokens), flush=True)

    print(f"serving {cfg.model_name} (vocab {cfg.vocab_size}); one "
          "prompt of space-separated token ids per line:", flush=True)
    n_prompts = 0
    for lineno, line in enumerate(sys.stdin, 1):
        parts = line.split()
        if not parts:
            continue
        # '@NAME ' prefix routes the prompt to that tenant (--slo);
        # otherwise --tenants N labels prompts t0..t{N-1} round-robin.
        tenant = None
        if parts[0].startswith("@") and len(parts[0]) > 1:
            tenant = parts[0][1:]
            parts = parts[1:]
            if not parts:
                continue
        elif args.tenants:
            tenant = f"t{n_prompts % args.tenants}"
        n_prompts += 1
        try:
            ids = [int(t) % cfg.vocab_size for t in parts]
        except ValueError as e:
            print(f"error: line {lineno} is not space-separated token "
                  f"ids ({e})", file=sys.stderr, flush=True)
            sys.exit(2)

        print("->", end="", flush=True)

        def stream(tok, handle):
            print(f" {tok}", end="", flush=True)

        try:
            srv.submit(ids, max_new_tokens=args.gen_len,
                       stream_cb=stream, tenant=tenant)
        except (ValueError, QueueFullError) as e:
            # Too long for the configured capacity (or a tenant's own
            # backpressure): skip the request, keep the server alive
            # (old behaviour, same message spot).
            print(f" [skipped: {e}]", flush=True)
            continue
        run_serving()
        print(flush=True)

    # One-line serving summary on exit — the load data used to be
    # collected and silently dropped.
    st = srv.stats()
    line = (f"served {st['completed']} request(s), "
            f"{st['tokens_generated']} tokens, "
            f"{st['decode_dispatches']} decode dispatches")
    if st.get("dispatch_transport"):
        line += f", transport={st['dispatch_transport']}"
    if st.get("prefill_buckets"):
        line += (f", prefill_chunks={st['prefill_chunks']} "
                 f"(buckets {st['prefill_buckets']}, "
                 f"jit entries {st['prefill_cache_size']})")
    if st.get("migration_transport"):
        line += (f", roles={st['roles']}, "
                 f"migration={st['migration_transport']}, "
                 f"migrated_pages={st['migrated_pages']}")
    if st.get("attn_impl") not in (None, "ref") or st.get(
            "chunk_attn") not in (None, "ref"):
        line += (f", attn={st['attn_impl']}"
                 f" (chunk/verify {st['chunk_attn']})")
    if st.get("kv_dtype") not in (None, "bf16"):
        line += (f", kv_dtype={st['kv_dtype']} "
                 f"({st['kv_bytes_per_token']:.0f} B/token)")
    if args.megakernel:
        # Lane-capability line: smoke scripts gate on this instead of
        # grepping tracebacks for the old layer-path-only rejects.
        line += (f", mk: kv_dtype={st['mk_kv_dtype']} "
                 f"spec={st['mk_spec']} checkpointable="
                 f"{'yes' if st['mk_checkpointable'] else 'no'} "
                 f"chunked={st['mk_chunked_prefill'] or 'no'}")
    if args.kv_tiers:
        rate = st.get("kv_hot_hit_rate")
        line += (f", tiers: offloaded={st['offloaded_pages']} "
                 f"resumed={st['resumes']} "
                 f"hit-rate={'n/a' if rate is None else f'{rate:.2f}'}"
                 f" (tier_pages={st['tier_pages']} "
                 f"parked={st['parked_sessions']})")
    if args.fleet:
        ar = st.get("router_affinity_hit_rate")
        line += (f", fleet: routed={st['routed']} "
                 f"failovers={st['fleet_failovers']} "
                 f"(resumed={st['failover_resumed']} "
                 f"reprefilled={st['failover_reprefilled']}) "
                 f"shed={st['shed_requests']} "
                 f"affinity-hit-rate="
                 f"{'n/a' if ar is None else f'{ar:.2f}'} "
                 f"live={st['live_fleets']}/{len(srv.fleets)}")
    if args.slo:
        at = st.get("slo_attainment")
        tn = (st.get("slo") or {}).get("tenants") or {}
        per_lat = ((st.get("latency") or {}).get("per_tenant")
                   or {})
        line += (f", slo: attainment="
                 f"{'n/a' if at is None else f'{at:.2f}'} "
                 f"preemptions={st['slo_preemptions']} "
                 f"tenants={len(tn)}")
        for name in sorted(tn):
            t = tn[name]
            p99 = ((per_lat.get(name) or {}).get("ttft_ms")
                   or {}).get("p99")
            line += (f" {name}(released={t['released']} "
                     f"preempted={t['preempted']} p99-ttft="
                     f"{'n/a' if p99 is None else f'{p99:.0f}ms'})")
    if (st["retries"] or st["failovers"] or st["restored_requests"]
            or args.checkpoint_dir):
        line += (f", ft: retries={st['retries']} "
                 f"failovers={st['failovers']} "
                 f"restored={st['restored_requests']}")
    if st.get("spec"):
        sp = st["spec"]
        rate = sp["accept_rate"]
        line += (f", spec k={sp['k']} "
                 f"(accept={'n/a' if rate is None else f'{rate:.2f}'}, "
                 f"{sp['tokens_per_dispatch']:.2f} tok/dispatch)")
    if st.get("expert_load") is not None:
        load = st["expert_load"]
        hot = max(range(len(load)), key=load.__getitem__)
        tot = st["expert_totals"]
        share = tot[hot] / max(sum(tot), 1)
        line += (f"; expert-load: hot=e{hot} "
                 f"({share:.2f} of routed traffic), "
                 f"totals={tot}")
        if st.get("replicated_experts"):
            line += (", replicas=" + ",".join(
                f"e{e}->r{r}"
                for e, r in sorted(st["replicated_experts"].items())))
    print(line, flush=True)
    _dump_obs()


if __name__ == "__main__":
    main()
