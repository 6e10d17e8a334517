#!/usr/bin/env python3
"""Chip smoke: Qwen3-8B served through ``Engine`` -> ``ServingEngine`` on
the TPU, plus the fused TP kernels' ring schedules — the quickest proof
that the system still starts on the chip.

    python chip_smoke.py            # phases A, B; C and M too with >= 4 chips

- **A** (one chip): ``make_mesh(tp=1)`` -> ``Engine(qwen3_8b, mode="fused",
  bf16)`` -> ``Engine.serving(attn_impl="flash")``, both admission forms
  (monolithic prefill at fixed prompt lengths, then bucketed chunked
  prefill), more requests than decode slots, every request ``done``,
  logits against the ``mode="xla"`` / ``attn_impl="ref"`` lane.
- **B** (one chip): ``ag_gemm`` / ``gemm_rs`` / ``gemm_ar`` compiled by
  Mosaic through their ``sim_ranks`` self-ring at the model's MLP
  shapes, each against the plain GEMM its contract states.
- **C** (four chips): the same serving set at TP=4, full depth — the
  real rings over ICI — plus a one-hop ``p2p_put`` against
  ``lax.ppermute`` and a check that weights and KV pool are spread over
  the four chips as ``param_specs`` says.
- **M** (four chips): the megakernel lane — ``MegaKernelEngine`` at the
  same widths and TP=4, a few decode steps as one persistent kernel
  each, logits against the layer lane over the same float32 weights.

With fewer than four chips C and M print ``skipped``, which is a line
and not a pass.

There is no CPU mode: without a TPU the script exits non-zero. ONE
process holds the chips — every phase runs in this process, one after
another, and each frees its engines before the next starts; nothing is
spawned. A phase that fails raises; nothing turns that into a warning.
The last line of stdout is one JSON object, ``{"ok": true, "device":
{"platform", "kind", "count"}}`` with the device as JAX reports it and
no other key; the ``summary:`` line before it carries the phases'
results. ``tests/test_chip_smoke.py`` calls the same phase functions
at a tiny preset on the CPU mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import triton_dist_tpu as tdt
from triton_dist_tpu.models import Engine, ModelConfig
from triton_dist_tpu.utils.distributed import (enable_compile_cache,
                                               use_interpret)

# The contract gives 1200 s; past this the run is a hang — dump every
# thread's stack and die non-zero rather than be killed silently.
DEADLINE_S = 1150
# Megakernel phase depth. While the float32 arena is packed, the float32
# weights, the arena and a temporary of its size are resident. One chip
# cannot hold that at these widths at any depth (16.1 GiB with one
# layer, PERF.md); at TP=4 twelve layers are ~11.7 of a chip's 15.75 GiB.
MK_LAYERS = 12


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase needs besides the model config and the mesh.
    The defaults are the chip run; the CPU test passes tiny ones."""
    dtype: object = jnp.bfloat16
    max_len: int = 1152
    page: int = 128
    num_slots: int = 4
    gen: int = 24
    # Monolithic admission compiles one prefill per DISTINCT length, so
    # these are fixed: three lengths, three compiles. All are multiples
    # of 8 (the fused ring kernels' row tile at TP > 1); two are not
    # multiples of 128, so the TPU-only flash-attention branch of
    # layers/tp_attn.sdpa also runs its padded form.
    mono_prompts: tuple = (256, 520, 1000, 256, 520, 1000)
    # Chunked admission takes any length: the jit cache is bounded by
    # the buckets, not by the prompts.
    chunk_prompts: tuple = (200, 333, 777, 1000, 90, 611)
    buckets: tuple = (128, 512)
    block_m: int = 256
    block_n: int = 256
    block_k: int = 512
    # Phase B: prefill M for ag_gemm / gemm_rs (gemm_ar runs at
    # M = num_slots, the decode shape) and the simulated ring size.
    ring_m: int = 2048
    sim_ranks: int = 4
    # Agreement bounds, as |a - b|_max / |b|_max over a logits row.
    # Both lanes read the same bf16 weights and accumulate in f32; they
    # differ in where activations round to bf16 (the fused kernels keep
    # f32 partials across the reduction, XLA's psum of bf16 does not)
    # and in the flash kernels' online softmax. On the chip the worst
    # row is 9e-3 at 26 layers (PERF.md); 3e-2 leaves room for depth.
    # A lane that ran other weights, skipped a layer or dropped a
    # rank's partial is off by O(1).
    logit_tol: float = 3e-2
    # ag_gemm / gemm_rs / gemm_ar against jnp.dot of the same bf16
    # operands with f32 accumulation: one bf16 rounding of the output.
    gemm_tol: float = 1e-2
    # Megakernel against the layer lane, both float32 in memory: they
    # differ in summation order and in where the MXU's default
    # precision rounds an f32 operand to bf16 (per 128-wide tile in the
    # megakernel, per whole GEMM in XLA).
    mk_tol: float = 3e-2
    # Megakernel tile (weights and KV): None = its 128 defaults; the
    # tiny CPU preset needs tiles no wider than its hidden size.
    mk_tile: object = None


def device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(dev: dict) -> str:
    """The last line of stdout of a run whose every phase passed (a
    failed phase raises before it). The driver reads it and takes
    exactly these keys — ``ok`` and ``device`` with ``platform``,
    ``kind``, ``count`` — and no others; everything else the run has to
    say goes on the ``summary:`` line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}})


def _log(msg: str) -> None:
    print(msg, flush=True)


def _rel_err(got, want, tol: float, what: str) -> float:
    """max|got - want| / max|want|, checked: same shape, finite, within
    ``tol`` — or an AssertionError naming ``what``. Returned rounded to
    four figures (it is printed)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"{what}: shape {got.shape} vs {want.shape}, "
                             "or not finite")
    err = float(np.max(np.abs(got - want))
                / max(float(np.max(np.abs(want))), 1e-30))
    if err > tol:
        raise AssertionError(f"{what}: {err:.3e} from its reference "
                             f"(tolerance {tol:.1e})")
    return float(f"{err:.3e}")


def _mem_gib(mesh, key: str):
    """``memory_stats()[key]`` of every mesh device in GiB, or None
    where the backend reports none (the CPU test mesh)."""
    stats = [d.memory_stats() for d in mesh.devices.flat]
    if not all(s and key in s for s in stats):
        return None
    return [round(s[key] / 2**30, 2) for s in stats]


# Seconds XLA and Mosaic spent compiling so far, or fetching compiled
# programs from the persistent cache — each phase reports its share.
# (Tracing and lowering the unrolled layers is host time on top; the
# phase's "seconds" has everything.)
_COMPILE_S = [0.0]


def _on_duration(name: str, secs: float, **_) -> None:
    if name in ("/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
        _COMPILE_S[0] += secs


# ---------------------------------------------------------------------------
# Serving (phases A and C)
# ---------------------------------------------------------------------------

def _prompts(lengths, vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lengths]


class _LogitTap:
    """Stands in for ``ServingEngine._pick`` on ONE server instance:
    records every logits row the server samples from, keyed
    ``(request_id, step)``. With ``forced`` (the other lane's recorded
    tokens) it answers with that lane's token instead of its own
    arg-max, so both lanes decode one token history and their logits
    stay comparable row for row — with random weights the arg-max flips
    on rounding, and from a flip on two free-running lanes diverge."""

    def __init__(self, forced=None):
        self.rows = {}
        self.tokens = {}
        self.forced = forced

    def __call__(self, logits_row, req, step):
        key = (req.request_id, step)
        self.rows[key] = np.array(logits_row, np.float32)
        tok = (self.forced[key] if self.forced is not None
               else int(np.argmax(logits_row)))
        self.tokens[key] = tok
        return tok


def _serve(engine, prompts, sizes: Sizes, *, attn_impl, buckets, tap,
           tag):
    """Submit ``prompts`` (more than there are slots), run to idle
    through the public serving API, and return the jit-cache sizes."""
    srv = engine.serving(num_slots=sizes.num_slots, page=sizes.page,
                         attn_impl=attn_impl, prefill_buckets=buckets)
    srv._pick = tap
    streamed = {}
    handles = [
        srv.submit(p, max_new_tokens=sizes.gen, request_id=f"{tag}-{i}",
                   stream_cb=lambda tok, h: streamed.setdefault(
                       h.request.request_id, []).append(tok))
        for i, p in enumerate(prompts)]
    assert len(handles) > sizes.num_slots, "admission must happen mid-flight"
    srv.run()
    _log(f"  {tag}: {engine.mode}/{attn_impl} served {len(handles)} requests")
    for h in handles:
        if h.status != "done":
            raise RuntimeError(f"request {h.request.request_id} ended "
                               f"{h.status}: {h.error!r}")
        assert len(h.tokens) == sizes.gen, (h.request.request_id, h.tokens)
        assert streamed[h.request.request_id] == h.tokens
    st = srv.stats()
    assert st["tokens_generated"] == sizes.gen * len(prompts), st
    return {"decode_cache": srv.decode_cache_size(),
            "prefill_cache": srv.prefill_cache_size(),
            "decode_dispatches": st["decode_dispatches"]}


def _compare(test: _LogitTap, ref: _LogitTap, tol: float) -> float:
    assert test.rows.keys() == ref.rows.keys() and ref.rows
    return max(_rel_err(test.rows[key], want, tol,
                        f"logits {key}, fused/flash lane vs xla/ref lane")
               for key, want in ref.rows.items())


def check_spread(engine, srv_cache) -> dict:
    """Phase C: the model is spread over the mesh, not parked on device
    0. Every parameter and KV-pool leaf is addressable on every mesh
    device with the sharding ``param_specs`` states, and the bytes each
    chip holds are of one order."""
    from jax.sharding import NamedSharding

    mesh = engine.mesh
    ndev = mesh.devices.size

    def leaf_ok(x, spec):
        want = NamedSharding(mesh, spec)
        assert len(x.addressable_shards) == ndev, (x.shape, x.sharding)
        assert x.sharding.is_equivalent_to(want, x.ndim), (
            x.shape, x.sharding, spec)

    jax.tree.map(leaf_ok, engine.params, engine._specs)
    kv_spec = engine.model.paged_cache_specs(engine.axis)
    jax.tree.map(leaf_ok, srv_cache, kv_spec)
    used = _mem_gib(mesh, "bytes_in_use")
    if used is None:
        return {}
    assert max(used) < 2 * min(used), (
        f"GiB in use per chip {used}: one chip holds the model")
    return {"bytes_in_use_gib": used}


def phase_serving(cfg: ModelConfig, mesh, sizes: Sizes, *, seed: int = 0,
                  spread: bool = False) -> dict:
    """The main path on ``mesh``: both admission forms through the
    fused/flash lane, each compared on logits with the xla/ref lane
    over the same weights."""
    t0 = time.perf_counter()
    kw = dict(dtype=sizes.dtype, max_len=sizes.max_len,
              block_m=sizes.block_m, block_n=sizes.block_n,
              block_k=sizes.block_k)
    fused = Engine(cfg, mesh, mode="fused", seed=seed, fallback=None, **kw)
    jax.block_until_ready(fused.params)
    # Same arrays, same placement: the reference lane costs no weights.
    ref = Engine(cfg, mesh, mode="xla", params=fused.params, **kw)
    out = {"init_s": round(time.perf_counter() - t0, 1)}
    _log(f"  weights initialised under param_specs in {out['init_s']} s")

    if spread:
        probe = fused.serving(num_slots=sizes.num_slots, page=sizes.page)
        out.update(check_spread(fused, probe.cache))
        del probe

    for form, lengths, buckets in (
            ("monolithic", sizes.mono_prompts, None),
            ("chunked", sizes.chunk_prompts, sizes.buckets)):
        t1 = time.perf_counter()
        prompts = _prompts(lengths, cfg.vocab_size, seed + 1)
        ref_tap = _LogitTap()
        _serve(ref, prompts, sizes, attn_impl="ref", buckets=buckets,
               tap=ref_tap, tag=form)
        tap = _LogitTap(forced=ref_tap.tokens)
        counts = _serve(fused, prompts, sizes, attn_impl="flash",
                        buckets=buckets, tap=tap, tag=form)
        # One decode program; one prefill program per distinct prompt
        # length (monolithic) or per bucket (chunked: the prompt
        # lengths are chosen so that every bucket is used).
        want_prefill = (len(set(lengths)) if buckets is None
                        else len(buckets))
        assert counts["decode_cache"] == 1, counts
        assert counts["prefill_cache"] == want_prefill, counts
        out[form] = {
            **counts, "requests": len(prompts),
            "logit_rows": len(ref_tap.rows),
            "max_rel_err": _compare(tap, ref_tap, sizes.logit_tol),
            "seconds": round(time.perf_counter() - t1, 1)}
        gc.collect()

    assert fused.mode == "fused", "the engine degraded underneath the smoke"
    peak = _mem_gib(mesh, "peak_bytes_in_use")
    if peak is not None:
        out["peak_hbm_gib"] = peak
    return out


# ---------------------------------------------------------------------------
# The ring kernels on one chip (phase B)
# ---------------------------------------------------------------------------

def phase_rings(cfg: ModelConfig, mesh, sizes: Sizes, *,
                seed: int = 0) -> dict:
    """``ag_gemm``, ``gemm_rs`` and ``gemm_ar`` through their
    ``sim_ranks`` self-ring on a size-1 ``tp`` axis, at the MLP shapes
    one of ``sim_ranks`` TP ranks would see: the full ring schedule,
    semaphores and VMEM staging, wire = HBM. Each sim contract states
    its verifiable result: the plain local GEMM."""
    from triton_dist_tpu.ops import (
        ag_gemm, create_ag_gemm_context, create_gemm_ar_context,
        create_gemm_rs_context, gemm_ar, gemm_rs)

    assert mesh.shape["tp"] == 1, "sim_ranks needs a size-1 tp axis"
    mctx = tdt.MeshContext.from_mesh(mesh)
    sim, d = sizes.sim_ranks, cfg.hidden_size
    ff_loc = cfg.intermediate_size // sim
    blocks = (sizes.block_m, sizes.block_n, sizes.block_k)
    ag = create_ag_gemm_context(mctx, "tp", *blocks)
    rs = create_gemm_rs_context(mctx, "tp", *blocks)
    ar = create_gemm_ar_context(mctx, "tp", sizes.block_n, sizes.block_k)
    cases = {
        # column-parallel up-projection of one rank: (M, d) x (d, ff/n)
        "ag_gemm": (lambda a, b: ag_gemm(a, b, ag, sim_ranks=sim),
                    (sizes.ring_m, d, ff_loc)),
        # row-parallel down-projection: (M, ff/n) x (ff/n, d)
        "gemm_rs": (lambda a, b: gemm_rs(a, b, rs, sim_ranks=sim),
                    (sizes.ring_m, ff_loc, d)),
        # the same down-projection at the decode batch
        "gemm_ar": (lambda a, b: gemm_ar(a, b, ar, sim_ranks=sim),
                    (sizes.num_slots, ff_loc, d)),
    }
    rep = P(None, None)
    out = {}
    for i, (name, (fn, (m, k, n))) in enumerate(cases.items()):
        t0 = time.perf_counter()
        ka, kb = jax.random.split(jax.random.PRNGKey(seed + i))
        a = jax.random.normal(ka, (m, k), sizes.dtype)
        b = jax.random.normal(kb, (k, n), sizes.dtype) * k ** -0.5
        got = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(rep, rep), out_specs=rep,
            check_vma=False))(a, b)
        want = jnp.dot(a, b, preferred_element_type=jnp.float32)
        out[name] = {"m_k_n": [m, k, n], "sim_ranks": sim,
                     "max_rel_err": _rel_err(
                         got, want, sizes.gemm_tol,
                         f"{name} (M,K,N)={m, k, n} vs the plain GEMM"),
                     "seconds": round(time.perf_counter() - t0, 1)}
    return out


# ---------------------------------------------------------------------------
# Four chips (phase C): ring order first, then the serving set at TP=4
# ---------------------------------------------------------------------------

def check_ring_order(mesh) -> None:
    """One hop right through the remote-DMA path against
    ``lax.ppermute``: ``logical_device_id`` assumes a device's row-major
    position in the mesh IS its Pallas LOGICAL id, while on a TPU
    ``make_mesh`` lets ``mesh_utils`` order the devices. Every ring
    trusts this; check it before trusting a ring."""
    from triton_dist_tpu.ops.p2p import p2p_put

    n = mesh.shape["tp"]
    mctx = tdt.MeshContext.from_mesh(mesh)
    perm = tuple((i, (i + 1) % n) for i in range(n))
    x = jnp.arange(n * 8 * 128, dtype=jnp.float32).reshape(n * 8, 128)
    spec = P("tp", None)

    def run(fn):
        return np.asarray(jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=spec, out_specs=spec,
            check_vma=False))(x))

    got = run(lambda v: p2p_put(v, perm, ctx=mctx, axis="tp"))
    want = run(lambda v: jax.lax.ppermute(v, "tp", perm))
    np.testing.assert_array_equal(got, want)


def phase_four_chips(cfg: ModelConfig, mesh, sizes: Sizes, *,
                     seed: int = 0) -> dict:
    check_ring_order(mesh)
    _log("  one-hop p2p_put agrees with lax.ppermute")
    return {"ring_order": "ok",
            **phase_serving(cfg, mesh, sizes, seed=seed, spread=True)}


# ---------------------------------------------------------------------------
# The megakernel lane (phase M): a few decode steps as one persistent
# kernel each, against the layer lane on logits
# ---------------------------------------------------------------------------

def phase_megakernel(cfg: ModelConfig, mesh, sizes: Sizes, *,
                     seed: int = 0, steps: int = 3) -> dict:
    """``MegaKernelEngine.decode_step`` at positions 0..steps-1 against
    the layer ``Engine`` (``mode="xla"``) over the same float32 weights
    (the arena is float32, ``megakernel/builder.py``), teacher-forced on
    the layer lane's arg-max. ``mesh`` is the 1-D ``("tp",)`` mesh the
    megakernel engine takes."""
    from triton_dist_tpu.megakernel.engine import MegaKernelEngine

    b = sizes.num_slots
    max_len = max(sizes.page, 16)
    # The layer engine's own initialiser: one copy of the weights, made
    # in place; the megakernel engine packs its arena from these arrays.
    layer = Engine(cfg, mesh, mode="xla", dtype=jnp.float32,
                   max_len=max_len, seed=seed)
    jax.block_until_ready(layer.params)
    _log(f"  float32 weights resident: {_mem_gib(mesh, 'bytes_in_use')} "
         "GiB in use")
    mk = MegaKernelEngine(cfg, mesh, batch=b, max_len=max_len,
                          params=layer.params, tile_w=sizes.mk_tile,
                          t_tile=sizes.mk_tile)
    _log(f"  arena packed: {_mem_gib(mesh, 'bytes_in_use')} GiB in use")
    rng = np.random.default_rng(seed)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, size=b), jnp.int32)
    worst = 0.0
    cache = None
    for pos in range(steps):
        if pos == 0:
            want, cache = layer.prefill(tok[:, None])
        else:
            want, cache = layer.decode(tok, cache)
        worst = max(worst, _rel_err(
            mk.decode_step(tok, pos), want, sizes.mk_tol,
            f"megakernel logits at position {pos} vs the layer lane"))
        tok = jnp.argmax(want, axis=-1).astype(jnp.int32)
    return {"steps": steps, "batch": b, "queue_slots": mk.builder.qlen,
            "arena_gib": round(mk.builder.arena_rows * mk.builder.w * 4
                               / 2**30, 2),
            "max_rel_err": worst}


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

def nothing_gave_way() -> None:
    """Nothing fell back underneath the smoke."""
    from triton_dist_tpu.resilience import policy

    assert not policy._GLOBAL._failed, policy._GLOBAL._failed
    assert not os.environ.get("TRITON_DIST_TPU_FORCE_XLA")
    assert not use_interpret(), "kernels ran in the Pallas interpreter"


def one_chip_depth(cfg: ModelConfig, sizes: Sizes, hbm_bytes: int) -> int:
    """Layers of ``cfg`` that fit one chip beside the embedding, the
    head, two KV pools (one serving engine is collected while the next
    is built) and 3 GiB of headroom for activations and the per-step
    pool copy XLA makes around the paged kernels."""
    item = np.dtype(sizes.dtype).itemsize
    d, ff, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    per_layer = item * (
        2 * d * cfg.num_attention_heads * hd
        + 2 * d * cfg.num_key_value_heads * hd + 3 * d * ff)
    fixed = item * 2 * cfg.vocab_size * d + 3 * 2**30
    plan = cfg.kv_cache_plan(max_len=sizes.max_len, page=sizes.page,
                             num_slots=sizes.num_slots, dtype_bytes=item)
    per_layer += 2 * plan["pool_bytes_per_rank"] // cfg.num_hidden_layers
    return int(min(cfg.num_hidden_layers, (hbm_bytes - fixed) // per_layer))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phases", default="A,B,C,M",
                    help="comma list out of A,B,C,M (default: all)")
    args = ap.parse_args(argv)
    phases = [p.strip().upper() for p in args.phases.split(",")]

    t_start = time.perf_counter()
    dev = device_line()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU — JAX found platform={dev['platform']!r}; "
              "this script has no CPU mode", file=sys.stderr)
        return 1
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cache_dir = enable_compile_cache()
    import importlib.metadata

    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    _log(f"device: platform={dev['platform']} device_kind={dev['kind']!r} "
         f"count={dev['count']} jax={jax.__version__} "
         f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    _log(f"compile cache: {cache_dir} "
         f"({'warm' if os.path.isdir(cache_dir) and os.listdir(cache_dir) else 'cold'})")

    sizes = Sizes()
    full = ModelConfig.qwen3_8b()
    hbm = (jax.devices()[0].memory_stats() or {}).get(
        "bytes_limit", 16 * 2**30)
    depth = one_chip_depth(full, sizes, hbm)
    one = dataclasses.replace(full, num_hidden_layers=depth)
    summary = {}

    def run(name, title, fn, reduced):
        if name not in phases:
            return
        _log(f"phase {name}: {title}; reduced: {reduced or 'nothing'}")
        t0, c0 = time.perf_counter(), _COMPILE_S[0]
        res = fn()
        res["seconds"] = round(time.perf_counter() - t0, 1)
        res["compile_s"] = round(_COMPILE_S[0] - c0, 1)
        summary[name] = res
        _log(f"phase {name}: passed {json.dumps(res)}")
        gc.collect()

    devs = jax.devices()
    mesh1 = tdt.make_mesh(tp=1, devices=devs[:1])
    run("A", f"{full.model_name} widths, {depth} layers, TP=1, bf16, "
             "mode=fused, attn_impl=flash",
        lambda: phase_serving(one, mesh1, sizes, seed=args.seed),
        f"depth {full.num_hidden_layers}->{depth} ({hbm / 2**30:.1f} GiB "
        "of HBM on one chip)" if depth < full.num_hidden_layers else "")
    run("B", f"ag_gemm/gemm_rs/gemm_ar sim_ranks={sizes.sim_ranks} "
             "self-ring at the MLP shapes",
        lambda: phase_rings(full, mesh1, sizes, seed=args.seed), "")
    if len(devs) >= 4:
        # make_mesh's own device order when the host is exactly the
        # mesh (the normal entry point); the first four otherwise.
        mesh4 = (tdt.make_mesh(tp=4) if len(devs) == 4
                 else tdt.make_mesh(tp=4, devices=devs[:4]))
        run("C", f"{full.model_name} full depth, TP=4, rings over ICI",
            lambda: phase_four_chips(full, mesh4, sizes, seed=args.seed), "")
        from jax.sharding import Mesh
        mk_cfg = dataclasses.replace(full, num_hidden_layers=MK_LAYERS)
        run("M", f"megakernel lane, {full.model_name} widths, {MK_LAYERS} "
                 "layers, TP=4, float32 arena, one decode step per kernel",
            lambda: phase_megakernel(
                mk_cfg, Mesh(np.array(devs[:4]), ("tp",)), sizes,
                seed=args.seed),
            f"depth {full.num_hidden_layers}->{MK_LAYERS} (float32 weights, "
            "arena and packing temporary together in a chip's HBM)")
    else:
        for name in ("C", "M"):
            if name in phases:
                summary[name] = f"skipped: needs 4 chips, have {len(devs)}"
                _log(f"phase {name}: {summary[name]}")

    nothing_gave_way()
    faulthandler.cancel_dump_traceback_later()
    _log("summary: " + json.dumps({
        "jax": jax.__version__, "libtpu": libtpu, "model": full.model_name,
        "one_chip_layers": depth, "phases": summary,
        "seconds": round(time.perf_counter() - t_start, 1),
        "claim": None}))
    print(result_line(dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
