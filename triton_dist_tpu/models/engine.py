"""Inference engine (reference: ``models/engine.py:37`` ``Engine`` —
CUDA-graph capture :75, ``serve()`` decode loop :113).

TPU form: no CUDA-graph analogue is needed — ``jax.jit`` already compiles
the whole decode step into one XLA program (the role cudagraph capture
plays in the reference); donated KV-cache buffers keep decode in-place.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.models import dense
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.parallel.mesh import MeshContext


def _donated_lost(args) -> bool:
    """True when any array argument was already donated into the failed
    dispatch (decode donates the KV cache): a retry would dispatch on
    deleted buffers and mask the original error, so the caller must
    re-raise instead. Trace-time failures (the common fused-path case)
    happen before donation and retry safely."""
    for leaf in jax.tree.leaves(args):
        if isinstance(leaf, jax.Array) and leaf.is_deleted():
            return True
    return False


class Engine:
    """Greedy-decoding TP inference engine over a mesh.

    ``model`` is any module exposing the dense functional contract
    (``init_params`` / ``param_specs`` / ``prefill`` / ``decode_step`` /
    ``cache_specs``) — ``models.dense`` by default,
    ``models.qwen_next`` for the hybrid GDN family.
    """

    def __init__(self, cfg: ModelConfig, mesh: Mesh, *, axis: str = "tp",
                 mode: str = "xla", dtype=jnp.float32, max_len: int = 512,
                 params=None, seed: int = 0,
                 block_m: int = 256, block_n: int = 256,
                 block_k: int = 512, model=None,
                 moe_impl: Optional[str] = None, ep_axis=None,
                 ep_capacity: Optional[int] = None,
                 ep_transport: Optional[str] = None,
                 fallback: Optional[str] = None, probe: bool = False,
                 timeout_s: Optional[float] = None):
        """``moe_impl`` selects the MoE regime for ``models.qwen_moe``
        ("tp" | "ep"); with ``"ep"`` the Engine builds the EPContext
        itself (reference: the Engine serving the MoE demo). ``ep_axis``
        is the expert axis name, or an ``(outer, inner)`` tuple for the
        hierarchical ICI-by-DCN dispatch (``create_ep2d_context``);
        ``ep_capacity`` opts into the capped-drop dispatch (see
        ``create_ep_context`` for the drop-free mode's memory scaling).
        ``ep_transport`` picks the DECODE dispatch path
        ("ar" | "ragged" | "ll" | "auto" — see
        :func:`triton_dist_tpu.layers.ep_moe.fwd_decode`); prefill
        always rides the full dispatch/combine. ``"auto"`` resolves
        against the tune cache at trace time with the actual decode
        batch shape.

        Resilience knobs:

        - ``fallback="xla"``: when a fused prefill/decode dispatch
          raises, log once, rebuild that dispatch with ``mode="xla"``
          (the plain-XLA collective path), and re-serve the request —
          graceful degradation instead of a dead replica. Retry is
          never attempted for a :class:`CommTimeoutError` — the wedged
          dispatch still holds the device (and on decode the KV cache
          was donated into it), so the timeout is re-raised as-is.
        - ``probe=True`` (with ``fallback``): run
          ``resilience.policy.health_probe`` at construction; if the
          fused comm path is unhealthy on this platform, start degraded
          immediately.
        - ``timeout_s``: bound every prefill/decode wait; a miss raises
          :class:`~triton_dist_tpu.resilience.CommTimeoutError`
          carrying rank, op, and the last-completed decode-step
          counter.
        """
        if fallback not in (None, "xla"):
            raise ValueError(f"fallback must be None or 'xla', "
                             f"got {fallback!r}")
        if probe and fallback is None:
            raise ValueError(
                "probe=True requires fallback='xla' — a failed probe "
                "has nowhere to degrade to otherwise")
        self.fallback = fallback
        self.timeout_s = timeout_s
        if probe and fallback == "xla" and mode != "xla":
            from triton_dist_tpu.resilience import policy as _policy

            if not _policy.health_probe(mesh, axis):
                _policy.note_failure(
                    f"engine[mode={mode}]",
                    RuntimeError("startup health probe failed"))
                mode = "xla"
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.mode = mode
        self.max_len = max_len
        model = model if model is not None else dense
        self.model = model
        mctx = MeshContext.from_mesh(mesh)
        self.ctxs = dense.make_fwd_contexts(mctx, axis, block_m, block_n,
                                            block_k)

        # A MoE-contract model (param_specs takes moe_impl) defaults to
        # TP experts when the caller didn't pick a regime — so
        # Engine(model=qwen_moe) works out of the box.
        import inspect

        takes_moe = "moe_impl" in inspect.signature(
            model.param_specs).parameters
        if moe_impl is None and takes_moe:
            moe_impl = "tp"
        if moe_impl is not None and not takes_moe:
            # Without this the call below dies in a confusing TypeError
            # inside param_specs (ADVICE r4).
            raise ValueError(
                f"moe_impl={moe_impl!r} given, but model "
                f"{getattr(model, '__name__', model)!r} is not a MoE "
                "model (its param_specs takes no moe_impl)")

        model_kwargs = {}
        if moe_impl is not None:
            from triton_dist_tpu.ops.ep_a2a import (
                create_ep_context, create_ep2d_context,
            )

            ep_ctx = None
            if moe_impl == "ep":
                if isinstance(ep_axis, (tuple, list)):
                    ep_ctx = create_ep2d_context(
                        mctx, num_experts=cfg.num_experts,
                        topk=cfg.num_experts_per_tok,
                        outer_axis=ep_axis[0], inner_axis=ep_axis[1])
                else:
                    ep_ctx = create_ep_context(
                        mctx, num_experts=cfg.num_experts,
                        topk=cfg.num_experts_per_tok,
                        capacity=ep_capacity, axis=ep_axis or axis)
            model_kwargs = {"moe_impl": moe_impl, "ep_ctx": ep_ctx}
            if ep_transport is not None:
                from triton_dist_tpu.layers.ep_moe import (
                    DECODE_TRANSPORTS)

                if ep_transport not in DECODE_TRANSPORTS:
                    raise ValueError(
                        f"ep_transport={ep_transport!r} not in "
                        f"{DECODE_TRANSPORTS}")
                if moe_impl != "ep":
                    raise ValueError(
                        "ep_transport is an EP decode knob; it needs "
                        f"moe_impl='ep' (got {moe_impl!r})")
                model_kwargs["transport"] = ep_transport
            spec_ep_axis = (tuple(ep_axis) if isinstance(
                ep_axis, (tuple, list)) else (ep_axis or axis))
            specs = model.param_specs(cfg, moe_impl=moe_impl, axis=axis,
                                      ep_axis=spec_ep_axis)
        else:
            specs = model.param_specs(cfg, axis)
        self.model_kwargs = model_kwargs
        self.ep_transport = (model_kwargs.get("transport")
                             if moe_impl == "ep" else None)
        self._specs = specs
        if params is None:
            self.params = self.sharded_init()(
                jax.random.PRNGKey(seed), cfg, dtype)
        else:
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, specs, is_leaf=lambda x: isinstance(x, jax.Array)
                or isinstance(x, np.ndarray))

        self._prefill, self._decode = self._build(mode)

    def sharded_init(self):
        """``model.init_params(key, cfg, dtype)`` jitted with
        ``param_specs`` as its output sharding: every device generates
        only its own shard of each weight (threefry is partitionable),
        so no leaf ever exists unsharded — at 8B an unsharded init is
        16 GB on device 0 before the first ``device_put``. Values equal
        the eager ``model.init_params`` for the same key. ``cfg`` and
        ``dtype`` are static, so engines of one configuration share one
        traced and compiled initialiser."""
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._specs,
            is_leaf=lambda s: isinstance(s, P))
        return jax.jit(self.model.init_params, static_argnums=(1, 2),
                       out_shardings=shardings)

    def _build(self, mode):
        """Jit the prefill/decode dispatches for ``mode`` (called once
        at construction, and again with mode="xla" on degradation)."""
        model, cfg, axis = self.model, self.cfg, self.axis
        model_kwargs, specs = self.model_kwargs, self._specs
        max_len = self.max_len

        def _prefill(params, ids):
            return model.prefill(params, ids, cfg, mode=mode, axis=axis,
                                 ctxs=self.ctxs, max_len=max_len,
                                 **model_kwargs)

        def _decode(params, tok, cache):
            return model.decode_step(params, tok, cache, cfg, mode=mode,
                                     axis=axis, ctxs=self.ctxs,
                                     **model_kwargs)

        kv_spec = model.cache_specs(axis)
        pre = jax.jit(jax.shard_map(
            _prefill, mesh=self.mesh,
            in_specs=(specs, P(None, None)),
            out_specs=(P(None, None), kv_spec),
            check_vma=False))
        dec = jax.jit(jax.shard_map(
            _decode, mesh=self.mesh,
            in_specs=(specs, P(None), kv_spec),
            out_specs=(P(None, None), kv_spec),
            check_vma=False), donate_argnums=(2,))
        return pre, dec

    def _degrade(self):
        """Rebuild both dispatches on the plain-XLA collective path."""
        if self.mode != "xla":
            self.mode = "xla"
            self._prefill, self._decode = self._build("xla")

    def _dispatch(self, op: str, *args, retriable: bool = True):
        """Run one prefill/decode dispatch under the resilience policy:
        optional watchdog deadline, and (``fallback="xla"``) one
        degrade-and-retry when the fused path raises."""
        from triton_dist_tpu.resilience import policy as _policy
        from triton_dist_tpu.resilience.watchdog import (
            CommTimeoutError, block_until_ready)

        fn = self._prefill if op == "prefill" else self._decode
        try:
            out = fn(self.params, *args)
            if self.timeout_s is not None:
                out = block_until_ready(
                    out, timeout_s=self.timeout_s, op=f"engine.{op}",
                    progress_fn=lambda: getattr(self, "_host_len", None))
            return out
        except CommTimeoutError:
            raise          # wedged dispatch: inputs may be donated/lost
        except Exception as e:  # noqa: BLE001 — degrade-and-retry
            if (self.fallback != "xla" or self.mode == "xla"
                    or not retriable or _donated_lost(args)):
                raise
            _policy.note_failure(f"engine.{op}[mode={self.mode}]", e)
            self._degrade()
            return self._dispatch(op, *args, retriable=False)

    def prefill(self, input_ids) -> Tuple[jax.Array, KVCache]:
        input_ids = jnp.asarray(input_ids)
        out = self._dispatch("prefill", input_ids)
        # Host-side mirror of cache.length: lets decode() guard overruns
        # without forcing a device sync per generated token. Set only
        # after the dispatch is known-good so a raise cannot desync it.
        self._host_len = int(input_ids.shape[1])
        return out

    def decode(self, tokens, cache) -> Tuple[jax.Array, KVCache]:
        # dynamic_update_slice clamps out-of-range starts, which would
        # silently overwrite the last cache slot — fail loudly instead.
        # The host counter tracks engine-driven prefill/decode; fall back
        # to a (synchronizing) device read for externally-built caches.
        length = getattr(self, "_host_len", None)
        if length is None:
            length = int(np.asarray(cache.length))
        if length >= self.max_len:
            raise ValueError(
                f"KV cache full ({self.max_len}); cannot decode further")
        out = self._dispatch("decode", tokens, cache)
        # Advance only after _decode returned: a raised step must leave
        # the overflow guard exactly where it was.
        self._host_len = length + 1
        return out

    def serving(self, **kw):
        """Wrap this engine in a continuous-batching
        :class:`~triton_dist_tpu.serving.ServingEngine` (paged KV pool,
        request queue, streaming) — the production request path;
        :meth:`serve` below stays the fixed-batch loop it is token-
        exact against. Keyword args pass through (num_slots, page,
        policy, deadlines, ...)."""
        from triton_dist_tpu.serving import ServingEngine

        return ServingEngine(self, **kw)

    def serve(self, input_ids, gen_len: int = 32, *,
              temperature: float = 0.0, top_k: int = 0,
              seed: int = 0):
        """Token generation (reference ``Engine.serve`` decode loop,
        ``engine.py:113`` — greedy there; sampling is capability-plus).

        input_ids: (B, S) → (B, gen_len) tokens. ``temperature`` 0
        (default) is greedy argmax; > 0 samples from the softmax at
        that temperature, optionally truncated to the ``top_k``
        highest-probability tokens. Sampling is deterministic per
        ``seed`` (a fold of jax PRNG keys, one per step).
        """
        input_ids = jnp.asarray(input_ids)
        b, s = input_ids.shape
        if s + gen_len > self.max_len:
            raise ValueError(
                f"sequence {s}+{gen_len} exceeds max_len={self.max_len}")

        if top_k < 0 or top_k > self.cfg.vocab_size:
            raise ValueError(f"top_k={top_k} outside [0, vocab="
                             f"{self.cfg.vocab_size}]")

        def pick(logits, step):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lg = logits.astype(jnp.float32) / temperature
            if top_k > 0:
                # O(V log k) threshold, not a full vocab sort per token.
                kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            return jax.random.categorical(key, lg, axis=-1
                                          ).astype(jnp.int32)

        logits, cache = self.prefill(input_ids)
        out = [pick(logits, 0)]
        for i in range(gen_len - 1):
            logits, cache = self.decode(out[-1], cache)
            out.append(pick(logits, i + 1))
        return jnp.stack(out, axis=1)
