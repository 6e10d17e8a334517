"""AOT compile/load and perf-model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.tools import (
    compile_aot, load_aot, gemm_time_s, collective_time_s,
    ChipSpec,
)
from triton_dist_tpu.tools.perf_model import overlap_efficiency_bound


def test_aot_roundtrip(tmp_path):
    def fn(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    x = jnp.ones((16, 32))
    y = jnp.ones((32, 8))
    path = compile_aot(fn, (x, y), str(tmp_path / "fn.bin"))
    exe = load_aot(path)
    out = exe(x, y)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fn(x, y)))


def test_perf_model_sanity():
    # Bigger GEMMs take longer; memory-bound for skinny shapes.
    assert gemm_time_s(4096, 4096, 4096) > gemm_time_s(1024, 1024, 1024)
    assert collective_time_s(1 << 26, 8) > collective_time_s(1 << 20, 8)
    assert collective_time_s(1 << 20, 8, kind="all_reduce") > \
        collective_time_s(1 << 20, 8, kind="all_gather")
    # Overlap bound in (0, 1]; big compute → full hiding.
    b = overlap_efficiency_bound(8192, 8192, 8192, 8)
    assert 0.0 < b <= 1.0


def test_jaxpr_flops_counts_dots_through_structure():
    """The synthetic flops table (CPU cost_analysis fallback): exact
    2*m*k*n per dot_general, scan bodies multiplied by trip count,
    cond branches maxed, nested jit recursed."""
    from triton_dist_tpu.tools.perf_model import jaxpr_flops

    a = jnp.ones((16, 32))
    b = jnp.ones((32, 8))

    plain = jax.make_jaxpr(lambda x, y: x @ y)(a, b)
    assert jaxpr_flops(plain) == 2.0 * 16 * 32 * 8

    def scanned(x, y):
        def body(c, _):
            return c, x @ y
        return jax.lax.scan(body, 0.0, None, length=5)[1]

    assert jaxpr_flops(jax.make_jaxpr(scanned)(a, b)) == 5 * 2.0 * 16 * 32 * 8

    def branched(p, x, y):
        return jax.lax.cond(p, lambda: (x @ y).sum(),
                            lambda: jnp.float32(0.0))

    # max over branches: the dot branch dominates the scalar one.
    assert (jaxpr_flops(jax.make_jaxpr(branched)(True, a, b))
            == 2.0 * 16 * 32 * 8)

    nested = jax.make_jaxpr(jax.jit(lambda x, y: x @ y))(a, b)
    assert jaxpr_flops(nested) == 2.0 * 16 * 32 * 8


# ---------------------------------------------------------------------------
# Topology introspection (tools/topology.py)
# ---------------------------------------------------------------------------

class _FakeDev:
    """Stub with the TPU device attribute surface."""

    def __init__(self, id, coords, slice_index=0, kind="TPU v5p",
                 process_index=0):
        self.id = id
        self.coords = coords
        self.slice_index = slice_index
        self.device_kind = kind
        self.platform = "tpu"
        self.process_index = process_index
        self.core_on_chip = 0


def test_topology_torus_hops_and_neighbors():
    from triton_dist_tpu.tools import topology as T

    # 4x2 torus, one slice.
    devs = [_FakeDev(i, (i % 4, i // 4)) for i in range(8)]
    mat = T.link_matrix(devs)
    assert mat[0][0] == 0
    assert mat[0][1] == 1           # +x neighbour
    assert mat[0][3] == 1           # x wraps: 0 -> 3 is one hop
    assert mat[0][4] == 1           # +y neighbour (y=2: no wrap gain)
    assert mat[0][7] == 2           # (3,1): wrap x (1) + y (1)
    nb = T.neighbors(devs)
    assert set(nb[0]) == {1, 3, 4}  # 2-long y axis: single y link

    dims = T.torus_dims(T.describe_devices(devs))
    assert dims == (4, 2)


def test_topology_slices_and_chip():
    from triton_dist_tpu.tools import topology as T
    from triton_dist_tpu.tools.perf_model import V5E

    devs = ([_FakeDev(i, (i, 0), slice_index=0) for i in range(4)]
            + [_FakeDev(4 + i, (i, 0), slice_index=1) for i in range(4)])
    groups = T.slice_groups(devs)
    assert groups == {0: [0, 1, 2, 3], 1: [4, 5, 6, 7]}
    # Cross-slice pairs ride DCN: hop distance is None.
    mat = T.link_matrix(devs)
    assert mat[0][4] is None and mat[0][1] == 1

    assert T.detect_chip([_FakeDev(0, (0, 0), kind="TPU v5 lite")]) is V5E
    # A TPU no row knows is an error, never another chip's peaks.
    with pytest.raises(ValueError, match="TPU v9x"):
        T.detect_chip([_FakeDev(0, (0, 0), kind="TPU v9x")])
    s = T.summary(devs)
    assert s["num_devices"] == 8 and s["torus_dims"] == [4, 1]


def test_topology_cpu_fallback():
    """CPU/interpret devices (no coords) degrade gracefully."""
    from triton_dist_tpu.tools import topology as T

    infos = T.describe_devices(jax.devices()[:2])
    assert all(i.coords is None for i in infos)
    mat = T.link_matrix(jax.devices()[:2])
    assert mat[0][0] == 0 and mat[0][1] == 1
    assert T.summary(jax.devices()[:2])["num_devices"] == 2


def test_aot_cache_manifest(tmp_path):
    """AOT bundle: multiple named kernels, manifest round-trip through
    a FRESH cache object, signature validation on call."""
    import jax.numpy as jnp
    from triton_dist_tpu.tools.aot import AOTCache

    cache = AOTCache(str(tmp_path / "aot"))
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    cache.add("matmul", lambda a, b: a @ b, (x, y))
    cache.add("double", lambda a: a * 2.0, (x,))
    assert cache.names() == ["double", "matmul"]

    fresh = AOTCache(str(tmp_path / "aot"))  # rehydrate from disk only
    out = fresh.call("matmul", x, y)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ y))
    np.testing.assert_allclose(np.asarray(fresh.call("double", x)),
                               2.0 * np.asarray(x))

    with pytest.raises(TypeError, match="signature mismatch"):
        fresh.call("matmul", y, x)
    with pytest.raises(KeyError):
        fresh.get("missing")


def test_aot_fused_decode_step(tmp_path):
    """AOT-export the fused split-KV decode step (reference exposes AOT
    host APIs for flash decode, flash_decode.py:763-1095).

    Interpret-mode kernels ride host callbacks, which ``jax.export``
    cannot serialize — so the export uses the REAL Mosaic lowering
    (available without a TPU chip) targeting the tpu platform, and the
    test asserts the serialize→rehydrate round-trip; execution parity
    is covered on the CPU mesh by ``test_sp.py`` and on silicon by the
    bench battery."""
    import jax
    from triton_dist_tpu.ops import sp_flash_decode_fused
    from triton_dist_tpu.utils.distributed import interpret_mode

    b, h, kvh, hd, t = 2, 4, 2, 16, 32
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (b, h, hd), jnp.float32) * 0.4
    k_hm = jax.random.normal(key, (b, kvh, t, hd), jnp.float32) * 0.4
    v_hm = jax.random.normal(jax.random.PRNGKey(10), (b, kvh, t, hd),
                             jnp.float32) * 0.4
    kv_len = jnp.array([t, 11], jnp.int32)

    def step(qq, kc, vc, l):
        return sp_flash_decode_fused(qq, kc, vc, l, ctx=None, axis="sp",
                                     page=8)

    with interpret_mode(False):
        path = compile_aot(step, (q, k_hm, v_hm, kv_len),
                           str(tmp_path / "decode.bin"),
                           platforms=["tpu"])
    exe = load_aot(path)
    assert exe.rehydrated.platforms == ("tpu",)
    assert exe.rehydrated.out_avals[0].shape == (b, h, hd)
