"""The chip smoke's phases at ``ModelConfig.tiny`` sizes on the CPU mesh
(the same functions ``chip_smoke.py`` runs at Qwen3-8B widths on the
TPU), the checks that nothing gave way underneath them, and the entry
point's refusal of a CPU backend."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import chip_smoke
import triton_dist_tpu as tdt
from triton_dist_tpu.models import ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every pallas buffer stays under the interpreter's per-buffer limit
# (docs/testing.md); float32 so the two lanes differ by rounding order
# only and the bound can be tight.
TINY = chip_smoke.Sizes(
    dtype=jnp.float32, max_len=48, page=8, num_slots=2, gen=3,
    mono_prompts=(8, 16, 8), chunk_prompts=(5, 12, 9), buckets=(4, 8),
    block_m=8, block_n=8, block_k=32, ring_m=32, sim_ranks=4,
    logit_tol=1e-4, gemm_tol=1e-5)
# The interpreter runs every DMA and semaphore of the four-device rings
# as a host callback: one layer, one slot, one prompt length, one bucket
# and whole-array blocks keep the TP=4 case to seconds.
TINY_TP4 = dataclasses.replace(
    TINY, max_len=16, num_slots=1, gen=2, mono_prompts=(8, 8),
    chunk_prompts=(5, 8), buckets=(8,), block_m=64, block_n=64,
    block_k=64)


@pytest.mark.parametrize("tp,layers,sizes", [(1, 2, TINY),
                                             (4, 1, TINY_TP4)])
def test_serving_phase(tp, layers, sizes):
    cfg = ModelConfig.tiny(vocab_size=64, num_hidden_layers=layers)
    mesh = tdt.make_mesh(tp=tp, devices=jax.devices()[:tp])
    res = chip_smoke.phase_serving(cfg, mesh, sizes, spread=tp > 1)
    for form, lengths, n_prefill in (
            ("monolithic", sizes.mono_prompts,
             len(set(sizes.mono_prompts))),
            ("chunked", sizes.chunk_prompts, len(sizes.buckets))):
        assert res[form]["decode_cache"] == 1
        assert res[form]["prefill_cache"] == n_prefill
        assert res[form]["requests"] == len(lengths) > sizes.num_slots
        # the prefill row and every decode step of every request
        assert res[form]["logit_rows"] == len(lengths) * sizes.gen
        assert res[form]["max_rel_err"] <= sizes.logit_tol
    # What nothing_gave_way() asserts on the chip, minus the one thing
    # that differs here: these kernels ran in the interpreter.
    from triton_dist_tpu.resilience import policy

    assert not policy._GLOBAL._failed
    assert not os.environ.get("TRITON_DIST_TPU_FORCE_XLA")


def test_ring_phase():
    mesh = tdt.make_mesh(tp=1, devices=jax.devices()[:1])
    res = chip_smoke.phase_rings(ModelConfig.tiny(), mesh, TINY)
    assert set(res) == {"ag_gemm", "gemm_rs", "gemm_ar"}
    assert res["gemm_ar"]["m_k_n"][0] == TINY.num_slots


def test_megakernel_phase():
    # vocab/widths as tests/test_megakernel.py: the arena stays under
    # the interpreter's per-buffer limit
    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))   # TP inside the kernel
    res = chip_smoke.phase_megakernel(
        cfg, mesh, dataclasses.replace(TINY, mk_tol=1e-4, mk_tile=16),
        steps=2)
    assert res["steps"] == 2 and res["max_rel_err"] <= 1e-4


def test_ring_order_check():
    chip_smoke.check_ring_order(
        tdt.make_mesh(tp=4, devices=jax.devices()[:4]))


def test_one_chip_depth():
    full = ModelConfig.qwen3_8b()
    sizes = chip_smoke.Sizes()
    assert chip_smoke.one_chip_depth(full, sizes, 16 * 2**30) < 36
    assert chip_smoke.one_chip_depth(full, sizes, 64 * 2**30) == 36


def test_last_line_is_the_contract(monkeypatch, capfd, tmp_path):
    """The driver refuses any last line but ``{"ok", "device":
    {"platform", "kind", "count"}}`` — no further key. Run the real
    entry with no phase selected, the device check answered as a chip
    would."""
    import json

    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_line", lambda: dict(dev))
    monkeypatch.setattr(chip_smoke, "enable_compile_cache",
                        lambda: str(tmp_path))
    monkeypatch.setattr(chip_smoke, "use_interpret", lambda: False)
    assert chip_smoke.main(["--phases", "none"]) == 0
    lines = capfd.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": dev}
    assert list(last) == ["ok", "device"]
    assert type(last["device"]["count"]) is int
    summary = json.loads(lines[-2].removeprefix("summary: "))
    assert summary["claim"] is None and summary["phases"] == {}


def test_entry_refuses_cpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform='cpu'" in r.stderr
    assert r.stdout.strip() == ""       # no result line without a chip
