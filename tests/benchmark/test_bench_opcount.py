"""Operation and byte counts against numbers worked by hand, at the
committed configuration's widths and at a second set (Qwen3-8B's, 24
layers), and the table of peaks."""

import json
import os

import pytest

from benchmark.harness import loader, opcount
from benchmark.harness.weights import Dims


def _dims(name):
    with open(os.path.join(loader.DATA_ROOT, "configs", name + ".json")) as f:
        return Dims.from_config(json.load(f))


def test_qwen3_8b_counts():
    d = Dims(vocab=151_936, d=4096, ff=12_288, layers=24, heads=32,
             kv_heads=8, head_dim=128, eps=1e-6, rope_theta=1e6,
             qk_norm=True, attention_bias=False, tie=False)
    # 2*4096*4096 (q, o) + 2*4096*1024 (k, v) + 3*4096*12288 (mlp)
    assert opcount.layer_params(d) == 192_937_984
    assert opcount.head_params(d) == 151_936 * 4096
    assert opcount.kv_bytes_per_token(d) == 2 * 8 * 128 * 2 * 24   # 98,304
    # 24 layers + head, bf16, read once: the 10.5 GB of the issue.
    assert opcount.decode_step_bytes(d, 0) == 10_505_682_944
    assert opcount.decode_step_bytes(d, 3000) == (
        10_505_682_944 + 3000 * 98_304)
    assert opcount.decode_step_bytes(d, 3000, tp=4) == pytest.approx(
        (10_505_682_944 + 3000 * 98_304) / 4)
    # 13.2 ms at the published bandwidth.
    ms = opcount.decode_step_bytes(d, 3000) / 819e9 * 1e3
    assert ms == pytest.approx(13.19, abs=0.01)


def test_seed_oss_36b_counts():
    d = _dims("seed-oss-36b-1chip")
    # 2*5120*10240 + 2*5120*1024 + 3*5120*27648
    assert opcount.layer_params(d) == 540_016_640
    gemm = 2 * 512 * 8 * 540_016_640                 # 4.42 TFLOP
    attn = 4 * 512 * 768.5 * 80 * 128 * 8            # 0.13 TFLOP
    head = 2 * 155_136 * 5120
    assert opcount.prefill_chunk_flops(d, 512, 768.5) == pytest.approx(
        gemm + attn + head)
    assert gemm == pytest.approx(4.4238e12, rel=1e-4)
    ms = opcount.prefill_chunk_flops(d, 512, 768.5) / 197e12 * 1e3
    assert ms == pytest.approx(23.1, abs=0.1)


def test_peaks_by_device_kind():
    p = opcount.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v5p", "TPU v4", ""])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError, match="no peaks"):
        opcount.peaks_for(kind)
