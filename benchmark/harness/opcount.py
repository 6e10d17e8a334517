"""Operations and bytes the ALGORITHM needs, from shapes alone, and the
table of peaks. Counted once each: weights read once a step, the keys and
values of the running sequences read once, no recomputation, no padding,
no temporary. So a share of a peak built on these cannot honestly pass
100%: a reading above it means the time left out part of the work.
"""

from __future__ import annotations

import json
import os

from .weights import Dims

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str, path: str = _PEAKS) -> dict:
    """The published peaks of ``device_kind``. A device that is not in
    the table is an error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def layer_params(d: Dims) -> int:
    """Matrix parameters of one decoder layer (norm gains and biases
    are thousands against hundreds of millions: left out)."""
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return 2 * d.d * q + 2 * d.d * kv + 3 * d.d * d.ff


def head_params(d: Dims) -> int:
    return d.vocab * d.d


def kv_bytes_per_token(d: Dims, itemsize: int = 2) -> int:
    """Keys and values of one position, all layers."""
    return 2 * d.kv_heads * d.head_dim * itemsize * d.layers


def decode_step_bytes(d: Dims, context_tokens: float, *, tp: int = 1,
                      itemsize: int = 2) -> float:
    """Bytes one chip must read for one decode step: its share of every
    layer's matrices and of the head, once, plus the cached keys and
    values of ``context_tokens`` positions (the running sequences'
    lengths, summed). The embedding rows gathered and the activations
    are kilobytes."""
    weights = (d.layers * layer_params(d) + head_params(d)) * itemsize
    return (weights + context_tokens * kv_bytes_per_token(d, itemsize)) / tp


def prefill_chunk_flops(d: Dims, rows: int, context_mean: float, *,
                        tp: int = 1) -> float:
    """Floating-point operations one chip needs for a prefill chunk of
    ``rows`` tokens: every layer's GEMMs (2 per parameter per row),
    attention's two products against ``context_mean`` keys a row (the
    causal mean: positions before the chunk plus half the chunk), and
    the head for the one row whose logits the chunk returns."""
    gemm = 2.0 * rows * d.layers * layer_params(d)
    attn = 4.0 * rows * context_mean * d.heads * d.head_dim * d.layers
    head = 2.0 * head_params(d)
    return (gemm + attn + head) / tp
