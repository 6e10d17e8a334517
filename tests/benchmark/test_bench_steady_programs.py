"""Which compiled programs a cell's per-layer metrics may name, and a
served gap that is not a number (PR 45):

- no metric of either ``longdocs`` cell reads ``jit__decode``;
  ``seed-oss-36b-1chip.docs`` keeps its pair;
- a NaN among the served tokens' gaps makes the run not ``correct``.
"""

import re
import types

import numpy as np
import pytest

from benchmark.harness import loader, reference

DOCS = "seed-oss-36b-1chip.docs"
MISTRAL = "mistral-small-4-1chip.longdocs"
NEMOTRON = "nemotron-3-super-1chip.longdocs"
DECODE_ONLY = "jit__decode(3)"       # an XLA module's name in a capture


def _reading(cell, program):
    """Names of the cell's per-layer metrics whose ``pattern`` matches
    the compiled program ``program``."""
    patterns = {m["name"]: spec.get("params", {}).get("pattern")
                for m, spec in cell.per_layer}
    return {name for name, pattern in patterns.items()
            if pattern and re.search(pattern, program)}


@pytest.mark.parametrize("name, chunk_metric", [
    (MISTRAL, "prefill_chunk_ms.longdocs"),
    (NEMOTRON, "prefill_chunk_ms.nemotron")])
def test_no_longdocs_metric_reads_the_decode_only_program(name,
                                                          chunk_metric):
    """``jit__decode`` runs only in ticks that hold no chunk: when every
    slot decodes at once. Under ``longdocs`` (32 clients over 16 slots,
    prompts of 2-6 chunks of 2,048 rows, 64 out) prefill is saturated
    and that state comes ONCE a run, while the closed loop fills: 30-35
    steps in ~0.35 s about 6 s into the window. Whether the traced 6 s
    (from ``run.TRACE_LEAD_S``) hold that burst is decided by how fast
    the chunk program is, and a pattern that matches no program fails
    the traced run (``trace_reduce.program_median_ms``: no match is an
    error, never a zero), so a sound gain of a tenth on the chunk
    program was refused ``run_failed`` (PR 44). A ``device_trace`` metric
    names a program only where the cell's STEADY loop runs it
    (PERF.md section 3)."""
    cell = loader.load_cell(name)
    assert _reading(cell, DECODE_ONLY) == set()
    # What the steady loop does run stays read, by the same rule.
    assert chunk_metric in _reading(cell, "jit__chunk(7)")


def test_docs_keeps_its_decode_metrics():
    """There the state recurs all window long (8 slots, prompts of 2-4
    chunks of 512 rows, 32 out): a fifth of its decode dispatches are
    ``jit__decode``."""
    cell = loader.load_cell(DOCS)
    assert _reading(cell, DECODE_ONLY) == {"decode_step_ms.docs",
                                           "decode_hbm_share.docs"}
    specs = {m["name"]: spec for m, spec in cell.per_layer}
    assert specs["decode_step_ms.docs"]["reducer"] == "program_ms"
    assert specs["decode_hbm_share.docs"]["reducer"] == "roofline_share"


@pytest.mark.parametrize("bad", [float("nan"), float("inf")])
def test_a_gap_that_is_not_finite_is_not_correct(monkeypatch, bad):
    """``max(widest, nan)`` keeps ``widest``: one request's NaN among
    sound gaps used to pass. The reference's logits are hand-made: the
    first request's are sound (its tokens the best everywhere), the
    second's hold ``bad`` at one served position, whichever comes
    first."""
    vocab, served = 8, 4
    sound = np.zeros((served, vocab), np.float32)
    sound[:, 5] = 1.0
    broken = sound.copy()
    broken[2, 6] = bad      # that row's best, and so its gap, is ``bad``
    req = lambda: types.SimpleNamespace(prompt=[1, 2, 3],
                                        tokens=[5] * served)

    for rows in ([sound, broken], [broken, sound]):
        monkeypatch.setattr(reference, "logits_at",
                            lambda *a, rows=rows, **k: list(rows))
        lines = []
        ok, numbers = reference.check_served(
            7, None, None, None, [req(), req()], 0.5, log=lines.append)
        assert ok is False and not np.isfinite(numbers["widest_gap"])
        assert numbers["served_tokens"] == 2 * served
        assert any("OVER" in ln for ln in lines)
    monkeypatch.setattr(reference, "logits_at",
                        lambda *a, **k: [sound, sound])
    ok, numbers = reference.check_served(
        7, None, None, None, [req(), req()], 0.5, log=lambda m: None)
    assert ok is True and numbers["widest_gap"] == 0.0
