"""PR 45's rule (PERF.md section 3; ``test_bench_steady_programs.py``
holds both ``longdocs`` cells to it) for ``ouro-2.6b-1chip.fewshot``: a
``device_trace`` metric names a compiled program only where the cell's
STEADY loop runs it.

Under ``fewshot`` (12 clients over 6 slots, prompts of 1 to 4 chunk
programs, 16 out) the decode batch rides a tick's FIRST chunk program
only, so about half its decode steps run in ticks that hold no chunk,
all window long, as in ``docs`` and unlike both ``longdocs`` cells:
``jit__decode`` ran 26 times in the capture from 6 s and 27 in one from
20 s, ``decode_fused_share.ouro`` 0.52 and 0.48 (PERF.md section 6, PR
48; 47 and 55 runs at the 32 outputs the issue named). So the cell keeps
a pair of metrics that read that program.
"""

import re

from benchmark.harness import loader

CELL = "ouro-2.6b-1chip.fewshot"


def _reading(cell, program):
    patterns = {m["name"]: spec.get("params", {}).get("pattern")
                for m, spec in cell.per_layer}
    return {name for name, pattern in patterns.items()
            if pattern and re.search(pattern, program)}


def test_the_decode_only_program_is_read_by_its_pair_alone():
    cell = loader.load_cell(CELL)
    assert _reading(cell, "jit__decode(3)") == {"decode_step_ms.ouro",
                                                "decode_hbm_share.ouro"}
    specs = {m["name"]: spec for m, spec in cell.per_layer}
    assert specs["decode_step_ms.ouro"]["reducer"] == "program_ms"
    assert specs["decode_hbm_share.ouro"] == {
        "reducer": "roofline_share",
        "params": {"bound": "bytes", "pattern": "^jit__decode"}}


def test_every_other_program_metric_reads_the_slowest_chunk_program():
    cell = loader.load_cell(CELL)
    chunk = _reading(cell, "jit__chunk(7)")
    assert chunk == {n + ".ouro" for n in (
        "prefill_chunk_ms", "chunk_roofline_share", "chunk_attn_ms",
        "chunk_mlp_ms", "decode_rows_attn_ms", "head_ms",
        "pass_overhead_ms")}
    specs = {m["name"]: spec["params"] for m, spec in cell.per_layer}
    assert all(specs[n]["variant"] == "slowest" for n in chunk)
    # The mix that makes the rule hold: twice the slots in clients, and
    # outputs long enough that decode-only ticks come back.
    mix, srv = cell.traffic, cell.config["serving"]
    assert mix["clients"] == 2 * srv["num_slots"]
    assert mix["output_tokens"]["value"] >= 16
