"""The seam between the harness and a model family.

- The dense decoder, moved behind it, makes the weights it always made:
  checksums taken on the parent of the move (PR 26's tree), which the
  moved code reproduces bit for bit.
- The harness knows no family: only ``system.py`` imports the program,
  no reference half of a family does, and no file of the harness holds a
  leaf name or a configuration key of the dense decoder.
- A second family, ``data/families/tiny_moe*.py``, arrives as files and
  appended entries: it is served through ``Engine(model=qwen_moe)``,
  checked against its own float32 layer and counted by its own counts,
  with no file of the harness touched.
"""

import ast
import copy
import hashlib
import json
import os
import re

import numpy as np
import pytest

from benchmark.harness import loader, run, serve_loop as L, weights as W
from benchmark.harness.reducers import RunContext, read_metric

DATA = run.REHEARSE_DATA
HARNESS = os.path.join(loader.DATA_ROOT, "harness")
D = loader.load_family("dense", [loader.DATA_ROOT])

# sha256 (first 16 hex digits) of each leaf's bytes on the CPU, layer 1,
# from ``harness/weights.py`` as it stood before the dense decoder moved.
SUMS = {
    ("tiny", 7): {
        "wq": "3f54cd1fdc663787", "wo": "b1c02a3d277b2f02",
        "w_down": "10a37b3558d27b9b", "ln_attn": "b715b70e20082bb7",
        "q_norm": "eb92b2e550d89091", "embed.0": "51d9427f7cc68eff",
        "lm_head.3": "e75b34c3395cb67b", "final_norm": "0df44bc6be89db55"},
    ("tiny", 2**31 + 12345): {
        "wq": "643b060b44a88773", "wo": "3070a6dbf92598d1",
        "w_down": "7d774d1fe9540af3", "ln_attn": "c576cf09d793a9c0",
        "q_norm": "81bc48db273d125b", "embed.0": "88ff2ba531d24cd0",
        "lm_head.3": "7233ada1252e9fe5", "final_norm": "b3a073cbe2a03bba"},
    ("tiny-tp4", 7): {
        "wk": "b060ae721f595aa6", "w_gate": "1c0654f5f0f4e4fa",
        "w_up": "19c8a29f6ba283d7", "ln_mlp": "c3bc142c718a5988",
        "bq": "7f36e7a00e410fe0", "bv": "3fd2c82f4b626510",
        "embed.0": "51d9427f7cc68eff", "final_norm": "0df44bc6be89db55"},
    ("tiny-tp4", 2**31 + 12345): {
        "wk": "d35837cf71f9a85c", "w_gate": "2415a167c87482b5",
        "w_up": "11688258bbe4552e", "ln_mlp": "2b3d6eb783565e28",
        "bq": "627a97444fe11e0a", "bv": "e8a2c8d22c75616a",
        "embed.0": "88ff2ba531d24cd0", "final_norm": "b3a073cbe2a03bba"},
    ("tiny-bf16", 7): {
        "wv": "f14cb7d117d846e8", "wo": "f648fb99d22d66ae",
        "w_down": "287bd1e1bd86aa4f", "k_norm": "59d0929fcb1b3ec5",
        "embed.0": "26ad513e9c8507af", "lm_head.3": "6aee3a8d3144c5f5",
        "final_norm": "d70113281c8b204a"},
    ("tiny-bf16", 2**31 + 12345): {
        "wv": "e008bd62dcbd9c23", "wo": "df0d69abe2e46a92",
        "w_down": "a4b6a824b2a788a0", "k_norm": "841628378c73ca36",
        "embed.0": "a1f17d5eb2553b1b", "lm_head.3": "6a5ef9d4ed3f5995",
        "final_norm": "fc3fe43cab5c20de"},
}


def _sum(x):
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


def _config(name):
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, seed", sorted(SUMS))
def test_the_moved_dense_family_makes_the_weights_it_always_made(name, seed):
    config = _config(name)
    dims, dtype = D.dims(config), W.DTYPES[config["dtype"]]
    root = W.root_key(seed)
    w = W.make_layer(root, 1, D.layer_leaves(dims, D.layer_kind(dims, 1)),
                     D.LEAF_IDS, dtype)
    got = {k: _sum(v) for k, v in w.items()}
    got["final_norm"] = _sum(W.make_final_norm(root, dims, dtype))
    for which, block in (("embed", 0), ("lm_head", 3)):
        got[f"{which}.{block}"] = _sum(
            W.make_table_block(root, which, block, dims, dtype))
    want = SUMS[name, seed]
    assert {k: got[k] for k in want} == want
    # Made in the order of their ids, as always: the programs that make
    # a layer (set-up's and the reference's) stay text for text the ones
    # the compile cache and the limits' readings know.
    assert list(w) == sorted(w, key=D.LEAF_IDS.get)


# -- the harness knows no family --------------------------------------------

def _python_files(top):
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(base, f)


def _imports(source):
    """The modules a file's text imports: by statement, or by name at
    run time (a call that is handed a module's name as a string)."""
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
        elif isinstance(node, ast.Call):
            yield from (a.value for a in node.args
                        if isinstance(a, ast.Constant)
                        and isinstance(a.value, str)
                        and re.fullmatch(r"[A-Za-z_][\w.]*", a.value))


def _may_import_the_program(rel):
    """The rule: of the harness ``system.py`` alone, and under any
    ``families`` directory the files named ``*_system.py``."""
    return (rel == "benchmark/harness/system.py"
            or (os.path.basename(os.path.dirname(rel)) == "families"
                and rel.endswith("_system.py")))


def test_only_system_and_a_familys_system_half_import_the_program():
    importers = []
    for root in (loader.DATA_ROOT, DATA):
        for path in _python_files(root):
            with open(path) as f:
                found = list(_imports(f.read()))
            if any(m.split(".")[0] == "triton_dist_tpu" for m in found):
                importers.append(os.path.relpath(path, loader.REPO_ROOT))
    inside = [p for p in importers if p.startswith("benchmark/harness/")
              or "/families/" in p]
    assert [p for p in inside if not _may_import_the_program(p)] == []
    # The scan sees an import where there is one: the harness's one
    # importer and every system half there is, the expert families' by a
    # plain statement since the list of files became this rule (PR 47).
    assert {"benchmark/harness/system.py",
            "benchmark/families/dense_system.py",
            "benchmark/families/mla_moe_system.py",
            "benchmark/families/mamba_latent_moe_system.py",
            "tests/benchmark/data/families/tiny_moe_system.py"} <= set(inside)
    # ... whose name decides, and an import by name at run time is one.
    assert not _may_import_the_program("benchmark/families/dense.py")
    assert not _may_import_the_program("benchmark/harness/reference.py")
    assert not _may_import_the_program("tests/benchmark/data/x_system.py")
    assert list(_imports('m = importlib.import_module("triton_dist_tpu.ops")'
                         )) == ["triton_dist_tpu.ops"]


def test_no_file_of_the_harness_holds_a_dense_leaf_or_key():
    with open(D.__file__) as f:
        keys = set(re.findall(r'c(?:\.get)?[\[(]"(\w+)"', f.read()))
    assert {"hidden_size", "num_key_value_heads", "qk_norm"} <= keys
    # The vocabulary is every family's: the harness reads it as
    # ``dims.vocab``, and the loader names the key in its rule on cuts.
    words = (keys | set(D.LEAF_IDS)) - {"vocab_size"}
    word = re.compile(r"\b(" + "|".join(sorted(words)) + r")\b")
    found = []
    for base, dirs, files in os.walk(HARNESS):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith((".py", ".json")):
                with open(os.path.join(base, name)) as f:
                    found += [(name, m) for m in word.findall(f.read())]
    assert found == []


# -- a second family, as files ------------------------------------------------

@pytest.fixture(scope="module")
def moe(tmp_path_factory):
    """The tests' benchmark with one configuration and one cell
    APPENDED; the family's two modules and the configuration's file lie
    in the tests' data root, and nothing of the harness knows them."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = copy.deepcopy(bench)
    bench["configs"].append({
        "name": "tiny-moe", "source": "tests only",
        "file": "tests/benchmark/data/configs/tiny-moe.json",
        "reduced": ["num_experts", "vocab_size"], "why": "tests only"})
    bench["workloads"].append({
        "name": "tiny-moe.docs", "config": "tiny-moe",
        "traffic": "tiny-docs", "chips": 1, "why": "tests only"})
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append("tiny-moe.docs")
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    path = tmp_path_factory.mktemp("moe") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def _run(bench, seed, capsys):
    assert run.main(["--rehearse", "--workload", "tiny-moe.docs", "--seed",
                     str(seed), "--seconds", "0.5", "--trace", "0",
                     "--benchmark-file", bench]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_a_family_added_as_files_is_found_by_name(moe):
    cell = loader.load_cell("tiny-moe.docs", moe, [DATA, loader.DATA_ROOT])
    assert cell.config["family"] == "tiny_moe"
    assert cell.family.__file__ == os.path.join(DATA, "families",
                                                "tiny_moe.py")
    dims = cell.family.dims(cell.config)
    assert (dims.experts, dims.experts_per_tok, dims.expert_ff) == (16, 2, 32)
    assert {"vocab", "d", "layers", "eps", "tie"} <= set(vars(dims))
    assert hash(dims) == hash(cell.family.dims(cell.config))
    leaves = cell.family.layer_leaves(dims, cell.family.layer_kind(dims, 0))
    assert leaves["experts_up"][0] == (16, 32, 32)
    assert set(leaves) <= set(cell.family.LEAF_IDS)
    # Its attention is the dense family's, under the same folds.
    assert all(cell.family.LEAF_IDS[k] == v for k, v in D.LEAF_IDS.items())
    build = loader.sibling(cell.family.__file__, "tiny_moe_system")
    assert build.F is cell.family
    assert build.engine_kwargs(cell.config)["moe_impl"] == "tp"
    assert build.model_config(cell.config).is_moe


def test_the_added_family_is_served_and_is_correct(moe, capsys):
    res, lines = _run(moe, 2**31 + 77, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4
    text = "\n".join(lines)
    assert "'mode': 'xla', 'mode_kept': True" in text
    assert "compiled inside the window: 0" in text


def test_the_added_family_with_a_token_altered_is_not_correct(
        moe, monkeypatch, capsys):
    from triton_dist_tpu.serving.server import ServingEngine

    sound = ServingEngine._pick
    calls = [0]

    def off_by_one(self, logits_row, req, step):
        # Every request's third token: whichever three the check draws,
        # and however the host's timing fell, it meets an altered one.
        calls[0] += 1
        tok = sound(self, logits_row, req, step)
        return (tok + 1) % len(logits_row) if step == 2 else tok

    monkeypatch.setattr(ServingEngine, "_pick", off_by_one)
    res, lines = _run(moe, 2**31 + 78, capsys)
    assert calls[0] > 20
    assert res["correct"] is False and res["failed"] == 0
    assert any("OVER" in ln for ln in lines)


def test_the_added_familys_counts_are_read_through_it():
    """16 experts of 3 * 32 * 32 parameters, 2 a token: a decode step
    of two sequences reads 4 of them a layer, a prefill row runs 2."""
    F = loader.load_family("tiny_moe", [DATA])
    dims = F.dims(_config("tiny-moe"))
    attn = 2 * 32 * 64 + 2 * 32 * 64            # q, o; k, v
    router, expert, head = 32 * 16, 3 * 32 * 32, 256 * 32
    kv = 2 * 8 * 8 * 2 * 2                       # a position, both layers
    assert F.decode_step_bytes(dims, 404.0, 2) == (
        (2 * (attn + router + 4 * expert) + head) * 2 + 404 * kv)
    # No more than the experts held, whatever the batch.
    assert F.decode_step_bytes(dims, 0, 64) == (
        (2 * (attn + router + 16 * expert) + head) * 2)
    assert F.prefill_chunk_flops(dims, 16, 8.5) == (
        2 * 16 * 2 * (attn + router + 2 * expert)
        + 4 * 16 * 8.5 * 8 * 8 * 2 + 2 * head)

    # ... and the roofline reducer hands it the window's decode batch.
    recs = []
    for rid, n_prompt in ((0, 100), (1, 300)):
        r = L.Record(rid, [1] * n_prompt, 4, due_at=0.9)
        r.token_times = [1.0, 1.02, 1.04, 1.06]
        r.tokens, r.status, r.finished_at = [5] * 4, "done", 1.06
        recs.append(r)
    ticks = [L.Tick(1.0 + 0.02 * i, 1.0 + 0.02 * i + 0.018, 2, False)
             for i in range(3)]
    rows = [{"plane": "/device:TPU:0", "line": "XLA Modules",
             "name": "jit__decode(1)", "start_ns": 0.0, "dur_ns": 5e6}]

    class _Cell:
        config = {"tp": 1}

    ctx = RunContext(cell=_Cell(), family=F, dims=dims,
                     peaks={"hbm_bytes_per_s": 1e9}, window=L.Window(
                         recs, ticks, 0.9, 2.0, [0.0, 0.0], 2.0),
                     traced=(0.9, 2.0), rows=rows, compile_s=0.0,
                     log=lambda m: None)
    got = read_metric({"reducer": "roofline_share", "params": {
        "bound": "bytes", "pattern": "^jit__decode"}}, ctx)
    assert got == pytest.approx(
        100 * F.decode_step_bytes(dims, 404.0, 2) / 1e9 / 5e-3)
