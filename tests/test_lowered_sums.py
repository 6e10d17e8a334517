"""``scripts/lowered_sums.py``: the property every use of it rests on.
Two copies of ONE tree, at paths of different depth, give the same
length and sha256 for a step program, so that a sum that differs
between two trees says that the programs differ and nothing else
(docs/testing.md, "Whether a change moves a program")."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM = "ouro-2.6b-1chip:chunk-128+6"     # the smallest bucket there is


def test_two_copies_of_one_tree_give_one_sum(tmp_path):
    from jax.experimental import topologies
    try:
        topologies.get_topology_desc(platform="tpu",
                                     topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — libtpu says why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    copies = [tmp_path / "a", tmp_path / "b" / "deeper" / "tree"]
    skip = shutil.ignore_patterns("__pycache__", "*.pyc")
    for copy in copies:
        for part in ("triton_dist_tpu", "benchmark"):
            shutil.copytree(os.path.join(REPO, part), copy / part,
                            ignore=skip)
        os.makedirs(copy / "scripts")
        shutil.copy(os.path.join(REPO, "scripts", "lowered_sums.py"),
                    copy / "scripts")
    # From a directory that is neither copy: the tree is the argument.
    runs = [subprocess.Popen(
        [sys.executable, str(copy / "scripts" / "lowered_sums.py"),
         str(copy), PROGRAM], cwd=tmp_path, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for copy in copies]
    lines = []
    for run in runs:
        out, err = run.communicate(timeout=150)
        assert run.returncode == 0, err[-2000:]
        lines.append(out.strip().splitlines())
    assert lines[0] == lines[1] and len(lines[0]) == 1
    config, program, length, sha = lines[0][0].split()
    assert f"{config}:{program}" == PROGRAM
    assert int(length) > 50_000 and len(sha) == 64
