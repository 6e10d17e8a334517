"""Generate ``docs/api.md`` from the package source (AST-walk — no
imports, so it runs without a backend).

A hand-maintained index drifts. This generator is
the single source; ``make ci`` runs ``--check`` and fails when the
committed file is stale. Format: one section per subsystem, one entry
per public (non-underscore) top-level symbol with its docstring's first
line; simple ``name = other`` aliases are labeled as such.

Usage::

    python -m triton_dist_tpu.tools.gen_api            # rewrite
    python -m triton_dist_tpu.tools.gen_api --check    # drift gate
"""

from __future__ import annotations

import ast
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)
OUT = os.path.join(REPO, "docs", "api.md")

# Subsystem presentation order + one-line banners.
SECTIONS = [
    ("lang", "in-kernel communication primitives"),
    ("shmem", "host-side symmetric-buffer management"),
    ("parallel", "mesh / logical-id plumbing"),
    ("ops", "fused distributed kernels"),
    ("layers", "model layers"),
    ("models", "model families, engine, checkpoints"),
    ("serving", "paged KV block manager + continuous batching"),
    ("obs", "serving telemetry: spans, histograms, merged traces"),
    ("megakernel", "persistent single-kernel decode"),
    ("profiler", "in-kernel profiling + Perfetto export"),
    ("resilience", "fault injection, watchdogs, degradation policy"),
    ("tools", "topology, perf models, AOT, tuning CLIs"),
    ("utils", "runtime bring-up, testing harness"),
    ("", "top-level modules"),
]


def _first_doc_line(node) -> str:
    doc = ast.get_docstring(node)
    if not doc:
        return ""
    return doc.strip().splitlines()[0].strip()


def _module_entries(path: str):
    """Public top-level symbols of one module: (name, kind, summary)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    entries = []
    defs = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if not node.name.startswith("_"):
                entries.append((node.name, "def", _first_doc_line(node)))
            defs.add(node.name)
        elif isinstance(node, ast.ClassDef):
            if not node.name.startswith("_"):
                entries.append((node.name, "class",
                                _first_doc_line(node)))
            defs.add(node.name)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            if (isinstance(tgt, ast.Name) and not tgt.id.startswith("_")
                    and isinstance(node.value, ast.Name)
                    and node.value.id in defs):
                entries.append((tgt.id, f"alias:{node.value.id}", ""))
    return ast.get_docstring(tree), entries


def _iter_modules():
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = sorted(d for d in dirnames
                             if not d.startswith("_"))
        for fn in sorted(filenames):
            if fn.endswith(".py") and fn != "__init__.py":
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, REPO)
                sub = os.path.relpath(dirpath, ROOT)
                yield ("" if sub == "." else sub.split(os.sep)[0],
                       rel, full)


def generate() -> str:
    by_section = {}
    for sub, rel, full in _iter_modules():
        by_section.setdefault(sub, []).append((rel, full))

    lines = [
        "# API index",
        "",
        "Every public symbol (non-underscore top-level def/class, plus",
        "simple aliases) with its docstring's first line, grouped by",
        "subsystem — the navigable form of PARITY.md's component",
        "mapping.",
        "",
        "GENERATED by `python -m triton_dist_tpu.tools.gen_api` — do",
        "not edit by hand; `make ci` fails when stale.",
        "",
        "@SYMBOL_COUNT@",
    ]
    count = 0
    for sub, banner in SECTIONS:
        mods = by_section.pop(sub, None)
        if not mods:
            continue
        title = sub if sub else "triton_dist_tpu"
        lines += ["", f"## {title} — {banner}"]
        for rel, full in mods:
            mod_doc, entries = _module_entries(full)
            if not entries:
                continue
            lines += ["", f"### `{rel}`"]
            if mod_doc:
                lines.append(f"*{mod_doc.strip().splitlines()[0]}*")
            lines.append("")
            for name, kind, summary in entries:
                count += 1
                if kind.startswith("alias:"):
                    lines.append(
                        f"- `{name}` — alias of `{kind[6:]}`")
                elif summary:
                    lines.append(f"- `{name}` ({kind}) — {summary}")
                else:
                    lines.append(f"- `{name}` ({kind})")
    for sub in sorted(by_section):
        raise SystemExit(
            f"gen_api: subpackage {sub!r} has no SECTIONS entry — add "
            "one so new subsystems are indexed deliberately")
    out = "\n".join(lines) + "\n"
    return out.replace("@SYMBOL_COUNT@", f"{count} symbols.")


def main(argv):
    text = generate()
    if "--check" in argv:
        try:
            with open(OUT) as f:
                current = f.read()
        except FileNotFoundError:
            current = ""
        if current != text:
            print("docs/api.md is STALE — regenerate with "
                  "`python -m triton_dist_tpu.tools.gen_api`",
                  file=sys.stderr)
            return 1
        print("docs/api.md up to date")
        return 0
    with open(OUT, "w") as f:
        f.write(text)
    print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
