#!/usr/bin/env python
"""Docs-consistency check: every file path, `repro.*` module reference,
markdown link target, and CLI flag mentioned in README.md, ROADMAP.md,
and docs/*.md must actually exist in the tree.

Docs that drift from the code are worse than no docs - this runs in CI
(see .github/workflows/ci.yml) so a rename or flag removal that leaves
a stale reference behind fails the build with a precise list.

Checks, per scanned document:

  * repo-rooted paths (src/... tests/... benchmarks/... docs/...
    examples/... tools/... .github/...) with a file extension -> must
    exist as a file; rooted directory refs ending in "/" -> must exist
    as a directory;
  * dotted module refs (repro.foo.bar[.attr...]) -> the longest module
    prefix must resolve under src/, and any trailing attribute must
    appear by name in that module's source;
  * relative markdown link targets -> must resolve from the doc's
    directory;
  * `--flag` tokens -> must be defined by some argparse entry point
    (benchmarks/*.py, src/repro/launch/*.py, tools/*.py, chip_smoke.py)
    or be on the allowlist of external flags (XLA/pytest flags we
    merely quote).

Usage: python tools/check_docs.py   (exit 0 = consistent)
"""
from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCANNED = ["README.md", "ROADMAP.md"]
DOCS_DIR = "docs"

PATH_ROOTS = ("src/", "tests/", "benchmarks/", "docs/", "examples/",
              "tools/", ".github/")
PATH_RE = re.compile(
    r"(?<![\w/.-])((?:src|tests|benchmarks|docs|examples|tools|\.github)"
    r"/[\w./-]+)")
MODULE_RE = re.compile(r"(?<![\w.])repro(?:\.\w+)+")
LINK_RE = re.compile(r"\]\(([^)\s]+)\)")
FLAG_RE = re.compile(r"(?<![\w-])(--[a-zA-Z][\w-]*)")
ARGPARSE_RE = re.compile(r"add_argument\(\s*[\"'](--[\w-]+)[\"']")

# Flags we quote but do not define: external tools' surface.
FLAG_ALLOWLIST = {
    "--xla_force_host_platform_device_count",   # XLA
    "--collect-only", "--ignore",               # pytest (quoted in docs)
}


def _defined_flags() -> set[str]:
    flags = set()
    scan = []
    for d in ("benchmarks", "tools",
              os.path.join("src", "repro", "launch")):
        full = os.path.join(REPO, d)
        scan += [os.path.join(full, f) for f in os.listdir(full)
                 if f.endswith(".py")]
    scan.append(os.path.join(REPO, "chip_smoke.py"))
    for path in scan:
        with open(path, encoding="utf-8") as fh:
            flags.update(ARGPARSE_RE.findall(fh.read()))
    return flags


def _check_module(ref: str) -> str | None:
    """Resolve repro.a.b[.attr...]: longest module prefix under src/,
    trailing attribute must appear in the module source."""
    parts = ref.split(".")
    base = os.path.join(REPO, "src")
    depth = 0
    mod_file = None
    for depth in range(len(parts), 0, -1):
        cand = os.path.join(base, *parts[:depth])
        if os.path.isfile(cand + ".py"):
            mod_file = cand + ".py"
            break
        if os.path.isdir(cand) and os.path.isfile(
                os.path.join(cand, "__init__.py")):
            mod_file = os.path.join(cand, "__init__.py")
            break
    if mod_file is None:
        return f"module {ref}: no repro package prefix resolves"
    if depth < len(parts):
        attr = parts[depth]
        with open(mod_file, encoding="utf-8") as fh:
            if not re.search(r"\b%s\b" % re.escape(attr), fh.read()):
                return (f"module {ref}: attribute {attr!r} not found in "
                        f"{os.path.relpath(mod_file, REPO)}")
    return None


def check_file(relpath: str) -> list[str]:
    errors = []
    path = os.path.join(REPO, relpath)
    with open(path, encoding="utf-8") as fh:
        text = fh.read()

    for ref in sorted(set(PATH_RE.findall(text))):
        ref_clean = ref.rstrip(".")          # sentence-final dot
        full = os.path.join(REPO, ref_clean)
        if ref_clean.endswith("/"):
            if not os.path.isdir(full):
                errors.append(f"{relpath}: directory {ref_clean} missing")
        elif "." in os.path.basename(ref_clean):
            if not os.path.isfile(full):
                errors.append(f"{relpath}: file {ref_clean} missing")
        elif not os.path.exists(full):
            errors.append(f"{relpath}: path {ref_clean} missing")

    for ref in sorted(set(MODULE_RE.findall(text))):
        err = _check_module(ref.rstrip("."))
        if err:
            errors.append(f"{relpath}: {err}")

    doc_dir = os.path.dirname(path)
    for target in sorted(set(LINK_RE.findall(text))):
        if target.startswith(("http://", "https://", "#", "mailto:")):
            continue
        rel = target.split("#")[0]
        if rel and not os.path.exists(os.path.join(doc_dir, rel)):
            errors.append(f"{relpath}: markdown link {target} dangling")

    defined = _defined_flags() | FLAG_ALLOWLIST
    for flag in sorted(set(FLAG_RE.findall(text))):
        if flag not in defined:
            errors.append(f"{relpath}: flag {flag} not defined by any "
                          f"entry point")
    return errors


def main() -> int:
    docs = list(SCANNED)
    docs_dir = os.path.join(REPO, DOCS_DIR)
    if os.path.isdir(docs_dir):
        docs += [os.path.join(DOCS_DIR, f)
                 for f in sorted(os.listdir(docs_dir))
                 if f.endswith(".md")]
    errors = []
    for doc in docs:
        if not os.path.isfile(os.path.join(REPO, doc)):
            errors.append(f"{doc}: scanned document itself is missing")
            continue
        errors += check_file(doc)
    if errors:
        print(f"docs-consistency: {len(errors)} stale reference(s):")
        for e in errors:
            print("  " + e)
        return 1
    print(f"docs-consistency: OK ({len(docs)} documents checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
