#!/usr/bin/env python
"""Minimal stand-in for ``ruff check`` when ruff is not installed.

Implements the conservative subset `make lint` relies on — E9 (files
must parse) and F401 (unused imports) — with ``# noqa`` support, so the
lint gate functions in hermetic containers that cannot pip-install.
When ruff IS available the Makefile prefers it (full F + E9 rule set
from pyproject.toml); this fallback intentionally checks less, never
more, than ruff would.

Usage: python tools/_lint_fallback.py [paths...]   (default: repo tree)
Exit 1 when any finding is reported.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import Iterator, List, Set, Tuple

DEFAULT_ROOTS = ("infw", "tools", "tests", "deploy", "bench.py",
                 "__graft_entry__.py")
EXCLUDE_DIRS = {"__pycache__", ".git", "testruns", "_build"}


def iter_py_files(roots) -> Iterator[str]:
    for root in roots:
        if os.path.isfile(root) and root.endswith(".py"):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d not in EXCLUDE_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def _noqa_lines(src: str) -> Set[int]:
    out = set()
    for i, line in enumerate(src.splitlines(), 1):
        if "# noqa" in line:
            out.add(i)
    return out


class _ImportCollector(ast.NodeVisitor):
    def __init__(self) -> None:
        self.imports: List[Tuple[str, str, int]] = []  # (bound, shown, line)
        self.used: Set[str] = set()
        self.exported: Set[str] = set()

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            bound = a.asname or a.name.split(".")[0]
            self.imports.append((bound, a.name, node.lineno))

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return  # future imports act by existing (ruff skips them too)
        for a in node.names:
            if a.name == "*":
                continue
            bound = a.asname or a.name
            self.imports.append((bound, a.name, node.lineno))

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        # __all__ = [...] marks re-exports
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id == "__all__":
                try:
                    for v in ast.literal_eval(node.value):
                        self.exported.add(str(v))
                except (ValueError, TypeError):
                    pass
        self.generic_visit(node)


def check_file(path: str) -> List[str]:
    with open(path, "rb") as f:
        raw = f.read()
    try:
        src = raw.decode("utf-8")
    except UnicodeDecodeError as e:
        return [f"{path}:1:1: E902 {e}"]
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}:{e.offset}: E999 {e.msg}"]
    noqa = _noqa_lines(src)
    col = _ImportCollector()
    col.visit(tree)
    # names referenced anywhere in string annotations also count as used
    # (cheap approximation: every identifier token in the file body)
    findings = []
    for bound, shown, lineno in col.imports:
        if lineno in noqa or bound in ("_", "__"):
            continue
        if bound in col.used or bound in col.exported:
            continue
        # conftest/init side-effect imports are conventional
        if os.path.basename(path) == "__init__.py":
            continue
        findings.append(
            f"{path}:{lineno}:1: F401 {shown!r} imported but unused"
        )
    return findings


def main(argv=None) -> int:
    args = (argv if argv is not None else sys.argv[1:]) or list(DEFAULT_ROOTS)
    roots = [a for a in args if os.path.exists(a)]
    findings: List[str] = []
    n = 0
    for path in iter_py_files(roots):
        n += 1
        findings.extend(check_file(path))
    for line in findings:
        print(line)
    print(f"fallback lint: {n} files, {len(findings)} finding(s) "
          "(ruff not installed; E9 + F401 subset)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
