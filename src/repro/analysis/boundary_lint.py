"""AST lint of repo conventions — stdlib-only, fast enough for CI.

Two rules, each encoding a convention this repo adopted and has no
other machine check for:

* **T001 tile/step literals**: tiling and step knobs (``bm``/``bn``/
  ``bd``/``bt``/``bs``/``bq``/``bk``/``block``/``eta``) are config, not
  call-site magic numbers. A numeric literal bound to one of these
  kwargs at a call site is flagged — EXCEPT when the callee is a config
  constructor (name ending in ``Config``/``Params``/``Spec``) or
  ``dataclasses.replace``, which are exactly where such values belong.
  Function-def defaults are inherently exempt (they ARE the config).
* **P001 pallas containment**: ``jax.experimental.pallas`` imports live
  only under ``src/repro/kernels/`` — every other layer consumes kernels
  through ``repro.kernels.ops`` so interpret-mode policy and padding
  stay in one place.

Suppression: append ``# lint: ignore[CODE]`` to a line, or put
``# lint: allow[CODE]`` anywhere in a file to waive that rule file-wide.
``scripts/lint.py`` is the CLI; ``tests/test_analysis.py`` pins that the
seeded fixtures under ``tests/fixtures/lint/`` fail and the real tree
passes.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Iterable

__all__ = ["LintViolation", "lint_file", "lint_paths", "walk_default",
           "RULES", "TILE_KNOBS"]

#: tiling/step kwargs that must come from config, not call-site literals
TILE_KNOBS = frozenset({"bm", "bn", "bd", "bt", "bs", "bq", "bk",
                        "block", "eta"})

#: callee names whose keywords ARE configuration (T001 exemption)
_CONFIG_CALL_RE = re.compile(r"(Config|Params|Spec)$|^replace$|^create$")

RULES = {
    "T001": "hardcoded tile/step size at a call site — move it into a "
            "config dataclass",
    "P001": "pallas import outside src/repro/kernels/ — consume kernels "
            "via repro.kernels.ops",
}

_IGNORE_RE = re.compile(r"#\s*lint:\s*ignore\[([A-Z]\d{3}(?:\s*,\s*[A-Z]\d{3})*)\]")
_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\[([A-Z]\d{3}(?:\s*,\s*[A-Z]\d{3})*)\]")


@dataclasses.dataclass(frozen=True)
class LintViolation:
    file: str
    line: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.file}:{self.line}: {self.code} {self.message}"


def _codes(match: re.Match) -> set[str]:
    return {c.strip() for c in match.group(1).split(",")}


def _suppressions(source: str) -> tuple[dict[int, set[str]], set[str]]:
    per_line: dict[int, set[str]] = {}
    per_file: set[str] = set()
    for i, text in enumerate(source.splitlines(), start=1):
        m = _IGNORE_RE.search(text)
        if m:
            per_line.setdefault(i, set()).update(_codes(m))
        m = _ALLOW_RE.search(text)
        if m:
            per_file.update(_codes(m))
    return per_line, per_file


class _Visitor(ast.NodeVisitor):
    def __init__(self, in_kernels: bool):
        self.in_kernels = in_kernels
        self.out: list[tuple[int, str, str]] = []

    # -- P001 --------------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if "pallas" in a.name.split(".") and not self.in_kernels:
                self.out.append((node.lineno, "P001",
                                 f"import of {a.name!r}: {RULES['P001']}"))
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        if node.level == 0:
            if "pallas" in mod.split(".") and not self.in_kernels:
                self.out.append((node.lineno, "P001",
                                 f"import from {mod!r}: {RULES['P001']}"))
            for a in node.names:
                fq = f"{mod}.{a.name}" if mod else a.name
                if "pallas" in fq.split(".") and not self.in_kernels:
                    self.out.append((node.lineno, "P001",
                                     f"import of {fq!r}: {RULES['P001']}"))
        self.generic_visit(node)

    # -- T001 --------------------------------------------------------------

    def _check_tile_literals(self, node: ast.Call) -> None:
        callee = None
        if isinstance(node.func, ast.Name):
            callee = node.func.id
        elif isinstance(node.func, ast.Attribute):
            callee = node.func.attr
        if callee is not None and _CONFIG_CALL_RE.search(callee):
            return
        for kw in node.keywords:
            if kw.arg in TILE_KNOBS and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, (int, float)) \
                    and not isinstance(kw.value.value, bool):
                self.out.append((kw.value.lineno, "T001",
                                 f"{kw.arg}={kw.value.value!r} passed to "
                                 f"{callee or 'a call'}(): "
                                 f"{RULES['T001']}"))

    def visit_Call(self, node: ast.Call) -> None:
        self._check_tile_literals(node)
        self.generic_visit(node)


def lint_file(path: str, source: str | None = None) -> list[LintViolation]:
    """Lint one file; returns violations after pragma suppression."""
    if source is None:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [LintViolation(file=path, line=e.lineno or 0, code="E999",
                              message=f"syntax error: {e.msg}")]
    per_line, per_file = _suppressions(source)
    in_kernels = "repro/kernels/" in path.replace(os.sep, "/")
    visitor = _Visitor(in_kernels)
    visitor.visit(tree)
    out = []
    for line, code, msg in visitor.out:
        if code in per_file or code in per_line.get(line, set()):
            continue
        out.append(LintViolation(file=path, line=line, code=code,
                                 message=msg))
    return sorted(out, key=lambda v: (v.file, v.line, v.code))


def walk_default(root: str) -> list[str]:
    """The default lint scope: src, benchmarks, examples, scripts —
    everything that ships; tests (and their seeded fixtures) opt in via
    explicit arguments."""
    files: list[str] = []
    for sub in ("src", "benchmarks", "examples", "scripts"):
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            files.extend(os.path.join(dirpath, f)
                         for f in sorted(filenames) if f.endswith(".py"))
    return sorted(files)


def lint_paths(paths: Iterable[str]) -> list[LintViolation]:
    out: list[LintViolation] = []
    for p in paths:
        out.extend(lint_file(p))
    return out
