"""Pass 1 — JAX compat linter.

Two invariants, both AST-checked over every .py file in the repo except
`alphafold2_tpu/compat.py` (the single module allowed to touch
version-dependent names):

  COMPAT001  no `jax.experimental.*` import or attribute access — the
             experimental namespace is where JAX renames things without
             deprecation cycles; every use funnels through compat.py.
  COMPAT002  no direct use of a drift-table symbol (drift.py): a name
             JAX has renamed before is spelled once, in compat.py, so the
             next rename is a one-file change.
  COMPAT003  no drift-table call keyword (`check_vma`,
             `ShapeDtypeStruct(vma=...)`) except on the compat wrappers.

Suppression: `# af2lint: disable=COMPAT002` on the offending line (used
by code that is itself version-probing, which should be rare — prefer
moving the probe into compat.py).
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Sequence

from alphafold2_tpu.analysis import drift
from alphafold2_tpu.analysis.common import (
    Finding,
    dotted_name,
    filter_suppressed,
    iter_py_files,
    parse_file,
    rel,
    suppressed_lines,
)

PASS = "compat"

# the one module allowed to spell version-dependent names
_EXEMPT_FILES = {("alphafold2_tpu", "compat.py")}

_EXPERIMENTAL_PREFIX = "jax.experimental"


def _is_exempt(path: Path) -> bool:
    parts = tuple(Path(path).parts[-2:])
    return parts in _EXEMPT_FILES


def _contains_compat_ref(node: ast.AST, attr: str, aliases: dict) -> bool:
    """True if any descendant resolves to the compat wrapper `attr`:
    `compat.<attr>`, or a bare name imported from alphafold2_tpu.compat
    (`from alphafold2_tpu.compat import shard_map`). Lets both
    `functools.partial(compat.shard_map, ..., check_vma=False)` and the
    direct `shard_map(..., check_vma=False)` through."""
    for sub in ast.walk(node):
        if (
            isinstance(sub, ast.Attribute)
            and sub.attr == attr
            and isinstance(sub.value, ast.Name)
            and sub.value.id == "compat"
        ):
            return True
        if isinstance(sub, ast.Name) and aliases.get(sub.id) == attr:
            return True
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.findings: List[Finding] = []
        self._seen = set()
        self._attr_idx = drift.attr_index()
        self._kw_idx = drift.keyword_index()
        self._full_names = {
            n for e in drift.DRIFT_TABLE for n in e.full_names
        }
        # local alias -> compat attribute, for names imported from compat
        self._compat_aliases: dict = {}

    def _emit(self, code: str, line: int, message: str):
        key = (code, line)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(PASS, code, self.path, line, message))

    # --- imports ---------------------------------------------------------
    def visit_Import(self, node: ast.Import):
        for alias in node.names:
            if alias.name == _EXPERIMENTAL_PREFIX or alias.name.startswith(
                _EXPERIMENTAL_PREFIX + "."
            ):
                self._emit(
                    "COMPAT001",
                    node.lineno,
                    f"import of {alias.name!r}: jax.experimental access is "
                    "reserved to alphafold2_tpu/compat.py",
                )
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        mod = node.module or ""
        if mod == "alphafold2_tpu.compat":
            for alias in node.names:
                self._compat_aliases[alias.asname or alias.name] = alias.name
        if mod == _EXPERIMENTAL_PREFIX or mod.startswith(_EXPERIMENTAL_PREFIX + "."):
            self._emit(
                "COMPAT001",
                node.lineno,
                f"import from {mod!r}: jax.experimental access is reserved "
                "to alphafold2_tpu/compat.py",
            )
        else:
            for alias in node.names:
                full = f"{mod}.{alias.name}" if mod else alias.name
                if full in self._full_names:
                    entry = next(
                        e for e in drift.DRIFT_TABLE if full in e.full_names
                    )
                    self._emit(
                        "COMPAT002",
                        node.lineno,
                        f"{full!r} is in the drift table "
                        f"({entry.renamed_in}); import {entry.compat_name} "
                        "instead",
                    )
        self.generic_visit(node)

    # --- attribute access ------------------------------------------------
    def visit_Attribute(self, node: ast.Attribute):
        name = dotted_name(node)
        if name:
            if name.startswith(_EXPERIMENTAL_PREFIX + ".") or name == _EXPERIMENTAL_PREFIX:
                self._emit(
                    "COMPAT001",
                    node.lineno,
                    f"attribute access {name!r}: jax.experimental access is "
                    "reserved to alphafold2_tpu/compat.py",
                )
                return  # don't also drift-match suffixes of the same chain
            if name in self._full_names:
                entry = next(
                    e for e in drift.DRIFT_TABLE if name in e.full_names
                )
                self._emit(
                    "COMPAT002",
                    node.lineno,
                    f"{name!r} is in the drift table ({entry.renamed_in}); "
                    f"use {entry.compat_name}",
                )
                return
        entry = self._attr_idx.get(node.attr)
        if entry is not None:
            base = node.value.id if isinstance(node.value, ast.Name) else None
            if base != "compat":
                self._emit(
                    "COMPAT002",
                    node.lineno,
                    f".{node.attr} is in the drift table ({entry.renamed_in}); "
                    f"use {entry.compat_name}",
                )
        self.generic_visit(node)

    # --- drifted call keywords -------------------------------------------
    def visit_Call(self, node: ast.Call):
        for kw in node.keywords:
            entry = self._kw_idx.get(kw.arg or "")
            if entry is None:
                continue
            if kw.arg == "vma":
                # only meaningful on ShapeDtypeStruct construction
                callee = dotted_name(node.func) or ""
                if not callee.endswith("ShapeDtypeStruct"):
                    continue
                self._emit(
                    "COMPAT003",
                    node.lineno,
                    f"ShapeDtypeStruct(vma=...) ({entry.renamed_in}); use "
                    f"{entry.compat_name}",
                )
            else:
                wrapper = entry.compat_name.split(".")[-1]
                if _contains_compat_ref(node, wrapper, self._compat_aliases):
                    continue
                self._emit(
                    "COMPAT003",
                    node.lineno,
                    f"{kw.arg}= keyword ({entry.renamed_in}); call "
                    f"{entry.compat_name}, which normalizes it",
                )
        self.generic_visit(node)


def run(root, files: Optional[Sequence] = None) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(root, files):
        if _is_exempt(path):
            continue
        src, tree = parse_file(path)
        rpath = rel(path, root)
        if tree is None:
            findings.append(
                Finding(PASS, "COMPAT000", rpath, 1, "file does not parse")
            )
            continue
        v = _Visitor(rpath)
        v.visit(tree)
        findings.extend(filter_suppressed(v.findings, suppressed_lines(src)))
    return findings
