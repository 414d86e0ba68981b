"""Shared plumbing for the af2lint passes: the Finding record, repo file
iteration, and `# af2lint: disable=CODE` suppression comments."""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Iterable, List, Optional, Sequence

# directories never worth analyzing (caches, VCS, build output,
# third-party code inside the tree — an in-repo virtualenv would otherwise
# flood the strict gate with findings from JAX's own source)
_SKIP_DIRS = {
    ".git",
    ".pytest_jax_cache",
    # git-ignored chip-tool scratch: debug scripts and unpacked archives,
    # what a chip run brings back, the in-checkout compile cache
    ".chipcheck",
    "chiprun_out",
    ".jax_cache",
    "__pycache__",
    ".eggs",
    "build",
    "dist",
    "node_modules",
    ".venv",
    "venv",
    ".tox",
    ".nox",
    "site-packages",
}

_SUPPRESS_RE = re.compile(r"#\s*af2lint:\s*disable=([A-Z0-9,\s]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One analyzer finding. All findings are failures under --strict."""

    pass_name: str
    code: str
    path: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} [{self.pass_name}] {self.message}"


def iter_py_files(root, files: Optional[Sequence] = None) -> List[Path]:
    """The .py files to analyze: an explicit list, or everything under
    `root` minus skip-dirs."""
    if files is not None:
        return [Path(f) for f in files]
    root = Path(root)
    out = []
    for p in sorted(root.rglob("*.py")):
        if any(part in _SKIP_DIRS for part in p.parts):
            continue
        out.append(p)
    return out


def parse_file(path: Path):
    """(source, ast.Module) for `path`; returns (source, None) on syntax
    errors — passes report those as their own finding rather than crash."""
    src = Path(path).read_text()
    try:
        return src, ast.parse(src, filename=str(path))
    except SyntaxError:
        return src, None


def suppressed_lines(src: str) -> dict:
    """{line_number: set(codes)} for `# af2lint: disable=...` comments."""
    out = {}
    for i, line in enumerate(src.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = {c.strip() for c in m.group(1).split(",") if c.strip()}
    return out


def suppressed(finding: Finding, supp: dict) -> bool:
    return finding.code in supp.get(finding.line, ())


def filter_suppressed(findings: Iterable[Finding], supp: dict) -> List[Finding]:
    return [f for f in findings if not suppressed(f, supp)]


def rel(path, root) -> str:
    """Repo-relative path when possible (stable CI output)."""
    try:
        return str(Path(path).resolve().relative_to(Path(root).resolve()))
    except ValueError:
        return str(path)


def dotted_name(node) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None
