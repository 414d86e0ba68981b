"""af2lint pass 8 "dispatch": the kernel-dispatch surface's monopoly.

PR 13 put ONE resolution point (ops/dispatch.py `resolve`) over every
hot op's backend arms. The surface only stays single if drift is a CI
failure, not a review nit — this pass makes four properties static:

  * **DISPATCH001** — every registered op has an ``xla_ref`` arm: the
    run-anywhere reference every kernel arm is pinned against, and the
    arm the cross-backend bench matrix times on chip-free hosts.
  * **DISPATCH002** — every registered op names a chip-free parity test
    that actually exists in tests/test_dispatch.py (kernel arm in
    interpret mode == ``xla_ref``, f32/bf16 + a padded shape). An op
    without parity coverage fails CI, not code review.
  * **DISPATCH003** — no module under ``alphafold2_tpu/`` outside
    ``ops/`` imports a Pallas kernel module
    (``ops/flash_kernel.py`` / ``ops/sparse_kernel.py`` /
    ``ops/quant_kernel.py`` / ``ops/geglu_kernel.py``) directly: call sites must go through the
    op modules, whose arm choice routes through the registry.
    ``analysis/`` is exempt — the smoke/lowering passes construct
    kernels ON PURPOSE to verify them.
  * **DISPATCH004** — no module under ``alphafold2_tpu/`` outside
    ``ops/knobs.py`` reads an ``AF2_*`` environment variable: one
    validated definition per knob, so the old three-copies-of-tri-state
    drift cannot recur.

Scope for the AST checks: the `alphafold2_tpu` package (tests and
scripts SET env vars for subprocesses, which is fine; they are out of
scope like in the metrics pass). Fixture-injectable via `check_registry`
/ `check_sources` for the linter's own tests.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Sequence

from alphafold2_tpu.analysis.common import (
    Finding,
    dotted_name,
    filter_suppressed,
    iter_py_files,
    parse_file,
    rel,
    suppressed_lines,
)

PASS = "dispatch"
TEST_FILE = Path("tests") / "test_dispatch.py"

_KERNEL_MODULES = ("flash_kernel", "sparse_kernel", "quant_kernel",
                   "geglu_kernel")
_KERNEL_DOTTED = tuple(
    f"alphafold2_tpu.ops.{m}" for m in _KERNEL_MODULES
)


def check_registry(root, registry=None, test_file=None) -> List[Finding]:
    """DISPATCH001/002 over the live registry (or an injected fixture:
    an iterable of objects with .name, .arm_names(), .parity_test)."""
    if registry is None:
        from alphafold2_tpu.ops import dispatch

        registry = [dispatch.get(op) for op in dispatch.ops()]
    test_path = Path(test_file) if test_file else Path(root) / TEST_FILE
    try:
        test_src = test_path.read_text()
    except OSError:
        test_src = None

    findings: List[Finding] = []
    for spec in registry:
        if "xla_ref" not in spec.arm_names():
            findings.append(Finding(
                PASS, "DISPATCH001", "alphafold2_tpu/ops/dispatch.py", 1,
                f"op {spec.name!r} has no xla_ref arm (arms: "
                f"{list(spec.arm_names())}) — every op needs the "
                f"run-anywhere reference arm the parity tier and the "
                f"CPU bench matrix use",
            ))
        if not spec.parity_test:
            findings.append(Finding(
                PASS, "DISPATCH002", "alphafold2_tpu/ops/dispatch.py", 1,
                f"op {spec.name!r} registers no parity test — chip-free "
                f"kernel-vs-xla_ref coverage is mandatory",
            ))
        elif test_src is None:
            findings.append(Finding(
                PASS, "DISPATCH002", str(TEST_FILE), 1,
                f"op {spec.name!r} registers parity test "
                f"{spec.parity_test!r} but {test_path} does not exist",
            ))
        elif f"def {spec.parity_test}(" not in test_src:
            findings.append(Finding(
                PASS, "DISPATCH002", str(TEST_FILE), 1,
                f"op {spec.name!r} registers parity test "
                f"{spec.parity_test!r}, which is not defined in "
                f"{test_path.name}",
            ))
    return findings


def _is_env_read(node) -> bool:
    """A Call reading an AF2_* env var: os.environ.get("AF2_...") /
    os.getenv("AF2_...")."""
    if not (isinstance(node, ast.Call) and node.args):
        return False
    name = dotted_name(node.func)
    if name not in ("os.environ.get", "os.getenv", "environ.get", "getenv"):
        return False
    arg = node.args[0]
    return (isinstance(arg, ast.Constant) and isinstance(arg.value, str)
            and arg.value.startswith("AF2_"))


def _is_env_subscript_read(node) -> bool:
    """os.environ["AF2_..."] in Load context."""
    if not (isinstance(node, ast.Subscript)
            and isinstance(node.ctx, ast.Load)):
        return False
    if dotted_name(node.value) not in ("os.environ", "environ"):
        return False
    sl = node.slice
    # py3.8 wraps the constant in ast.Index
    if isinstance(sl, ast.Index):  # pragma: no cover - py>=3.9 in CI
        sl = sl.value
    return (isinstance(sl, ast.Constant) and isinstance(sl.value, str)
            and sl.value.startswith("AF2_"))


def _kernel_import(node) -> Optional[str]:
    """The kernel module a statement imports, or None."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            if alias.name in _KERNEL_DOTTED:
                return alias.name
    elif isinstance(node, ast.ImportFrom):
        mod = node.module or ""
        if mod in _KERNEL_DOTTED:
            return mod
        if mod == "alphafold2_tpu.ops":
            for alias in node.names:
                if alias.name in _KERNEL_MODULES:
                    return f"{mod}.{alias.name}"
    return None


def check_sources(root, files: Optional[Sequence] = None) -> List[Finding]:
    """DISPATCH003/004 over the package sources."""
    root = Path(root)
    pkg = root / "alphafold2_tpu"
    findings: List[Finding] = []
    for path in iter_py_files(root, files):
        p = Path(path)
        if "tests" in p.parts:
            continue
        try:
            inside = p.resolve().is_relative_to(pkg.resolve())
        except AttributeError:  # py<3.9 has no is_relative_to
            inside = str(pkg) in str(p.resolve())
        if not inside:
            continue
        parts = p.parts
        in_ops = "ops" in parts
        in_analysis = "analysis" in parts
        is_knobs = p.name == "knobs.py" and in_ops
        src, tree = parse_file(p)
        if tree is None:
            continue
        supp = suppressed_lines(src)
        file_findings: List[Finding] = []
        for node in ast.walk(tree):
            if not (in_ops or in_analysis):
                mod = _kernel_import(node) if isinstance(
                    node, (ast.Import, ast.ImportFrom)) else None
                if mod:
                    file_findings.append(Finding(
                        PASS, "DISPATCH003", rel(p, root), node.lineno,
                        f"direct kernel import {mod!r} outside ops/ — "
                        f"route through the op module so the arm choice "
                        f"goes through ops/dispatch.py resolve()",
                    ))
            if not is_knobs and (
                _is_env_read(node) or _is_env_subscript_read(node)
            ):
                file_findings.append(Finding(
                    PASS, "DISPATCH004", rel(p, root), node.lineno,
                    "AF2_* env var read outside ops/knobs.py — every "
                    "knob has exactly one validated definition there",
                ))
        findings.extend(filter_suppressed(file_findings, supp))
    return findings


def run(root, files: Optional[Sequence] = None, registry=None,
        test_file=None) -> List[Finding]:
    findings = check_sources(root, files=files)
    # the registry side is repo-level (it inspects the live registry and
    # the test file, not the given sources); skip it for file-scoped
    # invocations, like the metrics pass's docs direction
    if files is None:
        findings.extend(check_registry(root, registry=registry,
                                       test_file=test_file))
    return findings
