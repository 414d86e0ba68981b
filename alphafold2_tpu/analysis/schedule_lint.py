"""Pass 6 — branch-parallel trunk-schedule verification.

The branch-parallel trunk schedule (models/trunk.py
`branch_parallel_layer_apply`, cfg.trunk_schedule="branch_parallel")
claims each layer's pair track and MSA track are two data-INDEPENDENT
branches that join only at the cross-attention exchange. Like the
overlap pass, the claim is structural — visible in the lowered program —
and must be checkable without a live chip. This pass lowers each
branch-parallel trunk variant for the TPU target on the CPU host
(`jax.export` on a subprocess-provisioned 8-device virtual platform, the
overlap_lint.py route) and asserts on the StableHLO text:

  * every layer emits exactly one JOIN marker — a multi-operand
    `stablehlo.optimization_barrier` (models/trunk.py `schedule_join`) —
    so a refactor that silently drops the schedule changes the count;
  * at every join, the operands' backward slices (the ops each branch
    computed since the previous join) partition into >= 2 groups sharing
    NO heavy op (dot_general / convolution / reduce): the branches are
    really data-independent before the join. Slice propagation stops at
    control-flow results (loop carries) and at OTHER barriers (each join
    scopes its own pre-join region), and linkage counts only heavy ops —
    CSE'd constants and scalar plumbing shared by both branches are not
    dependence;
  * the SERIAL trunk emits no barrier at all — the marker uniquely
    identifies the branch-parallel arm;
  * the self-check: a deliberately SERIALIZED twin
    (`branch_parallel_layer_apply(serialize_twin=True)` — the MSA branch
    arithmetically coupled behind the pair branch) must be FLAGGED by
    the same check. If a JAX upgrade changes the lowering enough to
    blind the detector, the pass fails loudly instead of rubber-stamping
    branch-parallel programs.

CLI: part of ``python -m alphafold2_tpu.analysis --strict`` (pass name
``schedule``); skipped for file-scoped invocations like the smoke pass.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Sequence, Set

from alphafold2_tpu.analysis.common import Finding
from alphafold2_tpu.analysis.overlap_lint import (
    _BARRIERS,
    _parse_ops,
    module_functions,
)

PASS = "schedule"

_JOIN_OP = "stablehlo.optimization_barrier"

# ops that constitute real compute: two branches sharing one of these in
# their pre-join slices are data-dependent. Constants, broadcasts, and
# elementwise plumbing (which CSE can legitimately share) never count.
_HEAVY = {
    "stablehlo.dot_general",
    "stablehlo.convolution",
    "stablehlo.reduce",
    "stablehlo.reduce_window",
}


def _backward_slice(ops, defs, seeds: Sequence[str]) -> Set[int]:
    """Op indices transitively feeding `seeds` within one function.

    Stops at control-flow results (a dot consuming a while result does
    not depend on any particular in-body op — overlap_lint semantics)
    AND at other optimization_barriers: each join scopes the region since
    the previous join, which is exactly the branch region the schedule
    claims independent."""
    seen_vals: Set[str] = set()
    out: Set[int] = set()
    stack = list(seeds)
    while stack:
        v = stack.pop()
        if v in seen_vals:
            continue
        seen_vals.add(v)
        d = defs.get(v)
        if d is None:
            continue
        out.add(d)
        dop = ops[d][0]
        if dop in _BARRIERS or dop == _JOIN_OP:
            continue
        stack.extend(ops[d][2])
    return out


def _components(link_sets: List[Set[int]]) -> int:
    """Connected components over operands, linked by shared heavy ops."""
    n = len(link_sets)
    parent = list(range(n))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for i in range(n):
        for j in range(i + 1, n):
            if link_sets[i] & link_sets[j]:
                parent[find(i)] = find(j)
    return len({find(i) for i in range(n)})


def analyze_joins(text: str):
    """[(function, op_index, n_operands, n_components)] for every
    multi-operand optimization_barrier in the module."""
    joins = []
    for fname, lines in module_functions(text):
        ops, defs = _parse_ops(lines)
        for idx, (opname, _res, operands) in enumerate(ops):
            if opname != _JOIN_OP or len(operands) < 2:
                continue
            slices = [_backward_slice(ops, defs, [v]) for v in operands]
            heavy = [
                {d for d in s if ops[d][0] in _HEAVY} for s in slices
            ]
            joins.append((fname, idx, len(operands), _components(heavy)))
    return joins


def check_branch_parallel(text: str, min_joins: int) -> List[str]:
    """The clean branch-parallel program: the expected number of join
    markers, every one of them with truly independent branches."""
    problems = []
    joins = analyze_joins(text)
    if len(joins) < min_joins:
        problems.append(
            f"expected >= {min_joins} schedule-join marker(s) "
            f"(one per layer / scanned body), found {len(joins)} — the "
            "branch-parallel schedule is not being emitted"
        )
    for fname, idx, n_ops, n_comp in joins:
        if n_comp < 2:
            problems.append(
                f"join at {fname}#{idx} ({n_ops} operands): branch slices "
                "share heavy compute — the branches are data-dependent "
                "before the join (schedule serialized)"
            )
    return problems


def check_serial_unmarked(text: str) -> List[str]:
    """The serial reference arm must carry NO join markers: the barrier
    uniquely identifies the branch-parallel schedule."""
    if _JOIN_OP in text:
        return [
            "serial-schedule program contains optimization_barrier(s) — "
            "the join marker no longer uniquely identifies the "
            "branch-parallel arm"
        ]
    return []


def check_serialized_twin_detected(text: str) -> List[str]:
    """Self-check: the deliberately serialized twin must be flagged."""
    joins = analyze_joins(text)
    if not joins:
        return [
            "serialized twin lowered with no join marker — wrong program "
            "under test"
        ]
    if all(n_comp >= 2 for _, _, _, n_comp in joins):
        return [
            "detector failed to flag the SERIALIZED twin schedule — the "
            "lowering shape changed and the branch-independence "
            "assertions above are no longer trustworthy"
        ]
    return []


# --- the worker (runs on a subprocess-provisioned 8-device platform) --------

_N_DEV = 8


def worker_main() -> None:
    """Build + export every branch-parallel trunk variant (and the serial
    + serialized-twin fixtures), run the schedule checks, print one JSON
    line of problems."""
    import jax

    if len(jax.devices()) < _N_DEV:
        print(json.dumps({"fatal": (
            f"virtual platform provisioning failed: need {_N_DEV} "
            f"devices, have {len(jax.devices())}")}))
        return
    import dataclasses

    from jax import export as jexport
    import jax.numpy as jnp

    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.models.reversible import (
        reversible_trunk_apply,
        reversible_trunk_init,
    )
    from alphafold2_tpu.models.trunk import (
        branch_parallel_layer_apply,
        sequential_trunk_apply,
        trunk_layer_init,
    )
    from alphafold2_tpu.parallel import make_mesh, sp_trunk_apply

    problems: Dict[str, List[str]] = {}

    def export_text(fn, *args) -> str:
        return jexport.export(jax.jit(fn), platforms=["tpu"])(
            *args
        ).mlir_module()

    depth = 2
    cfg = Alphafold2Config(
        dim=16, depth=depth, heads=2, dim_head=8, max_seq_len=64,
        msa_tie_row_attn=True,
    )
    cfg_bp = dataclasses.replace(cfg, trunk_schedule="branch_parallel")
    keys = jax.random.split(jax.random.PRNGKey(0), 2 + depth)
    layers = [trunk_layer_init(k, cfg) for k in keys[2:]]
    n = 2 * _N_DEV
    xs = jax.ShapeDtypeStruct((1, n, n, cfg.dim), jnp.float32)
    ms = jax.ShapeDtypeStruct((1, _N_DEV, n, cfg.dim), jnp.float32)
    x = jax.random.normal(keys[0], (1, n, n, cfg.dim))
    m = jax.random.normal(keys[1], (1, _N_DEV, n, cfg.dim))

    # --- sequential trunk: branch arm marked + independent; serial bare --
    txt = export_text(
        lambda a, b: sequential_trunk_apply(layers, cfg_bp, a, b), xs, ms
    )
    # unrolled: one join per layer
    problems["sequential_branch_parallel"] = check_branch_parallel(
        txt, min_joins=depth
    )
    txt = export_text(
        lambda a, b: sequential_trunk_apply(layers, cfg, a, b), xs, ms
    )
    problems["sequential_serial_unmarked"] = check_serial_unmarked(txt)

    # --- detector self-check: the serialized twin must be flagged --------
    txt = export_text(
        lambda a, b: branch_parallel_layer_apply(
            layers[0], cfg_bp, a, b, serialize_twin=True
        ),
        xs, ms,
    )
    problems["serialized_twin_detector"] = check_serialized_twin_detected(txt)

    # --- reversible trunk: the join rides inside the scanned body --------
    rcfg_bp = dataclasses.replace(cfg_bp, reversible=True)
    stacked = reversible_trunk_init(jax.random.PRNGKey(1), rcfg_bp)
    txt = export_text(
        lambda a, b: reversible_trunk_apply(stacked, rcfg_bp, a, b), xs, ms
    )
    problems["reversible_branch_parallel"] = check_branch_parallel(
        txt, min_joins=1
    )

    # --- SP trunk: branches (incl. their collectives) join under
    # shard_map, mapping onto disjoint mesh work -------------------------
    mesh = make_mesh({"seq": _N_DEV})
    txt = export_text(
        lambda a, b: sp_trunk_apply(layers[:1], cfg_bp, a, b, mesh), x, m
    )
    problems["sp_branch_parallel"] = check_branch_parallel(txt, min_joins=1)

    print(json.dumps({"problems": problems}))


def run(root=None, files=None, **_) -> List[Finding]:
    """Pass entry point: verify the branch schedules on a subprocess (the
    virtual multi-device platform must be set before jax's backend
    initializes)."""
    del root, files
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count={_N_DEV}"
    env["XLA_FLAGS"] = flags.strip()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    src = "alphafold2_tpu/analysis/schedule_lint.py"
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             "from alphafold2_tpu.analysis.schedule_lint import worker_main; "
             "worker_main()"],
            env=env, capture_output=True, text=True, timeout=900,
        )
    except subprocess.TimeoutExpired:
        return [Finding(PASS, "SCH000", src, 1,
                        "schedule-lint worker timed out (900s)")]
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        return [Finding(PASS, "SCH000", src, 1,
                        f"worker failed rc={proc.returncode}: "
                        f"{' | '.join(tail)[:300]}")]
    payload = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except ValueError:
            continue
    if payload is None:
        return [Finding(PASS, "SCH000", src, 1,
                        "no JSON verdict in worker output")]
    if "fatal" in payload:
        return [Finding(PASS, "SCH000", src, 1, payload["fatal"])]
    findings = []
    for program, probs in sorted(payload.get("problems", {}).items()):
        for p in probs:
            findings.append(Finding(PASS, "SCH001", program, 0, p))
    return findings
