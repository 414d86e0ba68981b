"""Pass 5 — overlap-aware collective-schedule verification.

The overlap layer (parallel/overlap.py, parallel/sequence.py,
training/harness.py `make_axis_accum_train_step`) claims its collectives
ride UNDER compute instead of fencing it. That claim is structural — it
is visible in the lowered program — and checking it must not need a live
(and chronically wedged) chip. This pass lowers each overlapped program
for the TPU target on the CPU host (`jax.export`, the
scripts/check_mosaic_lowering.py route, on a subprocess-provisioned
8-device virtual platform) and asserts the schedule on the StableHLO
text:

  * expected collective COUNTS — the double-buffered ring carries
    exactly one extra static ppermute site (prefetch) and the overlapped
    DP step exactly one extra all-reduce site per bucket (the in-loop
    reduction), so a refactor that silently drops the overlap changes
    the counts;
  * the FENCE property — a collective whose results transitively feed a
    `dot_general` in the same function serializes that compute behind
    the wire. Overlapped ring programs must have ZERO fenced
    collective-permutes (the permuted block is consumed by the NEXT
    iteration, via the loop carry, never by this iteration's dots);
    the overlapped DP step must place its in-loop all-reduces so no
    dot depends on them;
  * the self-check — the pass also lowers each SYNCHRONOUS twin and
    asserts the fence detector still CATCHES it (fenced permutes > 0 /
    no in-loop all-reduce). If a JAX upgrade changes the lowering shape
    enough to blind the detector, the pass fails loudly instead of
    rubber-stamping overlapped programs.

SSA analysis is per-function and does not propagate through control-flow
ops (`stablehlo.while` results conflate loop carries: the prefetch hop
legitimately feeds the LATER iterations through the carry — that is the
overlap, not a fence). `jnp.where`-style outlined helpers (`func.call`)
propagate like ordinary ops.

CLI: part of ``python -m alphafold2_tpu.analysis --strict`` (pass name
``overlap``); skipped for file-scoped invocations like the smoke pass.
Fixtures: tests/test_overlap.py lowers a deliberately re-serialized
schedule and asserts this pass's checker flags it.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Sequence, Tuple

from alphafold2_tpu.analysis.common import Finding

PASS = "overlap"

# StableHLO collective ops, keyed by the short name used in reports
COLLECTIVES = {
    "stablehlo.collective_permute": "collective_permute",
    "stablehlo.all_reduce": "all_reduce",
    "stablehlo.all_to_all": "all_to_all",
    "stablehlo.all_gather": "all_gather",
}

# control-flow ops whose results conflate region-carried values: a dot
# consuming a while RESULT does not depend on any particular in-body op
_BARRIERS = {"stablehlo.while", "stablehlo.if", "stablehlo.case"}

_FUNC_RE = re.compile(r"\s*func\.func\b.*@([\w$.]+)")
_RES_RE = re.compile(r"^(%[\w.]+)(?::\d+)?\s*=\s*(.*)$")
_OP_RE = re.compile(r'"?([a-z_]+\.[a-z_.]+|func\.call|call)"?')
_VAL_RE = re.compile(r"%[A-Za-z0-9_.]+")
_CALLEE_RE = re.compile(r"\bfunc\.call\s+@([\w$.]+)")


def module_functions(text: str) -> List[Tuple[str, List[str]]]:
    """Split an MLIR module into (function_name, body_lines) chunks."""
    out: List[Tuple[str, List[str]]] = []
    name, lines = None, []
    for line in text.splitlines():
        m = _FUNC_RE.match(line)
        if m:
            if name is not None:
                out.append((name, lines))
            name, lines = m.group(1), []
        elif name is not None:
            lines.append(line)
    if name is not None:
        out.append((name, lines))
    return out


def _parse_ops(lines: Sequence[str]):
    """(ops, defs): ops = [(opname, results, operands)] in program order;
    defs maps each SSA result name to its defining op index. One op per
    line (the StableHLO pretty-printer's format)."""
    ops: List[Tuple[str, List[str], List[str]]] = []
    defs: Dict[str, int] = {}
    for line in lines:
        s = line.strip()
        if not s or s.startswith(("//", "}", "^")):
            continue
        results: List[str] = []
        rhs = s
        m = _RES_RE.match(s)
        if m:
            results = [m.group(1)]
            rhs = m.group(2)
        om = _OP_RE.search(rhs)
        if not om:
            continue
        opname = om.group(1)
        operands = [v.split("#")[0] for v in _VAL_RE.findall(rhs)]
        ops.append((opname, results, operands))
        for r in results:
            defs[r.split("#")[0]] = len(ops) - 1
    return ops, defs


def _fenced_in_function(lines: Sequence[str]) -> Dict[str, int]:
    """Per collective kind: how many of this function's collectives
    transitively feed a dot_general in the SAME function (= fence the
    compute). Propagation stops at control-flow ops (loop carries)."""
    ops, defs = _parse_ops(lines)
    coll_idx = {
        j: COLLECTIVES[op]
        for j, (op, _, _) in enumerate(ops)
        if op in COLLECTIVES
    }
    fenced: Dict[str, set] = {}
    for j, (op, _res, operands) in enumerate(ops):
        if op != "stablehlo.dot_general":
            continue
        seen: set = set()
        stack = list(operands)
        while stack:
            v = stack.pop()
            if v in seen:
                continue
            seen.add(v)
            d = defs.get(v)
            if d is None:
                continue
            dop = ops[d][0]
            if d in coll_idx:
                fenced.setdefault(coll_idx[d], set()).add(d)
            if dop in _BARRIERS:
                continue  # do not walk through loop carries
            stack.extend(ops[d][2])
    return {k: len(v) for k, v in fenced.items()}


def _loop_scope_lines(text: str) -> Tuple[List[str], List[str]]:
    """(loop_lines, callees): every line inside a `stablehlo.while`
    region, plus the names of functions `func.call`'d from there (scan
    and fori_loop bodies are outlined as closed_call functions)."""
    loop_lines: List[str] = []
    # stack entries: [start_depth, region_opened] — a while's regions
    # (`cond { ... } do { ... }`) open on LATER lines, so an entry only
    # pops once the depth has risen above start and come back
    depth_stack: List[List] = []
    depth = 0
    for line in text.splitlines():
        starting = "stablehlo.while" in line
        if depth_stack:
            loop_lines.append(line)
        depth += line.count("{") - line.count("}")
        if starting:
            depth_stack.append([depth, False])
        for entry in depth_stack:
            if depth > entry[0]:
                entry[1] = True
        while depth_stack and depth_stack[-1][1] and depth <= depth_stack[-1][0]:
            depth_stack.pop()
    callees = sorted(set(_CALLEE_RE.findall("\n".join(loop_lines))))
    return loop_lines, callees


@dataclasses.dataclass
class ScheduleStats:
    """Structural census of one lowered program's collective schedule."""

    counts: Dict[str, int]        # whole-module collective counts
    fenced: Dict[str, int]        # collectives feeding same-function dots
    loop_counts: Dict[str, int]   # collectives inside loop bodies
    loop_dots: int                # dot_generals inside loop bodies
    dots: int                     # whole-module dot_generals


def analyze_schedule(text: str) -> ScheduleStats:
    counts = {short: text.count(full) for full, short in COLLECTIVES.items()}
    fenced: Dict[str, int] = {}
    for _name, lines in module_functions(text):
        for k, v in _fenced_in_function(lines).items():
            fenced[k] = fenced.get(k, 0) + v
    loop_lines, callees = _loop_scope_lines(text)
    # closure over outlined loop bodies (one hop of calls covers the
    # closed_call pattern; walk further calls for nested scans)
    funcs = dict(module_functions(text))
    pending, seen = list(callees), set()
    while pending:
        c = pending.pop()
        if c in seen or c not in funcs:
            continue
        seen.add(c)
        loop_lines.extend(funcs[c])
        pending.extend(_CALLEE_RE.findall("\n".join(funcs[c])))
    loop_text = "\n".join(loop_lines)
    loop_counts = {
        short: loop_text.count(full) for full, short in COLLECTIVES.items()
    }
    return ScheduleStats(
        counts=counts,
        fenced=fenced,
        loop_counts=loop_counts,
        loop_dots=loop_text.count("stablehlo.dot_general"),
        dots=text.count("stablehlo.dot_general"),
    )


# --- schedule expectations --------------------------------------------------


def check_overlapped_ring(stats: ScheduleStats, expected_permutes: int) -> List[str]:
    """The double-buffered ring: exact ppermute count (prefetch + in-loop
    sites), no permute fencing a dot, and real compute present."""
    problems = []
    got = stats.counts.get("collective_permute", 0)
    if got != expected_permutes:
        problems.append(
            f"expected {expected_permutes} collective-permutes "
            f"(prefetch + loop-body sites), found {got}"
        )
    f = stats.fenced.get("collective_permute", 0)
    if f:
        problems.append(
            f"{f} collective-permute(s) feed a dot_general in the same "
            "function — the ring schedule is (re)serialized: transfers "
            "fence the block compute instead of hiding under it"
        )
    if stats.dots == 0:
        problems.append("no dot_general in module — nothing to overlap "
                        "(wrong program under test)")
    return problems


def check_serialized_ring_detected(stats: ScheduleStats) -> List[str]:
    """Self-check on the synchronous twin: the fence detector must fire."""
    if stats.fenced.get("collective_permute", 0) == 0:
        return [
            "fence detector failed to flag the SYNCHRONOUS ring schedule "
            "— the lowering shape changed and the overlap assertions "
            "above are no longer trustworthy"
        ]
    return []


def check_overlapped_dp(stats: ScheduleStats, n_buckets: int) -> List[str]:
    """The backward-overlapped DP step: per-bucket all-reduce inside the
    accumulation loop (2B+1 sites total: B in-loop + B flush + 1 loss),
    none fencing the microbatch fwd/bwd dots."""
    problems = []
    expect_total = 2 * n_buckets + 1
    got = stats.counts.get("all_reduce", 0)
    if got != expect_total:
        problems.append(
            f"expected {expect_total} all-reduces "
            f"({n_buckets} in-loop + {n_buckets} flush + 1 loss), found {got}"
        )
    in_loop = stats.loop_counts.get("all_reduce", 0)
    if in_loop < n_buckets:
        problems.append(
            f"only {in_loop} all-reduce(s) inside the accumulation loop "
            f"(expected {n_buckets}) — the gradient reduction does not "
            "overlap the next microbatch's fwd/bwd"
        )
    if stats.loop_dots == 0:
        problems.append("no dot_general inside the accumulation loop — "
                        "wrong program under test")
    f = stats.fenced.get("all_reduce", 0)
    if f:
        problems.append(
            f"{f} all-reduce(s) feed a dot_general in the same function "
            "— the reduction fences compute"
        )
    return problems


def check_serialized_dp_detected(stats: ScheduleStats, n_buckets: int) -> List[str]:
    """Self-check on the synchronous DP twin: no in-loop reduction, and
    exactly the post-scan flush + loss all-reduces."""
    problems = []
    if stats.loop_counts.get("all_reduce", 0) != 0:
        problems.append(
            "synchronous DP arm unexpectedly has in-loop all-reduces — "
            "the A/B pair no longer isolates the overlap"
        )
    expect = n_buckets + 1
    got = stats.counts.get("all_reduce", 0)
    if got != expect:
        problems.append(
            f"synchronous DP arm: expected {expect} all-reduces "
            f"({n_buckets} flush + 1 loss), found {got}"
        )
    return problems


def check_overlapped_sp_trunk(stats: ScheduleStats, expected_permutes: int) -> List[str]:
    """The SP trunk's ring cross-attention under the overlapped schedule:
    same fence property as the plain ring; the trunk's OTHER collectives
    (all_to_all grid transposes, the tied-row logit psum) are semantic
    barriers and are allowed to fence."""
    problems = []
    got = stats.counts.get("collective_permute", 0)
    if got != expected_permutes:
        problems.append(
            f"expected {expected_permutes} collective-permutes in the SP "
            f"trunk (the ring cross-attention sites), found {got}"
        )
    f = stats.fenced.get("collective_permute", 0)
    if f:
        problems.append(
            f"{f} ring collective-permute(s) fence a dot_general — the "
            "SP trunk's ring cross-attention is (re)serialized"
        )
    return problems


# --- the worker (runs on a subprocess-provisioned 8-device platform) --------

_N_DEV = 8


def worker_main() -> None:
    """Build + export every overlapped program and its synchronous twin,
    run the schedule checks, print one JSON line of problems. Assumes the
    virtual CPU platform is already in force (the pass runner's
    subprocess sets it up)."""
    import jax

    if len(jax.devices()) < _N_DEV:
        print(json.dumps({"fatal": (
            f"virtual platform provisioning failed: need {_N_DEV} "
            f"devices, have {len(jax.devices())}")}))
        return
    from jax import export as jexport
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from alphafold2_tpu import compat
    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.models.trunk import trunk_layer_init
    from alphafold2_tpu.parallel import (
        make_dp_overlap_train_step,
        make_mesh,
        plan_buckets,
        ring_attention,
        sp_trunk_apply,
    )
    from alphafold2_tpu.training.harness import TrainConfig, train_state_init

    problems: Dict[str, List[str]] = {}

    def export_text(fn, *args) -> str:
        return jexport.export(jax.jit(fn), platforms=["tpu"])(
            *args
        ).mlir_module()

    # --- ring attention (XLA streaming hops), both schedules ---------------
    mesh = make_mesh({"seq": _N_DEV})
    spec = P(None, "seq", None, None)
    qs = jax.ShapeDtypeStruct((1, 4 * _N_DEV, 2, 8), jnp.float32)
    ms = jax.ShapeDtypeStruct((1, 4 * _N_DEV), jnp.bool_)

    def ring(overlap):
        return compat.shard_map(
            lambda q, k, v, m: ring_attention(
                q, k, v, "seq", mask=m, use_kernel=False, overlap=overlap
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec, P(None, "seq")),
            out_specs=spec,
        )

    txt = export_text(ring(True), qs, qs, qs, ms)
    # 3 permuted buffers (k, v, bias) x 2 static sites (prefetch + body)
    problems["ring_overlap"] = check_overlapped_ring(
        analyze_schedule(txt), expected_permutes=6
    )
    txt = export_text(ring(False), qs, qs, qs, ms)
    problems["ring_sync_detector"] = check_serialized_ring_detected(
        analyze_schedule(txt)
    )

    # --- SP trunk (ring cross-attention inside the full layer) -------------
    sp_cfg = Alphafold2Config(
        dim=16, depth=1, heads=2, dim_head=8, max_seq_len=32,
        msa_tie_row_attn=True,
    )
    layers = [trunk_layer_init(jax.random.PRNGKey(0), sp_cfg)]
    xs = jax.ShapeDtypeStruct((1, 2 * _N_DEV, 2 * _N_DEV, 16), jnp.float32)
    mss = jax.ShapeDtypeStruct((1, _N_DEV, 8, 16), jnp.float32)
    txt = export_text(
        lambda x, m: sp_trunk_apply(
            layers, sp_cfg, x, m, mesh, overlap=True
        ),
        xs, mss,
    )
    # one ring cross-attention (MSA<-pair) x 3 buffers x 2 sites
    problems["sp_trunk_overlap"] = check_overlapped_sp_trunk(
        analyze_schedule(txt), expected_permutes=6
    )

    # --- the SERVING-shaped SP program (ISSUE 14) --------------------------
    # exactly what a ServingEngine bucket executable runs under the SP arm:
    # embedder -> sp_seq trunk -> distogram head -> MDS, batch-shaped. The
    # ring cross-attention inside must keep the same overlap property the
    # bare trunk has — the serving wrapper (padding, masks, the replicated
    # head) must not reserialize the schedule.
    from alphafold2_tpu.models import alphafold2_init
    from alphafold2_tpu.serving.pipeline import predict_structure
    from alphafold2_tpu.serving.sp_arm import make_sp_apply_fn

    # depth 2, NOT 1: the distogram head consumes only the pair stream,
    # so the LAST layer's MSA<-pair ring is dead code the compiler
    # eliminates — layer 1's ring is the live site under test (exactly
    # the structure of any real multi-layer serving model)
    serve_cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                                 max_seq_len=2 * _N_DEV)
    serve_params = alphafold2_init(jax.random.PRNGKey(1), serve_cfg)
    sp_apply = make_sp_apply_fn(mesh, "sp_seq", axis_name="seq",
                                overlap=True)
    tok = jax.ShapeDtypeStruct((2, 2 * _N_DEV), jnp.int32)
    msk = jax.ShapeDtypeStruct((2, 2 * _N_DEV), jnp.bool_)
    msa_s = jax.ShapeDtypeStruct((2, _N_DEV, 2 * _N_DEV), jnp.int32)
    msam_s = jax.ShapeDtypeStruct((2, _N_DEV, 2 * _N_DEV), jnp.bool_)
    txt = export_text(
        lambda p, t, m, ms, mm: predict_structure(
            p, serve_cfg, t, mask=m, msa=ms, msa_mask=mm,
            mds_iters=2, mds_init="classical", model_apply_fn=sp_apply,
        ),
        serve_params, tok, msk, msa_s, msam_s,
    )
    # same single ring site as the bare trunk: 3 buffers x 2 static sites
    problems["serving_sp_overlap"] = check_overlapped_sp_trunk(
        analyze_schedule(txt), expected_permutes=6
    )

    # --- DP-overlap train step, both schedules -----------------------------
    dp_mesh = make_mesh({"data": _N_DEV})
    cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8,
                           max_seq_len=16)
    tcfg = TrainConfig(learning_rate=1e-3, grad_accum=3)
    batch = {
        "seq": jax.ShapeDtypeStruct((3, _N_DEV, 8), jnp.int32),
        "mask": jax.ShapeDtypeStruct((3, _N_DEV, 8), jnp.bool_),
        "coords": jax.ShapeDtypeStruct((3, _N_DEV, 8, 3), jnp.float32),
    }
    state = jax.eval_shape(
        lambda k: train_state_init(k, cfg, tcfg), jax.random.PRNGKey(0)
    )
    n_buckets = len(plan_buckets(state["params"])[1])
    for overlap, key, check in (
        (True, "dp_overlap",
         lambda s: check_overlapped_dp(s, n_buckets)),
        (False, "dp_sync_detector",
         lambda s: check_serialized_dp_detected(s, n_buckets)),
    ):
        step, _ = make_dp_overlap_train_step(
            cfg, tcfg, dp_mesh, batch, overlap=overlap, donate_state=False
        )
        txt = jexport.export(step, platforms=["tpu"])(
            state, batch
        ).mlir_module()
        problems[key] = check(analyze_schedule(txt))

    # --- DP-overlap on the MULTI-HOST mesh shape ---------------------------
    # A pod's mesh is hybrid: DP on the outer (DCN, cross-host) axis, the
    # bandwidth-hungry strategy on the inner (ICI) axis. The overlap
    # claim must survive THAT lowering — the all-reduce subgroups become
    # strided over the inner axis, which is exactly the reshuffle that
    # could silently reserialize the schedule. Same program, same
    # structural assertions, hybrid {"data": 2} x {"model": 4} mesh
    # (process-spanning in production; device-count-identical here, the
    # lowering is what's under test).
    from alphafold2_tpu.parallel import hybrid_mesh

    hb_mesh = hybrid_mesh({"data": 2}, {"model": 4})
    hb_batch = {
        "seq": jax.ShapeDtypeStruct((3, 2, 8), jnp.int32),
        "mask": jax.ShapeDtypeStruct((3, 2, 8), jnp.bool_),
        "coords": jax.ShapeDtypeStruct((3, 2, 8, 3), jnp.float32),
    }
    step, _ = make_dp_overlap_train_step(
        cfg, tcfg, hb_mesh, hb_batch, overlap=True, donate_state=False
    )
    txt = jexport.export(step, platforms=["tpu"])(
        state, hb_batch
    ).mlir_module()
    problems["dp_overlap_hybrid_mesh"] = check_overlapped_dp(
        analyze_schedule(txt), n_buckets
    )

    print(json.dumps({"problems": problems}))


def run(root=None, files=None, **_) -> List[Finding]:
    """Pass entry point: verify the overlap schedules on a subprocess
    (the virtual multi-device platform must be set before jax's backend
    initializes, which the calling process usually already did)."""
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
    src = "alphafold2_tpu/analysis/overlap_lint.py"
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             "from alphafold2_tpu.analysis.overlap_lint import worker_main; "
             "worker_main()"],
            env=env, capture_output=True, text=True, timeout=900,
        )
    except subprocess.TimeoutExpired:
        return [Finding(PASS, "OVL000", src, 1,
                        "overlap-lint worker timed out (900s)")]
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        return [Finding(PASS, "OVL000", src, 1,
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
        return [Finding(PASS, "OVL000", src, 1,
                        "no JSON verdict in worker output")]
    if "fatal" in payload:
        return [Finding(PASS, "OVL000", src, 1, payload["fatal"])]
    findings = []
    for program, probs in sorted(payload.get("problems", {}).items()):
        for p in probs:
            findings.append(Finding(PASS, "OVL001", program, 0, p))
    return findings
