"""The kernel dispatch surface: one registry, every hot op, every backend.

HelixFold (arxiv 2207.05477) ran the same model fast on a different
hardware stack by putting one dispatch surface over per-hardware
kernels; FastFold (arxiv 2203.00854) chose the execution strategy per
workload shape. This module is that surface for this repo: every hot op
(dense/fused flash attention, the int8 fused-dequant matmul, block-
sparse attention, the ring-attention hop, the experts' grouped product,
the GEGLU feed-forward block)
registers two named ARMS —

  * ``pallas_tpu`` — the Pallas Mosaic kernel (interpret mode off-TPU,
    which is what the chip-free parity tier exercises);
  * ``xla_ref``    — the pure-XLA reference arm: runs anywhere,
    bit-stable, the parity oracle every kernel arm is pinned against.
    Every platform that is not ``tpu`` resolves to it.

and the choice of arm happens in ONE place (`resolve`): platform ->
shape gate and measured crossover -> override. The op modules
(ops/flash.py, ops/quant.py, ops/sparse.py, ops/moe.py,
ops/feedforward.py, parallel/sequence.py) call
`resolve(op, request, **shapes)`, compare with `ARM_PALLAS_TPU` and keep
their own wiring. What can steer it:

  * a caller's ``use_kernel=True/False`` forces the kernel/XLA arm
    (loud `ValueError` when forcing an unsupported shape — forcing must
    never silently fall back);
  * ``AF2_KERNEL_BACKEND=<arm>`` forces one arm globally,
    ``AF2_KERNEL_BACKEND_<OP>`` per op (op name upper-cased); ``off``
    means the op's ``xla_ref`` arm, ``auto``/unset keeps the heuristic
    (ops/knobs.py `kernel_backend_override`). There is no other channel.

Whether attention streams at all or materializes its logits is decided
above this surface (ops/attention.py `attention_apply`); which FORM the
kernel arm takes and at what blocks is decided below it, from the shape
(ops/flash_kernel.py `rows_plan`, `causal_plan`, `supported*`).

af2lint's ``dispatch`` pass enforces the monopoly: every registered op
has an ``xla_ref`` arm and a registered chip-free parity test, no module
outside ``ops/`` imports a kernel module directly, and no module
outside ``ops/knobs.py`` parses an AF2_* env var.

Introspection: ``python -m alphafold2_tpu.ops.dispatch --check`` prints
the op x arm x resolved-on-this-host table (pinned by
tests/test_dispatch.py); `resolution_tag()` is the serving config-tag
fragment that keeps replicas on different arms out of one result-cache
keyspace (serving/engine.py).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax

from alphafold2_tpu.ops import knobs

__all__ = [
    "ARM_PALLAS_TPU",
    "ARM_XLA_REF",
    "Arm",
    "OpSpec",
    "decisions",
    "get",
    "main",
    "ops",
    "reset_decisions",
    "resolution_table",
    "resolution_tag",
    "resolve",
]

ARM_PALLAS_TPU = "pallas_tpu"
ARM_XLA_REF = "xla_ref"

# measured crossover for the flash family (dense, fused, ring hop): the
# lowest key length at which the kernel was measured to win on the chip. At
# i = j = 1152, dh = 64 (one 96-row batch chunk of the pair stream, v5e,
# jax 0.9.0) the whole-row form takes 6.2 us a (batch, head) row forward
# and 16.1 with its backward against the XLA streaming arm's 17.9 and
# 39.4; the streaming form at 384-blocks ties XLA there (16.8 / 41.4), so
# a shape the whole-row form does not take loses nothing
# (benchmarks/records/micro_attn_core_pr26.jsonl, PERF.md section 5).
# Nothing shorter was measured: the crosses (j = 32, 864) stay on XLA.
_FLASH_KERNEL_MIN_J = 1152

# measured crossover for the GEGLU kernel (v5e, jax 0.9.0, dim 256, mult 4;
# benchmarks/records/micro_geglu_*.jsonl): forward / gradient 0.26 / 0.68
# ms against the XLA arm's 0.41 / 0.98 at 16 384 rows, 0.24 / 0.62 against
# 0.23 / 0.70 at 4096 (a tie), 0.71 / 1.72 against 1.76 / 4.94 at 49 152
# (train_e2e's MSA stream; its pair stream has 1 327 104)
_GEGLU_KERNEL_MIN_ROWS = 16384

# measured crossover for the block-sparse kernel (v5e @ block=128:
# kernel 2.2x faster at n=8192, XLA ~1.3x faster at n=2048 — ops/sparse.py)
_SPARSE_KERNEL_MIN_N = 4096


@dataclasses.dataclass(frozen=True)
class Arm:
    """One backend arm of one op.

    `supported(platform, **shapes) -> bool` is the shape/dtype gate —
    pure host arithmetic (no tracing), so resolution is free and works
    under `jax.eval_shape`."""

    name: str
    supported: Callable[..., bool]
    note: str = ""


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One hot op's dispatch contract.

    `auto(platform, shapes) -> arm name` is the heuristic used when
    nothing forces an arm; `probe` is the representative shape set the
    introspection table / serving tag resolve at; `parity_test` names
    the chip-free parity test function in tests/test_dispatch.py that
    pins kernel-arm == xla_ref (af2lint's dispatch pass fails CI when
    the op has none)."""

    name: str
    arms: Tuple[Arm, ...]
    auto: Callable[[str, dict], str]
    probe: Dict[str, object]
    parity_test: str
    kernel_arm: str = ARM_PALLAS_TPU
    unsupported_msg: Optional[Callable[[str, dict], str]] = None

    def arm(self, name: str) -> Optional[Arm]:
        for a in self.arms:
            if a.name == name:
                return a
        return None

    def arm_names(self) -> Tuple[str, ...]:
        return tuple(a.name for a in self.arms)


_REGISTRY: Dict[str, OpSpec] = {}


def register(spec: OpSpec) -> OpSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"op {spec.name!r} already registered")
    if spec.arm(ARM_XLA_REF) is None:
        # the invariant the dispatch lint enforces repo-wide; refuse to
        # construct a registry that could not pass it
        raise ValueError(
            f"op {spec.name!r} must register an {ARM_XLA_REF} arm"
        )
    _REGISTRY[spec.name] = spec
    return spec


def ops() -> Tuple[str, ...]:
    """Registered op names, in registration order."""
    return tuple(_REGISTRY)


def get(op: str) -> OpSpec:
    try:
        return _REGISTRY[op]
    except KeyError:
        raise ValueError(
            f"unknown dispatch op {op!r}; registered: {list(_REGISTRY)}"
        ) from None


def _platform() -> str:
    return jax.devices()[0].platform


# every "auto" decision `resolve` has made in this process, by
# (op, arm, shapes): what a traced program's call sites were given
_DECISIONS: "collections.Counter[Tuple[str, str, Tuple]]" = collections.Counter()


def decisions() -> Dict[str, int]:
    """The tally of `resolve`'s "auto" decisions since the last
    `reset_decisions()`, one entry a distinct (op, arm, shapes):
    ``{"flash_attention -> pallas_tpu @ i=1152 j=1152 dh=64": 4, ...}``.

    Resolution happens while a program is TRACED, so read it after the
    step has compiled: it says which arm every call site of the compiled
    program runs, at the shapes it saw (the probe-shape tag of
    `resolution_tag()` cannot). Forced requests (use_kernel=True/False)
    and the introspection helpers are not decisions and are not counted;
    an env override is, under the arm it forced."""
    return {
        f"{op} -> {arm} @ " + " ".join(f"{k}={v}" for k, v in shapes): n
        for (op, arm, shapes), n in sorted(_DECISIONS.items(), key=repr)
    }


def reset_decisions() -> None:
    _DECISIONS.clear()


def resolve(op: str, request="auto", platform: Optional[str] = None,
            **shapes) -> str:
    """THE resolution point: (op, shapes, platform, env) -> arm name,
    tallied for `decisions()` when the request was "auto"."""
    arm = _resolve(op, request, platform, **shapes)
    if request == "auto":
        _DECISIONS[(op, arm, tuple(shapes.items()))] += 1
    return arm


def _resolve(op: str, request="auto", platform: Optional[str] = None,
             **shapes) -> str:
    """(op, shapes, platform, env) -> arm name, untallied.

    `request` is the call-site tri-state (the old `use_kernel`): True
    forces the op's kernel arm, False forces `xla_ref`, "auto" consults
    the env override (AF2_KERNEL_BACKEND_<OP> > AF2_KERNEL_BACKEND)
    and then the platform/shape heuristic. Forcing an unknown arm or an
    unsupported shape raises — a forced arm that silently fell back
    would record one arm's numbers under another's name."""
    spec = get(op)
    if platform is None:
        platform = _platform()

    forced: Optional[str] = None
    if request is True:
        forced = spec.kernel_arm
    elif request is False:
        forced = ARM_XLA_REF
    elif request == "auto":
        override = knobs.kernel_backend_override(op)
        if override == "off":
            forced = ARM_XLA_REF
        elif override is not None:
            forced = override
    else:
        raise ValueError(
            f"use_kernel must be True/False/'auto', got {request!r}"
        )

    if forced is not None:
        arm = spec.arm(forced)
        if arm is None:
            raise ValueError(
                f"{op}: unknown backend arm {forced!r} "
                f"(registered: {list(spec.arm_names())}; set "
                f"AF2_KERNEL_BACKEND[_{op.upper()}] to one of these, "
                f"'off', or 'auto')"
            )
        if not arm.supported(platform, **shapes):
            if spec.unsupported_msg is not None:
                raise ValueError(spec.unsupported_msg(forced, shapes))
            raise ValueError(
                f"{op}: forced arm {forced!r} does not support "
                f"{shapes} on platform {platform!r}"
            )
        return forced

    arm_name = spec.auto(platform, shapes)
    assert spec.arm(arm_name) is not None, (op, arm_name)
    return arm_name


# ---------------------------------------------------------------------------
# registered ops
# ---------------------------------------------------------------------------


def _always(platform, **shapes) -> bool:
    return True


def _flash_supported(platform, *, i, j, dh, dv=None, causal=False, **_):
    from alphafold2_tpu.ops import flash_kernel

    if causal:  # self-attention under the causal mask, v heads of dv
        return flash_kernel.supported_causal(i, j, dh, dh if dv is None else dv)
    return flash_kernel.supported(i, j, dh)


def _fused_supported(platform, *, i, j, dh, **_):
    from alphafold2_tpu.ops import flash_kernel

    return flash_kernel.supported_fused(i, j, dh)


def _flash_unsupported_msg(arm, s):
    return (
        f"flash kernel does not support shapes i={s.get('i')}, "
        f"j={s.get('j')}, dh={s.get('dh')} (row-vector VMEM bound / lane "
        f"alignment, see ops/flash_kernel.py supported)"
    )


def _flash_family_auto(supported):
    """The measured flash heuristic, shared by the dense, fused, and
    ring-hop ops: Pallas on TPU for supported shapes from the measured
    crossover (`_FLASH_KERNEL_MIN_J`) up, XLA streaming elsewhere."""

    def auto(platform: str, s: dict) -> str:
        if (
            platform == "tpu"
            and s["j"] >= _FLASH_KERNEL_MIN_J
            and supported(platform, **s)
        ):
            return ARM_PALLAS_TPU
        return ARM_XLA_REF

    return auto


register(OpSpec(
    name="flash_attention",
    arms=(
        Arm(ARM_PALLAS_TPU, _flash_supported,
            "ops/flash_kernel.py flash_attention_bnhd: whole-row or "
            "streaming form from the shape, the causal form (triangular "
            "grid) where the call is causal (interpret off-TPU)"),
        Arm(ARM_XLA_REF, _always,
            "ops/flash.py blockwise_attention — the parity oracle"),
    ),
    auto=_flash_family_auto(_flash_supported),
    probe={"i": 1152, "j": 4096, "dh": 64},
    parity_test="test_parity_flash_attention",
    unsupported_msg=_flash_unsupported_msg,
))

register(OpSpec(
    name="fused_attention",
    arms=(
        Arm(ARM_PALLAS_TPU, _fused_supported,
            "ops/flash_kernel.py flash_attention_fused (2-D pair bias + "
            "in-kernel gate)"),
        Arm(ARM_XLA_REF, _always,
            "ops/flash.py streamed_fused_attention / gate epilogue"),
    ),
    auto=_flash_family_auto(_fused_supported),
    probe={"i": 1152, "j": 4096, "dh": 64},
    parity_test="test_parity_fused_attention",
    unsupported_msg=_flash_unsupported_msg,
))


def _quant_supported(platform, *, m, k, n, x_dtype, **_):
    from alphafold2_tpu.ops.quant_kernel import supported_quant

    return supported_quant(m, k, n, x_dtype)


def _quant_auto(platform: str, s: dict) -> str:
    if platform == "tpu" and _quant_supported(platform, **s):
        return ARM_PALLAS_TPU
    return ARM_XLA_REF


def _quant_unsupported_msg(arm, s):
    import jax.numpy as jnp

    return (
        f"quant kernel does not support m={s.get('m')}, k={s.get('k')}, "
        f"n={s.get('n')}, x_dtype={jnp.dtype(s.get('x_dtype')).name} "
        f"(f32/bf16 activations, dims <= 2^24 — see ops/quant_kernel.py "
        f"supported_quant)"
    )


register(OpSpec(
    name="quant_matmul",
    arms=(
        Arm(ARM_PALLAS_TPU, _quant_supported,
            "ops/quant_kernel.py quant_matmul_tpu — int8 tiles cross HBM, "
            "dequant in the epilogue"),
        Arm(ARM_XLA_REF, _always,
            "ops/quant.py quant_matmul_xla — materialized-dequant "
            "reference"),
    ),
    auto=_quant_auto,
    probe={"m": 4096, "k": 512, "n": 512, "x_dtype": "float32"},
    parity_test="test_parity_quant_matmul",
    unsupported_msg=_quant_unsupported_msg,
))


def _sparse_auto(platform: str, s: dict) -> str:
    if platform == "tpu" and s["n"] >= _SPARSE_KERNEL_MIN_N:
        return ARM_PALLAS_TPU
    return ARM_XLA_REF


register(OpSpec(
    name="sparse_attention",
    arms=(
        Arm(ARM_PALLAS_TPU, _always,
            "ops/sparse_kernel.py block_sparse_attention_tpu (blocks "
            "stream; no per-row residency bound)"),
        Arm(ARM_XLA_REF, _always,
            "ops/sparse.py block_sparse_attention — the parity oracle"),
    ),
    auto=_sparse_auto,
    probe={"n": 2048},
    parity_test="test_parity_sparse_attention",
))

register(OpSpec(
    name="merge_lse",
    arms=(
        Arm(ARM_PALLAS_TPU, _flash_supported,
            "ops/flash_kernel.py flash_attention_lse per hop, log-space "
            "merge (ops/flash.py merge_lse)"),
        Arm(ARM_XLA_REF, _always,
            "ops/flash.py stream_block hop recurrence"),
    ),
    auto=_flash_family_auto(_flash_supported),
    probe={"i": 512, "j": 512, "dh": 64},
    parity_test="test_parity_merge_lse",
    unsupported_msg=_flash_unsupported_msg,
))

def _grouped_supported(platform, *, m, k, n, **_):
    from alphafold2_tpu.ops import moe

    return moe.grouped_kernel_supported(m, k, n)


def _grouped_auto(platform: str, s: dict) -> str:
    if platform == "tpu" and _grouped_supported(platform, **s):
        return ARM_PALLAS_TPU
    return ARM_XLA_REF


register(OpSpec(
    name="grouped_matmul",
    arms=(
        Arm(ARM_PALLAS_TPU, _grouped_supported,
            "ops/moe.py: JAX's megablox grouped-product kernels (gmm, and "
            "tgmm for the weights' gradient), tiles past the last group "
            "never visited (interpret off-TPU)"),
        Arm(ARM_XLA_REF, _always,
            "jax.lax.ragged_dot over rows sorted by group (ops/moe.py)"),
    ),
    auto=_grouped_auto,
    probe={"m": 4096, "k": 2048, "n": 768, "groups": 16},
    parity_test="test_parity_grouped_matmul",
))


def _geglu_supported(platform, *, rows, dim, hidden, itemsize, dropout=False,
                     quantized=False, **_):
    from alphafold2_tpu.ops import geglu_kernel

    return (not dropout and not quantized
            and geglu_kernel.plan(rows, dim, hidden, itemsize) is not None)


def _geglu_auto(platform: str, s: dict) -> str:
    if (platform == "tpu" and s["rows"] >= _GEGLU_KERNEL_MIN_ROWS
            and _geglu_supported(platform, **s)):
        return ARM_PALLAS_TPU
    return ARM_XLA_REF


register(OpSpec(
    name="geglu_ff",
    arms=(
        Arm(ARM_PALLAS_TPU, _geglu_supported,
            "ops/geglu_kernel.py geglu_ff: the whole block over row tiles, "
            "the intermediate in VMEM, one backward kernel that saves x "
            "only (interpret off-TPU)"),
        Arm(ARM_XLA_REF, _always,
            "ops/feedforward.py _ff_core, chunked by ff_chunk_size"),
    ),
    auto=_geglu_auto,
    probe={"rows": 1327104, "dim": 256, "hidden": 1024, "itemsize": 2,
           "dropout": False, "quantized": False},
    parity_test="test_parity_geglu_ff",
))


# ---------------------------------------------------------------------------
# introspection: the op x arm x resolved table, the serving tag, the CLI
# ---------------------------------------------------------------------------


def resolution_table(platform: Optional[str] = None):
    """[(op, probe, {arm: supported@probe}, resolved-or-error)] for this
    host (or an explicit `platform`), honoring the live env overrides —
    exactly what `resolve` would do at each op's probe shapes."""
    if platform is None:
        platform = _platform()
    rows = []
    for name, spec in _REGISTRY.items():
        supp = {
            a.name: bool(a.supported(platform, **spec.probe))
            for a in spec.arms
        }
        try:
            resolved = _resolve(name, request="auto", platform=platform,
                                **spec.probe)
        except ValueError as e:  # forced-unknown / forced-unsupported env
            resolved = f"ERROR: {e}"
        rows.append((name, dict(spec.probe), supp, resolved))
    return rows


def resolution_tag(platform: Optional[str] = None) -> str:
    """The backend-arm fragment of the serving config tag: which arm each
    registered op resolves to on this host under the live env. Two
    replicas whose envs force different arms get different tags, so the
    result LRU / AOT-executable keyspace never aliases across arms
    (rounding differs between a kernel and its XLA twin). A malformed
    override propagates as ValueError — an engine must not build with an
    unresolvable dispatch env."""
    if platform is None:
        platform = _platform()
    parts = []
    for name, spec in _REGISTRY.items():
        arm = _resolve(name, request="auto", platform=platform, **spec.probe)
        parts.append(f"{name}={arm}")
    return f"dispatch[{platform}](" + ",".join(parts) + ")"


def resolved_arm(op: str, platform: Optional[str] = None) -> str:
    """The arm one op resolves to on this host under the live env, at
    its probe shapes — the per-op slice of `resolution_tag()`. The
    serving cost ledger labels its cells with the flash_attention arm
    (the headline hot op, the same convention bench rows use for
    `backend_arm`)."""
    if platform is None:
        platform = _platform()
    spec = get(op)
    return _resolve(op, request="auto", platform=platform, **spec.probe)


def main(argv=None) -> int:
    """CLI: ``python -m alphafold2_tpu.ops.dispatch --check``."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m alphafold2_tpu.ops.dispatch",
        description="kernel dispatch registry introspection",
    )
    ap.add_argument("--check", action="store_true",
                    help="print the op x arm x resolved-on-this-host "
                         "table (the only mode; --check makes intent "
                         "explicit in runbooks)")
    ap.add_argument("--platform", default=None,
                    help="resolve for an explicit platform instead of "
                         "this host's (tpu, cpu; anything else "
                         "resolves as cpu does)")
    args = ap.parse_args(argv)

    platform = args.platform or _platform()
    print(f"kernel dispatch registry @ platform={platform}")
    for name, probe, supp, resolved in resolution_table(platform):
        probe_s = " ".join(f"{k}={v}" for k, v in probe.items())
        supp_s = " ".join(
            f"{arm}={'yes' if ok else 'no'}" for arm, ok in supp.items()
        )
        print(f"  {name:<17} probe[{probe_s}]  {supp_s}  -> {resolved}")
    print(f"  tag: {resolution_tag(platform)}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
