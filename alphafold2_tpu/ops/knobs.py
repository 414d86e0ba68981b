"""One validated home for every AF2_* environment knob.

Before this module, each env knob was parsed where it was consumed —
`ops/flash.py`, `ops/quant.py`, `parallel/overlap.py` and
`parallel/distributed.py` each had parsers of their own — with three
different ideas of what "0"/"false"/"off" mean and silent acceptance of
typos. This module is the single registry:

  * every knob has exactly ONE definition (`KNOBS`) carrying its type,
    default, accepted values, and the module that consumes it;
  * every parse is strict — an unrecognized value raises `ValueError`
    naming the knob and the accepted spellings, instead of silently
    defaulting (a mistyped A/B-sweep env var must fail the leg, not
    quietly measure the wrong arm);
  * the env-var reference table in docs/OPERATIONS.md is GENERATED from
    the registry (`python -m alphafold2_tpu.ops.knobs`, pinned in sync
    by tests/test_dispatch.py), so docs cannot drift from code.

Values are read from `os.environ` at every call (not cached): A/B
harnesses and tests flip knobs mid-process, and jitted programs bake the
result in at trace time — the same contract the scattered parsers had.

This module imports nothing from the package (and no jax), so any layer
— ops, parallel, serving, analysis — can read knobs without cycles.
af2lint's `dispatch` pass enforces that no other module under
`alphafold2_tpu/` reads an AF2_* variable directly.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

__all__ = [
    "KNOBS",
    "Knob",
    "auto_init",
    "comm_overlap_enabled",
    "coordinator",
    "flag",
    "generate_table",
    "kernel_backend_override",
    "num_processes",
    "pallas_interpret_override",
    "process_id",
]

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


@dataclasses.dataclass(frozen=True)
class Knob:
    """One env knob's single source of truth (name, contract, consumer)."""

    name: str
    values: str          # human-readable accepted values
    default: str         # human-readable default
    read_by: str         # the module whose behavior it changes
    help: str            # one-line description for the generated table


def _raw(name: str) -> str:
    return os.environ.get(name, "")


def flag(name: str, default: bool = False) -> bool:
    """Strict boolean knob: 1/true/yes/on vs 0/false/no/off ("" = unset
    -> default). Anything else raises — a typo must not silently pick a
    measurement arm."""
    raw = _raw(name).lower()
    if raw == "":
        return default
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    raise ValueError(
        f"{name} must be one of {_TRUE + _FALSE} (or unset), got {raw!r}"
    )


def env_int(name: str, default: int) -> int:
    raw = _raw(name)
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be an integer, got {raw!r}"
        ) from None


# --- kernel-dispatch knobs ---------------------------------------------------


def comm_overlap_enabled() -> bool:
    """AF2_COMM_OVERLAP: communication-compute overlap schedules
    (double-buffered ring attention, backward-overlapped DP reduction).
    Default ON; read at trace time (parallel/overlap.py)."""
    return flag("AF2_COMM_OVERLAP", default=True)


def pallas_interpret_override() -> Optional[bool]:
    """AF2_PALLAS_INTERPRET: force Pallas interpret mode on (1/true) or
    off (0/false); ""/unset -> None (platform default, resolved by
    ops/core.py pallas_interpret)."""
    raw = _raw("AF2_PALLAS_INTERPRET")
    if not raw:  # empty string = unset
        return None
    if raw.lower() in ("0", "false"):
        return False
    if raw.lower() in ("1", "true"):
        return True
    raise ValueError(
        f"AF2_PALLAS_INTERPRET must be 0/false or 1/true, got {raw!r}"
    )


def kernel_backend_override(op: str) -> Optional[str]:
    """The dispatch-registry backend override (ops/dispatch.py).

    Per-op `AF2_KERNEL_BACKEND_<OP>` (op name upper-cased) wins over the
    global `AF2_KERNEL_BACKEND` — including an explicit per-op "auto",
    which restores the heuristic for that op UNDER a global override
    (the one combination per-op-wins exists for). Values: "" -> fall
    through (per-op) / None (global), "auto" -> None (heuristic),
    "off" -> the op's `xla_ref` arm, anything else -> returned verbatim
    as a FORCED arm name — ops/dispatch.py validates it against the
    op's registered arms and raises loudly on unknown arms or
    unsupported shapes (forcing must not silently fall back)."""
    for name in (f"AF2_KERNEL_BACKEND_{op.upper()}", "AF2_KERNEL_BACKEND"):
        raw = _raw(name).strip().lower()
        if raw == "auto":
            return None  # explicitly set: do NOT fall through to global
        if raw:
            return raw
    return None


# --- multi-host launch contract (parallel/distributed.py) --------------------


def coordinator() -> Optional[str]:
    """AF2_COORDINATOR: host:port of process 0's coordination service."""
    return _raw("AF2_COORDINATOR") or None


def num_processes() -> int:
    """AF2_NUM_PROCESSES: pod process count (0/unset = single process)."""
    return env_int("AF2_NUM_PROCESSES", 0)


def process_id() -> Optional[int]:
    """AF2_PROCESS_ID: this host's process index (None when unset)."""
    raw = _raw("AF2_PROCESS_ID")
    return int(raw) if raw else None


def auto_init() -> bool:
    """AF2_AUTO_INIT: opt into jax.distributed.initialize() TPU-pod
    topology auto-detection."""
    return flag("AF2_AUTO_INIT")


# --- the registry ------------------------------------------------------------

_BOOL = "1/true/yes/on, 0/false/no/off"

KNOBS: Tuple[Knob, ...] = (
    Knob("AF2_KERNEL_BACKEND",
         "auto, off, or an arm name (pallas_tpu, xla_ref)", "auto",
         "ops/dispatch.py",
         "Global backend-arm override for every registered hot op: an arm "
         "name forces it (loud error if unsupported), off forces xla_ref, "
         "auto/unset keeps the platform/shape heuristic."),
    Knob("AF2_KERNEL_BACKEND_<OP>",
         "auto, off, or an arm name (per-op)", "auto",
         "ops/dispatch.py",
         "Per-op override (OP = registered op name upper-cased, e.g. "
         "AF2_KERNEL_BACKEND_QUANT_MATMUL); wins over the global knob."),
    Knob("AF2_PALLAS_INTERPRET", "1/true, 0/false", "platform default",
         "ops/core.py",
         "Force Pallas interpret mode on or off (default: interpret "
         "off-TPU, compiled on TPU)."),
    Knob("AF2_COMM_OVERLAP", _BOOL, "1", "parallel/overlap.py",
         "Communication-compute overlap schedules (double-buffered ring, "
         "backward-overlapped DP psum); baked in at trace time."),
    Knob("AF2_COORDINATOR", "host:port", "unset",
         "parallel/distributed.py",
         "Multi-host launch contract: process 0's coordination address."),
    Knob("AF2_NUM_PROCESSES", "integer", "0",
         "parallel/distributed.py",
         "Multi-host launch contract: pod process count."),
    Knob("AF2_PROCESS_ID", "integer", "unset",
         "parallel/distributed.py",
         "Multi-host launch contract: this host's process index."),
    Knob("AF2_AUTO_INIT", _BOOL, "0", "parallel/distributed.py",
         "Opt into TPU-pod topology auto-detection "
         "(jax.distributed.initialize with no arguments)."),
)


def generate_table() -> str:
    """The docs/OPERATIONS.md env-knob reference table, generated from
    the registry (one definition per knob — the docs block between the
    af2knobs markers must equal this string; pinned by
    tests/test_dispatch.py)."""
    lines = [
        "| Knob | Values | Default | Read by | What it does |",
        "| --- | --- | --- | --- | --- |",
    ]
    for k in KNOBS:
        lines.append(
            f"| `{k.name}` | {k.values} | {k.default} | `{k.read_by}` "
            f"| {k.help} |"
        )
    return "\n".join(lines)


if __name__ == "__main__":
    print(generate_table())
