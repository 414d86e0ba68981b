"""The JAX API drift table: symbols whose spelling has moved between JAX
releases and therefore MUST resolve through `alphafold2_tpu/compat.py`.

compat.py is written for the one installed JAX and keeps ONE spelling of
each name (no version branches); this table lists those names so the
compat linter can flag a direct use at any other call site. The seed's
failure was exactly that: two kernel files spelled a `pltpu` class
directly, JAX renamed it, and 20+ tier-1 tests went red.

When a JAX upgrade renames something (docs/STATIC_ANALYSIS.md):
  1. change the one spelling in compat.py;
  2. add or update the DriftEntry here;
  3. `python -m alphafold2_tpu.analysis --strict` then flags every direct
     use outside compat.py.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DriftEntry:
    """One compat-routed symbol.

    attr_names: attribute spellings that identify the symbol at a call
        site (matched against the last attribute of a dotted access);
    full_names: dotted prefixes that also identify it (e.g. bare-module
        paths), matched exactly;
    keywords: call keywords that belong to the symbol;
    compat_name: how call sites should spell it;
    renamed_in: what the installed JAX calls it (documentation string).
    """

    attr_names: Tuple[str, ...]
    compat_name: str
    renamed_in: str
    full_names: Tuple[str, ...] = ()
    keywords: Tuple[str, ...] = ()
    note: Optional[str] = None


DRIFT_TABLE: Tuple[DriftEntry, ...] = (
    DriftEntry(
        attr_names=("CompilerParams",),
        compat_name="compat.CompilerParams",
        renamed_in="jax.experimental.pallas.tpu.CompilerParams",
    ),
    DriftEntry(
        attr_names=("shard_map",),
        full_names=("jax.shard_map",),
        keywords=("check_vma",),
        compat_name="compat.shard_map",
        renamed_in="jax.shard_map(check_vma=)",
    ),
    DriftEntry(
        attr_names=("typeof",),
        full_names=("jax.typeof",),
        compat_name="compat.typeof_vma",
        renamed_in="jax.typeof(x).vma",
    ),
    DriftEntry(
        attr_names=(),
        full_names=(),
        keywords=("vma",),
        compat_name="compat.out_struct",
        renamed_in="jax.ShapeDtypeStruct(vma=...)",
        note="matched via the 'vma' call keyword on ShapeDtypeStruct calls",
    ),
    DriftEntry(
        attr_names=("pcast",),
        full_names=("jax.lax.pcast",),
        compat_name="compat.pcast",
        renamed_in="jax.lax.pcast",
    ),
    DriftEntry(
        attr_names=("create_hybrid_device_mesh",),
        full_names=("jax.experimental.mesh_utils.create_hybrid_device_mesh",),
        compat_name="compat.create_hybrid_device_mesh",
        renamed_in="jax.experimental.mesh_utils.create_hybrid_device_mesh",
        note="experimental-path import; routed through compat to keep the gate total",
    ),
)


def attr_index() -> dict:
    """{attribute_name: DriftEntry} for call-site matching."""
    out = {}
    for e in DRIFT_TABLE:
        for a in e.attr_names:
            out[a] = e
    return out


def keyword_index() -> dict:
    """{keyword: DriftEntry} for drifted call keywords."""
    out = {}
    for e in DRIFT_TABLE:
        for k in e.keywords:
            out[k] = e
    return out
