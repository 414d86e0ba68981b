"""The one place JAX's drift-prone names are spelled.

Written for the ONE installation this repo runs on (jax/jaxlib 0.9.0,
libtpu 0.0.34): every name below has exactly one spelling and no
fallback. A name the installed JAX lacks fails here, at import or at the
first call, with JAX's own error — never as a quiet default (the retired
`try/except -> return False` in `backend_initialized` kept the
joined-after-backend-init guard dead for a whole JAX upgrade).

Contract, enforced statically by `alphafold2_tpu.analysis` (the `compat`
pass): no module outside this file touches `jax.experimental.*` or any
symbol in the drift table (analysis/drift.py). When a JAX upgrade renames
something, this file is the only one that changes.

Import idiom:

    from alphafold2_tpu import compat
    from alphafold2_tpu.compat import pallas as pl, pallas_tpu as pltpu

    compat.CompilerParams(dimension_semantics=...)
    compat.shard_map(f, mesh=mesh, in_specs=..., out_specs=..., check_vma=False)
    compat.out_struct(shape, dtype, q, k, v)   # vma-aware ShapeDtypeStruct
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax

__all__ = [
    "CompilerParams",
    "backend_initialized",
    "broadcast_one_to_all",
    "enable_cpu_collectives",
    "create_hybrid_device_mesh",
    "describe_topology",
    "make_array_from_process_local_data",
    "make_global_array_from_host",
    "megablox",
    "out_struct",
    "pallas",
    "pallas_tpu",
    "pcast",
    "process_allgather",
    "row_major",
    "shard_map",
    "sync_global_devices",
    "typeof_vma",
]


# --- pallas ----------------------------------------------------------------
# The pallas modules live under jax.experimental; re-exported so kernel
# files never spell the experimental path (the compat linter forbids it
# outside this module). Resolved LAZILY (PEP 562 module __getattr__): most
# consumers of this module (parallel/mesh, sequence, pipeline, sp_trunk)
# only want shard_map/pcast, and the eager Pallas import costs ~0.26 s on
# top of jax's own import on every process start.


def __getattr__(name: str):
    if name == "pallas":
        from jax.experimental import pallas

        globals()["pallas"] = pallas
        return pallas
    if name == "pallas_tpu":
        from jax.experimental.pallas import tpu as pallas_tpu

        globals()["pallas_tpu"] = pallas_tpu
        return pallas_tpu
    if name == "megablox":
        # JAX's grouped-product kernels (gmm with a custom VJP through tgmm)
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        globals()["megablox"] = megablox
        return megablox
    if name == "CompilerParams":
        cp = __getattr__("pallas_tpu").CompilerParams
        globals()["CompilerParams"] = cp
        return cp
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# --- shard_map -------------------------------------------------------------


def shard_map(f=None, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """`jax.shard_map`, usable directly or as a decorator factory
    (``f=None``). `check_vma=None` keeps JAX's default checker setting."""
    if f is None:
        return functools.partial(
            shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma,
        )
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


# --- vma-aware ShapeDtypeStruct -------------------------------------------
# JAX tracks a `vma` (varying-across-mesh-axes) set on abstract values and
# requires pallas_call out_shapes under shard_map to declare theirs.


def typeof_vma(x: Any) -> frozenset:
    """The value's varying-across-mesh-axes set."""
    return frozenset(jax.typeof(x).vma)


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct whose `vma` is the union of the operands' — required
    for pallas_call under shard_map with vma checking (e.g. ring-attention
    hops)."""
    vma = frozenset().union(*(typeof_vma(o) for o in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def row_major(x):
    """x held to the row-major device layout (last axis minor), the one a
    Pallas call reads and writes. For a value that reaches a kernel's
    wrapper from somewhere that pins no layout (a residual read back from
    a scan's stack): XLA then lays it out for another consumer and copies
    it, and what is computed from it, back for the kernel."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def pcast(x, axis_names, *, to: str = "varying"):
    """`jax.lax.pcast`: mark a value varying/invariant over mesh axes so
    shard_map carry types line up after collectives."""
    return jax.lax.pcast(x, axis_names, to=to)


# --- compiling for a chip that is not attached ------------------------------


def describe_topology(platform: str, topology_name: str):
    """A described (not attached) accelerator topology, e.g.
    ("tpu", "v5e:2x2"): its `.devices` take shardings and
    `jit(...).lower(...).compile()` then compiles for that chip on any
    host (tests/test_chip_compile.py). Loads the platform's compiler
    library into this process: call it from a test or a fixture, never
    while a module is imported."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform=platform, topology_name=topology_name)


# --- multi-host runtime ----------------------------------------------------
# The multihost utilities live under jax.experimental; resolved here so
# parallel/distributed.py and training/checkpoint.py stay free of
# experimental imports (compat-lint contract).


def enable_cpu_collectives() -> None:
    """Select a cross-process collectives implementation for the CPU
    backend (Gloo). Without one, a multi-process CPU runtime enumerates
    the pod's devices but every cross-process computation dies with
    "Multiprocess computations aren't implemented on the CPU backend" —
    the 2-process test matrix (and any CPU-pod rehearsal) needs this set
    BEFORE backend init. Harmless on non-CPU backends; raises if the
    installed jaxlib has no such option."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def backend_initialized() -> bool:
    """True once any XLA backend has been created in this process — the
    point past which `jax.distributed.initialize` is too late (the
    backend already enumerated only-local devices). jax keeps the
    predicate private; a JAX that moves it fails this import loudly
    instead of answering False for ever."""
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def sync_global_devices(name: str) -> None:
    """Cross-process barrier (multihost_utils.sync_global_devices): every
    process blocks until all reach the same named point. No-op with one
    process — callers need no guard."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def broadcast_one_to_all(x, is_source: Optional[bool] = None):
    """multihost_utils.broadcast_one_to_all: process 0's value on every
    process (identity single-process)."""
    if jax.process_count() <= 1:
        return x
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(x, is_source=is_source)


def process_allgather(x, *, tiled: bool = True):
    """multihost_utils.process_allgather: the GLOBAL value of a (possibly
    cross-process-sharded) array, materialized host-side on every
    process. Identity-to-numpy single-process."""
    if jax.process_count() <= 1:
        import numpy as np

        return jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), x
        )
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x, tiled=tiled)


def make_global_array_from_host(x, sharding):
    """Global jax.Array from a host value EVERY process already holds.

    `jax.device_put(host_value, cross_process_sharding)` broadcasts the
    bytes from process 0 over the wire (and the CPU backend's gloo
    transport aborts on the interleaved small transfers a whole pytree
    produces). When the host value is identical on all processes —
    restored checkpoint bytes, same-seed init — no transfer is needed at
    all: each process feeds its OWN addressable shards from its local
    copy via `make_array_from_callback`. Single-process this degenerates
    to a plain sharded device_put."""
    import numpy as np

    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def make_array_from_process_local_data(sharding, local_data, global_shape=None):
    """`jax.make_array_from_process_local_data`: assemble a global
    jax.Array from this process's rows of the batch."""
    return jax.make_array_from_process_local_data(
        sharding, local_data, global_shape
    )


# --- device mesh helpers ---------------------------------------------------

def create_hybrid_device_mesh(**kwargs):
    """jax.experimental.mesh_utils.create_hybrid_device_mesh, resolved here
    so parallel/mesh.py stays free of experimental imports."""
    from jax.experimental import mesh_utils

    return mesh_utils.create_hybrid_device_mesh(**kwargs)
