"""Multi-host runtime entry: `jax.distributed` + process-spanning meshes.

The reference's multi-node story is an empty DeepSpeed launcher
(reference training_scripts/deepspeed.py, 0 bytes) that would have carried
NCCL underneath. The TPU-native runtime is the JAX distributed service:
every host runs the same program, `jax.distributed.initialize` wires them
into one runtime, and `jax.devices()` then spans the whole pod — meshes,
shardings, and collectives (psum over DCN/ICI) work unchanged
(SURVEY.md §2.2, communication backend row).

Launch contract (one command per host):

    AF2_COORDINATOR=host0:8476 AF2_NUM_PROCESSES=4 AF2_PROCESS_ID=$i \\
        python train_pre.py ...

On Cloud TPU pods the three variables can be omitted entirely —
`jax.distributed.initialize()` auto-detects the topology — pass
`AF2_AUTO_INIT=1` to opt into that. Single-process runs need nothing: with
no coordinator configured `initialize_from_env` is a no-op.

Verified by a real 2-process CPU smoke test (tests/test_distributed.py):
two OS processes x 4 virtual devices form one 8-device mesh and reduce a
process-sharded array to the same global sum on both hosts.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

import jax

from alphafold2_tpu import compat


def initialize_from_env(
    *,
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> bool:
    """Join the multi-host runtime if one is configured; else no-op.

    Reads AF2_COORDINATOR / AF2_NUM_PROCESSES / AF2_PROCESS_ID (explicit
    args win), or AF2_AUTO_INIT=1 for TPU-pod auto-detection — all
    parsed by ops/knobs.py, the one home for every AF2_* knob. Must run
    before any backend-initializing JAX call. Returns True when the
    distributed runtime was initialized.
    """
    from alphafold2_tpu.ops import knobs

    coordinator = coordinator or knobs.coordinator()
    if num_processes is None:
        num_processes = knobs.num_processes()
    if process_id is None:
        process_id = knobs.process_id()

    will_init = (coordinator and num_processes > 1) or knobs.auto_init()
    if will_init and compat.backend_initialized():
        # joining AFTER backend init would leave this process on its
        # local-only device view while claiming pod membership — every
        # mesh built from jax.devices() would silently be a one-host
        # mesh. Refuse loudly; the fix is ordering, not retrying.
        raise RuntimeError(
            "initialize_from_env() called after JAX's backend was already "
            "initialized — the distributed runtime must be joined BEFORE "
            "the first backend-initializing JAX call (jax.devices(), any "
            "computation, ...). Move the startup call (see "
            "distributed_startup) to the top of main()."
        )

    if coordinator and num_processes > 1:
        # CPU pods (the test matrix, accelerator-free hosts) need a
        # cross-process collectives impl picked before backend init;
        # harmless on non-CPU backends, so no platform sniffing — the
        # env var may be unset with the backend still resolving to CPU
        compat.enable_cpu_collectives()
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
        return True
    if knobs.auto_init():
        jax.distributed.initialize()  # TPU-pod metadata auto-detection
        return True
    return False


def distributed_startup(label: str = "") -> bool:
    """The shared CLI startup: every entry point (train_pre.py,
    train_end2end.py, serve.py, predict.py) calls this once, right after
    argparse and before anything that initializes the JAX backend.

    Joins the multi-host runtime when one is configured (the
    AF2_COORDINATOR/... contract above), errors LOUDLY if the backend
    was already initialized (see initialize_from_env), and prints one
    line describing the joined topology so multi-host logs self-identify
    their process. Returns True when a distributed runtime was joined.
    """
    joined = initialize_from_env()
    if joined:
        tag = f"{label}: " if label else ""
        print(
            f"{tag}joined multi-host runtime: process "
            f"{jax.process_index()}/{jax.process_count()}, "
            f"{jax.local_device_count()} local / {jax.device_count()} "
            "global devices",
            flush=True,
        )
    return joined


# --- CPU-pod rehearsal harness ----------------------------------------------
# One definition of "launch N coordinated CPU processes" shared by the
# 2-process test matrix (tests/test_distributed.py) and the MULTICHIP
# dryrun's multihost_dp leg (__graft_entry__.py) — the env hygiene here
# (CPU platform, no inherited XLA flags, NO shared persistent compile
# cache: an executable cached under one process topology must never be
# replayed under another) was learned the hard way and must not drift
# between the two callers.


def free_local_port() -> int:
    """An OS-assigned free TCP port for a localhost coordinator."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cpu_pod_env(
    *,
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    repo_path: Optional[str] = None,
    extra: Optional[Mapping[str, str]] = None,
) -> dict:
    """Scrubbed subprocess env for one process of a CPU-pod rehearsal.

    Pins the CPU platform, removes inherited XLA flags (workers
    provision their own virtual device counts), and turns the persistent
    compile cache off — the placed directory AND the in-checkout default
    the CLIs would otherwise pick (alphafold2_tpu/compile_cache.py;
    topology aliasing hazard — see module comment). With `coordinator`
    set, adds the AF2_COORDINATOR /
    AF2_NUM_PROCESSES / AF2_PROCESS_ID launch contract; `extra` wins
    over everything.
    """
    env = dict(os.environ)
    for var in (
        "JAX_PLATFORM_NAME",
        "JAX_COMPILATION_CACHE_DIR",
        "XLA_FLAGS",
    ):
        env.pop(var, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    if coordinator is not None:
        env["AF2_COORDINATOR"] = coordinator
        env["AF2_NUM_PROCESSES"] = str(num_processes)
        env["AF2_PROCESS_ID"] = str(process_id)
    if repo_path:
        env["PYTHONPATH"] = os.pathsep.join(
            [repo_path] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
    env.update(dict(extra) if extra else {})
    return env


def global_mesh(axes: Mapping[str, int]):
    """Mesh over ALL processes' devices (call after initialize_from_env).

    Axis sizes must multiply to the global device count; the per-host batch
    a data loader should feed is global_batch * local_device_count /
    device_count.
    """
    from alphafold2_tpu.parallel.mesh import make_mesh

    return make_mesh(axes, jax.devices())


def process_local_batch_size(global_batch: int) -> int:
    """This host's share of a globally-sharded batch axis."""
    if global_batch % jax.process_count() != 0:
        raise ValueError(
            f"global batch {global_batch} must divide across "
            f"{jax.process_count()} processes"
        )
    return global_batch // jax.process_count()
