"""Test configuration: force an 8-device virtual CPU platform so pjit/mesh
sharding paths are exercised without TPU hardware, and so numerical parity
tests run at full float32 precision (TPU matmul defaults would fail 1e-5
tolerances)."""

import os

# force CPU: on a TPU host JAX would otherwise take the chip; unit tests
# run on the virtual 8-device CPU mesh
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# a pytest plugin may have imported jax before this file ran, in which case
# the variable above came too late: pin the config flag as well (before any
# backend initialization)
jax.config.update("jax_platforms", "cpu")

# the suite is XLA-compile-dominated; the test-mode compile shortcut cuts
# cold-cache wall time ~40% with every numerical-parity suite still green
# (tolerances unaffected — fewer fusions/reassociations, not more). Set
# AF2_TEST_FULL_OPT=1 to run tests against fully optimized XLA output.
if os.environ.get("AF2_TEST_FULL_OPT") != "1":
    jax.config.update("jax_disable_most_optimizations", True)

# persistent compilation cache: the suite is COMPILE-dominated (tiny shapes,
# but dozens of jit/shard_map programs — the worst single test spends ~95%
# of its 99 s compiling). With the cache warm, re-runs pay only execution.
# Safe across processes (content-addressed); scoped to a repo-local dir so
# `git clean` or deleting .pytest_jax_cache resets it.
jax.config.update(
    "jax_compilation_cache_dir",
    os.path.join(os.path.dirname(__file__), ".pytest_jax_cache"),
)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
