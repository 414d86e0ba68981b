"""Where compiled programs are kept between processes.

Every entry point (the four CLIs, bench.py, chip_smoke.py, the bench
scripts' workers) calls `enable_compile_cache()` once, before its first
compile. The directory is part of JAX's cache key, so it must not move
between runs: no tempfile, pid or timestamp.

  * `JAX_COMPILATION_CACHE_DIR` set — the operator (or the machine image)
    placed the cache; JAX already read the variable at import and nothing
    is set in code.
  * unset — `<checkout>/.jax_cache` (git-ignored), so two runs from one
    checkout share compiled programs.

Either way it installs the process's compile recorder first
(`telemetry/compile_record.py`), so that every compile after it is timed by
phase and function and known as a compile or a cache load.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in force."""
    from alphafold2_tpu.telemetry import compile_record

    compile_record.install()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
