"""The no-fallback contract of the chip entry points, checked without a chip.

chip_smoke.py and bench.py must FAIL where there is no TPU (this suite runs
under JAX_PLATFORMS=cpu), an unknown `device_kind` must be an error, and the
compile cache must be placeable from outside and stable from inside.
"""

import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, *, env_extra=None, cwd=REPO):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(env_extra or {})
    cmd = ([sys.executable, code_or_script] if os.path.exists(code_or_script)
           else [sys.executable, "-c", code_or_script])
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=cwd, timeout=300)


def test_smoke_without_a_tpu_fails_and_names_what_it_found():
    out = _run(os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""  # no phase line, no result line


def test_bench_without_a_tpu_fails_and_prints_no_metric():
    out = _run(os.path.join(REPO, "bench.py"))
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr
    assert out.stdout.strip() == ""  # no metric line to mistake for a result


_CACHE_PROBE = (
    "import sys\n"
    "from alphafold2_tpu.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print('jax' in sys.modules)\n"
)


#: the helper with every `jax.config.update` it makes written down
_PLACED_PROBE = (
    "import json\n"
    "import jax\n"
    "set_in_code = []\n"
    "update = jax.config.update\n"
    "jax.config.update = lambda name, value: (set_in_code.append(name),\n"
    "                                         update(name, value))\n"
    "from alphafold2_tpu.compile_cache import enable_compile_cache\n"
    "from alphafold2_tpu.telemetry import compile_record\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(json.dumps(set_in_code))\n"
    "print(compile_record.RECORDER._installed)\n"
)


def test_compile_cache_is_placed_from_outside(tmp_path):
    placed = str(tmp_path / "placed")
    out = _run(_PLACED_PROBE, env_extra={"JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stderr[-400:]
    path, in_force, set_in_code, recorder = out.stdout.split()
    assert path == placed
    # nothing set in code on that path: JAX read the variable itself at
    # import, and the helper only installed the compile recorder
    assert in_force == placed and set_in_code == "[]"
    assert recorder == "True"


def test_compile_cache_default_is_one_path_inside_the_checkout(tmp_path):
    # two processes, two working directories: the same in-checkout path
    a = _run(_CACHE_PROBE, cwd=REPO)
    b = _run(_CACHE_PROBE, cwd=str(tmp_path))
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    path_a, path_b = a.stdout.split()[0], b.stdout.split()[0]
    assert path_a == path_b == os.path.join(REPO, ".jax_cache")


def test_pod_rehearsals_run_without_the_compile_cache(monkeypatch):
    # an executable cached under one process topology must never be replayed
    # under another (parallel/distributed.py): the rehearsal env drops a
    # placed directory AND switches off the CLIs' in-checkout default
    from alphafold2_tpu.parallel.distributed import cpu_pod_env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    env = cpu_pod_env()
    assert "JAX_COMPILATION_CACHE_DIR" not in env
    assert env["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert env["JAX_PLATFORMS"] == "cpu"


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    import bench

    assert bench._peak_flops(types.SimpleNamespace(
        device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        bench._peak_flops(types.SimpleNamespace(
            device_kind="TPU v9 imaginary"))
