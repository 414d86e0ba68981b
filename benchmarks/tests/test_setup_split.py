"""`tools/setup_split.py`: the compile recorder's split of a run's set-up,
over the phases of `common.Setup`."""
import time

import jax
import jax.numpy as jnp
import pytest

import common
from tools import setup_split

from alphafold2_tpu.telemetry.compile_record import CompileRecorder


@pytest.fixture
def recorder():
    rec = CompileRecorder().install()
    try:
        yield rec
    finally:
        rec.uninstall()


def _compile(scale):
    def set_up_fn(x):
        return jnp.tanh(x * scale).sum()
    return jax.jit(set_up_fn)


def test_the_split_reads_the_window_after_the_chip(recorder):
    x = jnp.ones((16,))
    setup = common.Setup(time.perf_counter())
    _compile(2.0)(x).block_until_ready()          # before the chip is held
    setup.mark(common.IMPORTS_PHASE)
    setup.mark("weights_and_state_on_device")
    _compile(3.0)(x).block_until_ready()
    setup.mark("trace_and_compile_or_cache_load")
    _compile(5.0)(x).block_until_ready()          # after set-up: the reference's
    rep = setup_split.report(setup, recorder)
    after = rep["after_device"]
    assert after["setup.programs_compiled"] == 1 and after["setup.cache_load_s"] == 0
    seconds = [after[k] for k in ("setup.jaxpr_trace_s", "setup.lower_s",
                                  "setup.xla_compile_s", "setup.cache_load_s")]
    assert all(s >= 0 for s in seconds) and after["setup.xla_compile_s"] > 0
    # the four phases lie one after another: their sum is within set-up
    assert sum(seconds) <= setup.total() - setup.phases[0][1]
    assert rep["union_s"] <= sum(seconds) + 1e-9
    phases = rep["phases"]
    assert list(phases) == [common.IMPORTS_PHASE, "weights_and_state_on_device",
                            "trace_and_compile_or_cache_load"]
    assert phases[common.IMPORTS_PHASE]["counts"]["xla_compile"] == 1
    assert phases["weights_and_state_on_device"]["counts"]["xla_compile"] == 0
    compiled = phases["trace_and_compile_or_cache_load"]
    assert compiled["counts"]["xla_compile"] == 1 and 0 < compiled["covered"] <= 1.0
    assert any("set_up_fn" in t["fun"] for t in rep["top"])


def test_the_five_numbers_are_named_for_their_metrics():
    snap = {"seconds": {"trace": 9.0, "lower": 2.0, "xla_compile": 3.0, "cache_load": 4.0},
            "counts": {"trace": 40, "lower": 7, "xla_compile": 5, "cache_load": 2}}
    assert setup_split.split(snap) == {
        "setup.jaxpr_trace_s": 9.0, "setup.lower_s": 2.0, "setup.xla_compile_s": 3.0,
        "setup.cache_load_s": 4.0, "setup.programs_compiled": 5}


def test_phase_windows_follow_the_marks():
    setup = common.Setup(t_process_start=100.0)
    setup.phases = [(common.IMPORTS_PHASE, 10.0), ("weights", 5.0), ("compile", 20.0)]
    setup.last = 135.0
    assert setup_split.phase_windows(setup) == [
        (common.IMPORTS_PHASE, 100.0, 110.0), ("weights", 110.0, 115.0),
        ("compile", 115.0, 135.0)]
