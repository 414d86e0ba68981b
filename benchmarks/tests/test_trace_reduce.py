"""The reduction from a profiler trace to busy time, idle share and named
gaps, on a small trace recorded on a TPU v5e (three executions of a
four-matmul program under `bench.step`, 20 ms sleeps under `bench.wait`):
`trace_reduce.py`'s primitives and the one reduction built from them,
`scope_reduce.reduce_scopes`."""
import os

import pytest

import scope_reduce
import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_merges_overlaps():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


def test_busy_and_gaps_clip_to_window():
    ops = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "c")]
    busy, gaps = trace_reduce.busy_and_gaps(ops, (0.5, 3.5))
    assert busy == pytest.approx(2.0)
    assert gaps == [(2.0, 3.0)]


def test_attribute_needs_half_cover():
    spans = [(0.0, 1.0, "bench.wait"), (1.0, 1.2, "bench.submit")]
    assert trace_reduce.attribute((0.1, 0.9), spans) == "bench.wait"
    assert trace_reduce.attribute((1.1, 2.0), spans) == "unattributed"


def test_names():
    assert trace_reduce.short_name("%fusion.3 = bf16[4096]{0} fusion(...)") == "fusion.3"
    assert trace_reduce.module_name("jit_f(1549198243489773811)") == "jit_f"


@pytest.mark.parametrize("scope,phase,want", [
    ("seq_ff/geglu", "backward", "seq_ff/geglu backward"),
    ("seq_attn/attn_core", "reconstruct", "seq_attn/attn_core reconstruct"),
    ("optimizer", "other", "optimizer other"),
    # no documented scope: the HLO name stays, under its program
    ("unscoped", "other", "jit_train_step/while.2202"),
    ("unscoped/attn_core", "forward", "jit_train_step/while.2202"),
])
def test_an_operation_is_named_by_scope_and_phase(scope, phase, want):
    assert trace_reduce.op_label(scope, phase, "jit_train_step(123)",
                                 "%while.2202 = (s32[]) while(...)") == want


def test_recorded_trace():
    out = scope_reduce.reduce_scopes(TRACE, window_span="bench.step")
    # three executions of about 2.8 ms each inside a 52 ms window
    assert out["n_ops"] == 24
    assert 0.007 < out["busy_s"] < 0.009
    assert 0.050 < out["window_s"] < 0.055
    assert 0.80 < out["idle_share"] < 0.90
    assert out["device_ops"][0][0].startswith("jit_f/fusion")
    # the two long gaps are the sleeps between the steps
    (name1, s1), (name2, s2) = out["idle_gaps"][:2]
    assert name1 == name2 == "bench.wait"
    assert 0.019 < s2 <= s1 < 0.024


def test_whole_trace_without_a_window_span():
    out = scope_reduce.reduce_scopes(TRACE, window_span="no.such.span")
    assert out["busy_s"] > 0.008 and out["window_s"] > out["busy_s"]
