"""The reduction from a profiler trace to device seconds by scope and
phase: the name-stack rules, self times, the xplane reader, and a small
trace recorded on a TPU v5e (`data/scoped.xplane.pb`: two steps of the
depth-1 toy reversible end-to-end train step with chunked attention and
feed-forward, under `bench.window` / `bench.step` and a live tracer's
`train.step` / `train.metrics_fetch`; PR 25, call c2. The capture was
14 MB: the file keeps the TPU plane's `XLA Ops` and `XLA Modules` lines
and the host's `bench.*` / `train.*` events, without the events' own
stats, with the metadata stats the reducers read (`tf_op`,
`hlo_category`, `flops`, `bytes_accessed`), an operation's name cut to
the instruction's own)."""
import os

import jax
import pytest

import common
import run
import scope_reduce
import trace_reduce
import xplane
from alphafold2_tpu.telemetry import profiling

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
SMALL = os.path.join(DATA, "small.xplane.pb")
STEP = "jit(train_step)/while/body/closed_call/"
BWD = STEP + "transpose(jvp(trunk))/reversible_bwd/while/body/closed_call/"

# one name stack of each kind, as the v5e's compiler wrote them (PERF.md
# section 3)
PATHS = [
    (STEP + "jvp(trunk)/while/body/closed_call/seq_attn/while/body/closed_call/"
     "checkpoint/attn_core/while/body/closed_call/checkpoint/dot_general:",
     "seq_attn/attn_core", "forward"),
    (BWD + "jvp(seq_attn)/while/body/closed_call/attn_core/while/body/"
     "closed_call/checkpoint/bhqk,bkhd->bhqd/dot_general:",
     "seq_attn/attn_core", "reconstruct"),
    (BWD + "transpose(jvp(seq_attn))/while/body/closed_call/checkpoint/"
     "rematted_computation/qkv_proj/dot_general:", "seq_attn/qkv_proj", "remat"),
    (BWD + "transpose(jvp(seq_attn))/while/body/closed_call/checkpoint/attn_core/"
     "while/body/closed_call/checkpoint/rematted_computation/exp:",
     "seq_attn/attn_core", "remat"),
    (BWD + "transpose(jvp(seq_ff2))/while/body/closed_call/checkpoint/geglu/mul:",
     "seq_ff2/geglu", "backward"),
    (BWD + "sub:", "trunk", "backward"),
    (STEP + "jvp(trunk)/while/body/closed_call/seq_cross/kv_compress/conv:",
     "seq_cross/kv_compress", "forward"),
    (STEP + "jvp(mds)/jit(mds)/while/body/closed_call/div:", "mds", "forward"),
    (STEP + "transpose(jvp(mds))/jit(mds)/while/body/mul:", "mds", "backward"),
    ("jit(train_step)/optimizer/mul:", "optimizer", "other"),
    ("jit(train_step)/while/body/add:", "unscoped", "other"),
    (STEP + "jvp()/mul:", "unscoped", "forward"),
    ("jit(run)/trunk/msa_attn/attn_core/exp;jit(run)/trunk/msa_attn/out_proj/add:",
     "msa_attn/attn_core", "forward"),
    ("jit(run)/attn_core/exp:", "unscoped/attn_core", "other"),
    ("jit(f)/dot_general:", "unscoped", "other"),
    ("", "unscoped", "other"),
]


@pytest.mark.parametrize("tf_op,scope,phase", PATHS)
def test_classify(tf_op, scope, phase):
    assert scope_reduce.classify(tf_op) == (scope, phase)


def test_a_jitted_function_named_like_a_scope_is_not_a_scope():
    assert scope_reduce.unwrap("jit(refiner)") == "jit(refiner)"
    assert scope_reduce.unwrap("transpose(jvp(refiner))") == "refiner"
    assert scope_reduce.classify("jit(f)/jit(refiner)/mul:") == ("unscoped", "other")


def test_self_times_take_the_children_out_once():
    # a while that holds two ops and a nested while with one op; a later op
    events = [(0, 10, "while"), (1, 3, "a"), (3, 4, "b"), (5, 9, "while.2"),
              (6, 7, "c"), (12, 13, "d")]
    got = dict((events[i][2], t) for t, i in scope_reduce.self_times(events, (0, 20)))
    assert got == {"while": 3, "a": 2, "b": 1, "while.2": 3, "c": 1, "d": 1}
    assert sum(got.values()) == 11  # the union of the intervals


def test_self_times_clip_to_the_window():
    events = [(0, 10, "while"), (1, 3, "a"), (8, 9, "b")]
    got = dict((events[i][2], t) for t, i in scope_reduce.self_times(events, (2, 8.5)))
    assert got == {"while": 5.0, "a": 1, "b": 0.5}


def test_reader_agrees_with_profile_data():
    """Planes, lines, event names, starts and durations as
    `jax.profiler.ProfileData` reads them, plus the metadata it hides."""
    space = xplane.read(SMALL)
    data = jax.profiler.ProfileData.from_file(SMALL)
    assert [p.name for p in space.planes] == [p.name for p in data.planes]
    seen = 0
    for mine, theirs in zip(space.planes, data.planes):
        md = xplane.metadata_of(mine)
        for line, ref in zip(mine.lines, theirs.lines):
            assert line.name == ref.name
            events, ref_events = list(xplane.events_of(line)), list(ref.events)
            assert len(events) == len(ref_events)
            for (s, e, i), r in zip(events, ref_events):
                assert md[i]["name"] == r.name
                assert s == pytest.approx(r.start_ns * 1e-9, abs=1e-9)
                assert e - s == pytest.approx(r.duration_ns * 1e-9, abs=1e-9)
                seen += 1
    assert seen > 100
    (tpu,) = [p for p in space.planes if p.name == "/device:TPU:0"]
    fusions = [row for row in xplane.metadata_of(tpu).values()
               if row["display_name"].startswith("fusion")]
    assert {row["tf_op"] for row in fusions} == {"jit(f)/dot_general:"}
    assert all(row["hlo_category"] == "convolution fusion" and row["flops"] > 1e11
               and row["bytes_accessed"] > 1e8 for row in fusions)


def _by_profile_data(path, window_span):
    """(busy_s, window_s) of the first TPU plane as `jax.profiler.
    ProfileData` reads the file: the second witness of the reader."""
    data = jax.profiler.ProfileData.from_file(path)
    ops, marks = [], []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                if plane.name.startswith("/device:TPU:") and line.name == "XLA Ops":
                    ops.append(span)
                elif plane.name == "/host:CPU" and e.name == window_span:
                    marks.append(span)
    window = (min(s for s, _, _ in marks), max(e for _, e, _ in marks))
    return trace_reduce.busy_and_gaps(ops, window)[0], window[1] - window[0]


def test_a_trace_without_names_is_all_unscoped():
    out = scope_reduce.reduce_scopes(SMALL, window_span="bench.step")
    busy, _ = _by_profile_data(SMALL, "bench.step")
    # ProfileData rounds to nanoseconds, the events are kept in picoseconds
    assert out["busy_s"] == pytest.approx(busy, rel=1e-5)
    assert all(name.startswith("jit_f/") for name, _ in out["device_ops"])
    assert list(out["scopes"]) == ["unscoped"]
    assert out["scopes"]["unscoped"]["other"] == pytest.approx(out["busy_s"])
    # four 4096^3 bf16 matmuls an execution: near the chip's peak by XLA's count
    assert 150 < out["xla"]["unscoped"]["xla_tflops_per_s"] < 210
    assert out["idle_gaps"][0][0] == "bench.wait"


@pytest.fixture(scope="module")
def scoped():
    return scope_reduce.reduce_scopes(SCOPED)


def test_recorded_step_self_times_add_up_to_busy(scoped):
    busy, window_s = _by_profile_data(SCOPED, "bench.window")
    # the toy's ten thousand events last a few hundred nanoseconds each, and
    # ProfileData rounds every start and duration to a nanosecond
    assert scoped["busy_s"] == pytest.approx(busy, rel=2e-3)
    assert scoped["window_s"] == pytest.approx(window_s, rel=1e-5)
    assert scoped["sum_self_s"] == pytest.approx(scoped["busy_s"], rel=1e-6)
    assert abs(scoped["residue_s"]) < 1e-6 * scoped["busy_s"]
    total = sum(sum(cell.values()) for cell in scoped["scopes"].values())
    assert total == pytest.approx(scoped["sum_self_s"])


def test_recorded_step_nested_whiles_are_not_counted_twice(scoped):
    """The events' durations add up to well over the busy time, because a
    `while` lasts as long as its body; the self times do not."""
    devices, _ = scope_reduce.read_planes(SCOPED)
    (entry,) = devices.values()
    durations = sum(e - s for s, e, _ in entry["ops"])
    containers = [row for _, _, row in entry["ops"] if scope_reduce.is_container(row)]
    assert containers and durations > 1.5 * scoped["busy_s"]


@pytest.mark.parametrize("phase", scope_reduce.PHASES)
def test_recorded_step_has_every_phase(scoped, phase):
    assert scope_reduce.seconds_of(scoped["scopes"], phases=(phase,)) > 0


@pytest.mark.parametrize("name", profiling.TRUNK_OP_SCOPES)
def test_recorded_step_has_every_trunk_op_in_every_phase(scoped, name):
    for phase in ("forward", "reconstruct", "backward"):
        assert scope_reduce.seconds_of(scoped["scopes"], outer=(name,), phases=(phase,)) > 0
    if name.endswith("attn") or name.endswith("cross"):
        assert scoped["scopes"][f"{name}/attn_core"]["forward"] > 0
    else:
        assert scoped["scopes"][f"{name}/geglu"]["forward"] > 0


def test_recorded_step_reports_unscoped_and_names_gaps(scoped):
    unscoped = scope_reduce.seconds_of(scoped["scopes"], outer=("unscoped",))
    assert 0 < unscoped < 0.25 * scoped["busy_s"]
    assert scoped["top_unscoped"] and scoped["top_unscoped"][0][3] > 0
    # the tracer's spans are on the capture's host plane beside bench.*
    _, host = scope_reduce.read_planes(SCOPED)
    names = {name for _, _, name in host}
    assert {"bench.window", "bench.step", "train.step", "train.metrics_fetch"} <= names
    assert scoped["idle_gaps"][0][0].startswith(("bench.", "train."))


# --- the traced result line, read from that one reduction (PR 31) --------------

def _scope_share_metrics():
    import glob

    names = [os.path.basename(p)[:-len(".json")]
             for p in sorted(glob.glob(os.path.join(common.HERE, "metrics", "*.json")))]
    return [n for n in names
            if common.load_json("metrics", n + ".json")["reader"] == "scope_share"
            and not n.startswith("lm.")]


def test_the_trunk_has_eight_scope_shares():
    assert len(_scope_share_metrics()) == 8


@pytest.mark.parametrize("name", _scope_share_metrics())
def test_recorded_step_gives_every_trunk_share(scoped, name):
    value = run.read_metric(name, {"scopes": scoped})
    assert value is not None and 0 < value < 100
    if name.startswith(("trunk.", "tail.")):
        assert value > 1.0  # every layer of the toy takes a visible share


def test_recorded_step_shares_add_up(scoped):
    """The five layers, the optimizer and the unnamed rest, with the three
    scopes that have no metric of their own, are the whole busy time."""
    listed = [n for n in _scope_share_metrics() if "recompute" not in n]
    total = sum(run.read_metric(n, {"scopes": scoped}) for n in listed)
    rest = 100.0 * scope_reduce.seconds_of(
        scoped["scopes"], outer=("embed", "template_tower", "trunk")) / scoped["busy_s"]
    assert total + rest == pytest.approx(100.0, abs=1e-6)


def test_recorded_step_names_device_ops_by_scope(scoped):
    ops = scoped["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda kv: -kv[1])
    phases = tuple(" " + p for p in scope_reduce.PHASES)
    named = [name for name, _ in ops if name.endswith(phases)]
    assert len(named) >= 8, ops
    assert all(name.split(" ")[0].split("/")[0] in profiling.OUTER_SCOPES for name in named)
    # self seconds: the ten heaviest cannot outweigh the device's busy time
    assert sum(seconds for _, seconds in ops) <= scoped["busy_s"] * (1 + 1e-9)
    assert 0 < scoped["idle_share"] < 1


def test_a_traced_run_reduces_its_trace_once(tmp_path, monkeypatch):
    """`run.reduce_trace_once`: one pass over the capture feeds the readers
    (`scopes`), the device entry and the breakdown (`trace`), and the
    capture is gone afterwards."""
    import shutil

    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(SCOPED, trace_dir / "t.xplane.pb")
    calls = []
    real = scope_reduce.reduce_scopes
    monkeypatch.setattr(scope_reduce, "reduce_scopes",
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    facts = {"trace_dir": str(trace_dir)}
    reduced = run.reduce_trace_once(facts, {"trace_steps": 2})
    assert len(calls) == 1 and not trace_dir.exists()
    assert facts["trace"] is facts["scopes"] is reduced and facts["trace_steps"] == 2
    assert run.read_metric("device.idle_share.train", facts) == pytest.approx(
        100.0 * reduced["idle_share"])
    # a kind that brings its own table keeps it
    trace_dir.mkdir()
    shutil.copy(SCOPED, trace_dir / "t.xplane.pb")
    own = {"scopes": {}, "busy_s": 1.0}
    facts = {"trace_dir": str(trace_dir), "scopes": own, "trace_steps": 3}
    run.reduce_trace_once(facts, {"trace_steps": 2})
    assert facts["scopes"] is own and facts["trace_steps"] == 3
