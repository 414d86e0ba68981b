"""af2lint (alphafold2_tpu/analysis) tests: every pass must fire on its
violation fixture and stay silent on the matching clean fixture — the
analyzer is repo infrastructure, so it gets tier-1 coverage like any op.

The repo-wide strict run (the CI gate) is also pinned here: the compat /
trace / sharding passes must be clean on this very repo, and a
deliberately re-introduced `pltpu.CompilerParams` direct access (the
exact API-drift defect that had the seed suite red) must be caught.
"""

import json
import os
import textwrap
import threading

import pytest

from alphafold2_tpu.analysis import PASSES, PASS_SUMMARIES, run_passes
from alphafold2_tpu.analysis.__main__ import main as af2lint_main
from alphafold2_tpu.analysis.compat_lint import run as compat_run
from alphafold2_tpu.analysis.concurrency_lint import lock_graph
from alphafold2_tpu.analysis.concurrency_lint import run as conc_run
from alphafold2_tpu.analysis.lock_runtime import LockMonitor
from alphafold2_tpu.analysis.sharding_lint import run as sharding_run
from alphafold2_tpu.analysis.trace_safety import run as trace_run

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def _codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# compat pass
# ---------------------------------------------------------------------------


class TestCompatPass:
    def test_reintroduced_compiler_params_is_caught(self, tmp_path):
        """The seed's actual defect, re-introduced on purpose: direct
        pltpu.CompilerParams access must be flagged."""
        f = _write(
            tmp_path,
            "kernel.py",
            """
            from jax.experimental.pallas import tpu as pltpu

            PARAMS = pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            )
            """,
        )
        findings = compat_run(tmp_path, files=[f])
        assert "COMPAT001" in _codes(findings)  # the experimental import
        assert [x.line for x in findings if x.code == "COMPAT002"] == [4]

    def test_experimental_attribute_access_flagged(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            import jax

            mesh = jax.experimental.mesh_utils.create_device_mesh((2,))
            """,
        )
        assert _codes(compat_run(tmp_path, files=[f])) == ["COMPAT001"]

    def test_from_jax_import_shard_map_flagged(self, tmp_path):
        """`from jax import shard_map` — the exact line that had
        tests/test_sequence_parallel.py red at collection on old JAX."""
        f = _write(tmp_path, "m.py", "from jax import shard_map\n")
        assert "COMPAT002" in _codes(compat_run(tmp_path, files=[f]))

    def test_drifted_keyword_flagged_and_compat_route_allowed(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            import functools
            from somewhere import shard_map as sm
            from alphafold2_tpu import compat
            from alphafold2_tpu.compat import shard_map

            bad = sm(lambda x: x, mesh=None, in_specs=(), out_specs=(),
                     check_vma=False)
            ok1 = shard_map(lambda x: x, mesh=None, in_specs=(),
                            out_specs=(), check_vma=False)
            ok2 = functools.partial(compat.shard_map, mesh=None, in_specs=(),
                                    out_specs=(), check_vma=False)
            """,
        )
        findings = compat_run(tmp_path, files=[f])
        assert [x.code for x in findings] == ["COMPAT003"]
        assert findings[0].line == 7

    def test_suppression_comment(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            "import jax.experimental.pallas  # af2lint: disable=COMPAT001\n",
        )
        assert compat_run(tmp_path, files=[f]) == []

    def test_clean_compat_usage_not_flagged(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            from alphafold2_tpu import compat
            from alphafold2_tpu.compat import pallas as pl, pallas_tpu as pltpu

            P = compat.CompilerParams(dimension_semantics=("parallel",))
            S = compat.out_struct((2, 2), "float32")
            """,
        )
        assert compat_run(tmp_path, files=[f]) == []


# ---------------------------------------------------------------------------
# trace-safety pass
# ---------------------------------------------------------------------------


class TestTracePass:
    def test_all_four_codes_fire(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                print("tracing")
                y = np.asarray(x)
                if x > 0:
                    return float(x)
                return helper(x)

            def helper(z):
                return z.tolist()
            """,
        )
        codes = _codes(trace_run(tmp_path, files=[f]))
        assert codes == ["TRACE001", "TRACE002", "TRACE003", "TRACE004"]

    def test_reachability_through_local_calls(self, tmp_path):
        """helper() is flagged ONLY because a jitted entry point reaches it."""
        f = _write(
            tmp_path,
            "m.py",
            """
            import jax

            def helper(z):
                return z.tolist()

            g = jax.jit(lambda x: helper(x))
            """,
        )
        findings = trace_run(tmp_path, files=[f])
        assert _codes(findings) == ["TRACE004"]

    def test_unreached_code_not_flagged(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            def host_side(z):
                print(z)
                return float(z)
            """,
        )
        assert trace_run(tmp_path, files=[f]) == []

    def test_static_metadata_and_guards_not_flagged(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x, m):
                if m is None:
                    m = jnp.ones(x.shape[:1], bool)
                if x.ndim != 2:
                    raise ValueError(x.shape)
                if len(x.shape) > 1 and x.shape[0] % 8 != 0:
                    raise ValueError("pad first")
                return jnp.where(m[:, None], x, 0.0)
            """,
        )
        assert trace_run(tmp_path, files=[f]) == []

    def test_suppression(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            import jax

            @jax.jit
            def f(x):
                print("deliberate")  # af2lint: disable=TRACE001
                return x
            """,
        )
        assert trace_run(tmp_path, files=[f]) == []


# ---------------------------------------------------------------------------
# sharding pass
# ---------------------------------------------------------------------------


class TestShardingPass:
    AXES = {"data", "model", "seq"}

    def test_unknown_axis(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            'from jax.sharding import PartitionSpec as P\nS = P(None, "dat")\n',
        )
        fs = sharding_run(tmp_path, files=[f], axes=self.AXES)
        assert _codes(fs) == ["SHARD002"]

    def test_duplicate_axis(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            'from jax.sharding import PartitionSpec as P\n'
            'S = P("data", None, "data")\n',
        )
        assert _codes(sharding_run(tmp_path, files=[f], axes=self.AXES)) == [
            "SHARD003"
        ]

    def test_rank_annotation_mismatch(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            'from jax.sharding import PartitionSpec as P\n'
            'S = P(None, "data", None)  # af2lint: rank=2\n'
            'OK = P(None, "data")  # af2lint: rank=4 — trailing dims replicate\n',
        )
        fs = sharding_run(tmp_path, files=[f], axes=self.AXES)
        assert _codes(fs) == ["SHARD001"] and fs[0].line == 2

    def test_shard_map_arity_mismatch(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            from alphafold2_tpu.compat import shard_map
            from jax.sharding import PartitionSpec as P

            spec = P("data")
            fn = shard_map(lambda q, k, v: q, mesh=None,
                           in_specs=(spec, spec), out_specs=spec)
            """,
        )
        fs = sharding_run(tmp_path, files=[f], axes=self.AXES)
        assert _codes(fs) == ["SHARD004"]

    def test_axes_registry_static_parse_fallback(self, tmp_path):
        """The fallback for an unimportable parallel package: KNOWN_AXES is
        read statically out of mesh.py (and agrees with the live registry
        on the real repo)."""
        from alphafold2_tpu.analysis.sharding_lint import _parse_axes_registry
        from alphafold2_tpu.parallel.mesh import KNOWN_AXES

        mesh_py = tmp_path / "mesh.py"
        mesh_py.write_text('KNOWN_AXES = frozenset({"data", "xaxis"})\n')
        assert _parse_axes_registry(mesh_py) == {"data", "xaxis"}
        assert _parse_axes_registry(tmp_path / "missing.py") is None
        real = os.path.join(
            REPO_ROOT, "alphafold2_tpu", "parallel", "mesh.py"
        )
        assert _parse_axes_registry(real) == set(KNOWN_AXES)

    def test_registry_unavailable_is_loud(self, tmp_path, monkeypatch):
        import alphafold2_tpu.analysis.sharding_lint as sl

        monkeypatch.setattr(sl, "_default_axes", lambda root: None)
        f = _write(
            tmp_path, "m.py",
            'from jax.sharding import PartitionSpec as P\nS = P("typo")\n',
        )
        fs = sl.run(tmp_path, files=[f], axes=None)
        assert "SHARD000" in _codes(fs)

    def test_clean_specs(self, tmp_path):
        f = _write(
            tmp_path,
            "m.py",
            """
            from jax.sharding import PartitionSpec as P

            A = P(None, "seq", None, None)  # af2lint: rank=4
            B = P(("data", "model"), None)
            """,
        )
        assert sharding_run(tmp_path, files=[f], axes=self.AXES) == []


# ---------------------------------------------------------------------------
# the repo itself + CLI
# ---------------------------------------------------------------------------


class TestMetricsPass:
    """Pass 7: metric-name drift vs the docs/OBSERVABILITY.md inventory."""

    def _repo(self, tmp_path, code, doc):
        pkg = tmp_path / "alphafold2_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text(code)
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "OBSERVABILITY.md").write_text(doc)
        return tmp_path

    DOC = (
        "prose mentioning `not_a_metric` outside the block\n"
        "<!-- af2lint:metrics:begin -->\n"
        "| metric | kind | labels | meaning |\n"
        "|---|---|---|---|\n"
        "| `good_total` | counter | `code` | fine |\n"
        "{extra}"
        "<!-- af2lint:metrics:end -->\n"
    )

    def test_clean_when_call_sites_match_inventory(self, tmp_path):
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(
            tmp_path,
            "def f(reg):\n    reg.counter('good_total', code='x').inc()\n",
            self.DOC.format(extra=""),
        )
        assert run(root) == []

    def test_undocumented_call_site_flagged(self, tmp_path):
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(
            tmp_path,
            "def f(reg):\n"
            "    reg.counter('good_total').inc()\n"
            "    reg.gauge('sneaky_depth').set(1)\n",
            self.DOC.format(extra=""),
        )
        findings = run(root)
        assert [f.code for f in findings] == ["METRICS001"]
        assert "sneaky_depth" in findings[0].message

    def test_stale_doc_entry_flagged_and_wildcard_vouches(self, tmp_path):
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(
            tmp_path,
            "def f(reg, prefix):\n"
            "    reg.counter('good_total').inc()\n"
            "    reg.gauge(f'{prefix}_last_seconds').set(1)\n",
            self.DOC.format(
                extra="| `ghost_total` | counter | | gone |\n"
                      "| `compile_last_seconds` | gauge | | dynamic |\n"
            ),
        )
        findings = run(root)
        # ghost_total: documented, never registered; compile_last_seconds
        # is vouched for by the f-string's *_last_seconds wildcard
        assert [f.code for f in findings] == ["METRICS002"]
        assert "ghost_total" in findings[0].message

    def test_generic_wildcard_does_not_vouch_without_prefix(self, tmp_path):
        """`f"{pre}_total"` becomes the wildcard `*_total`, which matches
        MOST counters — letting it vouch would make METRICS002 vacuous.
        A short wildcard must not cover an arbitrary stale doc row."""
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(
            tmp_path,
            "def f(reg, pre):\n"
            "    reg.counter('good_total').inc()\n"
            "    reg.counter(f'{pre}_total').inc()\n",
            self.DOC.format(
                extra="| `ghost_total` | counter | | deleted metric |\n"),
        )
        findings = run(root)
        assert [f.code for f in findings] == ["METRICS002"]
        assert "ghost_total" in findings[0].message

    def test_prefix_kwarg_anchors_generic_wildcard(self, tmp_path):
        """A literal `prefix="..."` kwarg (the CompileTracker idiom)
        anchors short wildcards: names it forms are vouched for."""
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(
            tmp_path,
            "def f(reg, pre):\n"
            "    reg.counter('good_total').inc()\n"
            "    reg.counter(f'{pre}_total').inc()\n"
            "def make(reg):\n"
            "    return Tracker(reg, prefix='my_compile')\n",
            self.DOC.format(
                extra="| `my_compile_total` | counter | | dynamic family |\n"),
        )
        assert run(root) == []

    def test_missing_markers_flagged(self, tmp_path):
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(tmp_path, "x = 1\n", "# no inventory here\n")
        findings = run(root)
        assert [f.code for f in findings] == ["METRICS003"]

    def test_suppression_comment_honored(self, tmp_path):
        from alphafold2_tpu.analysis.metrics_lint import run

        root = self._repo(
            tmp_path,
            "def f(reg):\n"
            "    reg.counter('good_total').inc()\n"
            "    reg.counter('internal_total').inc()"
            "  # af2lint: disable=METRICS001\n",
            self.DOC.format(extra=""),
        )
        assert run(root) == []

    def test_metrics_pass_clean_on_repo(self):
        """The real contract: every metric registered in this repo is in
        the OBSERVABILITY.md inventory and vice versa."""
        findings = run_passes(REPO_ROOT, select=("metrics",))
        assert findings == [], "\n".join(f.render() for f in findings)


class TestRepoIsClean:
    def test_static_passes_clean_on_repo(self):
        """The CI gate, pinned as a test: compat + trace + sharding must
        hold on this very repo (smoke is covered separately — it traces
        real programs and gets the slow marker)."""
        findings = run_passes(
            REPO_ROOT, select=("compat", "trace", "sharding")
        )
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_cli_strict_exit_codes(self, tmp_path, capsys):
        bad = _write(
            tmp_path,
            "bad.py",
            "from jax.experimental import pallas\n",
        )
        assert af2lint_main(["--strict", "--select", "compat", bad]) == 1
        # non-strict never gates
        assert af2lint_main(["--select", "compat", bad]) == 0
        ok = _write(tmp_path, "ok.py", "import jax\n")
        assert af2lint_main(["--strict", "--select", "compat", ok]) == 0
        capsys.readouterr()

    def test_file_scoped_run_skips_smoke(self, tmp_path, capsys):
        """`af2lint path/to/file.py` must not pay (or fail on) the
        repo-wide eval_shape sweep; selecting smoke explicitly still runs
        it."""
        from alphafold2_tpu.analysis import run_passes

        ok = _write(tmp_path, "ok.py", "import jax\n")
        called = []
        import alphafold2_tpu.analysis as an

        orig = an.PASSES["smoke"]
        an.PASSES["smoke"] = lambda *a, **k: called.append(1) or []
        try:
            run_passes(tmp_path, files=[ok])
            assert called == []
            run_passes(tmp_path, select=("smoke",), files=[ok])
            assert called == [1]
        finally:
            an.PASSES["smoke"] = orig

    @pytest.mark.slow
    def test_abstract_smoke_clean_on_repo(self):
        from alphafold2_tpu.analysis.abstract_smoke import run as smoke_run

        findings = smoke_run()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_abstract_smoke_single_target_fast(self):
        """One cheap eval_shape target inline in tier-1 so the smoke
        harness itself (registry construction + thunk execution) cannot
        rot unnoticed between slow-tier runs."""
        from alphafold2_tpu.analysis.abstract_smoke import _targets

        targets = _targets()
        assert "ops.feed_forward" in targets
        targets["ops.feed_forward"]()  # raises on breakage


# ---------------------------------------------------------------------------
# concurrency pass
# ---------------------------------------------------------------------------


class TestConcurrencyPass:
    """Every CONC rule fires on its broken twin and stays silent on the
    clean one; fixtures are injected via `files=` + `allowlist=[]` so
    the repo's own allowlist can never mask a fixture regression."""

    def _run(self, tmp_path, *paths, allowlist=()):
        return conc_run(tmp_path, files=list(paths),
                        allowlist=list(allowlist))

    # ---- CONC001: multi-entry-point writes without a common lock

    CONC1_BROKEN = """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def _loop(self):
                self._n += 1

            def bump(self):
                self._n += 1
        """

    def test_conc001_fires_on_unlocked_shared_write(self, tmp_path):
        bad = _write(tmp_path, "bad1.py", self.CONC1_BROKEN)
        findings = self._run(tmp_path, bad)
        assert _codes(findings) == ["CONC001"]
        assert "Counter._n" in findings[0].message
        # both the thread root and the external-caller root are named
        assert "thread:" in findings[0].message

    def test_conc001_silent_when_writes_share_a_lock(self, tmp_path):
        ok = _write(tmp_path, "ok1.py", """
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def start(self):
                    self._t = threading.Thread(target=self._loop)
                    self._t.start()

                def _loop(self):
                    with self._lock:
                        self._n += 1

                def bump(self):
                    with self._lock:
                        self._n += 1
            """)
        assert self._run(tmp_path, ok) == []

    def test_conc001_silent_for_single_root(self, tmp_path):
        """A private attr only the external caller ever writes (classic
        start/stop pair) is single-root — no lock demanded."""
        ok = _write(tmp_path, "ok1b.py", """
            import threading

            class Runner:
                def start(self):
                    self._t = threading.Thread(target=self._loop)
                    self._t.start()

                def stop(self):
                    self._t = None

                def _loop(self):
                    pass
            """)
        assert self._run(tmp_path, ok) == []

    # ---- CONC002: lock-order inversion

    CONC2_BROKEN = """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        pass

            def backward(self):
                with self._b:
                    self._inner()

            def _inner(self):
                with self._a:
                    pass
        """

    def test_conc002_fires_on_inversion_through_a_call(self, tmp_path):
        bad = _write(tmp_path, "bad2.py", self.CONC2_BROKEN)
        findings = self._run(tmp_path, bad)
        assert "CONC002" in _codes(findings)
        msg = next(f for f in findings if f.code == "CONC002").message
        assert "Pair._a" in msg and "Pair._b" in msg
        assert "via Pair._inner" in msg

    def test_conc002_silent_on_consistent_order(self, tmp_path):
        ok = _write(tmp_path, "ok2.py", """
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def forward(self):
                    with self._a:
                        with self._b:
                            pass

                def backward(self):
                    with self._a:
                        self._inner()

                def _inner(self):
                    with self._b:
                        pass
            """)
        assert self._run(tmp_path, ok) == []

    def test_conc002_lock_graph_export(self, tmp_path):
        bad = _write(tmp_path, "bad2.py", self.CONC2_BROKEN)
        edges = lock_graph(tmp_path, files=[bad])
        assert "Pair._b" in edges["Pair._a"]
        assert "Pair._a" in edges["Pair._b"]

    # ---- CONC003: blocking while holding a lock

    CONC3_BROKEN = """
        import queue
        import threading

        class Drainer:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                pass

            def stop(self):
                with self._lock:
                    self._t.join()

            def drain(self):
                with self._lock:
                    return self._q.get()
        """

    def test_conc003_fires_on_join_and_unbounded_get_under_lock(
            self, tmp_path):
        bad = _write(tmp_path, "bad3.py", self.CONC3_BROKEN)
        findings = self._run(tmp_path, bad)
        assert _codes(findings) == ["CONC003"]
        msgs = " | ".join(f.message for f in findings)
        assert "join" in msgs and "get" in msgs

    def test_conc003_silent_outside_lock_or_with_timeout(self, tmp_path):
        ok = _write(tmp_path, "ok3.py", """
            import queue
            import threading

            class Drainer:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    pass

                def stop(self):
                    with self._lock:
                        t = self._t
                    t.join()

                def drain(self):
                    with self._lock:
                        return self._q.get(timeout=1.0)
            """)
        assert self._run(tmp_path, ok) == []

    # ---- CONC004: daemon thread reaching jax

    CONC4_BROKEN = """
        import threading

        import jax

        class Background:
            def start(self):
                self._t = threading.Thread(
                    target=self._loop, daemon=True, name="bg")
                self._t.start()

            def _loop(self):
                jax.device_count()
        """

    def test_conc004_fires_on_daemon_thread_reaching_jax(self, tmp_path):
        bad = _write(tmp_path, "bad4.py", self.CONC4_BROKEN)
        findings = self._run(tmp_path, bad)
        assert _codes(findings) == ["CONC004"]
        assert "Background._loop" in findings[0].message

    def test_conc004_silent_when_nondaemon_or_no_jax(self, tmp_path):
        ok = _write(tmp_path, "ok4.py", """
            import threading

            import jax

            class Background:
                def start(self):
                    # non-daemon may reach jax; daemon may not reach jax
                    self._t = threading.Thread(target=self._loop)
                    self._u = threading.Thread(target=self._idle,
                                               daemon=True)
                    self._t.start()
                    self._u.start()

                def _loop(self):
                    jax.device_count()

                def _idle(self):
                    pass
            """)
        assert self._run(tmp_path, ok) == []

    # ---- suppression comment

    def test_inline_disable_comment(self, tmp_path):
        ok = _write(tmp_path, "sup4.py", """
            import threading

            import jax

            class Background:
                def start(self):
                    self._t = threading.Thread(target=self._loop, daemon=True)  # af2lint: disable=CONC004
                    self._t.start()

                def _loop(self):
                    jax.device_count()
            """)
        assert self._run(tmp_path, ok) == []

    # ---- allowlist round-trip

    def test_allowlist_suppresses_with_justification(self, tmp_path):
        bad = _write(tmp_path, "bad4.py", self.CONC4_BROKEN)
        entry = {"rule": "CONC004", "path": "bad4.py",
                 "match": "Background._loop",
                 "why": "fixture: abandonment contract documented"}
        assert self._run(tmp_path, bad, allowlist=[entry]) == []

    def test_allowlist_empty_why_is_a_finding_not_a_suppression(
            self, tmp_path):
        bad = _write(tmp_path, "bad4.py", self.CONC4_BROKEN)
        entry = {"rule": "CONC004", "path": "bad4.py",
                 "match": "Background._loop", "why": "   "}
        findings = self._run(tmp_path, bad, allowlist=[entry])
        assert _codes(findings) == ["CONC000", "CONC004"]

    def test_allowlist_stale_entry_flagged(self, tmp_path):
        ok = _write(tmp_path, "ok.py", "import threading\n")
        entry = {"rule": "CONC004", "path": "gone.py",
                 "match": "nothing", "why": "was justified once"}
        findings = self._run(tmp_path, ok, allowlist=[entry])
        assert _codes(findings) == ["CONC000"]
        assert "stale" in findings[0].message

    # ---- the repo itself

    def test_concurrency_pass_clean_on_repo(self):
        """The tree (plus its checked-in allowlist: every entry both
        justified and still matching) carries zero concurrency findings."""
        findings = run_passes(REPO_ROOT, select=("concurrency",))
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_repo_static_lock_graph_is_acyclic(self):
        """Pin the static acquisition graph's shape: acyclic, and the
        known engine->metrics / fleet->health edges present."""
        edges = lock_graph(REPO_ROOT)
        # acyclicity via Kahn's algorithm
        nodes = set(edges) | {b for d in edges.values() for b in d}
        indeg = {n: 0 for n in nodes}
        for a, outs in edges.items():
            for b in outs:
                indeg[b] += 1
        frontier = [n for n in nodes if indeg[n] == 0]
        seen = 0
        while frontier:
            n = frontier.pop()
            seen += 1
            for b in edges.get(n, ()):
                indeg[b] -= 1
                if indeg[b] == 0:
                    frontier.append(b)
        assert seen == len(nodes), f"static lock graph has a cycle: {edges}"
        assert "ServingMetrics._counts_lock" in edges.get(
            "ServingEngine._inflight_lock", {})
        assert "HealthMonitor._lock" in edges.get("ServingFleet._lock", {})


# ---------------------------------------------------------------------------
# pass registry & CLI surface
# ---------------------------------------------------------------------------


class TestPassListing:
    def test_nine_passes_registered_in_order(self):
        assert list(PASSES) == [
            "compat", "trace", "sharding", "smoke", "overlap",
            "schedule", "metrics", "dispatch", "concurrency",
        ]

    def test_every_pass_has_a_summary(self):
        assert set(PASS_SUMMARIES) == set(PASSES)
        for name, summary in PASS_SUMMARIES.items():
            assert summary.strip(), f"pass {name!r} has an empty summary"

    def test_cli_list_passes(self, capsys):
        assert af2lint_main(["--list-passes"]) == 0
        out = capsys.readouterr().out
        for name in PASSES:
            assert name in out
        assert "9 passes" in out

    def test_cli_json_groups_findings_per_pass(self, tmp_path, capsys):
        bad = _write(tmp_path, "bad.py", """
            from jax.experimental import pallas as pl
            """)
        rc = af2lint_main(["--select", "compat,concurrency", "--json",
                           "--strict", bad])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["passes"] == ["compat", "concurrency"]
        assert doc["strict"] is True
        assert doc["total"] == len(doc["findings"]["compat"])
        assert doc["findings"]["concurrency"] == []
        rec = doc["findings"]["compat"][0]
        assert set(rec) == {"rule", "path", "line", "message"}

    def test_cli_json_clean_exit_zero(self, tmp_path, capsys):
        ok = _write(tmp_path, "ok.py", "import jax\n")
        rc = af2lint_main(["--select", "concurrency", "--json",
                           "--strict", str(ok)])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["total"] == 0


# ---------------------------------------------------------------------------
# lock_runtime: the instrumented-lock harness
# ---------------------------------------------------------------------------


class _TwoLocks:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()


class TestLockMonitor:
    def test_consistent_order_is_acyclic(self):
        mon = LockMonitor()
        obj = _TwoLocks()
        wrapped = mon.instrument(obj)
        assert wrapped == ["_TwoLocks._a", "_TwoLocks._b"]
        for _ in range(3):
            with obj._a:
                with obj._b:
                    pass
        mon.assert_acyclic()
        assert mon.edges() == {("_TwoLocks._a", "_TwoLocks._b"): 3}

    def test_inverted_order_is_a_cycle(self):
        mon = LockMonitor()
        obj = _TwoLocks()
        mon.instrument(obj)
        with obj._a:
            with obj._b:
                pass
        with obj._b:
            with obj._a:
                pass
        assert mon.cycles() != []
        with pytest.raises(AssertionError, match="lock-order graph"):
            mon.assert_acyclic()

    def test_mutual_exclusion_preserved_through_proxy(self):
        """The proxy delegates to the SAME raw lock, so a thread that
        captured the lock before instrumentation still excludes one
        that acquires through the proxy."""
        raw = threading.Lock()
        mon = LockMonitor()
        proxy = mon.wrap(raw, "x")
        raw.acquire()
        assert not proxy.acquire(blocking=False)
        raw.release()
        assert proxy.acquire(blocking=False)
        proxy.release()

    def test_long_hold_recorded(self):
        mon = LockMonitor(long_hold_s=0.0)
        obj = _TwoLocks()
        mon.instrument(obj)
        with obj._a:
            pass
        snap = mon.snapshot()
        assert snap["acquires"] == {"_TwoLocks._a": 1}
        assert snap["long_holds"] and \
            snap["long_holds"][0]["lock"] == "_TwoLocks._a"

    def test_cross_thread_edges_merge(self):
        """Edges observed on different threads land in one graph —
        that is the whole point (thread A: a->b, thread B: b->a)."""
        mon = LockMonitor()
        obj = _TwoLocks()
        mon.instrument(obj)

        def locked_pair(first, second):
            with first:
                with second:
                    pass

        t = threading.Thread(target=locked_pair, args=(obj._a, obj._b))
        t.start()
        t.join()
        locked_pair(obj._b, obj._a)
        assert set(mon.edges()) == {
            ("_TwoLocks._a", "_TwoLocks._b"),
            ("_TwoLocks._b", "_TwoLocks._a"),
        }
        assert mon.cycles() != []
