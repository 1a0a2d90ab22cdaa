"""dctlint v2 whole-program analysis: ProjectIndex units, the new
project-scope checkers against seeded fixture trees, the incremental
cache, `--changed` scoping, and the cold-run perf budget.

The fixture trees mirror the acceptance criteria of the whole-program
pass: a two-lock ordering cycle, a blocking call under a lock, a
fault-point/doc-catalog mismatch (both directions), a conflicting
metric family, a schema key that never round-trips, and a jitted
closure over ``self`` — each must produce exactly the expected
diagnostic, and the clean variants must stay clean.
"""
import sys
import textwrap
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.dctlint import core as lint_core  # noqa: E402
from tools.dctlint.core import _analyze_source  # noqa: E402
from tools.dctlint.project import (  # noqa: E402
    ProjectIndex, module_name_for)

TIER1_LINT_PATHS = ["determined_clone_tpu", "tools"]
PERF_BUDGET_S = 10.0


def _write_tree(tmp_path, files):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))


def _run_tree(tmp_path, files, select=None, **kw):
    _write_tree(tmp_path, files)
    return lint_core.run([str(tmp_path)], select=select,
                         relative_to=tmp_path, **kw)


def _index(files):
    """ProjectIndex straight from sources (repo-relative paths)."""
    facts = {}
    for rel, src in files.items():
        mod, ispkg = module_name_for(rel)
        res = _analyze_source(rel, textwrap.dedent(src), mod, ispkg)
        facts[rel] = res["facts"]
    return ProjectIndex(facts)


# ---------------------------------------------------------------------------
# ProjectIndex units: alias + relative-import resolution, propagation
# ---------------------------------------------------------------------------

def test_relative_import_resolves_to_defining_module():
    idx = _index({
        "pkg/__init__.py": "",
        "pkg/a.py": """
            import threading

            _glock = threading.Lock()

            def helper():
                with _glock:
                    pass
            """,
        "pkg/b.py": """
            from .a import helper

            def caller():
                helper()
            """,
    })
    ni = idx.files["pkg/b.py"]["name_imports"]
    assert ni["helper"] == "pkg.a.helper"
    targets = idx.resolve_call("pkg.b.caller",
                               idx.functions["pkg.b.caller"]
                               ["facts"]["calls"][0][0])
    assert ("pkg.a.helper", True) in targets
    acq = idx.eventual_acquires("pkg.b.caller")
    assert "pkg.a._glock" in acq
    assert acq["pkg.a._glock"]["certain"]


def test_condition_alias_collapses_onto_wrapped_lock():
    idx = _index({
        "mod.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)
            """,
    })
    resolved = idx.resolve_lockref("mod", ["c", "C", "_cond"])
    assert resolved == ("mod.C._lock", "lock")


def test_typed_self_attribute_call_is_certain():
    idx = _index({
        "mod.py": """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()

                def take(self):
                    with self._lock:
                        pass

            class Owner:
                def __init__(self):
                    self.pool = Pool()

                def use(self):
                    self.pool.take()
            """,
    })
    desc = idx.functions["mod.Owner.use"]["facts"]["calls"][0][0]
    assert idx.resolve_call("mod.Owner.use", desc) == \
        [("mod.Pool.take", True)]


def test_mutable_attrs_excludes_init_only_state():
    idx = _index({
        "mod.py": """
            class C:
                def __init__(self):
                    self.frozen = 1

                def poke(self):
                    self.counter = 2
            """,
    })
    assert idx.mutable_attrs("mod.C") == {"counter"}


# ---------------------------------------------------------------------------
# CONC003 — lock-order cycles and the documented hierarchy
# ---------------------------------------------------------------------------

def test_conc003_two_lock_cycle_fixture(tmp_path):
    diags = _run_tree(tmp_path, {
        "locks.py": """
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
            """,
    }, select=["CONC003"])
    assert [d.rule for d in diags] == ["CONC003"]
    assert "lock-order cycle" in diags[0].message
    assert "locks.Pair._a" in diags[0].message
    assert "locks.Pair._b" in diags[0].message
    assert "hierarchy" in diags[0].hint


def test_conc003_consistent_order_is_clean(tmp_path):
    diags = _run_tree(tmp_path, {
        "locks.py": """
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ab_again(self):
                    with self._a:
                        with self._b:
                            pass
            """,
    }, select=["CONC003"])
    assert diags == []


def test_conc003_cycle_through_call_graph(tmp_path):
    diags = _run_tree(tmp_path, {
        "graph.py": """
            import threading

            class A:
                def __init__(self, b):
                    self._lock = threading.Lock()
                    self.b = b

                def work(self):
                    with self._lock:
                        self.b.poke()

            class B:
                def __init__(self, a):
                    self._lock = threading.Lock()
                    self.a = a

                def poke(self):
                    with self._lock:
                        pass

                def back(self):
                    with self._lock:
                        self.a.ping()

            class AHelper:
                pass
            """,
        "graph2.py": """
            import threading
            from graph import A

            class Other:
                def __init__(self):
                    self.a = A(None)

                def go(self):
                    self.a.work()
            """,
    }, select=["CONC003"])
    # A._lock -> B._lock via work(); no back edge resolves certainly
    # (A.ping doesn't exist), so the graph stays acyclic
    assert diags == []


def test_conc003_report_names_documented_hierarchy(tmp_path):
    stats = {}
    _run_tree(tmp_path, {
        "mod.py": """
            import threading

            _l = threading.Lock()

            def f():
                with _l:
                    pass
            """,
    }, select=["CONC003"], stats=stats)
    summary = stats["summaries"]["CONC003"]
    assert "hierarchy verified: " \
        "control < serving < resource < recorder < sink < leaf" in summary


def test_conc003_plain_lock_self_reacquire(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """,
    }, select=["CONC003"])
    assert [d.rule for d in diags] == ["CONC003"]
    assert "re-acquired" in diags[0].message
    assert "RLock" in diags[0].hint


def test_conc003_rlock_reentrancy_is_fine(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """,
    }, select=["CONC003"])
    assert diags == []


# ---------------------------------------------------------------------------
# CONC004 — blocking call while a lock is held
# ---------------------------------------------------------------------------

def test_conc004_sleep_under_lock_fixture(tmp_path):
    diags = _run_tree(tmp_path, {
        "box.py": """
            import threading
            import time

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()

                def slow(self):
                    with self._lock:
                        time.sleep(0.5)
            """,
    }, select=["CONC004"])
    assert [d.rule for d in diags] == ["CONC004"]
    assert "time.sleep" in diags[0].message
    assert "box.Box._lock" in diags[0].message
    assert "outside the critical section" in diags[0].hint


def test_conc004_propagates_through_certain_calls(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import threading
            import time

            def nap():
                time.sleep(1)

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        nap()
            """,
    }, select=["CONC004"])
    assert [d.rule for d in diags] == ["CONC004"]
    assert "may block" in diags[0].message
    assert "mod.nap" in diags[0].message


def test_conc004_sleep_outside_lock_is_clean(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        n = 1
                    time.sleep(n)
            """,
    }, select=["CONC004"])
    assert diags == []


def test_conc004_condition_wait_own_lock_exempt(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)

                def wait(self):
                    with self._cond:
                        self._cond.wait()
            """,
    }, select=["CONC004"])
    assert diags == []


# ---------------------------------------------------------------------------
# CONTRACT001 — fault-point catalog sync (both directions)
# ---------------------------------------------------------------------------

_FAULTS_STUB = """
    def point(name):
        pass
    """

_FAULT_DOC = """
    # Fault tolerance

    ### Fault points

    | point | where |
    |---|---|
    | `db.write` | the documented one |
    | `db.ghost` | this point no longer exists |
    """


def test_contract001_missing_and_stale_rows(tmp_path):
    diags = _run_tree(tmp_path, {
        "faults.py": _FAULTS_STUB,
        "docs/fault_tolerance.md": _FAULT_DOC,
        "app.py": """
            import faults

            def save():
                faults.point("db.write")
                faults.point("db.commit")
            """,
    }, select=["CONTRACT001"])
    assert len(diags) == 2
    missing = [d for d in diags if d.path == "app.py"]
    stale = [d for d in diags if d.path == "docs/fault_tolerance.md"]
    assert len(missing) == 1 and len(stale) == 1
    assert 'fault point "db.commit" has no row' in missing[0].message
    assert "add the missing row" in missing[0].hint
    assert 'row "db.ghost"' in stale[0].message
    assert "no longer exists" in stale[0].message


def test_contract001_synced_catalog_is_clean(tmp_path):
    diags = _run_tree(tmp_path, {
        "faults.py": _FAULTS_STUB,
        "docs/fault_tolerance.md": """
            ### Fault points

            | point | where |
            |---|---|
            | `db.write` / `db.commit` | both live here |
            """,
        "app.py": """
            import faults

            def save():
                faults.point("db.write")
                faults.point("db.commit")
            """,
    }, select=["CONTRACT001"])
    assert diags == []


def test_contract001_stale_rows_skipped_on_partial_view(tmp_path):
    # linting a subtree that doesn't include the faults runtime must
    # not declare every documented point stale
    diags = _run_tree(tmp_path, {
        "docs/fault_tolerance.md": _FAULT_DOC,
        "app.py": "x = 1\n",
    }, select=["CONTRACT001"])
    assert diags == []


# ---------------------------------------------------------------------------
# CONTRACT002 — metric family registry
# ---------------------------------------------------------------------------

def test_contract002_conflicting_types_fixture(tmp_path):
    diags = _run_tree(tmp_path, {
        "m1.py": """
            def setup(registry):
                registry.counter("jobs_total")
            """,
        "m2.py": """
            def setup(registry):
                registry.gauge("jobs_total")
            """,
    }, select=["CONTRACT002"])
    assert [d.rule for d in diags] == ["CONTRACT002"]
    assert 'family "jobs_total"' in diags[0].message
    assert "one name, one type" in diags[0].message
    assert "gauge" in diags[0].message and "counter" in diags[0].message


def test_contract002_undocumented_family(tmp_path):
    diags = _run_tree(tmp_path, {
        "docs/observability.md": "Catalog: `jobs_total` is here.\n",
        "m.py": """
            def setup(registry):
                registry.counter("jobs_total")
                registry.counter("ghosts_total")
            """,
    }, select=["CONTRACT002"])
    assert [d.rule for d in diags] == ["CONTRACT002"]
    assert 'family "ghosts_total" is not documented' in diags[0].message


def test_contract002_documented_consistent_registry_is_clean(tmp_path):
    diags = _run_tree(tmp_path, {
        "docs/observability.md": "`jobs_total` and `depth` exist.\n",
        "m.py": """
            def setup(registry):
                registry.counter("jobs_total")
                registry.gauge("depth")
            """,
        "m2.py": """
            def again(registry):
                registry.counter("jobs_total")
            """,
    }, select=["CONTRACT002"])
    assert diags == []


# ---------------------------------------------------------------------------
# CONTRACT003 — schema keys round-trip to ExperimentConfig
# ---------------------------------------------------------------------------

def test_contract003_unconsumed_key_and_fieldless_schema(tmp_path):
    diags = _run_tree(tmp_path, {
        "config/__init__.py": "",
        "config/schema.py": """
            EXPERIMENT_SCHEMA = {
                "type": "object",
                "properties": {
                    "name": {"type": "string"},
                    "mystery": {"type": "integer"},
                },
            }
            """,
        "config/experiment.py": """
            import dataclasses

            @dataclasses.dataclass
            class ExperimentConfig:
                name: str = ""
                extra_field: int = 0
            """,
    }, select=["CONTRACT003"])
    assert len(diags) == 2
    by_path = {d.path: d for d in diags}
    schema_diag = by_path["config/schema.py"]
    cfg_diag = by_path["config/experiment.py"]
    assert 'schema key "mystery"' in schema_diag.message
    assert "never consumed" in schema_diag.message
    assert "PASSTHROUGH_KEYS" in schema_diag.hint
    assert 'field "extra_field" has no EXPERIMENT_SCHEMA key' \
        in cfg_diag.message


def test_contract003_raw_get_counts_as_consumption(tmp_path):
    diags = _run_tree(tmp_path, {
        "config/__init__.py": "",
        "config/schema.py": """
            EXPERIMENT_SCHEMA = {
                "type": "object",
                "properties": {
                    "name": {"type": "string"},
                    "profiling": {"type": "object"},
                },
            }
            """,
        "config/experiment.py": """
            import dataclasses

            @dataclasses.dataclass
            class ExperimentConfig:
                name: str = ""
                profiling_on: bool = False

                @classmethod
                def from_dict(cls, raw):
                    prof = raw.get("profiling", {})
                    return cls(name=raw.get("name", ""),
                               profiling_on=bool(prof))
            """,
    }, select=["CONTRACT003"])
    # "profiling" has no field but IS consumed; "profiling_on" has no
    # schema key -> exactly one reverse-direction diag
    assert len(diags) == 1
    assert 'field "profiling_on"' in diags[0].message


def test_contract003_skips_partial_view_without_config_class(tmp_path):
    diags = _run_tree(tmp_path, {
        "config/__init__.py": "",
        "config/schema.py": """
            EXPERIMENT_SCHEMA = {
                "type": "object",
                "properties": {"orphan": {"type": "string"}},
            }
            """,
    }, select=["CONTRACT003"])
    assert diags == []


# ---------------------------------------------------------------------------
# JAX004 — jit-boundary purity
# ---------------------------------------------------------------------------

def test_jax004_bound_method_closure_over_self(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import jax

            class Runner:
                def __init__(self):
                    self.scale = 1.0

                def _step(self, x):
                    return x * self.scale

                def compile(self):
                    return jax.jit(self._step)
            """,
    }, select=["JAX004"])
    assert [d.rule for d in diags] == ["JAX004"]
    assert "bound method self._step" in diags[0].message
    assert "captures self" in diags[0].message
    assert "free function" in diags[0].hint


def test_jax004_side_effect_through_call_graph(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import time
            import jax

            def helper(x):
                time.sleep(1)
                return x

            def step(x):
                return helper(x)

            step_fn = jax.jit(step)
            """,
    }, select=["JAX004"])
    assert [d.rule for d in diags] == ["JAX004"]
    assert "time.sleep" in diags[0].message
    assert "mod.helper" in diags[0].message
    assert "jax.jit at mod.py" in diags[0].message


def test_jax004_global_store_in_traced_region(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import jax

            _steps = 0

            def step(x):
                global _steps
                _steps = _steps + 1
                return x

            step_fn = jax.jit(step)
            """,
    }, select=["JAX004"])
    assert [d.rule for d in diags] == ["JAX004"]
    assert "writes module global _steps" in diags[0].message


def test_jax004_pure_pipeline_is_clean(tmp_path):
    diags = _run_tree(tmp_path, {
        "mod.py": """
            import jax
            import jax.numpy as jnp

            def helper(x):
                return jnp.tanh(x)

            def step(x):
                return helper(x) * 2

            step_fn = jax.jit(step)
            """,
    }, select=["JAX004"])
    assert diags == []


# ---------------------------------------------------------------------------
# incremental cache + --changed scoping + perf budget
# ---------------------------------------------------------------------------

_CACHE_TREE = {
    "a.py": "def f():\n    return 1\n",
    "b.py": "def g():\n    return 2\n",
    "c.py": "def h():\n    return 3\n",
}


def test_cache_hits_and_invalidation(tmp_path):
    _write_tree(tmp_path, _CACHE_TREE)
    cache = tmp_path / "cache.json"
    s1, s2, s3 = {}, {}, {}
    lint_core.run([str(tmp_path)], relative_to=tmp_path,
                  cache_path=cache, stats=s1)
    assert s1["analyzed"] == 3 and s1["cache_hits"] == 0
    lint_core.run([str(tmp_path)], relative_to=tmp_path,
                  cache_path=cache, stats=s2)
    assert s2["analyzed"] == 0 and s2["cache_hits"] == 3
    (tmp_path / "b.py").write_text("def g():\n    return 20\n")
    diags = lint_core.run([str(tmp_path)], relative_to=tmp_path,
                          cache_path=cache, stats=s3)
    assert s3["analyzed"] == 1 and s3["cache_hits"] == 2
    assert diags == []


def test_cached_run_still_reports_cross_file_violations(tmp_path):
    """Cache reuse must not lose project-scope findings: the facts are
    cached, the project pass always re-runs over the full index."""
    files = {
        "m1.py": "def a(registry):\n    registry.counter('dup')\n",
        "m2.py": "def b(registry):\n    registry.gauge('dup')\n",
    }
    _write_tree(tmp_path, files)
    cache = tmp_path / "cache.json"
    first = lint_core.run([str(tmp_path)], select=["CONTRACT002"],
                          relative_to=tmp_path, cache_path=cache)
    stats = {}
    second = lint_core.run([str(tmp_path)], select=["CONTRACT002"],
                           relative_to=tmp_path, cache_path=cache,
                           stats=stats)
    assert stats["cache_hits"] == 2
    assert [d.message for d in second] == [d.message for d in first]
    assert len(second) == 1


def test_changed_only_filters_reporting_not_analysis(tmp_path):
    """--changed scopes the report to touched files while the project
    pass still sees everything — a cross-file conflict whose *other*
    half moved is still attributed to its defining site."""
    files = {
        "m1.py": "def a(registry):\n    registry.counter('dup')\n",
        "m2.py": "def b(registry):\n    registry.gauge('dup')\n",
    }
    _write_tree(tmp_path, files)
    only_m2 = lint_core.run([str(tmp_path)], select=["CONTRACT002"],
                            relative_to=tmp_path,
                            changed_only={"m2.py"})
    assert [d.path for d in only_m2] == ["m2.py"]
    only_m1 = lint_core.run([str(tmp_path)], select=["CONTRACT002"],
                            relative_to=tmp_path,
                            changed_only={"m1.py"})
    assert only_m1 == []  # the diag anchors on m2.py, out of scope


def test_perf_budget_cold_full_tree():
    """A cold serial run over the whole tree (per-file pass + facts +
    every project checker) stays under the documented budget: every file
    analysed, none from a cache, in this thread, within ``PERF_BUDGET_S``
    of this thread's own CPU time (``docs/static_analysis.md``: "on one
    CPU core"). Not of the wall clock: tier-1 runs beside five other
    workers, and their load is not dctlint's cost."""
    stats = {}
    cpu0 = time.thread_time()
    lint_core.run([str(REPO / p) for p in TIER1_LINT_PATHS],
                  relative_to=REPO, jobs=1, stats=stats)
    cpu_s = time.thread_time() - cpu0
    assert stats["files"] >= 100
    assert stats["jobs"] == 1 and stats["cache_hits"] == 0
    assert stats["analyzed"] == stats["files"]
    assert cpu_s < PERF_BUDGET_S, (
        f"cold dctlint run took {cpu_s:.2f}s of CPU over "
        f"{stats['files']} files (budget {PERF_BUDGET_S}s; "
        f"{stats['wall_s']:.2f}s of wall clock) — profile "
        f"the per-file pass before raising the budget")


def test_stats_summaries_cover_all_project_checkers():
    stats = {}
    lint_core.run([str(REPO / p) for p in TIER1_LINT_PATHS],
                  relative_to=REPO, stats=stats)
    assert set(stats["project_checkers"]) == {
        "CONC003", "CONC004", "CONTRACT001", "CONTRACT002",
        "CONTRACT003", "JAX004"}
    for rule in stats["project_checkers"]:
        assert rule in stats["summaries"], rule
