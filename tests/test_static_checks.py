"""Tier-1 static checks: the dctlint suite (docs/static_analysis.md).

Three layers:

1. **The gate** — ``python -m tools.dctlint determined_clone_tpu tools``
   must exit 0, so a new JAX/concurrency/clock violation anywhere in
   the library or the tools fails CI.
2. **Checker fixtures** — every rule (JAX001-003, CONC001-002, TIME001,
   EXC001, RETRY001) has paired true-positive / true-negative snippets, so a checker
   that goes blind (or trigger-happy) fails here before it lies in CI.
3. **Framework mechanics** — suppression comments require reasons,
   baselines filter exactly what they name, the legacy
   ``check_swallowed_exceptions`` shim keeps its contract.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))
sys.path.insert(0, str(REPO))

import check_swallowed_exceptions as csx  # noqa: E402
from tools.dctlint import CHECKERS, core as lint_core  # noqa: E402

TIER1_LINT_PATHS = ["determined_clone_tpu", "tools"]
BASELINE = REPO / "tools" / "dctlint" / "baseline.json"


def _lint(snippet, tmp_path, select=None, name="snippet.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(snippet))
    return lint_core.lint_file(f, select=select)


def _rules(diags):
    return [d.rule for d in diags]


# ---------------------------------------------------------------------------
# the tier-1 gate
# ---------------------------------------------------------------------------

def test_tier1_tree_is_clean():
    """The committed tree passes the full suite (fix, baseline with a
    justification, or suppress inline with a reason — never ignore)."""
    diags = lint_core.run([str(REPO / p) for p in TIER1_LINT_PATHS],
                          baseline=BASELINE, relative_to=REPO)
    assert diags == [], "\n" + "\n".join(d.format() for d in diags)


def test_module_entrypoint_exit_codes(tmp_path):
    clean = subprocess.run(
        [sys.executable, "-m", "tools.dctlint", *TIER1_LINT_PATHS],
        cwd=REPO, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    bad = tmp_path / "bad.py"
    bad.write_text("import time\ndeadline = time.time() + 5\n")
    dirty = subprocess.run(
        [sys.executable, "-m", "tools.dctlint", str(bad)],
        cwd=REPO, capture_output=True, text=True)
    assert dirty.returncode == 1
    assert "TIME001" in dirty.stdout


def test_cli_lint_subcommand():
    from determined_clone_tpu.cli.cli import main as cli_main

    assert cli_main(["lint", "--list-checkers"]) == 0
    assert cli_main(["lint", str(REPO / "tools" / "dctlint")]) == 0


def test_all_rules_registered():
    assert {"JAX001", "JAX002", "JAX003", "JAX004", "CONC001",
            "CONC002", "CONC003", "CONC004", "CONTRACT001",
            "CONTRACT002", "CONTRACT003", "TIME001", "EXC001",
            "RETRY001"} <= set(CHECKERS)


def test_project_rules_marked_project_scope():
    for rule in ("CONC003", "CONC004", "CONTRACT001", "CONTRACT002",
                 "CONTRACT003", "JAX004"):
        assert CHECKERS[rule].project, rule
    for rule in ("JAX001", "CONC001", "TIME001"):
        assert not CHECKERS[rule].project, rule


# ---------------------------------------------------------------------------
# JAX001 — host sync / side effect inside traced code
# ---------------------------------------------------------------------------

def test_jax001_print_in_jit_decorated(tmp_path):
    v = _lint(
        """
        import jax

        @jax.jit
        def f(x):
            print(x)
            return x
        """, tmp_path, select=["JAX001"])
    assert _rules(v) == ["JAX001"]
    assert "print" in v[0].message


def test_jax001_numpy_in_scan_body(tmp_path):
    v = _lint(
        """
        import jax
        import numpy as np

        def body(carry, x):
            return carry, np.sum(x)

        def outer(xs):
            return jax.lax.scan(body, 0, xs)
        """, tmp_path, select=["JAX001"])
    assert _rules(v) == ["JAX001"]
    assert "numpy.sum" in v[0].message


def test_jax001_item_and_float_in_jit_call(tmp_path):
    v = _lint(
        """
        import jax

        def step(state, batch):
            loss = state - batch
            a = loss.item()
            b = float(loss)
            return state

        step = jax.jit(step)
        """, tmp_path, select=["JAX001"])
    assert len(v) == 2
    assert ".item()" in v[0].message and "float()" in v[1].message


def test_jax001_clean_outside_trace_and_debug_print(tmp_path):
    v = _lint(
        """
        import jax
        import numpy as np

        def host_side(x):
            print(np.sum(x))           # not traced: fine
            return float(x)

        @jax.jit
        def f(x):
            jax.debug.print("x={x}", x=x)   # the sanctioned print
            y = float(1.0)                  # constant: folds harmlessly
            return x * y
        """, tmp_path, select=["JAX001"])
    assert v == []


# ---------------------------------------------------------------------------
# JAX002 — constant PRNGKey in per-step code / key reuse without split
# ---------------------------------------------------------------------------

def test_jax002_constant_key_in_loss(tmp_path):
    v = _lint(
        """
        import jax

        def loss_fn(params, batch):
            rng = jax.random.PRNGKey(0)
            return model(params, batch, rng)
        """, tmp_path, select=["JAX002"])
    assert _rules(v) == ["JAX002"]
    assert "constant" in v[0].message


def test_jax002_seeded_key_in_setup_is_fine(tmp_path):
    v = _lint(
        """
        import jax

        def main(seed):
            rng = jax.random.PRNGKey(seed)   # non-constant: seeded chain
            return rng

        def build_bench():
            k = jax.random.PRNGKey(0)        # setup code, not per-step
            return k
        """, tmp_path, select=["JAX002"])
    assert v == []


def test_jax002_key_reused_without_split(tmp_path):
    v = _lint(
        """
        import jax

        def train(params, batch, seed):
            key = jax.random.PRNGKey(seed)
            a = dropout_a(params, key)
            b = dropout_b(params, key)
            return a + b
        """, tmp_path, select=["JAX002"])
    assert _rules(v) == ["JAX002"]
    assert "without an intervening jax.random.split" in v[0].message


def test_jax002_split_keys_are_fine(tmp_path):
    v = _lint(
        """
        import jax

        def train(params, batch, seed):
            key = jax.random.PRNGKey(seed)
            k1, k2 = jax.random.split(key)
            a = dropout_a(params, k1)
            b = dropout_b(params, k2)
            return a + b
        """, tmp_path, select=["JAX002"])
    assert v == []


# ---------------------------------------------------------------------------
# JAX003 — jitted train step without donate_argnums
# ---------------------------------------------------------------------------

def test_jax003_jit_call_missing_donation(tmp_path):
    v = _lint(
        """
        import jax

        def train_step(state, batch):
            return state

        train_step = jax.jit(train_step)
        """, tmp_path, select=["JAX003"])
    assert _rules(v) == ["JAX003"]
    assert "donate_argnums" in v[0].message


def test_jax003_decorator_missing_donation(tmp_path):
    v = _lint(
        """
        import jax

        @jax.jit
        def train_step(state, batch):
            return state
        """, tmp_path, select=["JAX003"])
    assert _rules(v) == ["JAX003"]


def test_jax003_donated_and_eval_steps_are_fine(tmp_path):
    v = _lint(
        """
        import jax

        def train_step(state, batch):
            return state

        train_step = jax.jit(train_step, donate_argnums=(0,))

        def make_eval_step(fn):
            def step_fn(state, batch):   # eval-shaped: nothing to donate
                return fn(state, batch)
            return jax.jit(step_fn)
        """, tmp_path, select=["JAX003"])
    assert v == []


def test_jax003_kwargs_splat_is_undecidable_not_flagged(tmp_path):
    v = _lint(
        """
        import jax

        def train_step(state, batch):
            return state

        kwargs = dict(donate_argnums=(0,))
        train_step = jax.jit(train_step, **kwargs)
        """, tmp_path, select=["JAX003"])
    assert v == []


# ---------------------------------------------------------------------------
# CONC001 — threading.Thread without daemon= and name=
# ---------------------------------------------------------------------------

def test_conc001_anonymous_thread(tmp_path):
    v = _lint(
        """
        import threading

        t = threading.Thread(target=print)
        u = threading.Thread(target=print, daemon=True)
        """, tmp_path, select=["CONC001"])
    assert _rules(v) == ["CONC001", "CONC001"]
    assert "daemon= and name=" in v[0].message
    assert "name=" in v[1].message and "daemon" not in v[1].message


def test_conc001_named_daemon_thread_is_fine(tmp_path):
    v = _lint(
        """
        import threading

        t = threading.Thread(target=print, daemon=True, name="worker")
        u = threading.Thread(**thread_kwargs)   # splat: undecidable
        """, tmp_path, select=["CONC001"])
    assert v == []


def test_conc001_subclass_super_init(tmp_path):
    v = _lint(
        """
        import threading

        class Bad(threading.Thread):
            def __init__(self):
                super().__init__()

        class Good(threading.Thread):
            def __init__(self):
                super().__init__(daemon=True, name="good-worker")
        """, tmp_path, select=["CONC001"])
    assert _rules(v) == ["CONC001"]
    assert "Bad" in v[0].message


# ---------------------------------------------------------------------------
# CONC002 — Lock.acquire() outside with / try-finally
# ---------------------------------------------------------------------------

def test_conc002_bare_acquire(tmp_path):
    v = _lint(
        """
        import threading

        lock = threading.Lock()

        def critical():
            lock.acquire()
            do_work()
            lock.release()
        """, tmp_path, select=["CONC002"])
    assert _rules(v) == ["CONC002"]
    assert "deadlock" in v[0].message


def test_conc002_try_finally_and_with_are_fine(tmp_path):
    v = _lint(
        """
        import threading

        lock = threading.Lock()

        def guarded():
            lock.acquire()
            try:
                do_work()
            finally:
                lock.release()

        def timed():
            if lock.acquire(timeout=1.0):
                try:
                    do_work()
                finally:
                    lock.release()

        def scoped():
            with lock:
                do_work()
        """, tmp_path, select=["CONC002"])
    assert v == []


# ---------------------------------------------------------------------------
# TIME001 — time.time() arithmetic
# ---------------------------------------------------------------------------

def test_time001_delta_and_deadline(tmp_path):
    v = _lint(
        """
        import time

        def measure():
            t0 = time.time()
            work()
            return time.time() - t0

        def wait():
            deadline = time.time() + 5
            return deadline
        """, tmp_path, select=["TIME001"])
    assert _rules(v) == ["TIME001", "TIME001"]


def test_time001_aliased_import(tmp_path):
    v = _lint(
        """
        import time as _t

        def wait(timeout):
            return _t.time() + timeout
        """, tmp_path, select=["TIME001"])
    assert _rules(v) == ["TIME001"]


def test_time001_monotonic_and_reported_wallclock_are_fine(tmp_path):
    v = _lint(
        """
        import time

        def measure():
            t0 = time.monotonic()
            work()
            return time.monotonic() - t0

        def report():
            return {"time": time.time(), "stamp": int(time.time())}
        """, tmp_path, select=["TIME001"])
    assert v == []


def test_time001_taint_does_not_leak_across_scopes(tmp_path):
    v = _lint(
        """
        import time

        def reports():
            now = time.time()       # wall clock, reported only
            return {"time": now}

        def rates(prev):
            now = time.monotonic()  # same name, different clock
            return now - prev
        """, tmp_path, select=["TIME001"])
    assert v == []


# ---------------------------------------------------------------------------
# RETRY001 — hand-rolled retry loop (sleep + except in a loop)
# ---------------------------------------------------------------------------

def test_retry001_sleep_in_retry_loop(tmp_path):
    v = _lint(
        """
        import time as _t

        def fetch(call):
            while True:
                try:
                    return call()
                except ConnectionError:
                    _t.sleep(1.0)
        """, tmp_path, select=["RETRY001"])
    assert _rules(v) == ["RETRY001"]
    assert "hand-rolled" in v[0].message


def test_retry001_for_loop_with_backoff(tmp_path):
    v = _lint(
        """
        import time

        def fetch(call):
            for attempt in range(5):
                try:
                    return call()
                except OSError:
                    pass
                time.sleep(2 ** attempt)
        """, tmp_path, select=["RETRY001"])
    assert _rules(v) == ["RETRY001"]


def test_retry001_poll_loop_without_handler_is_fine(tmp_path):
    v = _lint(
        """
        import time

        def wait_ready(check):
            while not check():
                time.sleep(0.5)   # plain poll, no exception pacing
        """, tmp_path, select=["RETRY001"])
    assert v == []


def test_retry001_handler_in_nested_function_is_fine(tmp_path):
    v = _lint(
        """
        import time

        def tick(fns):
            for fn in fns:
                def guarded():
                    try:
                        fn()
                    except Exception:
                        raise
                guarded()
                time.sleep(0.1)   # pacing, not retry: no handler in loop
        """, tmp_path, select=["RETRY001"])
    assert v == []


def test_retry001_retry_module_itself_is_exempt(tmp_path):
    (tmp_path / "utils").mkdir()
    v = _lint(
        """
        import time

        def retry_call(fn):
            while True:
                try:
                    return fn()
                except Exception:
                    time.sleep(0.1)
        """, tmp_path / "utils", select=["RETRY001"], name="retry.py")
    assert v == []


# ---------------------------------------------------------------------------
# suppression mechanism
# ---------------------------------------------------------------------------

def test_suppression_with_reason(tmp_path):
    v = _lint(
        """
        import time

        deadline = time.time() + 5  # dctlint: disable=TIME001 NTP-aware wall deadline is the point here
        """, tmp_path, select=["TIME001"])
    assert v == []


def test_suppression_without_reason_is_itself_flagged(tmp_path):
    v = _lint(
        """
        import time

        deadline = time.time() + 5  # dctlint: disable=TIME001
        """, tmp_path, select=["TIME001"])
    # the reasonless disable does NOT suppress, and is reported itself
    assert sorted(_rules(v)) == ["DCT000", "TIME001"]


def test_suppression_next_line(tmp_path):
    v = _lint(
        """
        import time

        # dctlint: disable-next-line=TIME001 demo fixture for the docs
        deadline = time.time() + 5
        """, tmp_path, select=["TIME001"])
    assert v == []


def test_suppression_wrong_rule_does_not_apply(tmp_path):
    v = _lint(
        """
        import time

        deadline = time.time() + 5  # dctlint: disable=JAX001 wrong rule id
        """, tmp_path, select=["TIME001"])
    assert _rules(v) == ["TIME001"]


def test_suppression_all_with_reason(tmp_path):
    v = _lint(
        """
        import time

        deadline = time.time() + 5  # dctlint: disable=all generated fixture, exempt wholesale
        """, tmp_path, select=["TIME001"])
    assert v == []


# ---------------------------------------------------------------------------
# baseline mechanism
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_filters_exactly_whats_named(tmp_path):
    bad = tmp_path / "legacy.py"
    bad.write_text("import time\ndeadline = time.time() + 5\n")
    diags = lint_core.lint_file(bad, select=["TIME001"])
    assert len(diags) == 1

    baseline = tmp_path / "baseline.json"
    assert lint_core.write_baseline(baseline, diags) == 1
    entries = lint_core.load_baseline(baseline)
    assert entries[0]["rule"] == "TIME001"
    assert "justification" in entries[0]

    # the baselined violation is filtered...
    assert lint_core.apply_baseline(diags, entries) == []
    # ...but a new violation in the same file is not
    bad.write_text("import time\ndeadline = time.time() + 5\n"
                   "other = time.time() - 1\n")
    fresh = lint_core.lint_file(bad, select=["TIME001"])
    remaining = lint_core.apply_baseline(fresh, entries)
    assert len(remaining) == 1
    assert "time.time() - 1" in remaining[0].message


def test_committed_baseline_entries_all_have_justifications():
    for e in lint_core.load_baseline(BASELINE):
        assert e.get("justification", "").strip(), \
            f"baseline entry without justification: {e}"
        assert "TODO" not in e["justification"], \
            f"unfilled baseline justification: {e}"


# ---------------------------------------------------------------------------
# EXC001 + the legacy shim contract (tools/check_swallowed_exceptions.py)
# ---------------------------------------------------------------------------

def _violations(snippet, tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(snippet))
    return list(csx.check_file(f))


def test_library_is_clean():
    assert csx.main([str(REPO / "determined_clone_tpu")]) == 0


def test_tools_are_clean():
    assert csx.main([str(REPO / "tools")]) == 0


def test_flags_uncommented_swallow(tmp_path):
    v = _violations(
        """
        try:
            work()
        except Exception:
            pass
        """, tmp_path)
    assert len(v) == 1
    assert "except Exception" in v[0][1]


def test_flags_bare_except_and_ellipsis(tmp_path):
    v = _violations(
        """
        try:
            work()
        except:
            ...
        """, tmp_path)
    assert len(v) == 1


def test_comment_on_pass_line_suppresses(tmp_path):
    assert _violations(
        """
        try:
            work()
        except Exception:
            pass  # best-effort cleanup; never mask the original error
        """, tmp_path) == []


def test_comment_above_try_suppresses(tmp_path):
    assert _violations(
        """
        # Transient poll failures must not kill training; the watcher
        # retries on its next tick.
        try:
            work()
        except Exception:
            pass
        """, tmp_path) == []


def test_narrow_handler_is_fine(tmp_path):
    assert _violations(
        """
        try:
            work()
        except KeyError:
            pass
        """, tmp_path) == []


def test_broad_handler_with_real_body_is_fine(tmp_path):
    assert _violations(
        """
        try:
            work()
        except Exception:
            log.warning("work failed")
        """, tmp_path) == []


def test_tuple_including_broad_is_flagged(tmp_path):
    v = _violations(
        """
        try:
            work()
        except (ValueError, Exception):
            pass
        """, tmp_path)
    assert len(v) == 1


def test_exc001_is_the_same_check_via_dctlint(tmp_path):
    v = _lint(
        """
        try:
            work()
        except Exception:
            pass
        """, tmp_path, select=["EXC001"])
    assert _rules(v) == ["EXC001"]
