"""dctlint — project-specific AST static analysis for JAX & concurrency
pitfalls (ISSUE 3; catalog + workflow in docs/static_analysis.md).

Run as ``python -m tools.dctlint [paths...]`` or ``dct lint``. Tier-1
runs it over ``determined_clone_tpu/`` and ``tools/`` via
tests/test_static_checks.py, so new violations fail CI.
"""
from tools.dctlint import checkers  # noqa: F401  (registers all checkers)
from tools.dctlint.core import (  # noqa: F401
    CHECKERS,
    Checker,
    Diagnostic,
    FileContext,
    ProjectChecker,
    apply_baseline,
    lint_file,
    lint_source,
    load_baseline,
    register,
    run,
    write_baseline,
)
from tools.dctlint.project import (  # noqa: F401
    ProjectIndex,
    extract_facts,
)

DEFAULT_PATHS = ("determined_clone_tpu", "tools")
