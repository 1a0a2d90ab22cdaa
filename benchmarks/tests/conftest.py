"""Tests of the benchmark's own files. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not under ``tests/``, so the repo's tier-1 run does not count them.
"""
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
