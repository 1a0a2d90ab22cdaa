"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (``chip_smoke.py``,
``exec/trial.py``, ``dct serve`` / ``dct fleet up``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it stands and
nothing here sets another directory; where it is not, the cache goes to
``<repo>/.jax_cache`` (git-ignored). The directory is part of the cache
key's lookup, so it is a fixed path — never a temporary name, a process id
or a timestamp, which could not hit twice. Programs of any compile time are
kept (JAX's default keeps only those that took a second), unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at the persistent compilation cache and return the
    directory in use. Call before the process's first compile: JAX
    decides once, at that compile, whether it has a cache."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # JAX keeps only programs that took a second to compile. A chip
        # machine starts every call cold, and the main paths compile many
        # programs that each stay under that (most of the serving warm-up
        # ladder, 20 s together on a v5e in PR 21's smoke): keep them all.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
