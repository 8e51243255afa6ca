"""Where JAX's persistent compilation cache lives.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` when it is imported, and worker
processes inherit the driver's environment (raylet ``_popen_worker``), so the
entry points (``chip_smoke.py``, ``benchmarks/run.py``) place the cache once,
before the cluster starts, and no other code sets a cache directory or what
goes into it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# JAX persists a program only if it took this long to compile, by default a
# second. The serving engine's decode programs take 0.9-1.5 s each on a v5e
# (PERF.md, PR 31), so at the default which of them a warm start finds in the
# cache is luck, and each one missed is compiled again before the replica is
# ready. Programs that compile in tens of milliseconds stay out.
MIN_COMPILE_ENV_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
MIN_COMPILE_SECS = "0.25"


def export_compile_cache_dir(entry_file: str) -> str:
    """Make sure ``JAX_COMPILATION_CACHE_DIR`` is set and return it. A
    directory the caller set is left alone. Otherwise the cache goes to
    ``.jax_cache`` beside ``entry_file``: the path is part of the cache's
    key, so it must be the same on every run of the same checkout — never
    the working directory, a temp dir, a pid or a timestamp. Likewise the
    least compile time worth persisting (``MIN_COMPILE_SECS``) unless the
    caller set one."""
    os.environ.setdefault(MIN_COMPILE_ENV_VAR, MIN_COMPILE_SECS)
    if not os.environ.get(ENV_VAR):
        os.environ[ENV_VAR] = os.path.join(
            os.path.dirname(os.path.abspath(entry_file)), ".jax_cache"
        )
    return os.environ[ENV_VAR]
