"""Persistent XLA compile cache for the executables.

One rule, one place. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
and the program sets nothing — whoever runs the program placed the cache.
Otherwise the executables (``chip_smoke.py``,
``scripts/tpu_smoke.py``, ``python -m deeperspeed_tpu.autotune``,
``serving/replica_worker.py``) point ``jax_compilation_cache_dir`` at ONE
fixed, git-ignored directory inside the checkout. The path is part of the
cache key, so it is never a temporary, pid or timestamp path: a directory
that moves never hits.

Only device executables are cached. On the host CPU backend nothing is
placed: an XLA:CPU AOT result is tied to the compiling machine's feature
list and reloads with "could lead to SIGILL" errors, and the CPU test suite
must not fill the tree the chip tool copies.

Called by executables only — not by ``initialize()`` or ``ServingEngine`` —
so importing the library writes nothing into the tree.
"""

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Place the persistent compile cache for this process (call it once
    the process is meant to own a backend, before the first compile).
    Returns the directory in use, or None on the host CPU backend."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
