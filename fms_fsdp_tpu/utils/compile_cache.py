"""Where JAX's persistent compilation cache lives.

A 7B-width train step takes minutes to compile, and every restart — a
supervisor relaunch, a replica respawn, a new chip machine — pays it
again unless the executables persist. The directory is part of the
cache key's stability: one that moves never hits.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this code
  sets nothing, so whoever runs the program decides where the cache is.
- unset: one fixed, git-ignored directory at the root of the checkout,
  derived from this package's location — no temporary name, pid or
  clock. It is exported through the environment so child processes
  (fleet replicas, the run supervisor's trainee) land in the same place.
"""

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX at the persistent cache; returns the directory. Safe to
    call before or after ``import jax`` and more than once."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:  # imported already: it read the environment then
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_entry_count(path: str) -> int:
    """Number of cached executables under ``path`` (0 if absent)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
