"""Where XLA's persistent compilation cache lives.

Every process that compiles for the device calls ``enable()`` before its
first compile: train workers (train/backend.py), serving replicas
(serve/llm/engine.py), the benchmarks, chip_smoke.py and tests/conftest.py.
One rule decides the directory, so all of them hit the same cache:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it on import; nothing is
  set in code.
- unset: ``<checkout>/.jax_cache`` (git-ignored). The path never depends
  on a pid, the time or a temporary name — a directory that moves never
  hits.

Imports jax only if the caller already has.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
FIXED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable() -> str:
    """Point this process, and every process it spawns, at the cache
    directory; returns it."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    # children (raylet-spawned workers copy os.environ) inherit the choice
    os.environ[ENV_VAR] = FIXED_DIR
    if "jax" in sys.modules:
        # jax read the (unset) variable when it was imported
        import jax

        jax.config.update("jax_compilation_cache_dir", FIXED_DIR)
    return FIXED_DIR
