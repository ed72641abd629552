"""Compilation bookkeeping for the launchers.

* :func:`enable_cache` turns on JAX's persistent compilation cache.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  is changed; otherwise the cache goes to ``.jax_cache/`` at the root of
  this checkout.  The path is fixed because it is part of the cache key: a
  directory that moves never hits.
* :class:`CompileLog` counts the programs JAX lowers and sums their XLA
  compile seconds, so a launcher can show that a step compiles once.

Nothing here runs at import time: the launchers call it from ``main``.
"""

from __future__ import annotations

import collections
import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

# one per jit-cache miss, also when the persistent cache then hits
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# XLA compilation proper (skipped on a persistent-cache hit)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def enable_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileLog:
    """Context manager that records compilations while it is open.

    ``count`` is the number of programs lowered; ``seconds`` maps each
    program name to its XLA compile seconds.
    """

    def __init__(self):
        self.count = 0
        self.seconds: dict[str, float] = collections.defaultdict(float)

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == _LOWER_EVENT:
            self.count += 1
        elif event == _COMPILE_EVENT:
            self.seconds[str(kw.get("fun_name", "?"))] += duration

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)
