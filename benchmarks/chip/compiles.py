"""The benchmark's compilation cache and compile counter.

* ``enable_cache`` turns on JAX's persistent compilation cache.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and it is kept;
  otherwise the cache is ``.jax_cache/`` at the root of the checkout.  The
  path is fixed because it is part of the cache key: a directory that
  moves never hits.  Every program is cached, also those that compile in
  well under a second, so that a warm run compiles nothing.
* ``CompileLog`` counts the programs JAX lowers while it is open (one per
  jit-cache miss, also when the persistent cache then hits), so a run can
  show that its window compiles nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"

_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def enable_cache() -> str:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileLog:
    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == _LOWER_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)
