"""Where compiled programs are kept, and how many a window created.

``enable_compile_cache`` turns on JAX's persistent compilation cache for an
entry point that runs on the chip: in ``JAX_COMPILATION_CACHE_DIR`` when
that is set, otherwise in ``.jax_cache`` at the root of the checkout. The
path is part of the cache's key, so it never depends on the process, the
time or the temp directory.

``CompileCounter`` counts the executables JAX builds (compiled, or loaded
from that cache) while it is open; a serving window should count none.
"""

from __future__ import annotations

import os
import pathlib

import jax
from jax import monitoring

__all__ = ["CACHE_ENV", "compile_cache_dir", "enable_compile_cache",
           "CompileCounter"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
# The event JAX records around each backend compile or cache load.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_ENV) or str(_DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that path."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Context manager: ``count`` is the number of executables JAX built
    (compiled or loaded from the persistent cache) while it was open."""

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_duration_listener(self._listen)
