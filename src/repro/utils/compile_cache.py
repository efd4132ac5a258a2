"""JAX's persistent compilation cache, placed from outside the code.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache goes to a fixed
path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path
is part of each entry's key, so a temporary or per-process directory
would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts XLA backend compiles and their seconds while active — the
    build's compile bound and the smoke's compile seconds read these (a
    persistent-cache hit compiles nothing, so a warm cache shows as
    fewer compiles)::

        with CompileCounter() as cc:
            ...
        cc.compiles, cc.seconds
    """

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._COMPILE:
            self.compiles += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
